"""Plain reference of qwen2-0.5b as its configuration file states it: tied
embeddings, 24 pre-norm blocks of grouped-query attention (QKV bias, RoPE)
and SwiGLU, a final RMSNorm.  Layer weights are stacked on a leading axis
of length ``num_layers``, the layout the trained state uses."""
from __future__ import annotations

import numpy as np

import refmath as R


def _lin(din, dout, lead=()):
    return (tuple(lead) + (din, dout), ("normal", 1.0 / np.sqrt(din)))


def dense_block_specs(m: dict, prefix: str, lead=()) -> dict:
    D, H, Hkv, hd, F = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    lead = tuple(lead)
    s = {
        f"{prefix}/ln1/scale": (lead + (D,), ("ones",)),
        f"{prefix}/ln2/scale": (lead + (D,), ("ones",)),
        f"{prefix}/attn/wq/w": _lin(D, H * hd, lead),
        f"{prefix}/attn/wk/w": _lin(D, Hkv * hd, lead),
        f"{prefix}/attn/wv/w": _lin(D, Hkv * hd, lead),
        f"{prefix}/attn/wo/w": _lin(H * hd, D, lead),
        f"{prefix}/ffn/gate/w": _lin(D, F, lead),
        f"{prefix}/ffn/up/w": _lin(D, F, lead),
        f"{prefix}/ffn/down/w": _lin(F, D, lead),
    }
    if m.get("qkv_bias"):
        for name, width in (("wq", H * hd), ("wk", Hkv * hd), ("wv", Hkv * hd)):
            s[f"{prefix}/attn/{name}/b"] = (lead + (width,), ("zeros",))
    return s


def param_specs(m: dict) -> dict:
    s = {
        "embed/table": ((m["vocab_size"], m["d_model"]), ("normal", 0.02)),
        "final_norm/scale": ((m["d_model"],), ("ones",)),
    }
    s.update(dense_block_specs(m, "seg0", (m["num_layers"],)))
    return s


def loss(params, tokens, m: dict, z_loss: float, mode: str):
    table = params["embed"]["table"]
    h = table[tokens]
    h = R.scan_layers(lambda p, hh: R.dense_block(p, hh, m, mode), h, params["seg0"])
    return R.lm_loss(h, table, tokens, z_loss, m["norm_eps"],
                     params["final_norm"]["scale"], mode)
