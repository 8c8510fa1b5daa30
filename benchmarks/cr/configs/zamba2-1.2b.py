"""Plain reference of zamba2-1.2b as its configuration file states it.

Groups of ``shared_attn_period`` pre-norm Mamba2 layers; after each group
one call of the shared transformer block on a projection of
concat(hidden, embedding).  That block carries its own residual from its
input, and its output is added to the hidden stream.  The per-call LoRA
adapters of the published model are left out, as the configuration says.
Weights of the groups are stacked (groups, layers in group, ...)."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import numpy as np

import refmath as R


def _sibling(name):
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_").replace(".", "_"), Path(__file__).with_name(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_dense = _sibling("qwen2-0.5b.py")


def param_specs(m: dict) -> dict:
    D, V = m["d_model"], m["vocab_size"]
    E = m["ssm_expand"] * D
    N, P, W = m["ssm_state_dim"], m["ssm_head_dim"], m["ssm_conv_width"]
    H = E // P
    G, I = m["num_layers"] // m["shared_attn_period"], m["shared_attn_period"]
    mix = "seg0/mamba/mixer"
    s = {
        "embed/table": ((V, D), ("normal", 0.02)),
        "final_norm/scale": ((D,), ("ones",)),
        "seg0/mamba/ln1/scale": ((G, I, D), ("ones",)),
        f"{mix}/in_proj/w": ((G, I, D, 2 * E + 2 * N + H), ("normal", 1.0 / np.sqrt(D))),
        f"{mix}/conv_w": ((G, I, W, E + 2 * N), ("normal", 1.0 / np.sqrt(W))),
        f"{mix}/conv_b": ((G, I, E + 2 * N), ("zeros",)),
        f"{mix}/A_log": ((G, I, H), ("log_uniform", 1.0, 16.0)),
        f"{mix}/D": ((G, I, H), ("ones",)),
        f"{mix}/dt_bias": ((G, I, H), ("zeros",)),
        f"{mix}/norm/scale": ((G, I, E), ("ones",)),
        f"{mix}/out_proj/w": ((G, I, E, D), ("normal", 1.0 / np.sqrt(E))),
        "seg0/shared_in/w": ((G, 2 * D, D), ("normal", 1.0 / np.sqrt(2 * D))),
    }
    s.update(_dense.dense_block_specs(m, "shared_attn"))
    return s


def loss(params, tokens, m: dict, z_loss: float, mode: str):
    table = params["embed"]["table"]
    emb = table[tokens]
    shared = params["shared_attn"]
    eps = m["norm_eps"]

    def mamba_layer(p, h):
        return h + R.mamba2_mixer(p["mixer"], R.rms_norm(h, p["ln1"]["scale"], eps), m, mode)

    def group(p, h):
        h = R.scan_layers(mamba_layer, h, p["mamba"])
        x_in = R.mm(jax.numpy.concatenate([h, emb], axis=-1), p["shared_in"]["w"], mode)
        return h + R.dense_block(shared, x_in, m, mode)

    h = R.scan_layers(group, emb, params["seg0"])
    return R.lm_loss(h, table, tokens, z_loss, eps, params["final_norm"]["scale"], mode)
