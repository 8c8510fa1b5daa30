"""Model FLOPs per trained token, computed from a configuration's sizes.

Matmuls count 6 FLOPs per weight per token (forward 2, backward 4).  The
embedding lookup is free; with tied embeddings the table is counted once, as
the output head.  Attention adds 12 * heads * head_dim * seq per layer and
token (QK^T and PV over the whole sequence, forward and backward, the PaLM
convention).  The Mamba2 scan, in its quadratic form within a chunk, adds
6 * heads * seq * (state + head_dim) per layer and token.  Norms, biases and
elementwise work are left out, and so is recomputation.
"""
from __future__ import annotations


def _attn_block(m: dict, seq: int) -> tuple[int, int]:
    """(matmul weights, extra forward+backward FLOPs per token) of one
    pre-norm attention + SwiGLU block."""
    D, H, Hkv, hd, F = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    weights = D * H * hd + 2 * D * Hkv * hd + H * hd * D + 3 * D * F
    return weights, 12 * H * hd * seq


def _mamba_layer(m: dict, seq: int) -> tuple[int, int]:
    D = m["d_model"]
    E = m["ssm_expand"] * D
    N, P, W = m["ssm_state_dim"], m["ssm_head_dim"], m["ssm_conv_width"]
    H = E // P
    weights = D * (2 * E + 2 * N + H) + E * D + W * (E + 2 * N)
    return weights, 6 * H * seq * (N + P)


def flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward model FLOPs per token for the "model" block of a
    configuration file at sequence length ``seq``."""
    weights = m["d_model"] * m["vocab_size"]            # output head
    extra = 0
    if m.get("mixer", "attn") == "mamba2":
        period = m["shared_attn_period"]
        groups = m["num_layers"] // period
        mw, mx = _mamba_layer(m, seq)
        aw, ax = _attn_block(m, seq)
        weights += groups * (period * mw + 2 * m["d_model"] * m["d_model"] + aw)
        extra += groups * (period * mx + ax)
        tail = m["num_layers"] - groups * period
        weights += tail * mw
        extra += tail * mx
    else:
        aw, ax = _attn_block(m, seq)
        weights += m["num_layers"] * aw
        extra += m["num_layers"] * ax
    return 6.0 * weights + extra
