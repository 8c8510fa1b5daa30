"""RWKV6 (Finch) WKV recurrence — chunked formulations.

``wkv6_chunked_xla`` — pure-XLA chunked algorithm (log-space decays, fp32).
``wkv6_chunked`` — Pallas TPU kernel wrapper with the same contract.

Recurrence (matches ``ref.wkv6``):
    y_t   = r_t . (S_t + u * k_t v_t^T)
    S_t+1 = diag(w_t) S_t + k_t v_t^T
Unrolled within a chunk:  contribution of key j to query i>j carries the decay
prod_{l=j+1..i-1} w_l — computed as exp of cumulative-log differences.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _chunk_wkv_math(r, k, v, w, u, state_in):
    """One chunk, fp32.  r/k/v/w: (B,Q,H,D); u: (H,D); state: (B,H,D,D)."""
    B, Q, H, D = r.shape
    logw = jnp.log(jnp.maximum(w, 1e-30))
    cw = jnp.cumsum(logw, axis=1)                          # inclusive: sum_{l<=i} log w_l
    # decay from key j to query i (j < i): exp(cw_{i-1} - cw_j)
    # r_dec_i = r_i * exp(cw_{i-1}) ; k_dec_j = k_j * exp(-cw_j)
    cw_prev = jnp.concatenate([jnp.zeros_like(cw[:, :1]), cw[:, :-1]], axis=1)
    r_dec = r * jnp.exp(cw_prev)
    k_dec = k * jnp.exp(-cw)
    scores = jnp.einsum("bihd,bjhd->bhij", r_dec, k_dec)   # (B,H,Q,Q)
    ii = jnp.arange(Q)
    strict = (ii[None, :] < ii[:, None]).astype(scores.dtype)   # j < i
    scores = scores * strict[None, None]
    y = jnp.einsum("bhij,bjhd->bihd", scores, v)
    # diagonal (current-token) bonus term: r_i . (u * k_i v_i^T)
    diag = jnp.sum(r * u[None, None] * k, axis=-1)          # (B,Q,H)
    y = y + diag[..., None] * v
    # incoming state: y_i += (r_i * exp(cw_{i-1})) . S_in
    y = y + jnp.einsum("bihk,bhkv->bihv", r_dec, state_in)
    # state out: S_out = diag(prod w) S_in + sum_j (k_j * exp(cw_Q - cw_j)) v_j^T
    total = jnp.exp(cw[:, -1])                              # (B,H,D)
    k_carry = k * jnp.exp(cw[:, -1:, :, :] - cw)
    state_out = state_in * total[..., None] + jnp.einsum("bjhk,bjhv->bhkv", k_carry, v)
    return y, state_out


def wkv6_chunked_xla(r, k, v, w, u, *, chunk=128, init_state=None,
                     return_state=False):
    B, S, H, D = r.shape
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    if init_state is None:
        init_state = jnp.zeros((B, H, D, D), jnp.float32)

    f32 = jnp.float32
    xs = tuple(
        z.reshape(B, nc, Q, H, D).swapaxes(0, 1).astype(f32) for z in (r, k, v, w)
    )
    uf = u.astype(f32)

    def step(state, inp):
        rc, kc, vc, wc = inp
        y, state = _chunk_wkv_math(rc, kc, vc, wc, uf, state)
        return state, y

    state, ys = jax.lax.scan(step, init_state, xs)
    y = ys.swapaxes(0, 1).reshape(B, S, H, D).astype(r.dtype)
    if return_state:
        return y, state
    return y


def wkv6_step(r, k, v, w, u, state):
    """Single decode step.  r/k/v/w: (B,H,D); u: (H,D); state: (B,H,D,D)."""
    f32 = jnp.float32
    rf, kf, vf, wf = (z.astype(f32) for z in (r, k, v, w))
    kv = jnp.einsum("bhk,bhv->bhkv", kf, vf)
    y = jnp.einsum("bhk,bhkv->bhv", rf, state + u.astype(f32)[None, :, :, None] * kv)
    state = state * wf[..., None] + kv
    return y.astype(r.dtype), state


def wkv6_chunked(r, k, v, w, u, *, chunk=128, init_state=None, return_state=False,
                 interpret=False):
    from repro.kernels._rwkv6_pallas import wkv6_pallas

    return wkv6_pallas(r, k, v, w, u, chunk=chunk, init_state=init_state,
                       return_state=return_state, interpret=interpret)
