"""Ring attention (context parallelism) over a mesh axis — beyond-paper perf.

Motivation (see EXPERIMENTS.md §Perf): archs whose head counts don't divide the
16-way model axis (qwen2: 14 q heads, 2 kv heads) fall back to *replicated*
attention — every model shard computes the full S^2 attention.  Ring attention
shards the SEQUENCE over the model axis instead: each device holds S/P queries
and S/P keys/values, and KV shards rotate around the ring via
``collective_permute`` while an online softmax accumulates — per-device
attention FLOPs and memory drop by P for any head count.

TPU mapping: the permute rides the ICI ring (the natural v5e topology); each
hop's block matmul is the same MXU tile as the flash kernel.  Causality: block
pairs with no visible elements are skipped via a where-mask (v1 computes masked
blocks — the striped-layout halving is a recorded further iteration).

Used under ``jax.shard_map`` with seq-sharded q/k/v; positions are derived from
``axis_index``.  Exact vs the ref oracle (tests/test_ring_attention.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _block_attend(q, k, v, q_off, k_off, scale, causal):
    """One masked flash block in fp32.  q: (B,Sq,Hkv,G,D) k/v: (B,Sk,Hkv,D)."""
    s = jnp.einsum("bqkgd,bskd->bqkgs", q, k) * scale
    if causal:
        qpos = q_off + jnp.arange(q.shape[1])
        kpos = k_off + jnp.arange(k.shape[1])
        mask = kpos[None, :] <= qpos[:, None]                  # (Sq,Sk)
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    # fully-masked rows: exp(-inf - -inf) guards
    m_safe = jnp.where(jnp.isinf(m), 0.0, m)
    p = jnp.exp(jnp.where(jnp.isinf(s), -jnp.inf, s - m_safe[..., None]))
    p = jnp.where(jnp.isnan(p), 0.0, p)
    l = jnp.sum(p, axis=-1)
    pv = jnp.einsum("bqkgs,bskd->bqkgd", p, v)
    return m_safe, jnp.where(jnp.isinf(m), -jnp.inf, m_safe), l, pv


def ring_attention_local(q, k, v, *, axis_name: str, scale=None,
                         causal: bool = True, axis_size: Optional[int] = None):
    """Body to run under shard_map.  q/k/v: LOCAL shards (B, S/P, H|Hkv, D),
    sequence sharded over ``axis_name``.  Returns local out (B, S/P, H, Dv).
    ``axis_size`` is the static ring length; older jax has no
    ``jax.lax.axis_size``, so the wrapper passes it from the mesh."""
    if axis_size is not None:
        P = axis_size
    else:
        P = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Sq, H, Dq = q.shape
    Hkv, Dv = k.shape[2], v.shape[-1]
    G = H // Hkv
    if scale is None:
        scale = 1.0 / np.sqrt(Dq)
    qg = q.reshape(B, Sq, Hkv, G, Dq).astype(jnp.float32)
    q_off = idx * Sq

    perm = [(j, (j + 1) % P) for j in range(P)]

    def step(i, carry):
        acc, m, l, kb, vb = carry
        src = (idx - i) % P                     # rank that produced this block
        k_off = src * kb.shape[1]
        bm_raw, bm, bl, bpv = _block_attend(
            qg, kb.astype(jnp.float32), vb.astype(jnp.float32),
            q_off, k_off, scale, causal)
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(jnp.where(jnp.isinf(m), -jnp.inf, m - m_new))
        beta = jnp.exp(jnp.where(jnp.isinf(bm), -jnp.inf, bm - m_new))
        l = l * alpha + bl * beta
        acc = acc * alpha[..., None] + bpv * beta[..., None]
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (acc, m_new, l, kb, vb)

    acc0 = jnp.zeros((B, Sq, Hkv, G, Dv), jnp.float32)
    m0 = jnp.full((B, Sq, Hkv, G), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    acc, m, l, _, _ = jax.lax.fori_loop(
        0, P, step, (acc0, m0, l0, k, v))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, Dv).astype(q.dtype)


def ring_attention(q, k, v, *, mesh, axis: str = "model", scale=None,
                   causal: bool = True, batch_axes: Optional[tuple] = ("data",)):
    """pjit-callable wrapper: shards seq over ``axis``, batch over
    ``batch_axes``, runs the ring under shard_map."""
    from jax.sharding import PartitionSpec as P

    baxes = tuple(a for a in (batch_axes or ()) if a in mesh.axis_names)
    bspec = baxes[0] if len(baxes) == 1 else (baxes if baxes else None)
    spec_q = P(bspec, axis, None, None)
    fn = functools.partial(ring_attention_local, axis_name=axis, scale=scale,
                           causal=causal, axis_size=int(mesh.shape[axis]))
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(spec_q, spec_q, spec_q),
        out_specs=spec_q,
        check_vma=False,
    )(q, k, v)
