"""Pallas TPU checkpoint-integrity checksum — the C/R hot path on device.

The paper checksums checkpoint images on the host; at TPU scale the state lives
in HBM, and hashing it *before* the device->host transfer detects corruption at
HBM bandwidth instead of PCIe bandwidth (and lets the coordinator compare
per-worker digests without moving data).  The hash is an order-dependent
FNV-style mix (matching kernels/ref.py::checksum exactly): each 32-bit word is
mixed with its index, then XOR- and SUM-reduced.

Layout, shared by both kernels: a block of ``block`` words is one grid step,
laid out lane-dense as ``(block // L, L)`` with ``L = min(block, 128)``.  The
kernel mixes the block and folds its rows in halves (aligned to the 8-row
sublane tile) down to at most 8 rows, for XOR and for SUM separately, and
writes both partials.  XLA finishes the reduction over each block's (or the
whole stream's) partials.  Both reductions are associative and commutative,
so the digest is bit-identical to the oracle's.  The kernel computes in
int32: Mosaic reduces no unsigned type, and xor, multiply and wrapping add
give the same bits in either signedness.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PRIME = 16777619
_LANES = 128
_FOLD_ROWS = 8


def require_pow2(value: int, name: str = "block") -> None:
    """Both kernels fold their reductions in halves, so the tile length must
    be a positive power of two — anything else would silently drop words.
    Raised eagerly (host-side), mirrored by kernels/ops.py so every impl
    fails the same way."""
    if value < 1 or value & (value - 1):
        raise ValueError(f"{name} must be a positive power of two, got {value}")


def _fold_rows(x, op):
    rows = x.shape[0]
    while rows > _FOLD_ROWS:
        rows //= 2
        x = op(x[:rows], x[rows:])
    return x


def _fold_kernel(w_ref, x_ref, s_ref, *, global_index):
    w = w_ref[0]
    rows, lanes = w.shape
    idx = (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
    if global_index:
        idx = idx + pl.program_id(0) * (rows * lanes)
    mixed = (w ^ (idx * PRIME)) * (idx | 1)
    x_ref[0] = _fold_rows(mixed, jnp.bitwise_xor)
    s_ref[0] = _fold_rows(mixed, jnp.add)


def _block_partials(words: jax.Array, block: int, interpret: bool,
                    global_index: bool):
    """(N,) uint32 with N % block == 0 -> per-block XOR and SUM partials,
    each (N // block, k) uint32.  ``global_index`` mixes each word with its
    position in the stream; otherwise with its position in its block."""
    lanes = min(block, _LANES)
    rows = block // lanes
    nb = words.shape[0] // block
    fold = min(rows, _FOLD_ROWS)
    w = jax.lax.bitcast_convert_type(words, jnp.int32).reshape(nb, rows, lanes)
    part = jax.ShapeDtypeStruct((nb, fold, lanes), jnp.int32)
    xp, sp = pl.pallas_call(
        functools.partial(_fold_kernel, global_index=global_index),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, rows, lanes), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, fold, lanes), lambda i: (i, 0, 0))] * 2,
        out_shape=[part, part],
        interpret=interpret,
    )(w)
    as_u32 = functools.partial(jax.lax.bitcast_convert_type,
                               new_dtype=jnp.uint32)
    return as_u32(xp).reshape(nb, -1), as_u32(sp).reshape(nb, -1)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def checksum_pallas(words: jax.Array, *, block: int = 2048,
                    interpret: bool = False) -> jax.Array:
    """words: (N,) uint32 -> uint32 digest.  N padded to a power-of-two block."""
    require_pow2(block)
    n = words.shape[0]
    if n == 0:
        # the ref oracle's empty digest: XOR and SUM over nothing are both 0
        return jnp.uint32(0)
    block = min(block, max(8, 1 << (n - 1).bit_length()))
    pad = (-n) % block
    if pad:
        # the mix is index-dependent, so zero padding changes the digest: the
        # digest is defined on the padded stream, and the ops wrapper hands
        # the ref oracle the same padded array
        words = jnp.pad(words, (0, pad))
    xp, sp = _block_partials(words, block, interpret, global_index=True)
    return jnp.bitwise_xor.reduce(xp, axis=None) + jnp.sum(sp, dtype=jnp.uint32)


def _chunk_fp_call(words: jax.Array, chunk_words: int,
                   interpret: bool) -> jax.Array:
    """Fingerprints of an ALIGNED word stream (len % chunk_words == 0)."""
    xp, sp = _block_partials(words, chunk_words, interpret, global_index=False)
    return jnp.bitwise_xor.reduce(xp, axis=1) + jnp.sum(sp, axis=1,
                                                        dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("chunk_words", "interpret"))
def chunk_fingerprints_pallas(words: jax.Array, *, chunk_words: int,
                              interpret: bool = False) -> jax.Array:
    """Per-chunk fingerprints of a uint32 word stream, on device.

    words: (N,) uint32 -> (ceil(N / chunk_words),) uint32, one digest per
    fixed-size chunk (the delta plane's dirty-chunk pre-filter: comparing
    these against the parent step's marks which chunks even need a content
    hash, at HBM bandwidth instead of host hash speed).  A ragged tail is
    zero-padded — same convention as every other impl, so the three agree
    bit-for-bit.  The pad touches ONLY the tail chunk (body and padded tail
    go through separate grids), so fingerprinting a big device-resident
    leaf never materializes an O(leaf) padded copy in HBM.  One grid step
    per chunk; index mixing is chunk-LOCAL, so the value matches
    serialization.fingerprint_chunks / ref.chunk_fingerprints whatever the
    chunk's position in the leaf.
    """
    require_pow2(chunk_words, name="chunk_words")
    n = words.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.uint32)
    rem = n % chunk_words
    if not rem:
        return _chunk_fp_call(words, chunk_words, interpret)
    tail = jnp.pad(words[n - rem:], (0, chunk_words - rem))
    tail_fp = _chunk_fp_call(tail, chunk_words, interpret)
    if n == rem:
        return tail_fp
    body_fp = _chunk_fp_call(words[: n - rem], chunk_words, interpret)
    return jnp.concatenate([body_fp, tail_fp])
