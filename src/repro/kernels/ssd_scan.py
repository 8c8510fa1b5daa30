"""Mamba2 SSD (state-space duality) chunked scan.

``ssd_chunked_xla`` — pure-XLA chunked algorithm (scan over chunks; within-chunk
quadratic + cross-chunk state recurrence).  Matches ``ref.ssd`` exactly in math,
but runs in O(S*Q) memory and turns the time recurrence into MXU-friendly
matmuls.  ``ssd_chunked`` — the Pallas TPU kernel with the same contract
(see bottom of file).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _chunk_ssd_math(x, dt, A, Bm, Cm, state_in):
    """One chunk, fp32. x:(B,Q,H,P) dt:(B,Q,H) A:(H,) Bm/Cm:(B,Q,N) state:(B,H,P,N)."""
    a = dt * A                                            # (B,Q,H), negative
    cA = jnp.cumsum(a, axis=1)                            # inclusive cumsum
    # within-chunk (diagonal) part: y_i += sum_{j<=i} exp(cA_i - cA_j) dt_j (C_i.B_j) x_j
    cb = jnp.einsum("bin,bjn->bij", Cm, Bm)               # (B,Q,Q)
    Q = x.shape[1]
    tri = np.tril(np.ones((Q, Q), np.float32))
    decay = jnp.exp(cA[:, :, None, :] - cA[:, None, :, :])     # (B,i,j,H)
    scores = cb[..., None] * decay * tri[None, :, :, None]     # (B,i,j,H)
    scores = scores * dt[:, None, :, :]                        # dt_j
    y_diag = jnp.einsum("bijh,bjhp->bihp", scores, x)
    # contribution of the incoming state: y_i += exp(cA_i) C_i . state_in
    y_off = jnp.einsum("bin,bhpn,bih->bihp", Cm, state_in, jnp.exp(cA))
    # chunk state update: state_out = state_in*exp(cA_Q) + sum_j exp(cA_Q-cA_j) dt_j B_j x_j
    last = jnp.exp(cA[:, -1, :])                               # (B,H)
    w = jnp.exp(cA[:, -1, None, :] - cA) * dt                  # (B,Q,H)
    state_new = jnp.einsum("bjn,bjh,bjhp->bhpn", Bm, w, x)
    state_out = state_in * last[:, :, None, None] + state_new
    return y_diag + y_off, state_out


def ssd_chunked_xla(x, dt, A_log, Bm, Cm, D, *, chunk=256, init_state=None,
                    return_state=False):
    """Same contract as ``ref.ssd`` (see kernels/ref.py)."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    A = -jnp.exp(A_log.astype(jnp.float32))
    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, N), jnp.float32)

    xs = (
        x.reshape(Bsz, nc, Q, H, P).swapaxes(0, 1).astype(jnp.float32),
        dt.reshape(Bsz, nc, Q, H).swapaxes(0, 1).astype(jnp.float32),
        Bm.reshape(Bsz, nc, Q, N).swapaxes(0, 1).astype(jnp.float32),
        Cm.reshape(Bsz, nc, Q, N).swapaxes(0, 1).astype(jnp.float32),
    )

    def step(state, inp):
        xc, dtc, bc, cc = inp
        y, state = _chunk_ssd_math(xc, dtc, A, bc, cc, state)
        return state, y

    state, ys = jax.lax.scan(step, init_state, xs)
    y = ys.swapaxes(0, 1).reshape(Bsz, S, H, P)
    y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    y = y.astype(x.dtype)
    if return_state:
        return y, state
    return y


def ssd_step(x, dt, A_log, Bm, Cm, D, state):
    """Single decode step.  x:(B,H,P) dt:(B,H) Bm/Cm:(B,N) state:(B,H,P,N)."""
    A = -jnp.exp(A_log.astype(jnp.float32))
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    decay = jnp.exp(dtf * A)                               # (B,H)
    dbx = jnp.einsum("bh,bn,bhp->bhpn", dtf, Bm.astype(jnp.float32), xf)
    state = state * decay[..., None, None] + dbx
    y = jnp.einsum("bhpn,bn->bhp", state, Cm.astype(jnp.float32))
    y = y + xf * D.astype(jnp.float32)[None, :, None]
    return y.astype(x.dtype), state


def ssd_chunked(x, dt, A_log, Bm, Cm, D, *, chunk=256, init_state=None,
                return_state=False, interpret=False):
    """Pallas TPU kernel wrapper (defined in this module, kernel body below)."""
    from repro.kernels._ssd_pallas import ssd_pallas

    return ssd_pallas(x, dt, A_log, Bm, Cm, D, chunk=chunk, init_state=init_state,
                      return_state=return_state, interpret=interpret)
