"""Plain reference for the training step: weights from the seed, the layers,
the loss, AdamW.  Straight ``jax.numpy`` in float32 at the highest matmul
precision; it imports nothing of the program under test.

``mode`` selects the matmul operands' precision:

* ``"f32"``: the reference.
* ``"fp8"``: the control.  Every matmul operand is rounded to float8 with a
  per-tensor scale (e4m3 forward, e5m2 for the incoming gradient), the
  step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# weights and batches from the seed
# ---------------------------------------------------------------------------

def seed_words(seed: int) -> tuple[int, int]:
    """A seed of any size as two int32 words (low 31 bits, the rest)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) % (2 ** 31))


def init_leaf(key, shape, init):
    """``init`` is ("normal", std) | ("zeros",) | ("ones",) | ("log_uniform", lo, hi)."""
    kind = init[0]
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * init[1]
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "log_uniform":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          minval=init[1], maxval=init[2]))
    raise ValueError(f"unknown init {init!r}")


def make_params(specs: dict, lo, hi):
    """Nested dict of float32 leaves from ``specs`` (path -> (shape, init));
    ``lo``/``hi`` are the seed words, traced so one program serves every
    seed."""
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    out: dict = {}
    for path, (shape, init) in sorted(specs.items()):
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = init_leaf(_leaf_key(key, path), tuple(shape), init)
    return out


def batch_tokens(seed: int, step: int, batch: int, seq: int, vocab: int) -> np.ndarray:
    """Token rows of one step: counter-based, so step k is the same whatever
    ran before it."""
    rng = np.random.default_rng([int(seed), int(step)])
    return rng.integers(0, vocab, size=(batch, seq), dtype=np.int32)


# ---------------------------------------------------------------------------
# matmul at the chosen precision
# ---------------------------------------------------------------------------

def _q8(x, dtype):
    amax = jnp.max(jnp.abs(x))
    top = float(jnp.finfo(dtype).max)
    s = jnp.where(amax > 0, amax / top, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _contract(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _contract_fp8(spec: str, a, b):
    return _contract(spec, _q8(a, jnp.float8_e4m3fn), _q8(b, jnp.float8_e4m3fn))


def _contract_fp8_fwd(spec, a, b):
    aq, bq = _q8(a, jnp.float8_e4m3fn), _q8(b, jnp.float8_e4m3fn)
    return _contract(spec, aq, bq), (aq, bq)


def _contract_fp8_bwd(spec, res, g):
    _, vjp = jax.vjp(functools.partial(_contract, spec), *res)
    return vjp(_q8(g, jnp.float8_e5m2))


_contract_fp8.defvjp(_contract_fp8_fwd, _contract_fp8_bwd)


def ein(spec: str, a, b, mode: str):
    """An einsum of two operands; in ``"fp8"`` mode both operands, and the
    gradient that comes back, are rounded to float8 first."""
    if mode == "fp8":
        return _contract_fp8(spec, a, b)
    return _contract(spec, a, b)


def mm(x, w, mode: str):
    """x: (..., k) @ w: (k, n)."""
    return ein("...k,kn->...n", x, w, mode)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """x: (B, S, heads, hd); rotates the two halves of the head dim."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def gqa(p, x, m: dict, mode: str):
    """Causal grouped-query attention; ``p`` holds wq/wk/wv/wo (w and maybe b)."""
    B, S, _ = x.shape
    H, Hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]

    def proj(name, heads):
        y = mm(x, p[name]["w"], mode)
        if "b" in p[name]:
            y = y + p[name]["b"]
        return y.reshape(B, S, heads, hd)

    q = rope(proj("wq", H), m["rope_theta"])
    k = rope(proj("wk", Hkv), m["rope_theta"])
    v = proj("wv", Hkv)
    q = q.reshape(B, S, Hkv, H // Hkv, hd)
    s = ein("bqkgd,bskd->bkgqs", q, k, mode) / np.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = ein("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v, mode)
    return mm(o.reshape(B, S, H * hd), p["wo"]["w"], mode)


def swiglu(p, x, mode: str):
    g = mm(x, p["gate"]["w"], mode)
    u = mm(x, p["up"]["w"], mode)
    return mm(jax.nn.silu(g) * u, p["down"]["w"], mode)


def dense_block(p, h, m: dict, mode: str):
    """Pre-norm attention then pre-norm SwiGLU, each with its residual."""
    eps = m["norm_eps"]
    h = h + gqa(p["attn"], rms_norm(h, p["ln1"]["scale"], eps), m, mode)
    return h + swiglu(p["ffn"], rms_norm(h, p["ln2"]["scale"], eps), mode)


def ssd(x, dt, A_log, Bm, Cm, D, mode: str):
    """Mamba2 state-space scan in its quadratic form.

    x: (B,S,H,P), dt: (B,S,H) > 0, A_log/D: (H,), Bm/Cm: (B,S,N).
    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t,
    with A = -exp(A_log): the recurrence state_t = state_{t-1} exp(dt_t A)
    + dt_t B_t x_t^T, read out by C_t, unrolled."""
    S = x.shape[1]
    A = -jnp.exp(A_log)
    c = jnp.cumsum(dt * A, axis=1)                              # (B,S,H)
    diff = c[:, :, None, :] - c[:, None, :, :]                  # (B,t,s,H)
    causal = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = ein("btn,bsn->bts", Cm, Bm, mode)
    w = cb[..., None] * decay * dt[:, None, :, :]               # (B,t,s,H)
    y = ein("btsh,bshp->bthp", w, x, mode)
    return y + x * D[None, None, :, None]


def mamba2_mixer(p, x, m: dict, mode: str):
    B, S, _ = x.shape
    E = m["ssm_expand"] * m["d_model"]
    N, P, W = m["ssm_state_dim"], m["ssm_head_dim"], m["ssm_conv_width"]
    H = E // P
    proj = mm(x, p["in_proj"]["w"], mode)
    z, xbc, dt_raw = proj[..., :E], proj[..., E:2 * E + 2 * N], proj[..., 2 * E + 2 * N:]
    w = p["conv_w"]                                             # (W, C): tap W-1 is "now"
    conv = p["conv_b"] + xbc * w[W - 1]
    for i in range(1, W):
        shifted = jnp.pad(xbc, ((0, 0), (i, 0), (0, 0)))[:, :S]
        conv = conv + shifted * w[W - 1 - i]
    conv = jax.nn.silu(conv)
    xs = conv[..., :E].reshape(B, S, H, P)
    Bm, Cm = conv[..., E:E + N], conv[..., E + N:]
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])
    y = ssd(xs, dt, p["A_log"], Bm, Cm, p["D"], mode).reshape(B, S, E)
    y = rms_norm(y * jax.nn.silu(z), p["norm"]["scale"], m["norm_eps"])
    return mm(y, p["out_proj"]["w"], mode)


def scan_layers(fn, h, stacked):
    """Apply ``fn(p, h)`` over a leading layer axis, recomputing each
    layer's activations in the backward pass so the reference fits beside
    its optimizer state."""
    body = jax.checkpoint(lambda hh, p: (fn(p, hh), None))
    h, _ = jax.lax.scan(body, h, stacked)
    return h


# ---------------------------------------------------------------------------
# loss and optimizer
# ---------------------------------------------------------------------------

def lm_loss(h, table, tokens, z_loss: float, eps: float, final_scale, mode: str):
    """Next-token cross-entropy with a z-loss, logits tied to the embedding.
    The last position has no label and is left out."""
    h = rms_norm(h, final_scale, eps)
    logits = ein("bsd,vd->bsv", h, table, mode)
    labels = tokens[:, 1:]
    lg = logits[:, :-1]
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    n = labels.size
    return jnp.sum(lse - gold) / n + z_loss * jnp.sum(lse * lse) / n


def lr_at(opt: dict, step):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    span = max(opt["decay_steps"] - opt["warmup_steps"], 1)
    prog = jnp.clip((step - opt["warmup_steps"]) / span, 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * cos)


def adamw(params, grads, m, v, step: int, opt: dict):
    """One AdamW update with global-norm clipping.  Returns the new
    (params, m, v) and the gradient as the update used it (clipped)."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    lr = lr_at(opt, step)
    t = step + 1.0
    b1, b2 = opt["b1"], opt["b2"]
    tm = jax.tree_util.tree_map
    g = tm(lambda x: x * clip, grads)
    m = tm(lambda a, x: a * b1 + x * (1 - b1), m, g)
    v = tm(lambda a, x: a * b2 + x * x * (1 - b2), v, g)

    def upd(p, a, b):
        d = (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + opt["eps"])
        return p - lr * (d + opt["weight_decay"] * p)

    return tm(upd, params, m, v), m, v, g


def leaf_projections(tree, lo, hi, k: int = 4) -> jax.Array:
    """(leaves, k): each leaf's inner products with k random sign vectors
    drawn from the seed.  Each projection's square has the leaf's squared
    norm as its mean, and a difference between two trees shows in it to
    first order, where a gap of norms cancels it."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(lo), hi), 0x5EED)
    rows = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        lk = _leaf_key(key, jax.tree_util.keystr(path))
        x = x.astype(jnp.float32)
        rows.append(jnp.stack([
            jnp.sum(x * jax.random.rademacher(jax.random.fold_in(lk, j), x.shape, jnp.float32))
            for j in range(k)]))
    return jnp.stack(rows)


def leaf_norms(tree) -> jax.Array:
    """Per-leaf L2 norms, in the order of ``jax.tree_util.tree_leaves``."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])
