"""Train-step factory: pjit'd, microbatched (grad accumulation), sharded.

``make_train_step`` returns (jitted_step, state_shardings, batch_shardings).
The state is a plain pytree dict {params, opt{m,v}, step} so the checkpoint
substrate can serialize it without bespoke types.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models.layers import use_shard_resolver
from repro.optim import adamw
from repro.parallel.context import use_mesh_context
from repro.parallel.mesh_rules import Rules, batch_logical_axes

tree_map = jax.tree_util.tree_map


def state_logical_axes(cfg: ModelConfig):
    pax = M.param_logical_axes(cfg)
    return {"params": pax, "opt": {"m": pax, "v": pax}, "step": ()}


def predump_boundary(step: int, interval: int, lead: int = 1) -> bool:
    """True when ``step`` is inside the pre-dump window before an interval
    checkpoint: EVERY step in the ``lead`` steps before each boundary fires
    a ``CheckpointManager.precommit`` (iterative pre-copy, CRIU-style).
    Each pre-dump uses the previous one as its fingerprint reference, so
    lead N-1 re-hashes only what dirtied since lead N-2 and the save at the
    boundary pays only for the last step's churn.  ``lead=1`` reproduces
    the single-pre-dump schedule exactly.  ``lead >= interval`` would
    pre-dump a state staler than the previous checkpoint — clamped to
    ``interval - 1``.
    """
    if interval <= 1 or step < 0:
        return False            # interval=1: every step saves; nothing to overlap
    lead = max(1, min(lead, interval - 1))
    r = (-step) % interval      # steps until the next boundary
    return 1 <= r <= lead


def abstract_train_state(cfg: ModelConfig, oc: adamw.OptConfig):
    p = M.abstract_params(cfg)
    mdt = jnp.dtype(oc.moment_dtype)
    mom = tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, mdt), p)
    return {"params": p, "opt": {"m": mom, "v": mom}, "step": jax.ShapeDtypeStruct((), jnp.int32)}


def init_train_state(cfg: ModelConfig, oc: adamw.OptConfig, key) -> dict:
    params = M.init_params(cfg, key)
    return {
        "params": params,
        "opt": adamw.init_opt_state(params, oc),
        "step": jnp.zeros((), jnp.int32),
    }


def state_shardings(cfg: ModelConfig, oc: adamw.OptConfig, rules: Rules):
    ax = state_logical_axes(cfg)
    ab = abstract_train_state(cfg, oc)
    def is_axes_leaf(x):
        return isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)
    return tree_map(
        lambda a, s: rules.sharding(a, s.shape), ax, ab, is_leaf=is_axes_leaf)


def effective_microbatches(global_batch: int, requested: int, batch_shards: int) -> int:
    """Largest M <= requested such that B % M == 0 and each microbatch still
    covers the batch shards (no half-empty DP shards)."""

    def ok(m):
        return global_batch % m == 0 and (global_batch // m) >= min(batch_shards, global_batch)

    for m in range(max(1, min(requested, global_batch)), 0, -1):
        if ok(m):
            return m
    return 1


def make_train_step(cfg: ModelConfig, mesh, oc: adamw.OptConfig, *,
                    microbatches: int = 1, moe_groups: Optional[int] = None,
                    rules: Optional[Rules] = None, impl: Optional[str] = None,
                    accum_dtype: Optional[str] = None, z_loss: float = 1e-4,
                    donate: bool = True):
    rules = rules or Rules(mesh)
    resolver = rules.activation_resolver()
    batch_shards = rules.axis_group_size("batch")
    if moe_groups is None:
        moe_groups = batch_shards
    adt = jnp.dtype(accum_dtype or ("bfloat16" if cfg.param_dtype == "bfloat16" else "float32"))

    def loss_for(params, mb):
        return M.loss_fn(params, cfg, mb, moe_groups=moe_groups, impl=impl, z_loss=z_loss)

    grad_fn = jax.value_and_grad(loss_for, has_aux=True)

    param_sh = state_shardings(cfg, oc, rules)["params"]

    def train_step(state, batch):
        params = state["params"]
        B = batch["tokens"].shape[0]
        mb_count = effective_microbatches(B, microbatches, batch_shards)
        if mb_count == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                return x.reshape((mb_count, B // mb_count) + x.shape[1:])

            mbs = tree_map(split, batch)
            # the accumulator MUST be sharded like the params: an unconstrained
            # zeros carry makes GSPMD materialize full-size gradients and
            # all-reduce them per microbatch (observed: fp32 expert-weight
            # all-reduces dominating the collective term — EXPERIMENTS §Perf i1)
            zero_g = tree_map(
                lambda p, sh: jax.lax.with_sharding_constraint(
                    jnp.zeros(p.shape, adt), sh),
                params, param_sh)

            def body(carry, mb):
                gsum, lsum, ce = carry
                (l, mets), g = grad_fn(params, mb)
                gsum = tree_map(lambda a, b, sh: jax.lax.with_sharding_constraint(
                    a + b.astype(adt), sh), gsum, g, param_sh)
                return (gsum, lsum + l, ce + mets["ce"]), None

            (gsum, lsum, ce), _ = jax.lax.scan(
                body, (zero_g, jnp.zeros(()), jnp.zeros(())), mbs)
            grads = tree_map(lambda g: (g / mb_count).astype(jnp.float32), gsum)
            loss = lsum / mb_count
            metrics = {"ce": ce / mb_count}
        new_p, new_opt, om = adamw.apply_updates(
            params, grads, state["opt"], state["step"], oc)
        new_state = {"params": new_p, "opt": new_opt, "step": state["step"] + 1}
        out_metrics = {"loss": loss, "ce": metrics.get("ce", loss), **om}
        return new_state, out_metrics

    def sharded_train_step(state, batch):
        with use_shard_resolver(resolver), use_mesh_context(mesh, rules):
            return train_step(state, batch)

    st_sh = state_shardings(cfg, oc, rules)
    # batch shardings are resolved per-call shape; expose a helper
    def batch_shardings(batch_like):
        ax = batch_logical_axes(batch_like)
        return {
            k: rules.sharding(ax[k], batch_like[k].shape) for k in batch_like
        }

    jitted = jax.jit(
        sharded_train_step,
        donate_argnums=(0,) if donate else (),
        out_shardings=(st_sh, None),
    )
    return jitted, st_sh, batch_shardings
