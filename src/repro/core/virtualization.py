"""Topology virtualization — elastic (MxN) restart.

DMTCP virtualizes PIDs/fds so a restarted process keeps working on a different
node.  The framework analogue: checkpoints never record mesh coordinates — a
leaf is (path, global shape, dtype) and sharding is *re-derived* from the
logical-axis rules against whatever mesh the restarted job has.  A checkpoint
taken on (16,16) restores onto (2,16,16), (8,8), or one CPU device unchanged.

``place_tree`` is the single entry point: host pytree -> device pytree laid out
for the current mesh.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from repro.parallel.mesh_rules import Rules
from repro.utils import tracing as TR

tree_map = jax.tree_util.tree_map


def place_tree(host_tree, axes_tree, rules: Optional[Rules]):
    """device_put every leaf with the sharding derived from its logical axes.

    ``rules=None`` places on the default device (single-device restore)."""
    with TR.span("repro.place_tree") as sp:
        if rules is None:
            out = tree_map(jax.device_put, host_tree)
        else:
            flat_h, treedef = jax.tree_util.tree_flatten(host_tree)
            flat_a = treedef.flatten_up_to(axes_tree)
            leaves = []
            for arr, axes in zip(flat_h, flat_a):
                arr = np.asarray(arr)
                sh = rules.sharding(axes, arr.shape)
                leaves.append(jax.device_put(arr, sh))
            out = jax.tree_util.tree_unflatten(treedef, leaves)
        if TR.enabled():
            sp.set(bytes=_nbytes(out))
    return out


def fetch_tree(device_tree):
    """Device pytree -> host (numpy) pytree; works for any sharding because
    jax gathers fully-addressable arrays transparently."""
    with TR.span("repro.fetch_tree") as sp:
        out = tree_map(lambda x: np.asarray(jax.device_get(x)), device_tree)
        if TR.enabled():
            sp.set(bytes=_nbytes(out))
    return out


def _nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))
