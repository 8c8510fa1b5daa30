"""A run with the timed path broken underneath comes out as not correct.

Each test skips only the harness's look for a chip (``allow_cpu``) and
drives the rest of a run at a tiny size: set-up, window, checks.  The fault
is planted in the program once it is built.  The exchange between chips is
not among the faults: every cell runs on one chip.
"""
import time

import numpy as np
import pytest

import harness


def run(cell, root, hook=None, seed=2 ** 31 + 7):
    def both(tr):
        tr.ckpt.device_fp_impl = "pallas_interpret"      # no Mosaic on the CPU
        if hook is not None:
            hook(tr)

    return harness.run(cell, seed, 1.0, False, t_process=time.perf_counter(),
                       allow_cpu=True, hook=both, root=root)


def unchanged_state(tr):
    step = tr.jitted
    tr.jitted = lambda state, batch: (state, step(state, batch)[1])


def half_batch(tr):
    step = tr.jitted
    tr.jitted = lambda state, batch: step(
        state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})


def _alter_first_leaf(tree):
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    x = np.array(leaves[0], copy=True)
    x.reshape(-1)[0] += 1.0
    return jax.tree_util.tree_unflatten(treedef, [x] + leaves[1:])


def altered_save(tr):
    save = tr.ckpt.save
    tr.ckpt.save = lambda step, tree, extra_meta=None: save(
        step, _alter_first_leaf(tree), extra_meta=extra_meta)


def altered_restore(tr):
    restore = tr.ckpt.restore

    def wrong(*a, **kw):
        host, manifest = restore(*a, **kw)
        return _alter_first_leaf(host), manifest

    tr.ckpt.restore = wrong


@pytest.mark.parametrize("cell", ["qwen2-0.5b.save-full", "qwen2-0.5b.resume",
                                  "qwen2-0.5b.train", "qwen2-0.5b.save-delta",
                                  "zamba2-1.2b.train"])
def test_sound_run_is_correct(cell, tiny_root):
    out = run(cell, tiny_root)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell,fault,check", [
    ("qwen2-0.5b.train", unchanged_state, "grad_gap"),
    ("qwen2-0.5b.train", unchanged_state, "grad_dir_gap"),
    ("qwen2-0.5b.train", half_batch, "loss_gap"),
    ("zamba2-1.2b.train", half_batch, "loss_gap"),
    ("qwen2-0.5b.save-full", altered_save, "ckpt_leaves_differ"),
    ("qwen2-0.5b.save-delta", altered_save, "ckpt_leaves_differ"),
    ("qwen2-0.5b.resume", altered_restore, "resume_mismatch"),
])
def test_fault_is_not_correct(cell, fault, check, tiny_root):
    out = run(cell, tiny_root, fault)
    assert not out["correct"]
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]
