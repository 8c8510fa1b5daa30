"""Elastic (MxN) restart: a checkpoint taken under one mesh restores onto a
different mesh factorization with identical values — the framework analogue of
DMTCP's process virtualization.  Runs in subprocesses because the device count
must be forced before jax initializes (and must NOT leak into other tests)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

_SAVE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, jax, numpy as np
from pathlib import Path
from repro.configs.base import get_config, reduced
from repro.optim import adamw
from repro.train import step as TS
from repro.parallel.mesh_rules import Rules
from repro.checkpoint.store import TieredStore
from repro.checkpoint.manager import CheckpointManager
from repro.core.virtualization import fetch_tree, place_tree
from repro.data.pipeline import SyntheticTokens
from repro.launch.mesh import make_mesh

mesh_shape = eval(sys.argv[1]); out = sys.argv[2]; mode = sys.argv[3]
cfg = reduced(get_config("llama3.2-1b"))
oc = adamw.OptConfig(warmup_steps=2, decay_steps=10)
mesh = make_mesh(mesh_shape, ("data", "model")[:len(mesh_shape)] if len(mesh_shape)==2 else ("pod","data","model"))
rules = Rules(mesh)
step_fn, st_sh, bsf = TS.make_train_step(cfg, mesh, oc, rules=rules, donate=False)
store = TieredStore(Path(out))
mgr = CheckpointManager(store)
pipe = SyntheticTokens(cfg, 8, 32, seed=5)
if mode == "save":
    state = TS.init_train_state(cfg, oc, jax.random.PRNGKey(3))
    state = place_tree(fetch_tree(state), TS.state_logical_axes(cfg), rules)
    with mesh:
        for _ in range(3):
            state, m = step_fn(state, next(pipe))
    mgr.save(2, fetch_tree(state)); mgr.commit(2)
    print("SAVED", float(m["loss"]))
else:
    host, man = mgr.restore(TS.abstract_train_state(cfg, oc))
    state = place_tree(host, TS.state_logical_axes(cfg), rules)
    with mesh:
        state, m = step_fn(state, pipe.batch_at(3))
    print("STEP4", repr(float(m["loss"])))
"""


@pytest.mark.slow
@pytest.mark.parametrize("restore_mesh", ["(2, 4)", "(8, 1)", "(1, 8)", "(2, 2, 2)"])
def test_elastic_restore_other_mesh(tmp_path, restore_mesh):
    env = {**os.environ, "PYTHONPATH": SRC}
    env.pop("XLA_FLAGS", None)

    def run(mesh, mode):
        r = subprocess.run(
            [sys.executable, "-c", _SAVE, mesh, str(tmp_path), mode],
            env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        return r.stdout

    run("(4, 2)", "save")
    base = run("(4, 2)", "restore")          # same mesh: reference next-step loss
    other = run(restore_mesh, "restore")     # different mesh factorization
    l1 = base.strip().splitlines()[-1]
    l2 = other.strip().splitlines()[-1]
    assert l1.startswith("STEP4") and l2.startswith("STEP4")
    a, b = float(l1.split()[1]), float(l2.split()[1])
    # same restored state, same batch; resharded execution may reassociate
    # reductions, so allow tiny numerical slack
    assert abs(a - b) < 5e-4, (a, b)
