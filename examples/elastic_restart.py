"""Elastic (MxN) restart: checkpoint under one mesh, resume under another.

    PYTHONPATH=src python examples/elastic_restart.py

DMTCP's process virtualization lets a checkpoint restart on different nodes;
the framework's topology virtualization lets one restart on a different *chip
topology*.  This example trains on a simulated (4 data x 2 model) mesh,
checkpoints, then resumes on (2 data x 4 model) and on (8 data x 1 model) —
same bits, new sharding, training continues.  Each phase runs in a subprocess
because the host-device count must be set before jax initializes.
"""
import os
import subprocess
import sys
from pathlib import Path
import tempfile

ROOT = Path(__file__).resolve().parents[1]

PHASE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, jax
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

shape, out, mode = eval(sys.argv[1]), sys.argv[2], sys.argv[3]
axes = ("data", "model") if len(shape) == 2 else ("pod", "data", "model")
cfg = reduced(get_config("llama3.2-1b"))
oc = adamw.OptConfig(warmup_steps=2, decay_steps=20)
mesh = make_mesh(shape, axes)
rules = Rules(mesh)
step_fn, *_ = TS.make_train_step(cfg, mesh, oc, rules=rules, donate=False)
mgr = CheckpointManager(TieredStore(Path(out)))
pipe = SyntheticTokens(cfg, 8, 32, seed=1)
with mesh:
    if mode == "save":
        state = TS.init_train_state(cfg, oc, jax.random.PRNGKey(0))
        for step in range(4):
            state, m = step_fn(state, next(pipe))
        mgr.save(3, fetch_tree(state)); mgr.commit(3)
        print(f"saved at step 3 on mesh {shape}, loss {float(m['loss']):.5f}")
    else:
        host, man = mgr.restore(TS.abstract_train_state(cfg, oc))
        state = place_tree(host, TS.state_logical_axes(cfg), rules)
        sh = jax.tree_util.tree_leaves(state)[1].sharding
        state, m = step_fn(state, pipe.batch_at(4))
        print(f"resumed on mesh {shape}: step 4 loss {float(m['loss']):.5f} "
              f"(example param sharding: {sh.spec})")
"""


def run(shape, out, mode):
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", PHASE, repr(shape), out, mode],
                       env=env, text=True, capture_output=True, timeout=600)
    if r.returncode != 0:
        print(r.stdout, r.stderr)
        raise SystemExit(1)
    print("  " + r.stdout.strip().splitlines()[-1])


if __name__ == "__main__":
    with tempfile.TemporaryDirectory() as d:
        print("checkpoint on (4,2):")
        run((4, 2), d, "save")
        print("elastic restores:")
        for shape in [(4, 2), (2, 4), (8, 1), (2, 2, 2)]:
            run(shape, d, "restore")
        print("OK — one checkpoint, four topologies")
