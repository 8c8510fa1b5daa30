"""End-to-end behaviour tests for the paper's system: the train driver's C/R
surface (cold start, interval checkpoints, restore, async mode, incremental),
exercised through the public CLI in-process."""
import json
from pathlib import Path

import numpy as np
import pytest

from repro.launch import train as T
from repro.sched.slurmsim import REQUEUE_EXIT


def _run(tmp_path, extra, steps=8, tag="m"):
    out = tmp_path / f"{tag}.json"
    code = T.main([
        "--arch", "qwen2-0.5b", "--reduced", "--steps", str(steps),
        "--batch", "2", "--seq", "32", "--ckpt-dir", str(tmp_path / "ckpt"),
        "--metrics-out", str(out), *extra])
    metrics = json.loads(out.read_text()) if out.exists() else []
    return code, metrics


def test_cold_start_and_resume(tmp_path):
    code, m1 = _run(tmp_path, ["--interval-steps", "3"], steps=6, tag="a")
    assert code == 0 and len(m1) == 6
    # resume continues from the final checkpoint, not step 0
    code, m2 = _run(tmp_path, ["--interval-steps", "3"], steps=9, tag="b")
    assert code == 0
    assert m2[0]["step"] == 6, m2[:2]


def test_async_and_incremental_modes(tmp_path):
    # lr=0 keeps params frozen -> param leaves dedup across checkpoints, while
    # optimizer moments still change and are rewritten (AdamW touches every
    # moment every step; incremental pays off for frozen/stable subsets).
    code, m = _run(tmp_path, ["--interval-steps", "2", "--ckpt-mode", "async",
                              "--ckpt-incremental", "--lr", "0.0"], steps=6)
    assert code == 0 and len(m) == 6
    manifests = [json.loads(p.read_text())
                 for p in (tmp_path / "ckpt").rglob("MANIFEST.json")]
    assert manifests
    man = max(manifests, key=lambda m: m["step"])   # latest step, not path order
    reused = [e for e in man["leaves"] if e.get("reused")]
    rewritten = [e for e in man["leaves"] if not e.get("reused")]
    assert reused, "incremental never reused frozen params"
    assert any(e["path"].startswith("opt/") for e in rewritten)


def test_walltime_exit_requeues(tmp_path):
    code, m = _run(tmp_path, ["--walltime", "0.5", "--margin", "100",
                              "--step-sleep", "0.01"], steps=50)
    # margin > walltime => near_limit immediately after first step
    assert code == REQUEUE_EXIT
    assert len(m) >= 1
    req = json.loads((tmp_path / "ckpt" / "requeue.json").read_text())
    assert req["requeues"] == 1 and req["last_step"] >= 0


def test_loss_goes_down_on_learnable_data():
    """Uniform-random tokens start at the optimal CE (ln V) — overfit one
    fixed batch instead to verify the optimizer actually learns."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config, reduced
    from repro.launch.mesh import make_host_mesh
    from repro.optim import adamw
    from repro.parallel.mesh_rules import Rules
    from repro.train import step as TS

    cfg = reduced(get_config("qwen2-0.5b"))
    oc = adamw.OptConfig(lr=1e-3, warmup_steps=2, decay_steps=50)
    mesh = make_host_mesh()
    jitted, *_ = TS.make_train_step(cfg, mesh, oc, rules=Rules(mesh), donate=False)
    state = TS.init_train_state(cfg, oc, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)}
    losses = []
    for _ in range(25):
        state, metrics = jitted(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 1.0, (losses[0], losses[-1])


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "checkout"])
def test_compile_cache_has_one_fixed_path(tmp_path, monkeypatch, env_dir):
    """$JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache:
    never a path that moves between restarts."""
    import jax

    from repro.launch import compile_cache as CC

    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(CC.CHECKOUT_CACHE_DIR)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert CC.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert CC.CHECKOUT_CACHE_DIR == (
        Path(__file__).resolve().parents[1] / ".jax_cache")
