"""Shared set-up of the benchmark's own tests.  Run them by path, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/cr/tests
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

CR = Path(__file__).resolve().parents[1]
ROOT = CR.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(CR)]

# tiny sizes of the two configurations, computed in float32 so that the
# program and the reference agree to rounding
TINY = {
    "qwen2-0.5b": dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                       head_dim=16, d_ff=128, vocab_size=256),
    "zamba2-1.2b": dict(num_layers=4, d_model=64, num_heads=4, num_kv_heads=4,
                        head_dim=16, d_ff=128, vocab_size=256, ssm_state_dim=16,
                        ssm_head_dim=16, shared_attn_period=2, ssm_chunk=32),
}


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout-like root whose BENCHMARK.json names the real cells, on
    tiny copies of the configurations (references copied beside them)."""
    root = tmp_path_factory.mktemp("tiny")
    (root / "configs").mkdir()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # cells the benchmark keeps files for but does not run yet: the zamba2
    # configuration and the delta plane on qwen2
    bench["configs"].append({"name": "zamba2-1.2b",
                             "file": "benchmarks/cr/configs/zamba2-1.2b.json"})
    bench["workloads"] += [
        {"name": "zamba2-1.2b.train", "config": "zamba2-1.2b", "traffic": "train", "chips": 1},
        {"name": "qwen2-0.5b.save-delta", "config": "qwen2-0.5b", "traffic": "save-delta",
         "chips": 1}]
    for m in bench["end_to_end"]:
        if "qwen2-0.5b.save-full" in m.get("workloads", ()):
            m["workloads"].append("qwen2-0.5b.save-delta")
        if "qwen2-0.5b.train" in m.get("workloads", ()):
            m["workloads"].append("zamba2-1.2b.train")
    for entry in bench["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        cfg["model"].update(TINY[entry["name"]], compute_dtype="float32")
        cfg.update(batch=4, seq=16)
        (root / "configs" / f"{entry['name']}.json").write_text(json.dumps(cfg))
        shutil.copy(ROOT / entry["file"].replace(".json", ".py"),
                    root / "configs" / f"{entry['name']}.py")
        entry["file"] = f"configs/{entry['name']}.json"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(autouse=True)
def _cache_outside_checkout(monkeypatch, tmp_path_factory):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))
