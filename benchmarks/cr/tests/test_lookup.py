"""Every name in BENCHMARK.json resolves to its files, and a new cell is
one more entry over files that already exist."""
import json

import pytest

import harness
from conftest import CR, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_config_mix_and_metrics(cell):
    c = harness.Cell(cell)
    w = next(x for x in BENCH["workloads"] if x["name"] == cell)
    assert c.config["name"] == w["config"]
    assert c.traffic["loop"] in ("train", "resume")
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert callable(c.reference().loss)
    for m in c.per_layer:
        assert callable(harness.Cell.reader(m["name"]).read)


def test_every_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        assert (CR / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        harness.Cell("qwen2-0.5b.no-such-mix")


def test_next_cell_is_data_only(tmp_path):
    """qwen2-0.5b.save-delta: its configuration, its mix and the readers of
    the delta plane's counters are files that exist, so adding it is
    entries in BENCHMARK.json alone."""
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "qwen2-0.5b.save-delta", "config": "qwen2-0.5b",
                               "traffic": "save-delta", "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if "qwen2-0.5b.save-full" in m.get("workloads", ()):
            m["workloads"].append("qwen2-0.5b.save-delta")
    for name in ("save_d2h_s.delta", "save_write_s.delta"):
        bench["per_layer"].append({"name": name, "unit": "s", "better": "lower",
                                   "source": "program_counter", "layer": "x",
                                   "moves": "save_stall_s",
                                   "workloads": ["qwen2-0.5b.save-delta"]})
    for entry in bench["configs"]:
        entry["file"] = str(ROOT / entry["file"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = harness.Cell("qwen2-0.5b.save-delta", tmp_path)
    assert c.config["name"] == "qwen2-0.5b"
    assert c.traffic["ckpt"]["delta"] and c.traffic["ckpt"]["device_fp"]
    assert {m["name"] for m in c.end_to_end} == {"save_stall_s", "ckpt_durable_s", "setup_s"}
    for m in c.per_layer:
        assert callable(harness.Cell.reader(m["name"]).read)
