"""The control, the reference computed with float8 matmul operands, has to
come out as not correct, where the program comes out correct.  Here at a
tiny size on the CPU; on the chip at the cells' own size by
``benchmarks/cr/calibrate.py`` (readings in PERF.md)."""
import json

import pytest

import calibrate
import harness

SEEDS = [11, 12, 2 ** 31 + 13]


@pytest.mark.parametrize("cell", ["qwen2-0.5b.train", "zamba2-1.2b.train"])
def test_control_fails_where_program_passes(cell, tiny_root):
    config = cell.rsplit(".", 1)[0]
    limits = json.loads((tiny_root / "configs" / f"{config}.json").read_text())["limits"]
    recs = calibrate.readings(cell, SEEDS, SEEDS, root=tiny_root, allow_cpu=True,
                              emit=lambda _: None)
    for r in recs:
        over = harness.over_limit({k: r[k] for k in limits if k in r}, limits)
        if r["kind"] == "program":
            assert not over, r
        else:
            assert over, r
