"""Readings that the limits of ``correct`` are set from, at a cell's own size.

    python3 benchmarks/cr/calibrate.py --workload <cell> --program-seeds 1,2,... \
        --control-seeds 7,8,9 [--out <file.jsonl>]

In one process, for each program seed: the program's three steps through the
cell's loop, and the plain reference; their gaps are the lower readings.
For each control seed: the reference computed with float8 matmul operands
(the control) and the reference on half the batch (a fault), each against
the float32 reference.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def readings(workload: str, program_seeds, control_seeds, *, root: Path = ROOT,
             allow_cpu: bool = False, hook=None, emit=print):
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import harness as H
    import refmath as R

    cell = H.Cell(workload, root)
    H.check_device(cell.chips, allow_cpu)
    H.configure_jax()
    tmp = Path(tempfile.mkdtemp(prefix="crcal-"))
    tr = H.Trainer(cell, 0, H.Spans(), tmp / "ckpt")
    if hook is not None:
        hook(tr)
    ref = H.Reference(cell)
    out = []
    try:
        from repro.data.pipeline import SyntheticTokens
        for seed in program_seeds:
            tr.pipe = SyntheticTokens(tr.cfg, tr.batch, tr.seq, seed=seed)
            tr.state = ref.make_state(*R.seed_words(seed))
            got = H.program_readings(tr, ref, seed, 0)
            tr.state = None
            base = ref.readings(seed)
            rec = {"kind": "program", "seed": seed, **H.gaps(got, base),
                   "got": got, "ref": base}
            out.append(rec)
            emit(json.dumps(rec))
        emit(json.dumps({"leaves": ref.leaf_names()}))
        for seed in control_seeds:
            base = ref.readings(seed)
            for kind, kw in (("control_fp8", {"mode": "fp8"}),
                             ("fault_half_batch", {"rows": cell.config["batch"] // 2})):
                got = ref.readings(seed, **kw)
                rec = {"kind": kind, "seed": seed, **H.gaps(got, base), "got": got, "ref": base}
                out.append(rec)
                emit(json.dumps(rec))
    finally:
        tr.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.program_seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    sink = open(args.out, "a") if args.out else None

    def emit(line):
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    try:
        recs = readings(args.workload, seeds, controls, emit=emit)
    finally:
        if sink:
            sink.close()
    for kind in sorted({r.get("kind") for r in recs if "kind" in r}):
        rows = [r for r in recs if r["kind"] == kind]
        summary = {k: [min(r[k] for r in rows), max(r[k] for r in rows)]
                   for k in ("loss_gap", "grad_gap", "grad_gap_median",
                             "update_gap", "update_gap_median", "grad_dir_gap")}
        print(json.dumps({"summary": kind, "n": len(rows), "min_max": summary}), flush=True)
    print(f"calibrate: {time.perf_counter() - T_PROCESS:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
