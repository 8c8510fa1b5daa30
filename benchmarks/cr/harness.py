"""One run of one cell of the checkpoint-restart benchmark.

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name: ``BENCHMARK.json`` names them, ``configs/<config>.json`` (with
its plain reference ``configs/<config>.py`` beside it), ``traffic/<mix>.json``
and ``metrics/<metric>.py`` hold them.  A run builds the program's own
training loop (``launch/train.py``'s objects: the jitted step of
``train/step.py``, the data pipeline, ``CRManager`` with its checkpoint
manager and inline coordinator), drives it through set-up and a measured
window, then checks what the window produced against the plain reference.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
PRIME = 16777619


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "crbench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    print(f"[crbench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------

class Cell:
    def __init__(self, workload: str, root: Path = ROOT):
        bench = load_json(root / "BENCHMARK.json")
        try:
            w = next(x for x in bench["workloads"] if x["name"] == workload)
        except StopIteration:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json") from None
        entry = next(c for c in bench["configs"] if c["name"] == w["config"])
        self.name = workload
        self.chips = int(w["chips"])
        self.config_file = root / entry["file"]
        self.config = load_json(self.config_file)
        self.traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")

        def mine(m):
            return "workloads" not in m or workload in m["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]

    def reference(self):
        sys.path.insert(0, str(HERE))
        return load_module(self.config_file.with_suffix(".py"))

    @staticmethod
    def reader(metric: str):
        return load_module(HERE / "metrics" / f"{metric}.py")


# ---------------------------------------------------------------------------
# spans: on the host clock, and in the profiler's trace when it runs
# ---------------------------------------------------------------------------

class Spans:
    def __init__(self):
        self.records: list = []            # (name, t0, t1) on perf_counter

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def wrap(self, name: str, fn, sink=None):
        def wrapped(*a, **kw):
            with self(name):
                out = fn(*a, **kw)
            if sink is not None:
                sink.append(out)
            return out
        return wrapped

    def durations(self, name: str, lo: float, hi: float) -> list:
        return [t1 - t0 for n, t0, t1 in self.records
                if n == name and t0 >= lo and t1 <= hi]


# ---------------------------------------------------------------------------
# digests: equality of device trees without moving them to the host
# ---------------------------------------------------------------------------

def make_digest():
    import jax
    import jax.numpy as jnp

    def leaf(x):
        w = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
        i = jnp.arange(w.shape[0], dtype=jnp.uint32)
        mixed = (w ^ (i * jnp.uint32(PRIME))) * (i | jnp.uint32(1))
        return jnp.stack([jnp.bitwise_xor.reduce(mixed), jnp.sum(mixed, dtype=jnp.uint32)])

    fn = jax.jit(lambda tree: jnp.stack([leaf(x) for x in jax.tree_util.tree_leaves(tree)]))
    return lambda tree: np.asarray(fn(tree))


# ---------------------------------------------------------------------------
# the program's training loop, built as launch/train.py builds it
# ---------------------------------------------------------------------------

class Trainer:
    def __init__(self, cell: Cell, seed: int, spans: Spans, ckpt_root: Path):
        from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
        from repro.checkpoint.store import TieredStore
        from repro.configs.base import get_config
        from repro.core import cr_manager as CRM
        from repro.core.signals import SignalTrap
        from repro.core.worker import InlineCoordinator
        from repro.data.pipeline import SyntheticTokens
        from repro.launch.mesh import make_host_mesh
        from repro.optim import adamw
        from repro.parallel.mesh_rules import Rules
        from repro.train import step as TS

        c, tr = cell.config, cell.traffic
        self.spans = spans
        self.batch, self.seq = c["batch"], c["seq"]
        self.cfg = get_config(c["arch"]).replace(**c["model"])
        self.oc = adamw.OptConfig(**c["optimizer"])
        mesh = make_host_mesh()
        self.rules = Rules(mesh)
        self.jitted, _, _ = TS.make_train_step(
            self.cfg, mesh, self.oc, rules=self.rules, donate=False, z_loss=c["z_loss"])
        if tr.get("device_fp_impl"):
            os.environ["REPRO_DEVICE_FP_IMPL"] = tr["device_fp_impl"]
        self.ckpt = CheckpointManager(TieredStore(ckpt_root), CheckpointPolicy(**tr["ckpt"]))
        # spans around the calls into each layer, from the benchmark's side
        self.parts: list = []
        self.manifests: list = []
        self.restore_orig = self.ckpt.restore
        self.ckpt.save = spans.wrap("bench.ckpt_save", self.ckpt.save, self.parts)
        self.ckpt.commit = spans.wrap("bench.ckpt_commit", self.ckpt.commit, self.manifests)
        self.ckpt.restore = spans.wrap("bench.ckpt_restore", self.ckpt.restore)
        self._patched = [(CRM, "fetch_tree", CRM.fetch_tree), (CRM, "place_tree", CRM.place_tree)]
        CRM.fetch_tree = spans.wrap("bench.fetch_tree", CRM.fetch_tree)
        CRM.place_tree = spans.wrap("bench.place_tree", CRM.place_tree)
        self.client = InlineCoordinator(commit_fn=self.ckpt.commit)
        self.trap = SignalTrap().__enter__()
        self.crm = CRM.CRManager(
            self.ckpt, client=self.client, signal_trap=self.trap,
            interval_steps=tr["interval_steps"], cfg=self.cfg, rules=self.rules,
            log=lambda s: print(s, file=sys.stderr, flush=True))
        self.pipe = SyntheticTokens(self.cfg, self.batch, self.seq, seed=seed)
        self.templates = {"state": TS.abstract_train_state(self.cfg, self.oc)}
        self.axes = {"state": TS.state_logical_axes(self.cfg)}
        self.state = None
        self.check_s = 0.0            # set-up time spent on the checks, not on the program

    def close(self) -> None:
        try:
            self.crm.close()
        finally:
            self.trap.__exit__(None, None, None)
            for mod, name, fn in self._patched:
                setattr(mod, name, fn)

    def step(self) -> float:
        """One step as the training loop drives it: next batch, the jitted
        step, the loss on the host."""
        with self.spans("bench.step"):
            batch = next(self.pipe)
            self.state, metrics = self.jitted(self.state, batch)
            return float(metrics["loss"])

    def boundary(self, step: int) -> str:
        extra = {"data_state": self.pipe.state().to_dict()}
        state = self.state
        with self.spans("bench.step_boundary"):
            return self.crm.step_boundary(step, lambda: state, extra_meta=extra)

    def save_at(self, step: int, reason: str) -> dict:
        """The boundary of ``step`` with a checkpoint due: the stall the loop
        sees, and the time until the manifest is committed."""
        self.client.request(reason)
        wall0, t0 = time.time(), time.perf_counter()
        action = self.boundary(step)
        stall = time.perf_counter() - t0
        if action != "checkpointed":
            raise RuntimeError(f"boundary of step {step} did not save: {action}")
        man = self.manifests[-1]
        if man.get("step") != step:
            raise RuntimeError(f"committed step {man.get('step')}, expected {step}")
        return {"step": step, "stall_s": stall,
                "durable_s": man["committed_at"] - wall0,
                "delta": (self.parts[-1] or {}).get("delta")}


# ---------------------------------------------------------------------------
# the reference, run after the window
# ---------------------------------------------------------------------------

class Reference:
    """The plain reference of a configuration: weights from the seed, three
    AdamW steps on the rows the pipeline's counter gives each step."""

    def __init__(self, cell: Cell):
        import jax
        import refmath as R
        self.R, self.jax = R, jax
        c = cell.config
        self.c, self.m = c, c["model"]
        ref = cell.reference()
        self.specs = ref.param_specs(self.m)
        self.loss = ref.loss
        self._make = jax.jit(lambda lo, hi: R.make_params(self.specs, lo, hi))
        self.delta_norms = jax.jit(self._delta_norms)
        self._steps: dict = {}

    def make_state(self, lo, hi):
        """The initial train state, in one program on the device."""
        import jax.numpy as jnp
        R, specs = self.R, self.specs

        def build(lo, hi):
            p = R.make_params(specs, lo, hi)
            z = self.jax.tree_util.tree_map(jnp.zeros_like, p)
            return {"params": p, "opt": {"m": z, "v": self.jax.tree_util.tree_map(
                jnp.zeros_like, p)}, "step": jnp.zeros((), jnp.int32)}

        return self.jax.jit(build)(lo, hi)

    def leaf_names(self) -> list:
        """Names of the weight leaves, in the order of the per-leaf norms."""
        shapes = self.jax.eval_shape(lambda: self.R.make_params(self.specs, 0, 0))
        return [self.jax.tree_util.keystr(p)
                for p, _ in self.jax.tree_util.tree_flatten_with_path(shapes)[0]]

    def _delta_norms(self, lo, hi, params):
        """Per-leaf norms of (params - initial params), the initial ones
        drawn again from the seed inside the program."""
        p0 = self.R.make_params(self.specs, lo, hi)
        return self.R.leaf_norms(self.jax.tree_util.tree_map(lambda a, b: a - b, params, p0))

    def _step(self, mode: str, rows: int):
        key = (mode, rows)
        if key not in self._steps:
            R, jax, opt = self.R, self.jax, self.c["optimizer"]

            def step(params, m, v, tokens, k, lo, hi):
                loss, grads = jax.value_and_grad(self.loss)(
                    params, tokens, self.m, self.c["z_loss"], mode)
                params, m, v, g = R.adamw(params, grads, m, v, k, opt)
                return params, m, v, loss, R.leaf_norms(g), R.leaf_projections(g, lo, hi)

            self._steps[key] = jax.jit(step, donate_argnums=(0, 1, 2))
        return self._steps[key]

    def readings(self, seed: int, mode: str = "f32", rows: int | None = None,
                 steps: int = 3) -> dict:
        """Losses of each step, per-leaf norms of the first (clipped)
        gradient, and per-leaf norms of the weights' change after
        ``steps`` steps.  ``rows`` < batch takes the mean over the first rows
        only (a fault planted in the reference's place)."""
        import jax.numpy as jnp
        R, jax = self.R, self.jax
        lo, hi = R.seed_words(seed)
        rows = rows or self.c["batch"]
        with jax.default_matmul_precision("highest"):
            params = self._make(lo, hi)
            m = jax.tree_util.tree_map(jnp.zeros_like, params)
            v = jax.tree_util.tree_map(jnp.zeros_like, params)
            losses, gnorms, gproj = [], None, None
            step = self._step(mode, rows)
            for k in range(steps):
                tok = R.batch_tokens(seed, k, self.c["batch"], self.c["seq"],
                                     self.m["vocab_size"])[:rows]
                params, m, v, loss, gn, gp = step(params, m, v, jnp.asarray(tok),
                                                  jnp.asarray(k, jnp.float32), lo, hi)
                losses.append(float(loss))
                if gnorms is None:
                    gnorms, gproj = np.asarray(gn), np.asarray(gp)
            del m, v
            dn = np.asarray(self.delta_norms(lo, hi, params))
        del params
        return {"losses": losses, "grad_norms": gnorms.tolist(),
                "grad_proj": gproj.tolist(), "update_norms": dn.tolist()}


def program_readings(tr, ref, seed: int, step: int, last_boundary: bool = True) -> dict:
    """Three steps of the program from the seed's state, through the loop's
    own call and feed: each loss, per-leaf norms of the first gradient as
    the optimizer got it (Adam's first moment after one step, over 1 - b1),
    and per-leaf norms of the weights' change after the three."""
    import jax
    import refmath as R
    b1 = tr.oc.b1
    lo, hi = R.seed_words(seed)

    @jax.jit
    def first_grad(m, lo, hi):
        g = jax.tree_util.tree_map(lambda x: x / (1.0 - b1), m)
        return R.leaf_norms(g), R.leaf_projections(g, lo, hi)

    out = {"losses": []}
    for k in range(3):
        out["losses"].append(tr.step())
        if k == 0:
            t0 = time.perf_counter()
            gn, gp = first_grad(tr.state["opt"]["m"], lo, hi)
            out["grad_norms"], out["grad_proj"] = np.asarray(gn).tolist(), np.asarray(gp).tolist()
            tr.check_s += time.perf_counter() - t0
        if k < 2 or last_boundary:
            tr.boundary(step + k)
    t0 = time.perf_counter()
    out["update_norms"] = np.asarray(ref.delta_norms(lo, hi, tr.state["params"])).tolist()
    tr.check_s += time.perf_counter() - t0
    return out


def gaps(got: dict, ref: dict) -> dict:
    """The numbers that may be compared.  ``loss_gap``: the widest loss gap
    over the steps.  By leaf, the gap between the program's norm and the
    reference's, over the reference's norm of that leaf or of the median
    leaf, whichever is larger: ``grad_gap`` and ``update_gap`` take the
    worst leaf, ``grad_gap_median`` and ``update_gap_median`` the median
    leaf.  The change of weights leaves out leaves whose reference gradient
    is under a thousandth of the median leaf's (they move by round-off alone
    under Adam).  A NaN on either side reads as infinitely far."""
    gl, rl = np.asarray(got["losses"]), np.asarray(ref["losses"])
    gg, rg = np.asarray(got["grad_norms"]), np.asarray(ref["grad_norms"])
    gu, ru = np.asarray(got["update_norms"]), np.asarray(ref["update_norms"])
    gmed, umed = float(np.median(rg)), float(np.median(ru))
    gp, rp = np.asarray(got["grad_proj"]), np.asarray(ref["grad_proj"])
    moving = rg >= 1e-3 * gmed
    g_rel = np.abs(gg - rg) / np.maximum(rg, gmed)
    u_rel = np.abs(gu - ru)[moving] / np.maximum(ru[moving], umed)
    out = {
        "loss_gap": float(np.max(np.abs(gl - rl))),
        "grad_gap": float(np.max(g_rel)),
        "grad_gap_median": float(np.median(g_rel)),
        "update_gap": float(np.max(u_rel)),
        "update_gap_median": float(np.median(u_rel)),
        # the median leaf's gradient error, from random projections
        "grad_dir_gap": float(np.median(np.sqrt(np.mean((gp - rp) ** 2, axis=1))
                                        / np.maximum(rg, gmed))),
    }
    return {k: (float("inf") if np.isnan(v) else v) for k, v in out.items()}


def over_limit(checks: dict, limits: dict) -> list:
    """Names of the compared numbers above their limits (NaN counts as above)."""
    return [k for k, v in checks.items() if not v <= limits[k]]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def check_device(chips: int, allow_cpu: bool = False) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    log(f"device: platform {info['platform']}, device_kind {info['kind']}, count {info['count']}")
    if not allow_cpu and (info["platform"] == "cpu" or info["count"] < chips):
        raise SystemExit(f"[crbench] needs {chips} accelerator chip(s); found "
                         f"{info['count']} {info['platform']} device(s)")
    return info


def configure_jax() -> str:
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    path = configure_compile_cache()
    Path(path).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, allow_cpu: bool = False, hook=None,
        dump_trace: str | None = None, root: Path = ROOT) -> dict:
    """One run of ``workload``; returns the result line.  ``hook(trainer)``
    runs once the program is built (the fault tests break it there);
    ``allow_cpu`` skips the look for a chip."""
    cell = Cell(workload, root)
    device = check_device(cell.chips, allow_cpu)
    import jax
    cache_dir = configure_jax()
    log(f"compile cache: {cache_dir}")
    sys.path.insert(0, str(HERE))
    import peaks as PK

    peaks = None if allow_cpu else PK.peaks_for(device["kind"])
    tmp = Path(tempfile.mkdtemp(prefix="crbench-"))
    spans = Spans()
    tr = None
    try:
        tr = Trainer(cell, seed, spans, tmp / "ckpt")
        if hook is not None:
            hook(tr)
        out = _run(cell, tr, spans, seed, seconds, trace, t_process, device,
                   peaks, tmp, dump_trace, root)
    finally:
        if tr is not None:
            tr.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def warm_write_path(tr, tmp: Path, root: Path) -> float | None:
    """On the first run in this checkout, write what one save writes (the
    state's bytes once per replica, a thread per replica) to files beside
    the checkpoints, and delete them.  On a TPU v5e machine the first
    writes of that volume run slower than its later ones, so without this
    the first save on each fresh machine reads 1-2 s slow; a run in a
    checkout that has warmed it before skips it.  Returns its seconds, or
    None when skipped."""
    import threading
    import jax
    marker = root / ".crbench" / "write-path-warm"
    if marker.exists():
        return None
    nbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree_util.tree_leaves(tr.templates["state"]))
    block = np.random.default_rng(0).integers(0, 256, 1 << 26, dtype=np.uint8).tobytes()
    where = tmp / "warm"
    where.mkdir(parents=True)

    def fill(path: Path) -> None:
        with open(path, "wb") as f:
            left = nbytes
            while left > 0:
                left -= f.write(block[:min(left, len(block))])

    t0 = time.perf_counter()
    threads = [threading.Thread(target=fill, args=(where / f"replica{i}",))
               for i in range(tr.ckpt.replicas)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    shutil.rmtree(where)
    marker.parent.mkdir(parents=True, exist_ok=True)
    marker.write_text(f"{nbytes * len(threads)}\n")
    return time.perf_counter() - t0


def _run(cell, tr, spans, seed, seconds, trace, t_process, device, peaks, tmp,
         dump_trace, root):
    import jax
    import refmath as R
    tf = cell.traffic
    if tf.get("warm_write_path"):
        warm = warm_write_path(tr, tmp, root)
        log(f"{cell.name}: write path " + ("warm already" if warm is None
                                           else f"warmed in {warm:.3f} s"))
    ref = Reference(cell)
    digest = make_digest()
    lo, hi = R.seed_words(seed)

    # --- set-up: state from the seed, then three steps through the loop ---
    init = ref.make_state(lo, hi)
    _check_layout(init, tr.templates["state"])
    tr.state, meta, step = tr.crm.restore_or_init(lambda: init, tr.templates, tr.axes)
    del init
    if meta is not None:
        raise RuntimeError("a fresh checkpoint directory held a checkpoint")
    first = program_readings(tr, ref, seed, step, last_boundary=not tf["setup_save"])
    step += 3

    setup_save = None
    saved_digest = None
    if tf["setup_save"]:
        t0 = time.perf_counter()
        saved_digest = digest(tr.state)
        tr.check_s += time.perf_counter() - t0
        setup_save = tr.save_at(step - 1, "setup")
    uninterrupted = None
    t0 = time.perf_counter()
    if tf["loop"] == "resume":
        # the uninterrupted step the resumes are held to
        loss4 = tr.step()
        uninterrupted = {"loss": loss4, "digest": digest(tr.state)}
        tr.state = None
    elif tf["save_after_steps"]:
        digest(tr.state)                 # compiles the digest used after the save
    jax.block_until_ready(tr.state)
    tr.check_s += time.perf_counter() - t0
    # set-up is what the program needs before the window; the checks' own
    # work in it is left out
    setup_s = time.perf_counter() - t_process - tr.check_s
    log(f"{cell.name}: set-up {setup_s:.3f} s (checks {tr.check_s:.3f} s left out)"
        + (f", set-up save {setup_save['stall_s']:.3f} s" if setup_save else ""))

    # --- the measured window ---
    trace_dir = tmp / "trace"
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    saves, resumes, n_steps = [], [], 0
    with spans("bench.window"):
        t_open = time.perf_counter()
        if tf["loop"] == "train":
            due = set(tf["save_after_steps"])
            last_due = max(due, default=-1)
            k = 0
            # the window lasts its seconds, and at least until its saves are in
            while time.perf_counter() - t_open < seconds or k <= last_due:
                tr.step()
                n_steps += 1
                if k in due:
                    rec = tr.save_at(step, "interval")
                    rec["digest"] = digest(tr.state)
                    saves.append(rec)
                else:
                    tr.boundary(step)
                step += 1
                k += 1
        else:
            while time.perf_counter() - t_open < seconds:
                resumes.append(_resume_once(tr, spans, digest, saved_digest, uninterrupted))
                n_steps += 1
        t_close = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window_s = t_close - t_open
    dev = jax.devices()[0]
    device = dict(device)
    device["memory_peak_bytes"] = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    tr.state = None
    gc.collect()

    # --- what the window produced, against the reference ---
    checks = {}
    limits = cell.config["limits"]
    got_ref = ref.readings(seed)
    checks.update({k: v for k, v in gaps(first, got_ref).items() if k in limits})
    attempted, failed = 1, 0
    if saves:
        bad = 0
        for s in saves:
            host, _ = tr.restore_orig(tr.templates["state"], step=s["step"])
            bad += int(np.sum(np.any(digest(jax.device_put(host)) != s["digest"], axis=1)))
            del host
            attempted += 1
            failed += int(bad > 0)
        checks["ckpt_leaves_differ"] = bad
    if resumes:
        mism = sum(r["mismatch"] for r in resumes)
        attempted += len(resumes)
        failed += sum(1 for r in resumes if r["mismatch"])
        checks["resume_mismatch"] = mism
    over = over_limit(checks, limits)
    failed += int(any(k not in ("ckpt_leaves_differ", "resume_mismatch") for k in over))
    correct = not over

    # --- metrics ---
    tokens = n_steps * tr.batch * tr.seq
    e2e = {
        "setup_s": setup_s,
        "save_stall_s": statistics.mean(s["stall_s"] for s in saves) if saves else None,
        "ckpt_durable_s": statistics.mean(s["durable_s"] for s in saves) if saves else None,
        "resume_s": statistics.mean(r["resume_s"] for r in resumes) if resumes else None,
        "train_tokens_per_s": tokens / window_s if tf["loop"] == "train" else None,
    }
    log(f"{cell.name}: window {window_s:.3f} s, {n_steps} steps, "
        f"saves {[round(s['stall_s'], 4) for s in saves]}, "
        f"resumes {[round(r['resume_s'], 4) for r in resumes]}, first losses {first['losses']}")
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if not trace:
        metrics = {}
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is None:
                raise RuntimeError(f"{cell.name} measured no {m['name']}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    else:
        import tracered
        import flops as FL
        norm = tracered.normalize(str(trace_dir))
        if dump_trace:
            with open(dump_trace, "w") as f:
                json.dump(norm, f)
        red = tracered.reduce(norm)
        ctx = {
            "spans": spans, "t_open": t_open, "t_close": t_close, "window_s": window_s,
            "saves": saves, "resumes": resumes, "steps": n_steps, "tokens": tokens,
            "trace": norm, "reduced": red, "peaks": peaks,
            "flops_per_token": FL.flops_per_token(cell.config["model"], tr.seq),
            "train_tokens_per_s": e2e["train_tokens_per_s"],
        }
        metrics = {}
        for m in cell.per_layer:
            v = Cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["device"] = device
        result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check {k} {v} limit {limits[k]}", file=sys.stderr, flush=True)
    return result


def _resume_once(tr, spans, digest, saved_digest, uninterrupted) -> dict:
    """One requeue: drop the state and JAX's in-memory caches, restore,
    place, and take the first step on the restored state."""
    import jax
    from repro.data.pipeline import PipelineState
    tr.state = None
    jax.clear_caches()
    gc.collect()
    t0 = time.perf_counter()
    state, meta, start = tr.crm.restore_or_init(_no_checkpoint, tr.templates, tr.axes)
    tr.pipe.restore(PipelineState.from_dict(meta["data_state"]))
    tr.state = state
    with spans("bench.first_step"):
        loss = tr.step()
    resume_s = time.perf_counter() - t0
    mism = int(np.any(digest(state) != saved_digest))
    mism += int(loss != uninterrupted["loss"])
    mism += int(np.any(digest(tr.state) != uninterrupted["digest"]))
    del state
    return {"resume_s": resume_s, "start_step": start, "mismatch": mism}


def _no_checkpoint():
    raise RuntimeError("resume found no committed checkpoint")


def _check_layout(state, template) -> None:
    """The benchmark's weights have to fit the program's train state."""
    import jax
    got = jax.tree_util.tree_flatten_with_path(state)[0]
    want = jax.tree_util.tree_flatten_with_path(template)[0]
    gs = [(jax.tree_util.keystr(p), x.shape, str(x.dtype)) for p, x in got]
    ws = [(jax.tree_util.keystr(p), x.shape, str(x.dtype)) for p, x in want]
    if gs != ws:
        diff = sorted(set(gs) ^ set(ws))[:6]
        raise RuntimeError(f"the configuration's reference layout differs from "
                           f"the program's train state: {diff}")
