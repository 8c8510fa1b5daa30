"""The program's one tracer: spans and counters of the checkpoint-restart path.

``span(name, **attrs)`` times a region on ``time.perf_counter_ns()`` and
records its name, id, parent, thread and attributes; while the tracer is on
it also enters ``jax.profiler.TraceAnnotation(name)``, so every span lands
in a profiler trace on the clock of the device's ops.  The parent is the
innermost open span of the calling thread; work handed to another thread
passes ``parent=current()`` of the submitting thread along with the task.
``count(name, value, **labels)`` adds to a counter and records the
increment with its time, so a reader can take a counter inside any window.

Off by default.  Then ``span`` returns one shared null handle after a single
flag test: no annotation, no clock read, no allocation.  A caller that keeps
a field for the region's duration asks for ``timed=True`` and reads
``elapsed_s`` after the block; that handle reads the clock whether the
tracer is on or off, so the region has one timer.  ``cpu=True`` (the
top-level save, commit and restore spans) adds the process's user and system
CPU seconds over the span, while the tracer is on.

``enable()`` also records a ``repro.host.gc`` span per garbage collection
and JAX's compile events as ``repro.jit.*`` counters
(``launch/compile_cache.watch_jit``).  ``follow_profiler()`` turns the
tracer on while a ``jax.profiler`` trace is being collected and off again
after it, so a profiled window carries the program's spans without a flag
(a stopgap; see its docstring).
Records are held in memory up to ``MAX_RECORDS``; past that
``repro.tracing.dropped`` counts what was not kept.  ``dump(path)`` writes
them as JSON.  Every span and counter name starts with ``repro.``.
"""
from __future__ import annotations

import gc
import itertools
import json
import resource
import threading
import time

MAX_RECORDS = 1_000_000
DROPPED = "repro.tracing.dropped"

_on = False
_following = False            # turned on by follow_profiler, not by enable
_annotate = None              # jax.profiler.TraceAnnotation, bound by enable
_profile_state = None         # jax's profiler session holder, bound lazily
_ids = itertools.count(1)
_local = threading.local()
_lock = threading.RLock()     # counters only; a GC callback may re-enter it
_spans: list = []             # (name, id, parent, thread, t0_ns, t1_ns, attrs)
_events: list = []            # (name, t_ns, value, labels)
_counters: dict = {}


class _Null:
    """The handle of every span while the tracer is off."""
    __slots__ = ()
    id = None
    elapsed_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


NULL = _Null()


class _Timer(_Null):
    """A ``timed=True`` span while the tracer is off: the clock only."""
    __slots__ = ("t0", "elapsed_s")

    def __enter__(self):
        self.elapsed_s = 0.0
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.elapsed_s = (time.perf_counter_ns() - self.t0) / 1e9
        return False

    def lap_s(self) -> float:
        """Seconds since the span was entered."""
        return (time.perf_counter_ns() - self.t0) / 1e9


class _Span(_Timer):
    __slots__ = ("name", "id", "parent", "attrs", "cpu", "_ann", "_ru")

    def __init__(self, name, parent, cpu, attrs):
        self.name, self.parent, self.cpu, self.attrs = name, parent, cpu, attrs
        self.id = next(_ids)

    def __enter__(self):
        stack = _stack()
        if self.parent is None and stack:
            self.parent = stack[-1]
        stack.append(self.id)
        if self.cpu:
            self._ru = resource.getrusage(resource.RUSAGE_SELF)
        self._ann = _annotate(self.name)
        self._ann.__enter__()
        return super().__enter__()

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.elapsed_s = (t1 - self.t0) / 1e9
        self._ann.__exit__(None, None, None)
        if self.cpu:
            ru = resource.getrusage(resource.RUSAGE_SELF)
            self.attrs["cpu_user_s"] = ru.ru_utime - self._ru.ru_utime
            self.attrs["cpu_sys_s"] = ru.ru_stime - self._ru.ru_stime
        stack = _stack()
        if stack and stack[-1] == self.id:
            stack.pop()
        elif self.id in stack:
            stack.remove(self.id)
        _keep(_spans, (self.name, self.id, self.parent,
                       threading.current_thread().name, self.t0, t1,
                       self.attrs))
        return False

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _keep(where: list, rec) -> None:
    if len(_spans) + len(_events) < MAX_RECORDS:
        where.append(rec)
    else:
        with _lock:
            _counters[DROPPED] = _counters.get(DROPPED, 0) + 1


def span(name: str, *, timed: bool = False, cpu: bool = False,
         parent=None, **attrs):
    """Context manager for one region; see the module's docstring."""
    if not _on:
        return _Timer() if timed else NULL
    return _Span(name, parent, cpu, attrs)


def current():
    """Id of the calling thread's innermost open span, to hand to another
    thread as its task's ``parent`` (None while off)."""
    if not _on:
        return None
    stack = _stack()
    return stack[-1] if stack else None


def count(name: str, value=1, **labels) -> None:
    """Add ``value`` to counter ``name`` (one counter per set of labels)."""
    if not _on:
        return
    key = name + ("{" + ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
                  + "}" if labels else "")
    with _lock:
        _counters[key] = _counters.get(key, 0) + value
    _keep(_events, (name, time.perf_counter_ns(), value, labels))


def enabled() -> bool:
    return _on


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _local.gc = (time.perf_counter_ns(), _annotate("repro.host.gc"))
        _local.gc[1].__enter__()
        return
    started = getattr(_local, "gc", None)
    if started is None:
        return
    _local.gc = None
    t1 = time.perf_counter_ns()
    started[1].__exit__(None, None, None)
    stack = _stack()
    _keep(_spans, ("repro.host.gc", next(_ids), stack[-1] if stack else None,
                   threading.current_thread().name, started[0], t1,
                   {"generation": info.get("generation"),
                    "collected": info.get("collected")}))


def enable() -> None:
    """Turn the tracer on: spans, counters, GC spans and JAX's compile events."""
    global _on, _annotate
    if _on:
        return
    import jax
    from repro.launch import compile_cache
    _annotate = jax.profiler.TraceAnnotation
    gc.callbacks.append(_on_gc)
    compile_cache.watch_jit(True)
    _on = True


def disable() -> None:
    """Turn the tracer off; what it recorded stays until ``reset``."""
    global _on, _following
    if not _on:
        return
    from repro.launch import compile_cache
    _on = _following = False
    compile_cache.watch_jit(False)
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def follow_profiler() -> None:
    """On while a ``jax.profiler`` trace is being collected, off after it
    (only where this function turned it on).  Called at the C/R loop's
    entry points, so the spans of a profiled window reach the trace; it
    sees a trace end only at the next such call.

    A stopgap until the benchmark's harness calls ``enable()`` itself for a
    traced run: JAX has no public way to ask whether a trace is being
    collected, so this reads its private profiler state, and does nothing
    where a JAX release has moved that state."""
    global _following, _profile_state
    if _profile_state is None:
        try:
            from jax._src import profiler
            _profile_state = profiler._profile_state
        except (ImportError, AttributeError):
            _profile_state = False
    if getattr(_profile_state, "profile_session", None) is not None:
        if not _on:
            enable()
            _following = True
    elif _following:
        disable()


def records() -> list:
    """The finished spans, oldest first, as dicts (times in ns)."""
    return [{"name": n, "id": i, "parent": p, "thread": th, "t0_ns": t0,
             "t1_ns": t1, "attrs": dict(a)}
            for n, i, p, th, t0, t1, a in list(_spans)]


def events() -> list:
    """Every counter increment with its time: dicts of name, t_ns, value,
    labels."""
    return [{"name": n, "t_ns": t, "value": v, "labels": dict(lb)}
            for n, t, v, lb in list(_events)]


def counters() -> dict:
    with _lock:
        return dict(_counters)


def reset() -> None:
    """Forget every record and counter (the switch stays as it is)."""
    with _lock:
        _counters.clear()
    _spans.clear()
    _events.clear()


def dump(path) -> None:
    """Write spans, counter increments and counters as JSON, with one
    reading of ``perf_counter_ns`` and ``time_ns`` taken together: add
    ``time_ns - perf_counter_ns`` to a span's times to put it on the wall
    clock of a ``jax.profiler`` trace."""
    clock = {"perf_counter_ns": time.perf_counter_ns(), "time_ns": time.time_ns()}
    with open(path, "w") as f:
        json.dump({"clock": clock, "spans": records(), "events": events(),
                   "counters": counters()}, f, default=str)
