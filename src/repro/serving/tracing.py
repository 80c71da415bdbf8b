"""Step phases as spans, with two sinks, and per-request tracing with
Chrome/Perfetto export (DESIGN.md §15).

**One span API.**  ``span(name, tracer)`` is the one way the serving stack
names a phase of its work.  It always opens a
``jax.profiler.TraceAnnotation`` of that name, which lands on the device
trace's clock whenever a profiler session is running (and costs about a
microsecond when none is).  When a ``Tracer`` is given, the span also
records an ``X`` slice of the same name on the engine track of the
Perfetto export, timed by the engine's injectable clock.  So a profiler
trace and ``launch/serve.py --trace-out`` name the same phases:
``engine.step`` around each ``Engine.step`` and, inside it,
``engine.admit``, ``engine.plan``, ``engine.stage`` (with
``engine.propose`` when a speculator runs), ``engine.dispatch``,
``engine.wait``, ``engine.emit`` and ``engine.end_step``; on the HTTP
engine worker ``worker.inbox``, ``worker.fan_out`` and ``worker.idle``
(profiler only: an idle server polls); and ``engine.gc`` around each
garbage-collector pause (profiler only, process-wide).

**Process hooks.**  ``install_process_hooks`` (called by every ``Engine``,
effective once per process) adds the ``engine.gc`` hook and one JAX
monitoring listener that counts jaxpr traces -- one per jit cache miss --
into ``engine_compiles_total`` of the engine whose step runs on the
calling thread (``RUNNING``).

**Per-request tracing.**  A ``Tracer`` attached via ``EngineConfig.tracer``
also records request lifecycle spans on one Perfetto track per request
(pid ``PID_REQUESTS``, tid = rid): QUEUED -> PREFILL -> RUNNING ->
PREEMPTED -> RESTORED-RUNNING -> terminal instant (``finish`` with the
``FinishReason``).  Offload/restore page movement and injected faults
(``serving/faults.py``) land as instant events; prefill work slices carry
the bucketed chunk length and page-reservation annotations.  Every
timestamp comes from the engine's injectable clock (``serving/clock.py``):
the engine passes it in, or binds it as ``Tracer.clock`` for spans, so
under a ``ManualClock`` the exported trace is byte-deterministic across
runs.

Export is the Chrome ``trace_event`` JSON-object format (the one
``about:tracing`` and https://ui.perfetto.dev load directly):
``{"traceEvents": [...], "displayTimeUnit": "ms"}`` with ``ts``/``dur`` in
microseconds, ``M`` metadata events naming every track, ``X`` complete
slices and scoped ``i`` instants.  ``validate_trace`` is the schema check
the tests and the CI artifact gate run over exported files.

Tracing is pure host-side bookkeeping: no device value is ever read for a
span (the engine's one device->host transfer per step is unchanged, and
greedy outputs are bit-identical with tracing on or off -- both tested).
``Tracer(enabled=False)`` (or simply no tracer) is the opt-out for the
Perfetto sink; every record call short-circuits on one attribute check.
"""
from __future__ import annotations

import gc
import json
import threading

import jax

PID_ENGINE = 1
PID_REQUESTS = 2
TID_STEPS = 0

# span/instant categories
CAT_STEP = "engine"
CAT_LIFECYCLE = "request"
CAT_FAULT = "fault"

_ALLOWED_PH = {"M", "X", "i"}

# the monitoring event JAX records each time it traces a function to a
# jaxpr, which happens on every jit cache miss
JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def _us(t: float) -> float:
    """Seconds -> integer-friendly microseconds (rounded to 0.1us so float
    repr stays stable and the export byte-deterministic)."""
    return round(float(t) * 1e6, 1)


class Span:
    """One named phase: a profiler annotation and, with a ``Tracer``, an
    ``X`` slice on the engine track.  Use it through ``span``."""

    __slots__ = ("_ann", "_name", "_tracer", "_args", "_t0")

    def __init__(self, name: str, tracer: "Tracer | None", args: dict):
        self._name = name
        self._tracer = tracer if tracer is not None and tracer.enabled \
            else None
        self._args = args
        self._ann = jax.profiler.TraceAnnotation(name, **args)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        if self._tracer is not None:
            self._t0 = self._tracer.clock.now()
        return self

    def set(self, **args) -> None:
        """Attach ``args`` to the span in both sinks (values known only
        after the span opened, such as a step's width)."""
        self._ann.set_metadata(**args)
        if self._tracer is not None:
            self._args.update(args)

    def __exit__(self, *exc) -> None:
        if self._tracer is not None:
            self._tracer.complete(self._name, CAT_STEP, PID_ENGINE,
                                  TID_STEPS, self._t0,
                                  self._tracer.clock.now(), **self._args)
        self._ann.__exit__(*exc)


def span(name: str, tracer: "Tracer | None" = None, **args) -> Span:
    """``with span("engine.wait", eng.tracer):`` -- the phase as a
    profiler annotation and, when ``tracer`` is given, a Perfetto slice of
    the same name carrying ``args``."""
    return Span(name, tracer, args)


class _Running(threading.local):
    """The engine whose ``step`` runs on this thread, if any."""
    engine = None


RUNNING = _Running()
_hooks_lock = threading.Lock()
_hooks_installed = False
_gc_span = None


def _on_gc(phase: str, _info: dict) -> None:
    # gc callbacks of one collection run on one thread under the
    # interpreter lock, and collections never nest
    global _gc_span
    if phase == "start":
        if not jax.profiler.TraceAnnotation.is_enabled():
            return      # no profiler session: collections are frequent
        _gc_span = jax.profiler.TraceAnnotation("engine.gc")
        _gc_span.__enter__()
    elif _gc_span is not None:
        _gc_span.__exit__(None, None, None)
        _gc_span = None


def _on_event_duration(event: str, _secs: float, **_kwargs) -> None:
    if event == JAXPR_TRACE_EVENT:
        eng = RUNNING.engine
        if eng is not None:
            eng.metrics.compiles.inc()


def install_process_hooks() -> None:
    """Add the ``engine.gc`` hook and the compile listener, once per
    process (one listener serves every engine: a listener per engine would
    pile up across the engines a process builds)."""
    global _hooks_installed
    with _hooks_lock:
        if _hooks_installed:
            return
        gc.callbacks.append(_on_gc)
        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)
        _hooks_installed = True


class Tracer:
    """Collects trace events; one tracer serves one engine.

    Every timestamp is read from the engine's injectable clock: the engine
    passes it in, and binds ``clock`` (``Engine`` sets it to its own) for
    the slices ``span`` records -- which is what makes ManualClock runs
    reproduce byte-identical traces.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.clock = None       # bound by the engine the tracer serves
        self.events: list[dict] = []
        self._threads: dict[tuple[int, int], str] = {}
        self._processes: dict[int, str] = {PID_ENGINE: "engine",
                                           PID_REQUESTS: "requests"}
        self._open: dict[int, tuple[str, float, dict]] = {}  # rid -> state
        self._thread(PID_ENGINE, TID_STEPS, "steps")

    # ------------------------------------------------------------- primitives
    def _thread(self, pid: int, tid: int, name: str):
        self._threads.setdefault((pid, tid), name)

    def complete(self, name: str, cat: str, pid: int, tid: int,
                 t0: float, t1: float, **args):
        """One ``X`` slice [t0, t1] (seconds)."""
        if not self.enabled:
            return
        self.events.append({
            "name": name, "cat": cat, "ph": "X", "pid": pid, "tid": tid,
            "ts": _us(t0), "dur": max(0.0, _us(t1) - _us(t0)),
            "args": dict(args)})

    def instant(self, name: str, cat: str, pid: int, tid: int, t: float,
                **args):
        if not self.enabled:
            return
        self.events.append({
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "pid": pid, "tid": tid, "ts": _us(t), "args": dict(args)})

    # ---------------------------------------------------------- engine hooks
    def prefill_span(self, rid: int, t0: float, t1: float, **args):
        """Prefill work slice on the engine track (chunk/bucket + page
        annotations) — the request's own PREFILL lifecycle span covers
        queue-exit to first token on its request track."""
        self.complete("prefill", CAT_STEP, PID_ENGINE, TID_STEPS, t0, t1,
                      rid=rid, **args)

    def request_state(self, rid: int, state: str, t: float, **args):
        """Move a request's lifecycle track to ``state`` at time ``t``:
        closes the previous state's span (if any) as an ``X`` slice and
        opens the new one.  ``args`` attach to the span being *opened*."""
        if not self.enabled:
            return
        self._thread(PID_REQUESTS, rid, f"req {rid}")
        prev = self._open.pop(rid, None)
        if prev is not None:
            pstate, t0, pargs = prev
            self.complete(pstate, CAT_LIFECYCLE, PID_REQUESTS, rid, t0, t,
                          **pargs)
        self._open[rid] = (state, t, dict(args))

    def request_end(self, rid: int, reason: str, t: float, **args):
        """Terminal transition: close the open span and drop an instant
        (``finish``) carrying the ``FinishReason``."""
        if not self.enabled:
            return
        self.request_state(rid, "_end", t)      # closes the open span
        self._open.pop(rid, None)
        self.instant("finish", CAT_LIFECYCLE, PID_REQUESTS, rid, t,
                     reason=reason, **args)

    def request_instant(self, rid: int, name: str, t: float, **args):
        if not self.enabled:
            return
        self._thread(PID_REQUESTS, rid, f"req {rid}")
        self.instant(name, CAT_LIFECYCLE, PID_REQUESTS, rid, t, **args)

    def fault_instant(self, kind: str, t: float, **args):
        """Injected faults (``serving/faults.py``) land on the engine track
        so overload post-mortems line them up against step spans."""
        self.instant(f"fault:{kind}", CAT_FAULT, PID_ENGINE, TID_STEPS, t,
                     **args)

    # ------------------------------------------------------------------ export
    def flush_open(self, t: float):
        """Close still-open lifecycle spans at ``t`` (end-of-run export of a
        trace whose requests never finished)."""
        for rid in sorted(self._open):
            state, t0, args = self._open.pop(rid)
            self.complete(state, CAT_LIFECYCLE, PID_REQUESTS, rid, t0, t,
                          **args)

    def to_dict(self) -> dict:
        meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                 "args": {"name": name}}
                for pid, name in sorted(self._processes.items())]
        meta += [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                  "args": {"name": name}}
                 for (pid, tid), name in sorted(self._threads.items())]
        return {"displayTimeUnit": "ms", "traceEvents": meta + self.events}

    def to_json(self) -> str:
        """Canonical serialization: sorted keys, no whitespace — two runs
        with the same ManualClock schedule serialize byte-identically."""
        return json.dumps(self.to_dict(), sort_keys=True,
                          separators=(",", ":"))

    def export(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.to_json())
        return path


# ------------------------------------------------------------------ validation
def validate_trace(obj) -> list[str]:
    """Schema check for an exported trace (dict or JSON string).  Returns a
    list of problems — empty means the trace is well-formed Chrome
    ``trace_event`` JSON that Perfetto/about:tracing loads without
    warnings: metadata names every referenced track, slices have
    non-negative ``ts``/``dur``, instants carry a scope, args are
    JSON-serializable."""
    problems: list[str] = []
    if isinstance(obj, (str, bytes)):
        try:
            obj = json.loads(obj)
        except json.JSONDecodeError as e:
            return [f"not valid JSON: {e}"]
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["top level must be an object with 'traceEvents'"]
    events = obj["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' must be a list"]
    named: set[tuple[int, int]] = set()
    used: set[tuple[int, int]] = set()
    for i, ev in enumerate(events):
        where = f"event[{i}]"
        if not isinstance(ev, dict):
            problems.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in _ALLOWED_PH:
            problems.append(f"{where}: unknown phase {ph!r}")
            continue
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            problems.append(f"{where}: missing name")
        if not isinstance(ev.get("pid"), int) \
                or not isinstance(ev.get("tid"), int):
            problems.append(f"{where}: pid/tid must be ints")
            continue
        key = (ev["pid"], ev["tid"])
        if ph == "M":
            if ev["name"] == "thread_name":
                named.add(key)
            if not isinstance(ev.get("args", {}).get("name"), str):
                problems.append(f"{where}: metadata without args.name")
            continue
        used.add(key)
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            problems.append(f"{where}: bad ts {ts!r}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"{where}: X slice with bad dur {dur!r}")
        if ph == "i" and ev.get("s") not in ("g", "p", "t"):
            problems.append(f"{where}: instant without scope 's'")
        args = ev.get("args", {})
        if not isinstance(args, dict):
            problems.append(f"{where}: args must be an object")
        else:
            try:
                json.dumps(args)
            except (TypeError, ValueError):
                problems.append(f"{where}: args not JSON-serializable")
    for key in sorted(used - named):
        problems.append(f"track pid={key[0]} tid={key[1]} has events but no "
                        f"thread_name metadata")
    return problems

