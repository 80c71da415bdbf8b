"""From a profiler trace to busy time, time per kernel and idle gaps.

``load`` turns the profiler's ``.xplane.pb`` into plain events:
``{"kind": "device" | "host", "name", "t", "d"}`` (start and duration in
nanoseconds on the trace's one clock), keeping the device's XLA operations
and the host spans this benchmark writes (``engine.*``, ``worker.*``,
``harness.*``).  ``reduce`` works on such a list, so a small recorded trace
(``fixtures/``) checks it without a chip.

The traced window runs from the start of the first ``engine.step`` span to
the end of the last.  Busy time is the union of the device operations'
intervals inside it; an idle gap is a stretch of the window with no
operation, labelled by the innermost host span around its middle.
"""
from __future__ import annotations

import glob
import json
import os
import re

import chipbench

SPAN_PREFIXES = ("engine.", "worker.", "harness.")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_LINE = "XLA Ops"
_LAYOUT = re.compile(r"\{[^}]*\}")
_OPCODE = re.compile(r"\s([\w\-]+)\(")


def short_name(hlo: str) -> str:
    """``%name.N = <shape>{layout} opcode(operands...)`` -> ``%name.N
    opcode shape``: the device op names of a TPU trace are whole HLO
    instructions."""
    inst, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo
    rest = _LAYOUT.sub("", rest)
    shape = "tuple" if rest.startswith("(") else rest.split(" ", 1)[0]
    op = _OPCODE.search(" " + rest[len(shape):] if shape != "tuple"
                        else rest[rest.index(")") + 1:])
    return f"{inst} {op.group(1) if op else '?'} {shape}"


def load(trace_dir: str) -> list[dict]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != DEVICE_LINE:
                continue
            for e in line.events:
                if not device and not e.name.startswith(SPAN_PREFIXES):
                    continue
                out.append({"kind": "device" if device else "host",
                            "name": short_name(e.name) if device else e.name,
                            "t": float(e.start_ns),
                            "d": float(e.duration_ns)})
    return out


def _union(intervals):
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _self_times(ops):
    """Exclusive time of each (start, end, name) op: an op that encloses
    others (a loop around its body) keeps only the time outside them."""
    ops = sorted(ops, key=lambda o: (o[0], -(o[1] - o[0])))
    self_ns = [o[1] - o[0] for o in ops]
    stack: list[int] = []
    for i, (a, b, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= b - a
        stack.append(i)
    return [(o[2], s) for o, s in zip(ops, self_ns)]


def classify(name: str, classes: dict) -> str | None:
    for cls, patterns in classes.items():
        if any(re.search(p, name) for p in patterns):
            return cls
    return None


def classes(family) -> dict:
    """``kernels.json``'s kernel classes and the family's ``KERNELS``
    (class -> regexes over an operation's short name), none named twice."""
    with open(os.path.join(chipbench.BENCH_DIR, "kernels.json")) as f:
        table = json.load(f)
    extra = getattr(family, "KERNELS", {})
    twice = table.keys() & extra.keys()
    if twice:
        raise ValueError(f"kernel classes named twice: {sorted(twice)}")
    return {**table, **extra}


def reduce(events: list[dict], classes: dict) -> dict | None:
    """Busy and window seconds, exclusive seconds per device operation,
    seconds per kernel class (``classes``: class -> regexes over the
    operation's short name), idle seconds by host span, and the number of
    traced steps.  None when the trace holds no step."""
    steps = [e for e in events if e["kind"] == "host"
             and e["name"] == "engine.step"]
    if not steps:
        return None
    w0 = min(e["t"] for e in steps)
    w1 = max(e["t"] + e["d"] for e in steps)
    ops = []
    kernels: dict[str, float] = {c: 0.0 for c in classes}
    for e in events:
        if e["kind"] != "device":
            continue
        a, b = max(e["t"], w0), min(e["t"] + e["d"], w1)
        if b <= a:
            continue
        ops.append((a, b, e["name"]))
        cls = classify(e["name"], classes)
        if cls is not None:
            kernels[cls] += (b - a) / 1e9
    busy = _union([(a, b) for a, b, _ in ops])
    per_op: dict[str, float] = {}
    for name, ns in _self_times(ops):
        per_op[name] = per_op.get(name, 0.0) + ns / 1e9
    spans = sorted((e for e in events if e["kind"] == "host"),
                   key=lambda s: s["t"])
    idle: dict[str, float] = {}
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    active: list[dict] = []
    j = 0
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        while j < len(spans) and spans[j]["t"] <= mid:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s["t"] + s["d"] >= mid]
        label = (max(active, key=lambda s: (s["t"], -s["d"]))["name"]
                 if active
                 else "no host span")
        idle[label] = idle.get(label, 0.0) + (b - a) / 1e9
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "ops": per_op, "kernels": kernels, "idle": idle,
            "steps": len(steps)}


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The device operations that took most time and the idle time by host
    span, ``top`` of each, as ``[[name, seconds], ...]``."""
    def largest(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": largest(reduced["ops"]),
            "idle_gaps": largest(reduced["idle"])}
