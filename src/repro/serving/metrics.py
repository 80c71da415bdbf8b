"""Typed serving metrics registry (DESIGN.md §15).

The measurement substrate for the serving stack: a small, dependency-free
Prometheus-style registry — ``Counter`` / ``Gauge`` / ``Histogram`` families
with label sets and explicit bucket boundaries — that replaces the ad-hoc
attribute counting ``EngineStats`` used to do.  ``EngineStats``
(``serving/engine.py``) is now a thin read-view over this registry, so
existing callers and the BENCH_serving.json schema keep working unchanged.

Three consumers share one registry per engine:

* ``GET /metrics`` (``serving/http_api.py``) serves ``expose()`` — the
  Prometheus text exposition format 0.0.4, parseable back with
  ``parse_prometheus_text`` (tests + the CI gate round-trip it).
* ``benchmarks/bench_serving.py`` derives its ttft/tpot/latency percentiles
  from the histogram buckets (``Histogram.quantile`` /
  ``quantile_over``) instead of private per-request lists, and records
  ``snapshot()`` into BENCH_serving.json.
* The tracer (``serving/tracing.py``) annotates step spans with gauge
  snapshots (page-pool occupancy, queue depth).

Everything is plain host-side Python — observing a metric never touches a
device array, so the jitted hot path (one device->host transfer per decode
step) is unchanged whether metrics are on or off.  ``NULL_REGISTRY`` is the
opt-out: same API, every operation a no-op.

Timestamps never live here: latency *values* are observed into histograms
by the engine, which reads its injectable clock (``serving/clock.py``) —
this module is gated by ``tests/test_lint.py`` against direct ``time.*``
calls like every other serving module.
"""
from __future__ import annotations

import math
from typing import Iterable, Optional

# ---------------------------------------------------------------------------
# Bucket boundaries (seconds).  Chosen for the serving regime this repo
# measures: interpret-mode CPU steps are O(100ms..s), ManualClock overload
# simulations advance in whole simulated seconds, and real-backend decode
# steps land in the low-ms bins.  The +Inf bucket is implicit.
TTFT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0, 4.0,
                8.0, 16.0, 32.0, 64.0)
TPOT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.0, 4.0)
QUEUE_WAIT_BUCKETS = TTFT_BUCKETS
LATENCY_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0,
                   16.0, 32.0, 64.0, 128.0)
STEP_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.0, 4.0)
# accepted-draft-prefix length per verify step (DESIGN.md §16): small-integer
# buckets up to the SpecConfig.k ceiling of 16
SPEC_ACCEPT_BUCKETS = (0, 1, 2, 3, 4, 5, 6, 8, 12, 16)

_INF = float("inf")


def _fmt(v: float) -> str:
    """Prometheus sample-value formatting: integers without the trailing
    ``.0`` (counters stay exact), +Inf spelled the Prometheus way."""
    if v == _INF:
        return "+Inf"
    if isinstance(v, bool):
        return "1" if v else "0"
    f = float(v)
    return str(int(f)) if f.is_integer() else repr(f)


def _escape(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace("\n", "\\n").replace(
        '"', '\\"')


def _labels_str(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape(v)}"' for k, v in labels.items())
    return "{" + inner + "}"


# ------------------------------------------------------------------- children
class Counter:
    """Monotone counter child (one label set of a family)."""
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, n: float = 1.0):
        if n < 0:
            raise ValueError(f"counters only go up (inc by {n})")
        self.value += n


class Gauge:
    """Point-in-time value child."""
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float):
        self.value = float(v)

    def inc(self, n: float = 1.0):
        self.value += n

    def dec(self, n: float = 1.0):
        self.value -= n

    def set_max(self, v: float):
        """Ratchet: keep the running peak (e.g. deepest batch admitted)."""
        if v > self.value:
            self.value = float(v)


class Histogram:
    """Cumulative-bucket histogram child with explicit upper bounds.

    ``counts[i]`` is *non*-cumulative (observations landing in bucket i);
    the exposition and ``quantile`` cumulate on the fly.  The implicit
    +Inf bucket is ``counts[-1]``.
    """
    __slots__ = ("bounds", "counts", "sum", "count")

    def __init__(self, bounds: tuple):
        if not bounds or list(bounds) != sorted(bounds):
            raise ValueError(f"bucket bounds must be ascending, got {bounds}")
        self.bounds = tuple(float(b) for b in bounds)
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float):
        v = float(v)
        self.sum += v
        self.count += 1
        for i, b in enumerate(self.bounds):
            if v <= b:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def quantile(self, q: float) -> float:
        return quantile_over([self], q)


def quantile_over(hists: Iterable[Histogram], q: float) -> float:
    """Prometheus-style ``histogram_quantile`` over one or more children of
    the same family (bucket layouts must match): find the bucket holding the
    q-th observation and linearly interpolate within its bounds.  The +Inf
    bucket degrades to its lower bound; an empty histogram is 0.0."""
    hists = list(hists)
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    if not hists:
        return 0.0
    bounds = hists[0].bounds
    counts = [0] * (len(bounds) + 1)
    for h in hists:
        if h.bounds != bounds:
            raise ValueError("cannot aggregate histograms with different "
                             f"bounds: {h.bounds} vs {bounds}")
        for i, c in enumerate(h.counts):
            counts[i] += c
    total = sum(counts)
    if total == 0:
        return 0.0
    rank = q * total
    cum = 0.0
    for i, c in enumerate(counts):
        if c == 0:
            continue
        lo = bounds[i - 1] if i > 0 else 0.0
        if i == len(bounds):            # +Inf bucket: no upper bound to
            return lo                   # interpolate toward
        hi = bounds[i]
        if cum + c >= rank:
            return lo + (hi - lo) * max(0.0, rank - cum) / c
        cum += c
    return bounds[-1]


# -------------------------------------------------------------------- families
_TYPES = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class Family:
    """One named metric with a fixed label schema and per-label-set
    children.  ``labels(k=v, ...)`` returns (creating on first use) the
    child for that label combination; zero-label families proxy the metric
    methods straight through, so ``reg.counter("x", "...").inc()`` works."""

    def __init__(self, name: str, help: str, kind: str,
                 label_names: tuple = (), buckets: Optional[tuple] = None):
        if kind not in _TYPES:
            raise ValueError(f"unknown metric type {kind!r}")
        if kind == "histogram" and buckets is None:
            raise ValueError(f"histogram {name!r} needs explicit buckets")
        if kind != "histogram" and buckets is not None:
            raise ValueError(f"buckets only apply to histograms ({name!r})")
        self.name = name
        self.help = help
        self.kind = kind
        self.label_names = tuple(label_names)
        self.buckets = buckets
        self._children: dict[tuple, object] = {}
        if not self.label_names:
            self.labels()               # eager default child: always exposed

    def labels(self, **kv):
        if set(kv) != set(self.label_names):
            raise ValueError(
                f"{self.name} takes labels {self.label_names}, got "
                f"{tuple(kv)}")
        key = tuple(str(kv[n]) for n in self.label_names)
        child = self._children.get(key)
        if child is None:
            child = (Histogram(self.buckets) if self.kind == "histogram"
                     else _TYPES[self.kind]())
            self._children[key] = child
        return child

    def children(self) -> list[tuple[dict, object]]:
        """(labels dict, child) pairs in first-use order (deterministic)."""
        return [(dict(zip(self.label_names, key)), c)
                for key, c in self._children.items()]

    # zero-label conveniences -------------------------------------------------
    def _default(self):
        if self.label_names:
            raise ValueError(f"{self.name} is labeled "
                             f"{self.label_names}; call .labels(...)")
        return self._children[()]

    def inc(self, n: float = 1.0):
        self._default().inc(n)

    def dec(self, n: float = 1.0):
        self._default().dec(n)

    def set(self, v: float):
        self._default().set(v)

    def set_max(self, v: float):
        self._default().set_max(v)

    def observe(self, v: float):
        self._default().observe(v)

    def quantile(self, q: float) -> float:
        """Quantile over ALL children (aggregate across label sets)."""
        return quantile_over(
            [c for _, c in self.children()], q)

    @property
    def value(self) -> float:
        """Total across children (counter/gauge read path)."""
        return sum(c.value for _, c in self.children())

    @property
    def total_count(self) -> int:
        return sum(c.count for _, c in self.children())

    @property
    def total_sum(self) -> float:
        return sum(c.sum for _, c in self.children())


class MetricsRegistry:
    """Ordered collection of metric families with optional constant labels
    (attached to every sample — the engine stamps ``layout`` and
    ``kv_quant`` here so one scrape distinguishes engines)."""

    def __init__(self, const_labels: Optional[dict] = None):
        self.const_labels = dict(const_labels or {})
        self._families: dict[str, Family] = {}

    # ------------------------------------------------------------ registration
    def _register(self, name: str, help: str, kind: str, labels: tuple,
                  buckets: Optional[tuple]) -> Family:
        fam = self._families.get(name)
        if fam is not None:
            if (fam.kind, fam.label_names, fam.buckets) != (
                    kind, tuple(labels), buckets):
                raise ValueError(
                    f"metric {name!r} re-registered with a different "
                    f"type/labels/buckets")
            return fam
        fam = Family(name, help, kind, labels, buckets)
        self._families[name] = fam
        return fam

    def counter(self, name: str, help: str, labels: tuple = ()) -> Family:
        return self._register(name, help, "counter", labels, None)

    def gauge(self, name: str, help: str, labels: tuple = ()) -> Family:
        return self._register(name, help, "gauge", labels, None)

    def histogram(self, name: str, help: str, buckets: tuple,
                  labels: tuple = ()) -> Family:
        return self._register(name, help, "histogram", labels,
                              tuple(float(b) for b in buckets))

    def get(self, name: str) -> Family:
        return self._families[name]

    def __contains__(self, name: str) -> bool:
        return name in self._families

    def families(self) -> list[Family]:
        return list(self._families.values())

    # -------------------------------------------------------------- exposition
    def expose(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        out: list[str] = []
        for fam in self._families.values():
            out.append(f"# HELP {fam.name} {fam.help}")
            out.append(f"# TYPE {fam.name} {fam.kind}")
            for labels, child in fam.children():
                lab = {**self.const_labels, **labels}
                if fam.kind == "histogram":
                    cum = 0
                    for i, b in enumerate(child.bounds + (_INF,)):
                        cum += child.counts[i]
                        bl = {**lab, "le": _fmt(b)}
                        out.append(f"{fam.name}_bucket{_labels_str(bl)} "
                                   f"{cum}")
                    out.append(
                        f"{fam.name}_sum{_labels_str(lab)} {_fmt(child.sum)}")
                    out.append(
                        f"{fam.name}_count{_labels_str(lab)} {child.count}")
                else:
                    out.append(
                        f"{fam.name}{_labels_str(lab)} {_fmt(child.value)}")
        return "\n".join(out) + "\n"

    # ---------------------------------------------------------------- snapshot
    def snapshot(self) -> dict:
        """JSON-able dump for BENCH records: every family, every label set,
        histograms with their raw (non-cumulative) bucket counts."""
        snap: dict = {"const_labels": dict(self.const_labels), "families": {}}
        for fam in self._families.values():
            series = []
            for labels, child in fam.children():
                if fam.kind == "histogram":
                    series.append({"labels": labels,
                                   "buckets": list(child.bounds),
                                   "counts": list(child.counts),
                                   "sum": child.sum, "count": child.count})
                else:
                    series.append({"labels": labels, "value": child.value})
            snap["families"][fam.name] = {
                "type": fam.kind, "help": fam.help, "series": series}
        return snap


# ------------------------------------------------------------------- opt-out
class _NullChild:
    """Absorbs every metric operation; reads as empty/zero."""
    value = 0.0
    count = 0
    sum = 0.0
    bounds: tuple = ()
    counts: list = []

    def inc(self, n=1.0):
        pass

    dec = set = set_max = observe = inc

    def quantile(self, q):
        return 0.0


class _NullFamily(_NullChild):
    total_count = 0
    total_sum = 0.0

    def labels(self, **kv):
        return self

    def children(self):
        return []


class NullRegistry(MetricsRegistry):
    """The metrics opt-out (``EngineConfig(metrics=False)``): identical API,
    nothing recorded, empty exposition — so engine code never branches."""

    def __init__(self):
        super().__init__()
        self._null = _NullFamily()

    def _register(self, name, help, kind, labels, buckets):
        return self._null

    def get(self, name):
        return self._null

    def expose(self) -> str:
        return ""

    def snapshot(self) -> dict:
        return {"const_labels": {}, "families": {}}


NULL_REGISTRY = NullRegistry()


# --------------------------------------------------------------- text parsing
def parse_prometheus_text(text: str) -> dict:
    """Parse the exposition format back into
    ``{family: {"type": t, "samples": [(sample_name, labels, value)]}}``
    (histogram ``_bucket``/``_sum``/``_count`` samples land under their base
    family) — the round-trip check tests and the CI gate run over
    ``GET /metrics`` output.  Raises
    ``ValueError`` on malformed lines, unknown types, or samples that never
    saw a TYPE header (close enough to a promtool check for a stdlib-only
    repo)."""
    metrics: dict = {}
    typed: dict[str, str] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            parts = line.split(None, 3)
            if len(parts) < 4 or parts[3] not in _TYPES:
                raise ValueError(f"line {lineno}: malformed TYPE: {line!r}")
            typed[parts[2]] = parts[3]
            metrics[parts[2]] = {"type": parts[3], "samples": []}
            continue
        if line.startswith("#"):
            continue
        # sample: name{labels} value
        brace = line.find("{")
        labels: dict[str, str] = {}
        if brace != -1:
            close = line.rfind("}")
            if close == -1:
                raise ValueError(f"line {lineno}: unclosed labels: {line!r}")
            name, rest = line[:brace], line[close + 1:]
            body = line[brace + 1:close]
            for item in filter(None, body.split(",")):
                if "=" not in item:
                    raise ValueError(
                        f"line {lineno}: malformed label {item!r}")
                k, v = item.split("=", 1)
                if not (v.startswith('"') and v.endswith('"')):
                    raise ValueError(
                        f"line {lineno}: unquoted label value {item!r}")
                labels[k.strip()] = v[1:-1].replace('\\"', '"').replace(
                    "\\n", "\n").replace("\\\\", "\\")
        else:
            name, _, rest = line.partition(" ")
        name, rest = name.strip(), rest.strip()
        try:
            value = float(rest)
        except ValueError:
            raise ValueError(f"line {lineno}: bad sample value {rest!r}")
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            root = name[:-len(suffix)] if name.endswith(suffix) else None
            if root and typed.get(root) == "histogram":
                base = root
                break
        if base not in typed:
            raise ValueError(
                f"line {lineno}: sample {name!r} without a TYPE header")
        if math.isnan(value):
            raise ValueError(f"line {lineno}: NaN sample value")
        metrics[base]["samples"].append((name, labels, value))
    return metrics


# --------------------------------------------------------- the engine catalog
class EngineMetrics:
    """The serving metric catalog (DESIGN.md §15), bound to one registry.

    One instance per ``Engine``; attribute access is the hot-path-cheap
    handle the engine increments.  ``layout`` / ``kv_quant`` become constant
    labels so scrapes from different engine configs stay distinguishable.
    """

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        r = registry
        # counters ----------------------------------------------------------
        self.tokens_generated = r.counter(
            "engine_tokens_generated_total", "Decode tokens sampled")
        self.prefill_tokens = r.counter(
            "engine_prefill_tokens_total", "Prompt tokens prefilled")
        self.steps = r.counter(
            "engine_steps_total", "Engine.step() iterations")
        self.wall_seconds = r.counter(
            "engine_wall_seconds_total",
            "Clock seconds spent inside Engine.step (injectable clock)")
        # where a step's time goes (DESIGN.md §15): wall minus device wait
        # is the host's share of the step; the front end's share lies
        # outside Engine.step
        self.device_wait_seconds = r.counter(
            "engine_device_wait_seconds_total",
            "Clock seconds Engine.step spends blocked in its device->host "
            "transfer")
        self.frontend_seconds = r.counter(
            "engine_frontend_seconds_total",
            "Clock seconds the HTTP engine worker spends on submits, aborts "
            "and event fan-out, outside Engine.step and idle waits")
        self.compiles = r.counter(
            "engine_compiles_total",
            "JAX traces (jit cache misses) while Engine.step runs")
        self.prefix_hit_pages = r.counter(
            "engine_prefix_hit_pages_total",
            "KV pages served from the hashed-prefix cache")
        self.prefix_hit_tokens = r.counter(
            "engine_prefix_hit_tokens_total",
            "Prompt tokens skipped via prefix-cache hits")
        self.preemptions = r.counter(
            "engine_preemptions_total",
            "Victims evicted for higher-priority admissions")
        self.offloaded_pages = r.counter(
            "engine_offloaded_pages_total",
            "Pages checkpointed to host memory by preemption")
        self.offloaded_bytes = r.counter(
            "engine_offloaded_bytes_total",
            "Host bytes of preemption checkpoints")
        self.restored_pages = r.counter(
            "engine_restored_pages_total",
            "Checkpointed pages scattered back on-device")
        self.rejected_submits = r.counter(
            "engine_rejected_submits_total",
            "submit() refused at max_queued (HTTP 429)")
        self.deferred_admissions = r.counter(
            "engine_deferred_admissions_total",
            "Head-of-queue reservation failures (admission deferred)")
        self.shed_requests = r.counter(
            "engine_shed_requests_total",
            "Requests shed past their queue deadline (HTTP 503)")
        self.requests_finished = r.counter(
            "engine_requests_finished_total",
            "Requests leaving the engine, by finish reason",
            labels=("reason",))
        self.faults_injected = r.counter(
            "engine_faults_injected_total",
            "FaultInjector events fired, by kind", labels=("kind",))
        # speculative decoding (DESIGN.md §16) ------------------------------
        self.spec_proposed = r.counter(
            "engine_spec_proposed_total",
            "Draft tokens proposed by the speculator")
        self.spec_accepted = r.counter(
            "engine_spec_accepted_total",
            "Draft tokens accepted by the verify pass")
        self.spec_verify_steps = r.counter(
            "engine_spec_verify_steps_total",
            "Speculative verify steps executed")
        # gauges ------------------------------------------------------------
        self.active_requests = r.gauge(
            "engine_active_requests", "Requests currently decoding")
        self.waiting_requests = r.gauge(
            "engine_waiting_requests", "Requests queued for admission")
        self.peak_active = r.gauge(
            "engine_peak_active", "Deepest concurrent batch ever admitted")
        self.page_pool_pages = r.gauge(
            "engine_page_pool_pages", "Allocatable pages in the paged pool")
        self.page_pool_free = r.gauge(
            "engine_page_pool_free_pages", "Pages on the paged free list")
        self.page_pool_utilization = r.gauge(
            "engine_page_pool_utilization",
            "Fraction of the page pool allocated")
        self.offloaded_bytes_current = r.gauge(
            "engine_offloaded_bytes_current",
            "Host bytes currently held by preemption checkpoints")
        # per-device pool gauges (DESIGN.md §17): under tensor parallelism
        # every device owns the num_kv_heads/tp head-slice of the same
        # global page ids, so /metrics exposes pool skew (or, today, its
        # absence — the replicated free list keeps the shards in lockstep)
        # per shard instead of one aggregate
        self.page_pool_device_free = r.gauge(
            "engine_page_pool_device_free_pages",
            "Pages on the free list, by mesh device", labels=("device",))
        self.page_pool_device_bytes = r.gauge(
            "engine_page_pool_device_bytes",
            "Page-pool bytes resident on one mesh device (head-slice of "
            "payload + scale pools)", labels=("device",))
        self.offloaded_bytes_device = r.gauge(
            "engine_offloaded_bytes_device",
            "Host checkpoint bytes attributable to one mesh device's "
            "head-slice", labels=("device",))
        self._devices = 1
        self._device_pool_bytes = 0
        # histograms (explicit buckets, DESIGN.md §15) ----------------------
        self.ttft = r.histogram(
            "engine_ttft_seconds", "Time to first token, by priority class",
            TTFT_BUCKETS, labels=("priority",))
        self.tpot = r.histogram(
            "engine_tpot_seconds",
            "Per-output-token decode time (post-first-token)", TPOT_BUCKETS)
        self.queue_wait = r.histogram(
            "engine_queue_wait_seconds",
            "Submit-to-admission wait", QUEUE_WAIT_BUCKETS)
        self.request_latency = r.histogram(
            "engine_request_latency_seconds",
            "End-to-end request latency", LATENCY_BUCKETS)
        self.step_duration = r.histogram(
            "engine_step_duration_seconds",
            "Engine.step() duration (injectable clock)", STEP_BUCKETS)
        self.spec_accept_len = r.histogram(
            "engine_spec_accept_length",
            "Accepted-draft-prefix length per request per verify step",
            SPEC_ACCEPT_BUCKETS)

    def configure_devices(self, n: int, pool_bytes_per_device: int) -> None:
        """Declare the mesh size (1 on a single device) and each device's
        resident pool bytes so ``sync_pool`` can fan the occupancy out to
        the device-labeled gauges.  Called once at engine construction."""
        self._devices = max(1, int(n))
        self._device_pool_bytes = int(pool_bytes_per_device)

    def sync_pool(self, pc) -> None:
        """Refresh the page-pool occupancy/offload gauges from a
        ``PagedCache`` (``occupancy()``) — called once per step."""
        occ = pc.occupancy()
        self.page_pool_pages.set(occ["num_pages"])
        self.page_pool_free.set(occ["free_pages"])
        self.page_pool_utilization.set(occ["utilization"])
        self.offloaded_bytes_current.set(occ["offloaded_bytes"])
        # per-device fan-out: the free list is replicated bookkeeping (page
        # ids are global, every device holds its head-slice of every page),
        # and a checkpointed page's host bytes split evenly across shards
        for d in range(self._devices):
            self.page_pool_device_free.labels(device=d).set(
                occ["free_pages"])
            self.page_pool_device_bytes.labels(device=d).set(
                self._device_pool_bytes)
            self.offloaded_bytes_device.labels(device=d).set(
                occ["offloaded_bytes"] / self._devices)


def make_engine_metrics(layout: str, kv_quant: str,
                        enabled: bool = True) -> EngineMetrics:
    """Registry + catalog for one engine.  ``enabled=False`` binds the
    catalog to ``NULL_REGISTRY`` — every observation is a no-op and
    ``expose()`` is empty, the documented opt-out."""
    if not enabled:
        return EngineMetrics(NullRegistry())
    return EngineMetrics(MetricsRegistry(
        const_labels={"layout": layout, "kv_quant": kv_quant}))
