"""Stdlib-only OpenAI-style HTTP front-end over ``Engine.stream()``.

``POST /v1/completions`` with an OpenAI-ish JSON body serves completions
from the continuous-batching engine; ``"stream": true`` switches to SSE
(``data: {chunk}\\n\\n`` per token, terminated by ``data: [DONE]``).  The
repo has no tokenizer, so ``"prompt"`` must be a list of token ids and
``choices[].text`` carries the space-joined ids alongside
``choices[].token_ids``.

Threading model: HTTP handlers run on ``ThreadingHTTPServer`` threads, but
the ``Engine`` is single-threaded — one ``EngineWorker`` thread owns it and
pumps ``step_events()``.  Handlers talk to the worker through queues only:
submissions (and aborts, on client disconnect) go through ``worker.inbox``;
each request's ``StreamEvent``s come back on a per-request queue.  Requests
submitted while others are decoding join the running batch — continuous
batching straight through HTTP.

Overload behaviour (DESIGN.md §14): bounded admission maps
``QueueFullError`` to **429** with a ``Retry-After`` header; a request shed
on its queue deadline gets **503** (+ ``Retry-After``); and when
``stall_timeout_s`` is set, a watchdog thread monitors the worker's
heartbeat and fails every in-flight request with ``FinishReason.STALL``
(**503** on the blocking path, a terminal SSE chunk on the streaming path)
instead of letting clients hang on a wedged engine.

    eng = Engine(model, params, EngineConfig(...))
    server = make_server(eng, port=8000, model_name=cfg.name)
    server.serve_forever()          # or launch/serve.py --serve
"""
from __future__ import annotations

import dataclasses
import json
import queue
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from repro.runtime.fault_tolerance import Heartbeat
from repro.serving.api import FinishReason, QueueFullError, StreamEvent
from repro.serving.sampler import SamplingParams
from repro.serving.tracing import span

# how long a handler waits for the next token before giving up on the worker
EVENT_TIMEOUT_S = 300.0


@dataclasses.dataclass
class _Submission:
    """One HTTP request's hand-off to the engine worker."""
    tokens: list[int]
    max_new_tokens: int
    sampling: SamplingParams
    stop_token_ids: tuple[int, ...]
    ignore_eos: bool
    priority: int = 0
    queue_timeout_s: float | None = None
    # per-request StreamEvent fan-out queue, and the rid/Exception handshake
    events: queue.Queue = dataclasses.field(default_factory=queue.Queue)
    reply: queue.Queue = dataclasses.field(default_factory=queue.Queue)


class EngineWorker(threading.Thread):
    """The single thread that owns the engine.

    Drains control ops (submit/abort) from ``inbox``, pumps
    ``Engine.step_events()`` while requests are in flight, and fans each
    event out to its request's subscriber queue.  Idle polling is a short
    blocking ``inbox.get`` — no busy loop.  Its work outside
    ``Engine.step`` (submits, aborts, fan-out) is spanned as
    ``worker.inbox`` / ``worker.fan_out`` and counted into
    ``engine_frontend_seconds_total``; the idle wait is ``worker.idle``.

    With ``stall_timeout_s`` set, the loop beats a ``Heartbeat`` (read
    through the engine's injectable clock) each iteration and a watchdog
    thread ``check()``s it from outside.  On a stall the watchdog cannot
    touch the wedged engine — it fails the *clients*: every subscriber
    queue gets a synthetic terminal ``StreamEvent`` with
    ``FinishReason.STALL`` (``output is None``) and is unsubscribed, so no
    stream ever hangs past the timeout.
    """

    def __init__(self, engine, idle_poll_s: float = 0.02,
                 stall_timeout_s: float | None = None):
        super().__init__(daemon=True, name="engine-worker")
        self.eng = engine
        self.idle_poll_s = idle_poll_s
        self.inbox: "queue.Queue[tuple[str, object]]" = queue.Queue()
        self._halt = threading.Event()
        self._subs: dict[int, queue.Queue] = {}
        self._subs_lock = threading.Lock()
        self.stalled_requests = 0
        self.heartbeat: Heartbeat | None = None
        self._watchdog: threading.Thread | None = None
        if stall_timeout_s is not None:
            self.heartbeat = Heartbeat(timeout_s=stall_timeout_s,
                                       clock=engine.clock.now)
            self._watchdog = threading.Thread(
                target=self._watch, daemon=True, name="engine-watchdog")

    def start(self):
        super().start()
        if self._watchdog is not None:
            self._watchdog.start()

    # ---------------------------------------------- handler-thread interface
    def submit(self, sub: _Submission) -> int:
        """Hand a submission to the engine thread; returns its rid or raises
        the engine's validation error."""
        self.inbox.put(("submit", sub))
        res = sub.reply.get(timeout=EVENT_TIMEOUT_S)
        if isinstance(res, Exception):
            raise res
        return res

    def abort(self, rid: int):
        self.inbox.put(("abort", rid))

    def shutdown(self, timeout: float = 5.0):
        self._halt.set()
        self.join(timeout=timeout)

    # ------------------------------------------------------- engine thread
    def _handle(self, op: str, payload):
        if op == "submit":
            sub = payload
            try:
                rid = self.eng.submit(
                    sub.tokens, max_new_tokens=sub.max_new_tokens,
                    sampling=sub.sampling,
                    stop_token_ids=sub.stop_token_ids,
                    ignore_eos=sub.ignore_eos,
                    priority=sub.priority,
                    queue_timeout_s=sub.queue_timeout_s)
            except Exception as e:    # validation -> 400, QueueFull -> 429
                sub.reply.put(e)
                return
            with self._subs_lock:
                self._subs[rid] = sub.events
            sub.reply.put(rid)
        elif op == "abort":
            self.eng.abort(payload)          # terminal event reaches the
            # subscriber via the engine's event list on the next drain; a
            # disconnected client's queue simply goes unread after that
        else:                                # pragma: no cover
            raise AssertionError(f"unknown op {op!r}")

    def _fan_out(self, events):
        for ev in events:
            with self._subs_lock:
                q = self._subs.get(ev.rid)
                if q is not None and ev.finish_reason is not None:
                    self._subs.pop(ev.rid, None)
            if q is not None:
                q.put(ev)

    def _fail_subs(self, reason: FinishReason):
        """Watchdog path: terminate every subscribed client with a synthetic
        terminal event (``output is None`` — the engine never produced a
        ``RequestOutput``) and drop the subscriptions."""
        with self._subs_lock:
            victims = list(self._subs.items())
            self._subs.clear()
        for rid, q in victims:
            self.stalled_requests += 1
            q.put(StreamEvent(rid=rid, token=None, index=0,
                              finish_reason=reason, output=None))

    def _watch(self):
        hb = self.heartbeat
        poll_s = min(0.05, hb.timeout_s / 4)
        while not self._halt.is_set():
            if not hb.check():
                self._fail_subs(FinishReason.STALL)
            self._halt.wait(poll_s)

    def _front(self, name: str, fn, *args):
        """Front-end work on the engine thread, outside ``Engine.step``: a
        ``name`` span, its clock time counted into
        ``engine_frontend_seconds_total``."""
        eng = self.eng
        with span(name, eng.tracer):
            t0 = eng.clock.now()
            fn(*args)
            eng.metrics.frontend_seconds.inc(eng.clock.now() - t0)

    def run(self):
        while not self._halt.is_set():
            if self.heartbeat is not None:
                self.heartbeat.beat()
            while True:                      # drain all pending control ops
                try:
                    op, payload = self.inbox.get_nowait()
                except queue.Empty:
                    break
                self._front("worker.inbox", self._handle, op, payload)
            if self.eng.sched.idle:
                # an abort that idled the engine leaves its terminal event
                # pending — deliver it (and release the _subs entry) now
                events = self.eng.drain_events()
                if events:
                    self._front("worker.fan_out", self._fan_out, events)
                # waiting for a request: a profiler span only (an idle
                # server would fill the Perfetto export with polls)
                with span("worker.idle"):
                    try:
                        op, payload = self.inbox.get(
                            timeout=self.idle_poll_s)
                    except queue.Empty:
                        continue
                self._front("worker.inbox", self._handle, op, payload)
                continue
            events = self.eng.step_events()
            self._front("worker.fan_out", self._fan_out, events)


# --------------------------------------------------------------- HTTP layer
def _parse_completion_body(body: dict) -> _Submission:
    prompt = body.get("prompt")
    if (not isinstance(prompt, list) or not prompt
            or not all(isinstance(t, int) for t in prompt)):
        raise ValueError(
            "'prompt' must be a non-empty list of token ids (this server "
            "has no tokenizer)")
    temperature = float(body.get("temperature", 1.0))
    stop = body.get("stop", [])
    if isinstance(stop, int):
        stop = [stop]
    if not isinstance(stop, list) or not all(isinstance(t, int) for t in stop):
        raise ValueError("'stop' must be a token id or list of token ids")
    timeout = body.get("queue_timeout_s")
    if timeout is not None:
        timeout = float(timeout)
        if timeout <= 0:
            raise ValueError("'queue_timeout_s' must be > 0")
    return _Submission(
        tokens=list(prompt),
        max_new_tokens=int(body.get("max_tokens", 16)),
        sampling=SamplingParams(
            temperature=temperature,
            top_k=int(body.get("top_k", 0)),
            top_p=float(body.get("top_p", 1.0)),
            greedy=temperature == 0.0),
        stop_token_ids=tuple(stop),
        ignore_eos=bool(body.get("ignore_eos", False)),
        priority=int(body.get("priority", 0)),
        queue_timeout_s=timeout)


def _choice(ev_or_tokens, finish_reason=None) -> dict:
    toks = (ev_or_tokens if isinstance(ev_or_tokens, list)
            else [ev_or_tokens])
    return {"index": 0,
            "token_ids": toks,
            "text": " ".join(map(str, toks)),
            "finish_reason": (finish_reason.value
                              if finish_reason is not None else None)}


class CompletionsHandler(BaseHTTPRequestHandler):
    """``/v1/completions`` (+ ``/v1/models``, ``/healthz``, ``/metrics``)."""
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):            # keep benchmark/test output clean
        pass

    @property
    def worker(self) -> EngineWorker:
        return self.server.worker

    def _json(self, code: int, payload: dict,
              headers: dict | None = None):
        data = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        if self.path == "/healthz":
            self._healthz()
        elif self.path == "/metrics":
            self._metrics()
        elif self.path == "/v1/models":
            self._json(200, {"object": "list", "data": [
                {"id": self.server.model_name, "object": "model"}]})
        else:
            self._json(404, {"error": {"message": f"no route {self.path}"}})

    def _healthz(self):
        """Liveness + worker-heartbeat freshness.  With the watchdog armed
        (``stall_timeout_s``), a stale heartbeat turns this into a 503 so a
        scraper/load-balancer sees the wedged engine the same way in-flight
        clients do (DESIGN.md §15)."""
        hb = self.worker.heartbeat
        if hb is None:
            self._json(200, {"status": "ok", "watchdog": "disarmed"})
            return
        healthy = hb.healthy
        self._json(200 if healthy else 503, {
            "status": "ok" if healthy else "stalled",
            "watchdog": "armed",
            "heartbeat_stale_s": round(hb.stale_s, 6),
            "heartbeat_timeout_s": hb.timeout_s,
            "missed": hb.missed,
            "stalled_requests": self.worker.stalled_requests})

    def _metrics(self):
        """Prometheus text exposition (format 0.0.4) of the engine's
        registry.  The snapshot is read without pausing the worker — every
        sample is a plain float read, torn at worst by one step."""
        text = self.worker.eng.metrics.registry.expose()
        data = text.encode()
        self.send_response(200)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4; charset=utf-8")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_POST(self):
        if self.path != "/v1/completions":
            self._json(404, {"error": {"message": f"no route {self.path}"}})
            return
        try:
            n = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(n) or b"{}")
            sub = _parse_completion_body(body)
        except (ValueError, json.JSONDecodeError) as e:
            self._json(400, {"error": {"message": str(e),
                                       "type": "invalid_request_error"}})
            return
        try:
            rid = self.worker.submit(sub)
        except QueueFullError as e:          # bounded admission -> shed early
            self._json(429, {"error": {"message": str(e),
                                       "type": "overloaded_error"}},
                       headers={"Retry-After":
                                str(max(1, int(e.retry_after_s)))})
            return
        except (ValueError, queue.Empty) as e:
            self._json(400, {"error": {"message": str(e),
                                       "type": "invalid_request_error"}})
            return
        if body.get("stream", False):
            self._stream_response(rid, sub)
        else:
            self._blocking_response(rid, sub)

    # ------------------------------------------------------------ responses
    def _envelope(self, rid: int) -> dict:
        return {"id": f"cmpl-{rid}", "object": "text_completion",
                "created": int(self.worker.eng.clock.now()),
                "model": self.server.model_name}

    def _blocking_response(self, rid: int, sub: _Submission):
        toks: list[int] = []
        reason = None
        out = None
        while True:
            try:
                ev = sub.events.get(timeout=EVENT_TIMEOUT_S)
            except queue.Empty:
                # engine stalled: cancel the request so its reservation
                # frees, and tell the client instead of dropping the socket
                self.worker.abort(rid)
                self._json(504, {"error": {
                    "message": f"no token within {EVENT_TIMEOUT_S:.0f}s",
                    "type": "timeout_error"}})
                return
            if ev.token is not None:
                toks.append(ev.token)
            if ev.finish_reason is not None:
                reason = ev.finish_reason
                out = ev.output         # None on synthetic watchdog events
                break
        if reason in (FinishReason.SHED, FinishReason.STALL):
            # overload outcome: 503 + Retry-After; the SHED request never
            # produced a token, the STALL one may have partial output the
            # client opted not to stream
            self._json(503, {"error": {
                "message": f"request {reason.value} under overload",
                "type": "overloaded_error"}},
                headers={"Retry-After": "1"})
            return
        resp = self._envelope(rid)
        resp["choices"] = [_choice(toks, reason)]
        resp["usage"] = {
            "prompt_tokens": out.prompt_len, "completion_tokens": len(toks),
            "total_tokens": out.prompt_len + len(toks)}
        resp["metrics"] = {"ttft_s": out.ttft, "tpot_s": out.tpot,
                           "latency_s": out.latency}
        self._json(200, resp)

    def _stream_response(self, rid: int, sub: _Submission):
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Connection", "close")
        self.end_headers()
        try:
            while True:
                ev = sub.events.get(timeout=EVENT_TIMEOUT_S)
                chunk = self._envelope(rid)
                chunk["choices"] = [_choice(
                    [ev.token] if ev.token is not None else [],
                    ev.finish_reason)]
                self.wfile.write(b"data: " + json.dumps(chunk).encode()
                                 + b"\n\n")
                self.wfile.flush()
                if ev.finish_reason is not None:
                    break
            self.wfile.write(b"data: [DONE]\n\n")
            self.wfile.flush()
        except (BrokenPipeError, ConnectionResetError):
            # client went away mid-stream: cancel the request so its slot /
            # paged reservation (and prefix refcounts) free immediately
            self.worker.abort(rid)
        except queue.Empty:
            self.worker.abort(rid)


class CompletionsServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True

    def __init__(self, addr, handler, *, worker: EngineWorker,
                 model_name: str):
        super().__init__(addr, handler)
        self.worker = worker
        self.model_name = model_name

    def shutdown(self):
        super().shutdown()
        self.worker.shutdown()

    @property
    def port(self) -> int:
        return self.server_address[1]


def make_server(engine, host: str = "127.0.0.1", port: int = 0,
                model_name: str = "repro",
                stall_timeout_s: float | None = None) -> CompletionsServer:
    """Start the engine worker and bind the HTTP server (``port=0`` picks an
    ephemeral port — read it back from ``server.port``).  The caller runs
    ``server.serve_forever()``; ``server.shutdown()`` stops both.
    ``stall_timeout_s`` arms the worker watchdog (DESIGN.md §14)."""
    worker = EngineWorker(engine, stall_timeout_s=stall_timeout_s)
    worker.start()
    return CompletionsServer((host, port), CompletionsHandler,
                             worker=worker, model_name=model_name)
