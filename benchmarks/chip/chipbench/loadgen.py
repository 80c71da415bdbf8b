"""Load generator: a child process that never imports JAX.

    python -m chipbench.loadgen <spec.json> <result.json>

``spec.json`` holds ``port``, ``traffic`` (a traffic file's object),
``seed``, ``vocab`` and ``seconds``.  The generator draws its request plan
(``chipbench.traffic``), drives ``POST /v1/completions`` with
``"stream": true``, ``temperature 0`` and ``ignore_eos``, and times every
streamed token as it arrives.  It prints ``OPEN <t>`` and ``CLOSE <t>`` on
standard output when the measured window opens and closes, ``t`` on
``time.monotonic()`` (one clock for every process of the machine), and
writes every request's record to ``result.json``:

* closed loop: the clients send their first requests one at a time,
  ``STAGGER_S`` apart, so the server admits them in the same order in
  every run; the window opens once every client's first request has its
  first token, and lasts ``seconds``; clients send nothing new after it;
* open loop: requests go out at their planned times from the start, the
  window opens ``lead_s`` after the start, and nothing is sent after it.

Then the generator waits up to ``drain_s`` for answers still open, hangs up
on the rest (the server aborts them) and exits.
"""
from __future__ import annotations

import http.client
import json
import socket
import sys
import threading
import time

from chipbench import traffic as T

READ_TIMEOUT_S = 900.0
OPEN_TIMEOUT_S = 600.0
STAGGER_S = 0.02
STAGGER_TIMEOUT_S = 10.0


class Requests:
    """Records of every request sent, and the open connections to hang up
    on when the run ends."""

    def __init__(self, port: int):
        self.port = port
        self.records: list[dict] = []
        self._socks: set = set()
        self._lock = threading.Lock()
        self._hung_up = False

    def send(self, req: dict, rec: dict, on_first=None,
             on_sent=None) -> None:
        body = json.dumps({"prompt": req["prompt"],
                           "max_tokens": req["max_tokens"],
                           "temperature": 0.0, "ignore_eos": True,
                           "stream": True}).encode()
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=READ_TIMEOUT_S)
        with self._lock:
            if self._hung_up:
                rec["status"] = "not sent"
                return
            self.records.append(rec)
        rec["sent"] = time.monotonic()
        sock = None
        try:
            conn.connect()
            sock = conn.sock       # the response keeps reading this socket
            with self._lock:       # after the connection lets go of it
                self._socks.add(sock)
            if self._hung_up:
                raise ConnectionAbortedError("hung up before sending")
            conn.request("POST", "/v1/completions", body,
                         {"Content-Type": "application/json"})
            if on_sent is not None:
                on_sent()
            resp = conn.getresponse()
            rec["http"] = resp.status
            if resp.status != 200:
                rec["status"] = f"http {resp.status}: {resp.read()[:200]!r}"
                return
            while True:
                line = resp.readline()
                t = time.monotonic()
                if not line or self._hung_up:
                    rec["status"] = ("hung up" if self._hung_up
                                     else "closed early")
                    return
                line = line.strip()
                if line == b"data: [DONE]":
                    rec["status"] = "ok"
                    return
                if not line.startswith(b"data: "):
                    continue
                choice = json.loads(line[6:])["choices"][0]
                for tok in choice["token_ids"]:
                    if not rec["tokens"] and on_first is not None:
                        on_first()
                    rec["tokens"].append(tok)
                    rec["times"].append(t)
                if choice["finish_reason"] is not None:
                    rec["finish"] = choice["finish_reason"]
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["status"] = ("hung up" if self._hung_up
                             else f"error: {type(e).__name__}: {e}")
        finally:
            with self._lock:
                self._socks.discard(sock)
            conn.close()

    def hang_up(self) -> None:
        """Shut every open connection down (a streaming read then ends);
        no request is sent after this."""
        with self._lock:
            self._hung_up = True
            socks = list(self._socks)
        for sock in socks:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


def _record(req: dict, **extra) -> dict:
    return {"prompt": req["prompt"], "max_tokens": req["max_tokens"],
            "tokens": [], "times": [], "finish": None, "status": "pending",
            "sent": None, **extra}


def _say(word: str, t: float) -> None:
    print(f"{word} {t!r}", flush=True)


def _sleep_until(t: float) -> None:
    while (d := t - time.monotonic()) > 0:
        time.sleep(min(d, 0.05))


def _finish(reqs: Requests, threads: list, deadline: float) -> None:
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.monotonic()))
    reqs.hang_up()
    for th in threads:
        th.join(timeout=30.0)


def run_closed(reqs: Requests, plan: dict, traffic: dict, seconds: float):
    clients = plan["clients"]
    started = threading.Event()
    stop = threading.Event()
    lock = threading.Lock()
    waiting = {"n": len(clients)}

    def first_token():
        with lock:
            waiting["n"] -= 1
            if waiting["n"] == 0:
                started.set()

    def client(ci: int, sent: threading.Event):
        for ri, req in enumerate(clients[ci]):
            if stop.is_set():
                return
            reqs.send(req, _record(req, client=ci, index=ri),
                      first_token if ri == 0 else None,
                      sent.set if ri == 0 else None)
        print(f"client {ci} ran out of requests", file=sys.stderr)

    threads = []
    for i in range(len(clients)):
        sent = threading.Event()
        th = threading.Thread(target=client, args=(i, sent), daemon=True)
        threads.append(th)
        th.start()
        # the server takes the first requests in the clients' order
        sent.wait(STAGGER_TIMEOUT_S)
        time.sleep(STAGGER_S)
    if not started.wait(OPEN_TIMEOUT_S):
        reqs.hang_up()
        raise RuntimeError(f"{waiting['n']} of {len(clients)} clients had no "
                           f"first token after {OPEN_TIMEOUT_S:.0f} s")
    t_open = time.monotonic()
    _say("OPEN", t_open)
    _sleep_until(t_open + seconds)
    t_close = time.monotonic()
    _say("CLOSE", t_close)
    stop.set()
    _finish(reqs, threads, t_close + traffic["drain_s"])
    return t_open, t_close, {}


def run_open(reqs: Requests, plan: dict, traffic: dict, seconds: float):
    t0 = time.monotonic() + 0.2
    t_open = t0 + traffic["lead_s"]
    threads: list[threading.Thread] = []

    def dispatch():
        for i, req in enumerate(plan["requests"]):
            due = t0 + req["at"]
            _sleep_until(due)
            rec = _record(req, index=i, scheduled=due)
            th = threading.Thread(target=reqs.send, args=(req, rec),
                                  daemon=True)
            threads.append(th)
            th.start()

    sender = threading.Thread(target=dispatch, daemon=True)
    sender.start()
    _sleep_until(t_open)
    _say("OPEN", time.monotonic())
    _sleep_until(t_open + seconds)
    t_close = time.monotonic()
    _say("CLOSE", t_close)
    sender.join()
    _finish(reqs, threads, t_close + traffic["drain_s"])
    late = sorted(r["sent"] - r["scheduled"] for r in reqs.records
                  if r["sent"] is not None)
    lateness = ({"p50_s": late[len(late) // 2],
                 "p95_s": late[min(len(late) - 1, int(0.95 * len(late)))],
                 "max_s": late[-1]} if late else {})
    return t_open, t_close, lateness


def main(argv: list[str]) -> int:
    spec_path, out_path = argv
    with open(spec_path) as f:
        spec = json.load(f)
    traffic = spec["traffic"]
    plan = T.plan(traffic, spec["seed"], spec["vocab"], spec["seconds"])
    reqs = Requests(spec["port"])
    run = run_closed if plan["loop"] == "closed" else run_open
    result = {"error": None}
    try:
        t_open, t_close, lateness = run(reqs, plan, traffic, spec["seconds"])
        result.update(window=[t_open, t_close], lateness=lateness)
    except RuntimeError as e:
        result["error"] = str(e)
    result["records"] = reqs.records
    with open(out_path, "w") as f:
        json.dump(result, f)
    return 0 if result["error"] is None else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
