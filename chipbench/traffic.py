"""Traffic: the clients that send a mix's requests over HTTP on the real
clock, and the open loop's arrival schedule.

The requests themselves come from the operations module the mix names
(``chipbench/ops/<name>.py``); each has a ``route`` and a ``payload``. The
schedule follows the repository's ``poisson_arrivals``
(``serving/workload.py``), with one change that gives every seed the same
work: an open loop sends exactly ``rate * seconds`` requests at times drawn
uniformly over the window, which is a Poisson process conditioned on its
count.
"""
from __future__ import annotations

import dataclasses
import http.client
import json
import queue
import threading
import time
from typing import List, Optional

import jax
import numpy as np


def open_loop_times(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Sorted send times in [0, seconds): round(rate * seconds) of them."""
    rng = np.random.default_rng([seed, 2])
    return np.sort(rng.uniform(0.0, seconds, size=int(round(rate * seconds))))


@dataclasses.dataclass
class Record:
    """One request as the client saw it; times on ``time.perf_counter``."""

    item: int
    due: float
    sent: float = 0.0
    done: Optional[float] = None
    status: int = 0
    body: Optional[dict] = None

    @property
    def answered(self) -> bool:
        return (self.done is not None and self.status == 200 and self.body is not None
                and not self.body.get("error"))


class _Connection:
    """One keep-alive HTTP connection to the front end, opened and served
    once (``GET /healthz``) when it is made: the server's listen backlog is
    short, and a window that opens a caller's connection on its first
    request resets some of them when many callers start at once."""

    def __init__(self, host: str, port: int, timeout: float):
        self.args = (host, port, timeout)
        self.conn = http.client.HTTPConnection(host, port, timeout=timeout)
        self.conn.request("GET", "/healthz")
        self.conn.getresponse().read()

    def post(self, path: str, body: bytes) -> tuple:
        for attempt in (0, 1):
            try:
                self.conn.request("POST", path, body=body,
                                  headers={"Content-Type": "application/json"})
                resp = self.conn.getresponse()
                return resp.status, json.loads(resp.read())
            except (http.client.HTTPException, OSError):
                self.conn.close()
                self.conn = http.client.HTTPConnection(*self.args)
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def close(self) -> None:
        self.conn.close()


def _send(conn: _Connection, rec: Record, route: str, payload: bytes) -> None:
    rec.sent = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("chipbench.request", item=rec.item):
            rec.status, rec.body = conn.post(route, payload)
    except (http.client.HTTPException, OSError, ValueError) as e:
        rec.status, rec.body = -1, {"error": repr(e)}
    rec.done = time.perf_counter()


def send_each(host: str, port: int, items: list, timeout_s: float) -> List[Record]:
    """Every item at once, one connection each; returns when all replied."""
    recs = [Record(item=i, due=time.perf_counter()) for i in range(len(items))]
    conns = [_Connection(host, port, timeout_s + 30) for _ in items]
    threads = [threading.Thread(target=_send, args=(c, r, it.route, it.payload(timeout_s)))
               for c, r, it in zip(conns, recs, items)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for c in conns:
        c.close()
    return recs


class Clients:
    """Sends items to ``host:port`` for ``seconds``, as an open loop (each
    request at its own time, whatever is in flight) or a closed loop
    (``clients`` callers, each sending its next request on reply)."""

    def __init__(self, host: str, port: int, items: list, mix: dict,
                 seed: int, seconds: float):
        self.items = items
        self.mix = mix
        self.seconds = float(seconds)
        self.timeout_s = float(mix["request_timeout_s"])
        self.sends = [(it.route, it.payload(self.timeout_s)) for it in items]
        self.open = mix["loop"] == "open"
        self.times = open_loop_times(seed, mix["rate_qps"], seconds) if self.open else None
        n_threads = mix["workers"] if self.open else mix["clients"]
        self.conns = [_Connection(host, port, self.timeout_s + 30) for _ in range(n_threads)]
        self.records: List[Record] = []
        self._lock = threading.Lock()
        self._threads: list = []
        self.t0 = None

    def start(self) -> float:
        """Start sending; returns the window's start on ``perf_counter``."""
        self.t0 = time.perf_counter()
        if self.open:
            self.records = [Record(item=i % len(self.items), due=self.t0 + t)
                            for i, t in enumerate(self.times)]
            work: queue.Queue = queue.Queue()
            self._threads = [threading.Thread(target=self._schedule, args=(work,))]
            self._threads += [threading.Thread(target=self._worker, args=(c, work))
                              for c in self.conns]
        else:
            self._threads = [threading.Thread(target=self._caller, args=(c,))
                             for c in self.conns]
        for t in self._threads:
            t.start()
        return self.t0

    def _schedule(self, work: queue.Queue) -> None:
        for rec in self.records:
            delay = rec.due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            work.put(rec)
        for _ in self.conns:
            work.put(None)

    def _worker(self, conn: _Connection, work: queue.Queue) -> None:
        while (rec := work.get()) is not None:
            _send(conn, rec, *self.sends[rec.item])

    def _caller(self, conn: _Connection) -> None:
        end = self.t0 + self.seconds
        while (now := time.perf_counter()) < end:
            with self._lock:
                rec = Record(item=len(self.records) % len(self.items), due=now)
                self.records.append(rec)
            _send(conn, rec, *self.sends[rec.item])

    def join(self, grace_s: float) -> bool:
        """Wait until every request sent in the window has its reply, at
        most ``grace_s`` past the window's close; True when none is left."""
        give_up = self.t0 + self.seconds + grace_s
        for t in self._threads:
            t.join(timeout=max(give_up - time.perf_counter(), 0.0))
        return not any(t.is_alive() for t in self._threads)

    def close(self) -> None:
        for c in self.conns:
            c.close()
        for t in self._threads:
            t.join(timeout=5.0)

    def lateness_s(self) -> np.ndarray:
        """How late each open-loop request was sent against its time."""
        return np.asarray([r.sent - r.due for r in self.records if r.sent])
