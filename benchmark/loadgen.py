"""The one general load generator: scribe ``Log`` calls and HTTP reads,
closed or open loop, as a traffic file says. One process, a thread per
connection; the threads only ``sendall``/``recv`` frames that
``gen.Stream``'s producer made ahead of them and note the clock.
"""

from __future__ import annotations

import http.client
import math
import socket
import struct
import threading
import time
import urllib.parse

import numpy as np

from gen import decode_reply
from reference import hex_id

OK, TRY_LATER = 0, 1


def percentile(values, q: float):
    """The q-quantile by rank (nearest rank above): of every value."""
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def _recv_exact(sock, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("scribe server closed the connection")
        buf += chunk
    return bytes(buf)


class Ingest:
    """Sends the stream's frames from ``first`` on (up to ``limit``,
    where one is given) over ``connections`` sockets. Closed loop: each connection sends its next call when the
    last was answered. Open loop: call n is due at t0 + n * interval and
    is timed from then. TRY_LATER is resent after a backoff."""

    def __init__(self, port: int, stream, spec: dict, check_alive):
        self.port, self.stream, self.spec = port, stream, spec
        self.check_alive = check_alive
        self.lock = threading.Lock()
        self.next = 0
        self.records = []       # (frame, t_due, t_first_send, t_ack, tries, ok)
        self.try_later = 0
        self.sent_calls = 0     # sends, resends included
        self.last_acked = -1
        self.errors = []

    def run(self, first: int, limit: int = None, seconds: float = None,
            open_rate_spans_per_s: float = None) -> tuple:
        """Drive until ``limit`` frames are taken or ``seconds`` passed;
        in-flight calls are waited for. Returns (t0, t_end)."""
        self.next, self.limit = first, limit
        self.first = first
        self.interval = (self.stream.call_spans / open_rate_spans_per_s
                         if open_rate_spans_per_s else None)
        self.t0 = time.monotonic()
        self.t_end = self.t0 + seconds if seconds is not None else None
        threads = [threading.Thread(target=self._worker, daemon=True)
                   for _ in range(self.spec["connections"])]
        for t in threads:
            t.start()
        self.threads = threads
        return self.t0, self.t_end

    def join(self) -> None:
        for t in self.threads:
            t.join()
        if self.errors:
            raise self.errors[0]

    def _take(self):
        with self.lock:
            now = time.monotonic()
            if self.t_end is not None and now >= self.t_end:
                return None
            n = self.next
            if self.limit is not None and n >= self.limit:
                return None
            due = None
            if self.interval is not None:
                due = self.t0 + (n - self.first) * self.interval
                if self.t_end is not None and due >= self.t_end:
                    return None
            self.next = n + 1
            return n, due

    def _worker(self) -> None:
        try:
            sock = socket.create_connection(("127.0.0.1", self.port), 900.0)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            try:
                while True:
                    took = self._take()
                    if took is None:
                        return
                    n, due = took
                    if due is not None:
                        wait = due - time.monotonic()
                        if wait > 0:
                            time.sleep(wait)
                    self._call(sock, n, due)
            finally:
                sock.close()
        except Exception as e:  # surfaced by join()
            self.errors.append(e)

    def _call(self, sock, n: int, due) -> None:
        frame = self.stream.frame(n)
        backoff = self.spec.get("try_later_backoff_s", 0.05)
        t_first = None
        for tries in range(1, self.spec.get("max_tries", 100) + 1):
            t_send = time.monotonic()
            if t_first is None:
                t_first = t_send
            sock.sendall(frame)
            (size,) = struct.unpack(">i", _recv_exact(sock, 4))
            code = decode_reply(_recv_exact(sock, size))
            t_ack = time.monotonic()
            with self.lock:
                self.sent_calls += 1
                if code == OK:
                    self.records.append((n, due, t_first, t_ack, tries, True))
                    self.last_acked = max(self.last_acked, n)
                    return
                self.try_later += 1
            if code != TRY_LATER:
                raise RuntimeError(f"scribe answered result code {code}")
            self.check_alive()
            time.sleep(min(backoff * tries, 1.0))
        with self.lock:
            self.records.append((n, due, t_first, time.monotonic(),
                                 tries, False))


class Reads:
    """Open-loop HTTP reads: read j is due at t0 + j / per_s, its route
    drawn from the mix by the seed, and is timed from when it was due."""

    def __init__(self, http_port: int, stream, spec: dict, rng, ingest):
        self.port, self.stream, self.spec = http_port, stream, spec
        self.ingest = ingest
        self.routes = spec["routes"]
        names = [m["route"] for m in spec["mix"]]
        # Every seed sends the same reads in another order: the mix is a
        # fixed multiset per cycle (share x cycle_reads of each route),
        # shuffled cycle by cycle.
        per = spec["cycle_reads"]
        counts = [round(m["share"] * per) for m in spec["mix"]]
        if sum(counts) != per or not all(counts):
            raise ValueError("the mix's shares do not fill a cycle of "
                             f"{per} reads with whole counts: {counts}")
        one = np.repeat(np.arange(len(names)), counts)
        n = per * spec.get("schedule_cycles", 256)
        self.kind = np.concatenate(
            [rng.permutation(one) for _ in range(n // per)])
        self.names = names
        self.svc = rng.integers(0, len(stream.pool.services), size=n)
        self.u = rng.random(size=(n, 2))
        self.lock = threading.Lock()
        self.records = []  # (j, route, t_due, t_send, t_done, status, nbytes)
        self.errors = []

    def path_of(self, j: int) -> str:
        route = self.routes[self.names[self.kind[j % len(self.kind)]]]
        subs = {"service": self.stream.pool.services[
            self.svc[j % len(self.svc)]]}
        if "{trace}" in route["path"]:
            subs["trace"] = self._recent_trace(*self.u[j % len(self.u)])
        path = route["path"].format(**subs)
        params = {k: (v.format(**subs) if isinstance(v, str) else v)
                  for k, v in route.get("params", {}).items()}
        return path + ("?" + urllib.parse.urlencode(params) if params else "")

    def _recent_trace(self, u1: float, u2: float) -> str:
        """A trace from one of the ``trace_recent_calls`` calls acked
        before the newest ``trace_lag_calls``."""
        last = self.ingest.last_acked - self.spec.get("trace_lag_calls", 0)
        frame = max(0, last - int(u1 * self.spec.get("trace_recent_calls", 8)))
        c = self.stream.call_spans
        return hex_id(self.stream.trace_id_at(frame * c + int(u2 * c)))

    def run(self, seconds: float, per_s: float, first: int = 0) -> None:
        self.t0 = time.monotonic()
        self.t_end = self.t0 + seconds
        self.per_s, self.next, self.first = per_s, first, first
        self.threads = [threading.Thread(target=self._worker, daemon=True)
                        for _ in range(self.spec["workers"])]
        for t in self.threads:
            t.start()

    def join(self) -> None:
        for t in self.threads:
            t.join()
        if self.errors:
            raise self.errors[0]

    def _worker(self) -> None:
        try:
            while True:
                with self.lock:
                    j = self.next
                    due = self.t0 + (j - self.first) / self.per_s
                    if due >= self.t_end:
                        return
                    self.next = j + 1
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                self.get(j, due)
        except Exception as e:
            self.errors.append(e)

    def get(self, j: int, due: float = None) -> float:
        """One read; returns its seconds on the wire."""
        path = self.path_of(j)
        t_send = time.monotonic()
        conn = http.client.HTTPConnection(
            "127.0.0.1", self.port,
            timeout=self.spec.get("timeout_s", 120.0))
        try:
            conn.request("GET", path)
            r = conn.getresponse()
            body = r.read()
            status = r.status
        except (OSError, http.client.HTTPException):
            status, body = 0, b""
        finally:
            conn.close()
        t_done = time.monotonic()
        with self.lock:
            self.records.append(
                (j, self.names[self.kind[j % len(self.kind)]],
                 due if due is not None else t_send, t_send, t_done,
                 status, len(body)))
        return t_done - t_send
