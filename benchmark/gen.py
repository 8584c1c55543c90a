"""Span stream of a run, made from the seed alone.

A copy of ``zipkin_tpu/tracegen/gen.py:generate_traces``'s SHAPES
(TraceGen.scala: span trees of depth <= 7 with 0..2 children a node,
six annotations and two binary annotations a span, fixed vocabulary),
drawn in bulk with numpy instead of span by span, and encoded here to
thrift and to complete scribe ``Log`` frames. Nothing is imported from
the program: what this file makes is the benchmark's input AND the
plain reference's data (``reference.py``).

The stream is a POOL of spans followed by re-keyed passes over it,
without end: pass k XORs every trace/span/parent id with a salt drawn
from the seed and adds ``k * pass_shift_us`` to every timestamp, so
later passes are newer (a live stream whose frontier moves) and ids
never repeat. Stream position p is pool span ``p % pool`` in pass
``p // pool``.
"""

from __future__ import annotations

import binascii
import struct
import threading
import time

import numpy as np

WORDS = (
    "lorem", "ipsum", "dolor", "sit", "amet", "consectetur", "adipiscing",
    "elit", "vivamus", "posuere", "mauris", "tortor", "gravida", "sodales",
)
BASE_TS = 1_000_000_000_000
CUSTOM_ANNOTATION = "some custom annotation"
URI_KEY, URI_VALUE = "http.uri", b"/api/widgets"
ANNOTATIONS_PER_SPAN = 6
BINARY_PER_SPAN = 2
CATEGORY = b"zipkin"

# thrift binary protocol type ids
T_BOOL, T_I16, T_I32, T_I64, T_STRING, T_STRUCT, T_LIST = 2, 6, 8, 10, 11, 12, 15
VERSION_1, MSG_CALL = 0x80010000, 1


def _fh(ftype: int, fid: int) -> bytes:
    return struct.pack(">bh", ftype, fid)


_F_I64_1, _F_STR_3, _F_I64_4, _F_I64_5 = (
    _fh(T_I64, 1), _fh(T_STRING, 3), _fh(T_I64, 4), _fh(T_I64, 5))
_F_LIST_6, _F_LIST_8, _F_BOOL_9 = _fh(T_LIST, 6), _fh(T_LIST, 8), _fh(T_BOOL, 9)
_F_STR_1, _F_STR_2, _F_STRUCT_3, _F_STRUCT_4 = (
    _fh(T_STRING, 1), _fh(T_STRING, 2), _fh(T_STRUCT, 3), _fh(T_STRUCT, 4))
_F_I32_1, _F_I16_2, _F_I32_3 = _fh(T_I32, 1), _fh(T_I16, 2), _fh(T_I32, 3)
_ANN_LIST = _F_LIST_6 + struct.pack(">bi", T_STRUCT, ANNOTATIONS_PER_SPAN)
_BANN_LIST = _F_LIST_8 + struct.pack(">bi", T_STRUCT, BINARY_PER_SPAN)
_TAIL = _F_BOOL_9 + b"\x00" + b"\x00"  # debug=false, struct stop
BYTES_TYPE = 1  # AnnotationType.BYTES


def _s(b: bytes) -> bytes:
    return struct.pack(">i", len(b)) + b


class Pool:
    """Columns of the pool's spans (trace order: a parent before its
    children) and their thrift bytes in one buffer."""

    def __init__(self, seed: int, n_spans: int, n_services: int,
                 max_depth: int = 7):
        rng = np.random.default_rng([int(seed), 0x5A1])
        self.services = [f"{WORDS[rng.integers(0, len(WORDS))]}-{i}"
                         for i in range(n_services)]
        cols = _grow_trees(rng, n_spans, n_services, max_depth)
        (self.trace_idx, self.parent_pos, self.svc, self.client_svc,
         self.start, self.budget) = cols
        n = self.n = len(self.svc)
        self.n_traces = int(self.trace_idx[-1]) + 1
        # 62-bit ids, distinct by construction of the draw (a repeat in
        # 2^17 draws of 2^62 has probability 2^-29; re-drawn if seen).
        self.trace_id = _distinct_ids(rng, self.n_traces)[self.trace_idx]
        self.span_id = _distinct_ids(rng, n)
        self.has_parent = self.parent_pos >= 0
        self.parent_id = np.where(
            self.has_parent, self.span_id[np.maximum(self.parent_pos, 0)], 0)
        w = len(WORDS)
        self.name_w = rng.integers(0, w, size=(n, 2))       # span name
        self.custom_w = rng.integers(0, w, size=(n, 2))     # 3rd annotation
        self.bkey_w = rng.integers(0, w, size=n)            # 1st binary key
        self.bval_w = rng.integers(0, w, size=(n, 3))       # its value
        self.client_ip = rng.integers(1, 2**31, size=n)
        self.server_ip = rng.integers(1, 2**31, size=n)
        self.end = self.start + self.budget
        self._encode()

    # -- what a span says, as plain values (the reference reads these) --

    def span_name(self, i: int) -> str:
        a, b = self.name_w[i]
        return f"{WORDS[a]}-{WORDS[b]}"

    def annotations(self, i: int):
        """[(timestamp, value, (ipv4, port, service))] as sent."""
        st, bu = int(self.start[i]), int(self.budget[i])
        client = (int(self.client_ip[i]), 80, self.services[self.client_svc[i]])
        server = (int(self.server_ip[i]), 443, self.services[self.svc[i]])
        a, b = self.custom_w[i]
        return [
            (st, "cs", client),
            (st + 1, "sr", server),
            (st + bu // 2, f"{WORDS[a]}-{WORDS[b]}", server),
            (st + bu // 2 + 1, CUSTOM_ANNOTATION, server),
            (st + bu - 1, "ss", server),
            (st + bu, "cr", client),
        ]

    def binary_annotations(self, i: int):
        """[(key, value bytes, (ipv4, port, service))], type BYTES."""
        server = (int(self.server_ip[i]), 443, self.services[self.svc[i]])
        x, y, z = self.bval_w[i]
        return [
            (WORDS[self.bkey_w[i]],
             f"{WORDS[x]}-{WORDS[y]}-{WORDS[z]}".encode(), server),
            (URI_KEY, URI_VALUE, server),
        ]

    # -- thrift -----------------------------------------------------------

    def _encode(self) -> None:
        """Every pool span to thrift (pass 0), with the offsets of the
        fields a pass patches: three ids and six timestamps."""
        chunks = []
        starts = np.zeros(self.n + 1, np.int64)
        id_off = []   # absolute offsets of i64 id fields
        ts_off = []   # absolute offsets of i64 timestamp fields
        pos = 0
        q = struct.Struct(">q").pack
        for i in range(self.n):
            name = self.span_name(i).encode()
            anns = self.annotations(i)
            banns = self.binary_annotations(i)
            eps = {}
            for ep in (anns[0][2], anns[1][2]):
                eps[ep] = (_F_I32_1 + struct.pack(">i", ep[0])
                           + _F_I16_2 + struct.pack(">h", ep[1])
                           + _F_STR_3 + _s(ep[2].encode()) + b"\x00")
            parts = [_F_I64_1, q(int(self.trace_id[i])),
                     _F_STR_3, _s(name),
                     _F_I64_4, q(int(self.span_id[i]))]
            id_off.append(pos + 3)
            p = 3 + 8 + 3 + 4 + len(name) + 3
            id_off.append(pos + p)
            p += 8
            if self.has_parent[i]:
                parts += [_F_I64_5, q(int(self.parent_id[i]))]
                id_off.append(pos + p + 3)
                p += 11
            parts.append(_ANN_LIST)
            p += len(_ANN_LIST)
            for ts, value, ep in anns:
                v = value.encode()
                e = eps[ep]
                parts += [_F_I64_1, q(ts), _F_STR_2, _s(v),
                          _F_STRUCT_3, e, b"\x00"]
                ts_off.append(pos + p + 3)
                p += 11 + 7 + len(v) + 3 + len(e) + 1
            parts.append(_BANN_LIST)
            for key, value, ep in banns:
                parts += [_F_STR_1, _s(key.encode()), _F_STR_2, _s(value),
                          _F_I32_3, struct.pack(">i", BYTES_TYPE),
                          _F_STRUCT_4, eps[ep], b"\x00"]
            parts.append(_TAIL)
            raw = b"".join(parts)
            chunks.append(raw)
            pos += len(raw)
            starts[i + 1] = pos
        self.buf = np.frombuffer(b"".join(chunks), np.uint8)
        self.starts = starts
        self._id_idx = (np.asarray(id_off, np.int64)[:, None]
                        + np.arange(8)[None, :])
        self._ts_idx = (np.asarray(ts_off, np.int64)[:, None]
                        + np.arange(8)[None, :])

    def pass_bytes(self, salt: int, shift_us: int) -> np.ndarray:
        """The pool's thrift buffer with ids XOR ``salt`` and timestamps
        plus ``shift_us``."""
        if not salt and not shift_us:
            return self.buf
        b = self.buf.copy()
        ids = b[self._id_idx].view(">u8")
        b[self._id_idx] = (ids ^ np.uint64(salt)).astype(">u8").view(
            np.uint8).reshape(-1, 8)
        ts = b[self._ts_idx].view(">i8")
        b[self._ts_idx] = (ts + shift_us).astype(">i8").view(
            np.uint8).reshape(-1, 8)
        return b


def _distinct_ids(rng, n: int) -> np.ndarray:
    ids = rng.integers(1, 2**62, size=n, dtype=np.int64)
    while len(np.unique(ids)) != n:
        ids = rng.integers(1, 2**62, size=n, dtype=np.int64)
    return ids


def _grow_trees(rng, n_spans: int, n_services: int, max_depth: int):
    """TraceGen's walk, a level at a time: a node above ``max_depth``
    gets 0..2 children; child c's budget is max(2, budget // (2 + c)) and
    it starts inside its parent. Returns columns in trace order, cut to
    exactly ``n_spans`` (the last trace may be cut short, as a stream's
    last call would cut it)."""
    n_traces = n_spans // 5 + 64  # mean tree size is a little under 7
    lv_trace = np.arange(n_traces)
    lv_parent = np.full(n_traces, -1)
    lv_client = np.zeros(n_traces, np.int64)  # a root's caller: service 0
    lv_start = BASE_TS + rng.integers(0, 10_000_000, size=n_traces)
    lv_budget = rng.integers(10_000, 1_000_000, size=n_traces)
    levels = []
    base = 0
    for depth in range(1, max_depth + 1):
        m = len(lv_trace)
        lv_svc = rng.integers(0, n_services, size=m)
        levels.append((lv_trace, lv_parent, lv_svc, lv_client, lv_start,
                       lv_budget, np.full(m, depth)))
        if depth == max_depth or m == 0:
            break
        kids = rng.integers(0, 3, size=m)
        parent = np.repeat(np.arange(m), kids)
        c = np.concatenate([np.arange(k) for k in kids]) if m else parent
        budget = np.maximum(2, lv_budget[parent] // (2 + c))
        room = np.maximum(1, lv_budget[parent] - budget)
        start = lv_start[parent] + 1 + (rng.random(len(parent)) * room
                                        ).astype(np.int64)
        lv_trace, lv_client = lv_trace[parent], lv_svc[parent]
        lv_parent = parent + base  # index into the level-major table
        lv_start, lv_budget = start, budget
        base += m
    trace, parent, svc, client, start, budget, depth = (
        np.concatenate(x) for x in zip(*levels))
    # level-major -> trace order (stable: parents stay before children)
    order = np.lexsort((depth, trace))
    rank = np.empty(len(order), np.int64)
    rank[order] = np.arange(len(order))
    parent_pos = np.where(parent[order] >= 0,
                          rank[np.maximum(parent[order], 0)], -1)
    if len(order) < n_spans:
        raise ValueError("tree draw came out short; raise n_traces")
    cut = slice(0, n_spans)
    return (trace[order][cut], parent_pos[cut], svc[order][cut],
            client[order][cut], start[order][cut], budget[order][cut])


class Stream:
    """The span stream of a run, without end, and its scribe frames.

    Frames are made a pass at a time by a producer thread that stays
    ``ahead`` frames in front of the sender and are dropped once sent,
    so a run of any length or rate holds a bounded number of them. A
    sender that has to wait for a frame adds to ``starved_s``."""

    def __init__(self, seed: int, pool_spans: int, call_spans: int,
                 n_services: int, pass_shift_us: int, ahead: int = 128):
        if pool_spans % call_spans:
            raise ValueError("the pool must be whole calls")
        self.seed = int(seed)
        self.pool = Pool(seed, pool_spans, n_services)
        self.call_spans = call_spans
        self.per_pass = pool_spans // call_spans
        self.pass_shift_us = pass_shift_us
        self.ahead = ahead
        self.salts = [0]
        self.frames = {}
        self.made = 0          # frames [0, made) were made
        self.taken = 0         # highest frame number asked for, plus one
        self.starved_s = 0.0
        self.error = None
        self._cond = threading.Condition()
        self._salt_lock = threading.Lock()
        self._stop = False
        self._thread = threading.Thread(target=self._produce, daemon=True)
        self._thread.start()

    def salt(self, k: int) -> int:
        """Pass k's salt: a function of the seed and k alone, distinct
        from every earlier pass's. (The producer and the reference both
        ask, from different threads.)"""
        with self._salt_lock:
            while len(self.salts) <= k:
                rng = np.random.default_rng(
                    [self.seed, 0x5A17, len(self.salts)])
                x = int(rng.integers(1, 2**62))
                while x in self.salts:
                    x = int(rng.integers(1, 2**62))
                self.salts.append(x)
            return self.salts[k]

    def frame(self, n: int) -> bytes:
        """Frame n, once: it is dropped from the stream's memory."""
        with self._cond:
            self.taken = max(self.taken, n + 1)
            self._cond.notify_all()
            if n not in self.frames:
                t0 = time.monotonic()
                while n not in self.frames:
                    if self.error is not None:
                        raise self.error
                    if n < self.made:
                        raise KeyError(f"frame {n} was already taken")
                    self._cond.wait(1.0)
                self.starved_s += time.monotonic() - t0
            return self.frames.pop(n)

    def close(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join()

    def _produce(self) -> None:
        try:
            k = 0
            while True:
                with self._cond:
                    while (not self._stop
                           and self.made >= self.taken + self.ahead):
                        self._cond.wait(1.0)
                    if self._stop:
                        return
                for frame in self._pass_frames(k):
                    with self._cond:
                        self.frames[self.made] = frame
                        self.made += 1
                        self._cond.notify_all()
                k += 1
        except BaseException as e:  # surfaced by frame()
            with self._cond:
                self.error = e
                self._cond.notify_all()

    def _pass_frames(self, k: int):
        pool, c = self.pool, self.call_spans
        head = (struct.pack(">I", VERSION_1 | MSG_CALL) + _s(b"Log")
                + struct.pack(">i", 0) + _fh(T_LIST, 1)
                + struct.pack(">bi", T_STRUCT, c))
        entry = _F_STR_1 + _s(CATEGORY) + _F_STR_2
        b64 = binascii.b2a_base64
        pack = struct.Struct(">i").pack
        starts = pool.starts.tolist()
        raw = pool.pass_bytes(self.salt(k), k * self.pass_shift_us)
        mv = memoryview(raw)
        for f in range(self.per_pass):
            parts = [head]
            for i in range(f * c, (f + 1) * c):
                m = b64(mv[starts[i]:starts[i + 1]], newline=False)
                parts += (entry, pack(len(m)), m, b"\x00")
            parts.append(b"\x00")
            payload = b"".join(parts)
            yield pack(len(payload)) + payload

    # stream position -> pool span / pass
    def trace_id_at(self, pos: int) -> int:
        k, i = divmod(pos, self.pool.n)
        return int(self.pool.trace_id[i]) ^ self.salt(k)


def decode_reply(frame: bytes) -> int:
    """ResultCode of a Scribe.Log reply (0 OK, 1 TRY_LATER): strict
    binary protocol, ``{0: i32 success}``; anything else raises."""
    (first,) = struct.unpack_from(">i", frame, 0)
    if first >= 0:
        raise ValueError("unversioned thrift reply")
    if first & 0xFF == 3:
        raise ValueError("scribe server answered with an exception")
    (n,) = struct.unpack_from(">i", frame, 4)
    p = 8 + n + 4
    ftype, fid = struct.unpack_from(">bh", frame, p)
    if ftype != T_I32 or fid != 0:
        raise ValueError("scribe reply carries no result code")
    return struct.unpack_from(">i", frame, p + 3)[0]
