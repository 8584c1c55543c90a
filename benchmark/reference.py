"""The plain reference: what the daemon has to answer, worked out from
the stream (``gen.py``) and the set of ``Log`` calls it acked ``OK``.

Same semantics as the daemon's read routes, written straightforwardly
over the stream's own columns (numpy over all acked spans per request;
no index, no cache, no ring) and importing nothing of the program. The
original it mirrors is ``zipkin_tpu/store/memory.py`` behind
``zipkin_tpu/api/server.py``'s route table (PERF.md, Open questions).

Guarantees held: every span acked OK is read back, and read back
whole, while it is among the newest ``retained`` acked spans (the
deployment's rings keep the newest rows and overwrite the oldest: a
span older than that may be gone, in part or whole, and nothing is
asked of it); what the daemon keeps for all time (service and span
names, dependency links) counts every acked span. A deployment of n
shards keeps n sets of rings that evict apart: each shard holds whole
the newest ``retained`` / n acked spans of the traces it owns
(``shard_of``).
Answers are compared in a canonical order (spans by id, annotations by
(timestamp, value), binary annotations by key): order inside a trace is
not part of the guarantee.
"""

from __future__ import annotations

import base64

import numpy as np


def hex_id(x: int) -> str:
    return f"{int(x) & (2**64 - 1):x}"


def shard_of(trace_id, n_shards: int):
    """The shard that owns each trace, ids as signed 64-bit numbers:
    (id x 0x9E3779B97F4A7C15) mod n in whole numbers, never negative,
    as ``zipkin_tpu/parallel/multihost.py:shard_of`` routes. A product
    mod n is the product of the factors mod n, so nothing overflows."""
    t = np.asarray(trace_id, np.int64)
    return (t % n_shards) * (0x9E3779B97F4A7C15 % n_shards) % n_shards


class Reference:
    def __init__(self, stream, acked_frames, retained: int = None,
                 shards: int = 1):
        """``acked_frames``: numbers of the frames acked OK, any order.
        ``retained``: how many of the newest acked spans the deployment
        holds whole (None: all of them), ``shards`` sets of rings
        together, each an equal part of it."""
        self.stream, self.pool = stream, stream.pool
        c = stream.call_spans
        frames = np.unique(np.asarray(list(acked_frames), np.int64))
        self.frames = frames
        self.pos = (frames[:, None] * c + np.arange(c)[None, :]).ravel()
        self.k, self.i = np.divmod(self.pos, self.pool.n)
        stream.salt(int(self.k.max()) if len(self.k) else 0)
        self.mask = np.zeros((int(frames[-1]) + 1) * c if len(frames)
                             else 0, bool)
        self.mask[self.pos] = True
        n = len(self.pos)
        # Each shard holds the newest spans of its own traces, from a
        # call's edge on: a trace is cut only where a call's edge cuts
        # it. ``held_from[s]`` is shard s's first acked span held, as an
        # index into the acked spans, ``held`` says of each acked span
        # whether its shard holds it, and from ``first_retained`` on
        # every shard holds all of its spans.
        self.shards = shards
        self.held_from = np.zeros(shards, np.int64)
        salts = np.asarray(stream.salts, np.int64)[self.k]
        owner = shard_of(self.pool.trace_id[self.i] ^ salts, shards)
        if retained is not None:
            for s in range(shards):
                mine = np.flatnonzero(owner == s)
                older = len(mine) - retained // shards
                if older > 0:
                    self.held_from[s] = -(-int(mine[older]) // c) * c
        self.held = np.arange(n) >= self.held_from[owner]
        self.first_retained = int(self.held_from.max())

    def n_spans(self) -> int:
        return len(self.pos)

    # -- /api/services, /api/spans ----------------------------------------

    def services(self) -> list:
        p = self.pool
        ids = np.union1d(p.svc[self.i], p.client_svc[self.i])
        return sorted(p.services[j].lower() for j in ids)

    def _of_service(self, service: str) -> np.ndarray:
        """Acked spans that name the service on any annotation host."""
        p = self.pool
        j = [s.lower() for s in p.services].index(service.lower())
        return np.flatnonzero((p.svc[self.i] == j)
                              | (p.client_svc[self.i] == j))

    def span_names(self, service: str) -> list:
        sel = self._of_service(service)
        return sorted({self.pool.span_name(i)
                       for i in np.unique(self.i[sel])})

    # -- /api/query -----------------------------------------------------------

    def ranked_traces(self, service: str):
        """[(last timestamp, trace id)] newest first, one entry a trace
        (its newest matching span). Every span of this traffic carries
        the fixed annotation and the fixed binary annotation, so the
        three query kinds of the mix rank the same spans."""
        sel = self._of_service(service)
        k, i = self.k[sel], self.i[sel]
        ts = self.pool.end[i] + k * self.stream.pass_shift_us
        key = k * self.pool.n_traces + self.pool.trace_idx[i]
        order = np.lexsort((-ts, key))
        first = np.ones(len(order), bool)
        first[1:] = key[order][1:] != key[order][:-1]
        best = order[first]
        best = best[np.argsort(-ts[best], kind="stable")]
        salts = self.stream.salts
        return [(int(ts[b]), int(self.pool.trace_id[i[b]]) ^ salts[k[b]])
                for b in best]

    def check_query(self, service: str, limit: int, got_ids: list):
        """None when ``got_ids`` (hex) is a right answer, else why not.
        Ties in the timestamp may come in either order."""
        ranked = self.ranked_traces(service)
        if any(self._before_retained(t) for _, t in ranked[:limit]):
            raise RuntimeError(
                "a trace among the newest by timestamp is older than what "
                "the deployment holds whole: the traffic's pool is too "
                "long for this ring")
        ts_of = {hex_id(t): ts for ts, t in ranked}
        want_ts = [ts for ts, _ in ranked[:limit]]
        if len(set(got_ids)) != len(got_ids):
            return "a trace id twice"
        got_ts = [ts_of.get(g) for g in got_ids]
        if got_ts != want_ts:
            return (f"want {[hex_id(t) for _, t in ranked[:limit]]} "
                    f"got {got_ids}")
        return None

    def _before_retained(self, trace_id: int) -> bool:
        """Whether any acked span of the trace is older than the spans
        that its shard holds whole."""
        p = self.pool
        first = int(self.held_from[shard_of(trace_id, self.shards)])
        # a shard whose first held span lay in the last acked call holds
        # nothing from a call's edge on: every acked span is older
        cut = int(self.pos[first]) if first < len(self.pos) \
            else int(self.pos[-1]) + 1
        for k, salt in enumerate(self.stream.salts):
            idx = np.flatnonzero(p.trace_id == (trace_id ^ salt))
            if len(idx) and k * p.n + int(idx[0]) < cut:
                return True
        return False

    # -- /api/trace/<id> --------------------------------------------------------

    def trace(self, trace_id: int) -> list:
        """The trace's acked spans as the route's JSON, canonical order."""
        p, st = self.pool, self.stream
        out = []
        for k, salt in enumerate(st.salts):
            idx = np.flatnonzero(p.trace_id == (trace_id ^ salt))
            for i in idx:
                pos = k * p.n + int(i)
                if pos < len(self.mask) and self.mask[pos]:
                    out.append(self._span_json(int(i), k))
        return canonical_trace(out)

    def _span_json(self, i: int, k: int) -> dict:
        p, salt = self.pool, self.stream.salts[k]
        shift = k * self.stream.pass_shift_us

        def ep(e):
            return {"ipv4": e[0], "port": e[1], "serviceName": e[2]}

        return {
            "traceId": hex_id(int(p.trace_id[i]) ^ salt),
            "name": p.span_name(i),
            "id": hex_id(int(p.span_id[i]) ^ salt),
            "parentId": (hex_id(int(p.parent_id[i]) ^ salt)
                         if p.has_parent[i] else None),
            "annotations": [
                {"timestamp": ts + shift, "value": v, "endpoint": ep(e)}
                for ts, v, e in p.annotations(i)],
            "binaryAnnotations": [
                {"key": key, "value": base64.b64encode(v).decode("ascii"),
                 "type": "BYTES", "endpoint": ep(e)}
                for key, v, e in p.binary_annotations(i)],
            "debug": False,
        }

    # -- /api/dependencies --------------------------------------------------------

    def dependency_calls(self) -> dict:
        """{(parent service, child service): calls}: one call per acked
        span whose parent span is acked too."""
        p = self.pool
        child = np.flatnonzero(p.has_parent[self.i])
        ppos = self.k[child] * p.n + p.parent_pos[self.i[child]]
        child = child[self.mask[ppos]]
        ci = self.i[child]
        pair = p.svc[p.parent_pos[ci]] * len(p.services) + p.svc[ci]
        out = {}
        for key, n in zip(*np.unique(pair, return_counts=True)):
            a, b = divmod(int(key), len(p.services))
            out[(p.services[a], p.services[b])] = int(n)
        return out

    # -- which traces to ask for ---------------------------------------------------

    def longest_trace(self) -> int:
        """The longest among the traces still held whole."""
        r = self.held
        sizes = np.bincount(self.pool.trace_idx[self.i[r]]
                            + self.k[r] * self.pool.n_traces)
        key = int(np.argmax(sizes))
        k, t = divmod(key, self.pool.n_traces)
        i = int(np.searchsorted(self.pool.trace_idx, t))
        return int(self.pool.trace_id[i]) ^ self.stream.salts[k]

    def trace_id_of(self, nth_acked: int) -> int:
        return self.stream.trace_id_at(int(self.pos[nth_acked]))

    def span_keys(self):
        """(trace ids, span ids, frame number) of every acked span, as
        sent: what the write-ahead log has to hold."""
        p = self.pool
        salts = np.asarray(self.stream.salts, np.int64)[self.k]
        return (p.trace_id[self.i] ^ salts, p.span_id[self.i] ^ salts,
                self.pos // self.stream.call_spans)


def canonical_trace(spans: list) -> list:
    out = []
    for s in spans:
        s = dict(s)
        s["annotations"] = sorted(
            s["annotations"], key=lambda a: (a["timestamp"], a["value"]))
        s["binaryAnnotations"] = sorted(
            s["binaryAnnotations"], key=lambda b: b["key"])
        out.append(s)
    return sorted(out, key=lambda s: s["id"])
