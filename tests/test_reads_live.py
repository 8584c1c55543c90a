"""The web UI's reads beside a live ingest (ISSUE 36): answers exact
against the plain reference (store/memory.py) on lapped rings, in the
benchmark stream's arrival order, while the write path commits.

The stream is cut as benchmark/gen.py cuts it: passes of traces whose
timestamps are drawn over ten seconds (so arrival order is not
timestamp order inside a pass), each pass ten seconds newer than the
last, sent in calls of whole traces. The rings are small and lap
several times; the reference holds what the rings hold whole: the
newest spans of one lap of the ring that fills first (the annotation
ring), for the id lookups and whole traces; every span ever sent for
what the daemon keeps for all time (service and span names).

Each test bounds its own waits (``LIMIT_S``): a read or writer that
hangs fails the test instead of stalling the run.
"""

import dataclasses
import threading

import jax
import numpy as np

from zipkin_tpu.api.server import ApiServer
from zipkin_tpu.query.service import QueryService
from zipkin_tpu.store import device as dev
from zipkin_tpu.store.device import StoreConfig
from zipkin_tpu.store.base import (
    gather_with_escalation,
    topk_ids_with_escalation,
)
from zipkin_tpu.store.memory import InMemorySpanStore
from zipkin_tpu.columnar.encode import to_signed64
from zipkin_tpu.store.tpu import INDEX_GATES, TpuSpanStore, _fresh
from zipkin_tpu.tracegen import generate_traces

LIMIT_S = 240.0  # each test's own bound on any wait for another thread
PASS_SHIFT_US = 10_000_000
BASE_TS = 1_000_000_000_000
N_SERVICES = 8
CALL_SPANS = 32  # spans a call
END_TS = 1 << 62  # the API's "now": after every pass

CFG = StoreConfig(
    capacity=1 << 11, ann_capacity=1 << 12, bann_capacity=1 << 11,
    max_services=32, max_span_names=256, max_annotation_values=512,
    max_binary_keys=64, cms_width=1 << 10, hll_p=8, quantile_buckets=256,
)
# One lap: the spans that fill the ring that fills first (6 annotation
# rows a span into 4,096 rows); a call's worth less is held whole.
LAP = min(CFG.capacity, CFG.ann_capacity // 6, CFG.bann_capacity // 2)


def _shifted(span, dt: int):
    return dataclasses.replace(span, annotations=tuple(
        dataclasses.replace(a, timestamp=a.timestamp + dt)
        for a in span.annotations))


def _calls(seed: int, passes: int, traces_a_pass: int = 60):
    """[[span, ...] a call] in the benchmark's arrival order: one pool
    of traces (start times drawn over ten seconds) re-timed pass by
    pass, its spans in trace order cut into calls of CALL_SPANS (a
    call may cut a trace, as the benchmark's 2048-span calls do)."""
    traces = generate_traces(
        n_traces=passes * traces_a_pass, max_depth=4,
        rng=np.random.default_rng(seed), base_ts=BASE_TS,
        n_services=N_SERVICES)
    stream = [_shifted(s, (i // traces_a_pass) * PASS_SHIFT_US)
              for i, t in enumerate(traces) for s in t]
    return [stream[i:i + CALL_SPANS]
            for i in range(0, len(stream) - CALL_SPANS + 1, CALL_SPANS)]


def _held(calls, upto: int, room: int = 0):
    """The spans of calls[:upto] that the rings hold whole: the newest
    LAP less ``room`` spans, in whole calls."""
    out, n = [], 0
    for call in reversed(calls[:upto]):
        if n + len(call) > LAP - room:
            break
        out[:0] = call
        n += len(call)
    return out


def _memory(spans):
    m = InMemorySpanStore()
    m.apply(spans)
    return m


def _services(calls):
    return sorted({n for c in calls for s in c for n in s.service_names})


def _api(store):
    return ApiServer(QueryService(store, coalesce_window_s=0.0),
                     self_trace=False)


def _ui_reads(calls, upto: int, services):
    """One round of the UI's mix (routes as benchmark/traffic/
    ui-reads-live.json has them; dependencies are compared apart):
    (method, path, params, compared against "held" or "ever")."""
    reads = []
    for svc in services:
        reads += [
            ("/api/query", {"serviceName": svc, "limit": "10"}, "held"),
            ("/api/query", {"serviceName": svc, "limit": "10",
                            "annotationQuery": "some custom annotation"},
             "held"),
            ("/api/query", {"serviceName": svc, "limit": "10",
                            "annotationQuery": "http.uri=/api/widgets"},
             "held"),
            ("/api/spans", {"serviceName": svc}, "ever"),
        ]
    # Whole traces of the newest calls the rings hold.
    for call in calls[max(0, upto - 3):upto]:
        for tid in sorted({s.trace_id for s in call})[:2]:
            reads.append((f"/api/trace/{tid & ((1 << 64) - 1):x}", {},
                          "held"))
    reads.append(("/api/services", {}, "ever"))
    return reads


def _links(spans):
    """{(parent service, child service): calls} of every parent-child
    pair: what the daemon keeps for all time."""
    by_id = {(s.trace_id, s.id): s for s in spans}
    out = {}
    for s in spans:
        p = by_id.get((s.trace_id, s.parent_id))
        if s.parent_id is not None and p is not None:
            key = (p.service_name, s.service_name)
            out[key] = out.get(key, 0) + 1
    return out


def _dep_counts(body):
    return {(ln["parent"], ln["child"]): int(round(
        ln["durationMoments"]["count"])) for ln in body["links"]}


def test_ui_mix_through_the_api_matches_reference_between_commits():
    """(i) Reads in the UI's mix, through the HTTP API's route table,
    interleaved with ingest in the stream's arrival order on lapping
    rings: every answer equals the reference's over the calls committed
    before the read."""
    calls = _calls(seed=36, passes=9)
    assert sum(map(len, calls)) > 3 * LAP  # the rings lap
    store = TpuSpanStore(CFG)
    api = _api(store)
    services = _services(calls)
    checked = nonempty = 0
    for upto in range(1, len(calls) + 1):
        store.apply(calls[upto - 1])
        if upto % 5 and upto != len(calls):
            continue
        held = _api(_memory(_held(calls, upto)))
        ever = _api(_memory([s for c in calls[:upto] for s in c]))
        for path, params, ref in _ui_reads(calls, upto, services):
            got = api.handle("GET", path, dict(params))
            want = (held if ref == "held" else ever).handle(
                "GET", path, dict(params))
            assert got == want, (upto, path, params)
            checked += 1
            nonempty += bool(got[1])
        status, deps = api.handle("GET", "/api/dependencies", {})
        assert status == 200
        assert _dep_counts(deps) == _links(
            [s for c in calls[:upto] for s in c]), upto
    assert checked > 400 and nonempty > checked // 2


def _prefix_answers(calls, n_calls, queries, services):
    """The reference's answer to every query at every frontier (calls
    committed): ids from what the rings hold whole, names from all."""
    out = []
    for upto in range(n_calls + 1):
        held = _memory(_held(calls, upto, room=len(calls[0])))
        ever = _memory([s for c in calls[:upto] for s in c])
        row = []
        for kind, arg in queries:
            if kind == "ids":
                row.append(tuple(
                    (i.trace_id, i.timestamp)
                    for i in held.get_trace_ids_by_name(arg, None, END_TS,
                                                        10)))
            elif kind == "ann":
                row.append(tuple(
                    (i.trace_id, i.timestamp)
                    for i in held.get_trace_ids_by_annotation(
                        arg, "some custom annotation", None, END_TS, 10)))
            elif kind == "trace":
                row.append(tuple(sorted(
                    (s.id, len(s.annotations))
                    for t in held.get_spans_by_trace_ids([arg])
                    for s in t)))
            else:
                row.append(frozenset(ever.get_span_names(arg)))
        out.append(row)
    return out


def test_readers_during_pipelined_commits_see_one_frontier_each():
    """(ii) Eight reader threads run while the pipeline commits: every
    answer equals the reference at one of the frontiers (calls
    committed) that the read straddled."""
    calls = _calls(seed=37, passes=6)
    size = CALL_SPANS  # committed spans say how many calls committed
    store = TpuSpanStore(CFG)
    services = _services(calls)
    # The rings lap before the readers start, and the traces read whole
    # stay whole to the last frontier.
    warm = LAP // size + 4
    calls = calls[:warm + (LAP - 2 * size) // size - 1]
    assert len(calls) > warm + 8
    for c in calls[:warm]:
        store.apply(c)
    recent = sorted({s.trace_id for c in calls[warm - 2:warm] for s in c})
    queries = ([("ids", s) for s in services[:4]]
               + [("ann", s) for s in services[:2]]
               + [("names", s) for s in services[:2]]
               + [("trace", t) for t in recent[:4]])
    ref = _prefix_answers(calls, len(calls), queries, services)
    engine = QueryService(store, coalesce_window_s=0.001).engine

    def answer(kind, arg):
        if kind == "ids":
            return tuple((i.trace_id, i.timestamp)
                         for i in engine.get_trace_ids_by_name(
                             arg, None, END_TS, 10))
        if kind == "ann":
            return tuple((i.trace_id, i.timestamp)
                         for i in engine.get_trace_ids_by_annotation(
                             arg, "some custom annotation", None,
                             END_TS, 10))
        if kind == "trace":
            return tuple(sorted((s.id, len(s.annotations))
                                for t in engine.get_spans_by_trace_ids(
                                    [arg]) for s in t))
        return frozenset(engine.get_span_names(arg))

    done = threading.Event()
    errors, reads = [], [0]

    def reader(r):
        try:
            i = r
            while not done.is_set() or i < r + 2 * len(queries):
                q = queries[i % len(queries)]
                before = store._wp // size
                got = answer(*q)
                after = store._wp // size
                frontiers = range(before, after + 1)
                assert any(got == ref[c][i % len(queries)]
                           for c in frontiers), (q, before, after)
                reads[0] += 1
                i += 1
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    with store.pipelined(depth=2):
        threads = [threading.Thread(target=reader, args=(r,), daemon=True)
                   for r in range(8)]
        for t in threads:
            t.start()
        for c in calls[warm:]:
            store.apply(c)
        store.drain_pipeline()
        done.set()
        for t in threads:
            t.join(LIMIT_S)
    assert not any(t.is_alive() for t in threads), "a reader hung"
    assert not errors, errors[0]
    assert store._wp == size * len(calls)
    assert reads[0] >= 16 * len(queries)


def test_read_pending_across_the_next_donating_step_keeps_its_snapshot():
    """(iii) A read dispatched on a state whose buffers the next ingest
    step donates: the step is dispatched (and the old state's leaves
    deleted) while the read's result is still pending, and the read
    still returns its snapshot's answer. One launch a read: a scan, a
    whole-trace gather, an index probe, a copied leaf."""
    calls = _calls(seed=38, passes=3)
    store = TpuSpanStore(CFG)
    half = len(calls) // 2
    for c in calls[:half]:
        store.apply(c)
    svc = store.dicts.services.get(_services(calls)[0].lower())
    qids = np.unique(np.asarray(
        [to_signed64(s.trace_id) for s in calls[half - 1]], np.int64))
    launches = {
        "scan": lambda st: dev.query_trace_ids_by_service(
            st, svc, -1, END_TS, 64),
        "probe": lambda st: dev.iquery_trace_ids_by_service(
            st, svc, -1, END_TS, 64),
        "gather": lambda st: dev.gather_trace_rows(st, qids, 512, 1024,
                                                   512),
        "leaf": lambda st: _fresh(st.hll_traces),
    }
    real_get = jax.device_get
    for n, (name, launch) in enumerate(launches.items()):
        want = store._fetch(launch)
        fetched, released = threading.Event(), threading.Event()
        reader, seen = [], {}

        def slow_get(x):
            # The reader's fetch, after it let go of the read lock: held
            # until the writer has dispatched the next donating step.
            if threading.get_ident() in reader:
                fetched.set()
                assert released.wait(LIMIT_S)
            return real_get(x)

        def read():
            reader.append(threading.get_ident())
            try:
                seen["got"] = store._fetch(launch)
            except BaseException as e:  # noqa: BLE001 — reported below
                seen["error"] = e

        old = store.state
        jax.device_get = slow_get
        try:
            t = threading.Thread(target=read, daemon=True)
            t.start()
            assert fetched.wait(LIMIT_S), "the read never reached its fetch"
            store.apply(calls[half + n])  # the read let go of the lock
            assert old.trace_id.is_deleted(), name  # donated meanwhile
            released.set()
            t.join(LIMIT_S)
        finally:
            jax.device_get = real_get
        assert not t.is_alive(), f"{name}: the read hung"
        assert "error" not in seen, (name, seen.get("error"))
        for a, b in zip(jax.tree_util.tree_leaves(seen["got"]),
                        jax.tree_util.tree_leaves(want)):
            assert np.array_equal(a, b), name


def test_reads_compile_nothing_while_they_hold_the_read_lock():
    """A read that meets a program or a k it has not run compiles it
    before it takes the read lock (dev.compile_only), so the commit
    never waits behind a compile: the UI's mix through the API, on
    rings of a size no other test compiles for, lapping, compiles its
    programs and none of them under the lock."""
    import contextlib

    cfg = CFG._replace(capacity=1 << 10, ann_capacity=1 << 11,
                       bann_capacity=1 << 10)
    calls = _calls(seed=44, passes=6)
    store = TpuSpanStore(cfg)
    api = _api(store)
    services = _services(calls)
    store.apply(calls[0])  # the write path's own programs
    held, compiled = threading.local(), {"under": [], "off": 0}
    real_read = store._rw.read

    @contextlib.contextmanager
    def read():
        with real_read():
            held.on = True
            try:
                yield
            finally:
                held.on = False

    def listen(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            if getattr(held, "on", False):
                compiled["under"].append(seconds)
            else:
                compiled["off"] += 1

    store._rw.read = read
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for upto in range(2, len(calls) + 1):
            store.apply(calls[upto - 1])
            if upto % 8 and upto != len(calls):
                continue
            for path, params, _ in _ui_reads(calls, upto, services):
                assert api.handle("GET", path, dict(params))[0] == 200
            assert api.handle("GET", "/api/dependencies", {})[0] == 200
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        store._rw.read = real_read
    assert compiled["off"] > 0 and not compiled["under"], compiled


def test_readers_that_need_one_new_program_compile_it_once():
    """Readers that meet the same new read program at once (more of them
    than cores, the interpreter switching often) compile it once; the
    others wait for it, and every one gets its results' shapes."""
    import os
    import sys

    prog = dev.ReadProgram(lambda a, k: jax.lax.top_k(a * 2, k),
                           static_argnums=(1,))
    arg = jax.ShapeDtypeStruct((4099,), np.float32)
    n = 2 * (os.cpu_count() or 4) + 1
    compiles, got, errors = [], [], []
    start = threading.Barrier(n)

    def listen(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(seconds)

    def read():
        try:
            start.wait(LIMIT_S)
            with dev.compile_only():
                got.append(prog(arg, 7))
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        threads = [threading.Thread(target=read, daemon=True)
                   for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(LIMIT_S)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads), "a reader hung"
    assert not errors, errors[0]
    assert len(compiles) == 1 and len(got) == n
    assert all(o[0].shape == (7,) and o[1].dtype == np.int32 for o in got)
    assert not prog._pending  # nothing left waiting


def test_index_outcomes_are_counted_once_per_read_with_their_gate():
    """(iv) On lapped rings in the stream's arrival order, every
    index-tier read counts one hit or one fallback, and every fallback
    names the gate that refused it: an id lookup in /metrics'
    ``index_hits`` / ``index_scan_fallbacks`` (what they counted before
    PR 36), a whole-trace or durations read in ``row_index_hits`` or
    its gate's count alone. Counts, not times."""
    calls = _calls(seed=39, passes=9)
    store = TpuSpanStore(CFG)
    services = _services(calls)
    n_lookups = n_rows = 0
    for upto, c in enumerate(calls, 1):
        store.apply(c)
        if upto % 4:
            continue
        for svc in services:
            store.get_trace_ids_by_name(svc, None, END_TS, 10)
            store.get_trace_ids_by_annotation(
                svc, "some custom annotation", None, END_TS, 10)
            n_lookups += 2
        tids = sorted({s.trace_id for s in c})
        store.get_spans_by_trace_ids(tids[:3])
        store.get_traces_duration(tids[:3])
        n_rows += 2
    out = store.counters()
    hits, falls = out["index_hits"], out["index_scan_fallbacks"]
    gates = {g: out[f"index_fallback_{g}"] for g in INDEX_GATES}
    row_gates = sum(n for g, n in gates.items()
                    if g.startswith("gather_") or g == "durations")
    assert hits + falls == n_lookups
    assert sum(gates.values()) - row_gates == falls
    assert out["row_index_hits"] + row_gates == n_rows
    assert hits > 0 and out["row_index_hits"] > 0
    # The batched path counts a query it answers once, and sends one it
    # cannot prove to the singular path, which counts its own outcome.
    before = store.index_hits + store.index_fallbacks
    store.get_trace_ids_multi(
        [("name", s, None, END_TS, 10) for s in services])
    assert store.index_hits + store.index_fallbacks == before + len(services)
    # A store that never wrapped a bucket answers everything from the
    # index.
    fresh = TpuSpanStore(CFG)
    fresh.apply(calls[0])
    for svc in services:
        fresh.get_trace_ids_by_name(svc, None, END_TS, 10)
    fresh.get_spans_by_trace_ids(sorted({s.trace_id for s in calls[0]}))
    assert fresh.index_fallbacks == 0 and not any(
        fresh.index_fallback_gates.values())
    assert fresh.index_hits == len(services) and fresh.row_index_hits == 1


def _side_row_scan(cols, svc, name_lc, ann_value, bann_key, limit):
    """The O(ring) id scan as the kernels ran it before PR 36, over the
    host's copy of the rings: one candidate per live side row of the
    service (and of the annotation value or binary key), deduped."""
    from zipkin_tpu.store.base import dedup_rank_limit

    cap = cols["row_gid"].shape[0]

    def live(gid):
        slot = np.where(gid >= 0, gid % cap, 0)
        return slot, (gid >= 0) & (cols["row_gid"][slot] == gid)

    a_slot, a_live = live(cols["ann_gid"])
    hosted = np.zeros(cap, bool)
    hosted[a_slot[a_live & (cols["ann_service_id"] == svc)]] = True
    if ann_value is None and bann_key is None:
        slots = a_slot[a_live & (cols["ann_service_id"] == svc)]
    elif ann_value is not None:
        slots = a_slot[a_live & (cols["ann_value_id"] == ann_value)]
    else:
        b_slot, b_live = live(cols["bann_gid"])
        slots = b_slot[b_live & (cols["bann_key_id"] == bann_key)]
    slots = slots[hosted[slots] & cols["indexable"][slots]
                  & (cols["ts_last"][slots] >= 0)]
    if name_lc >= 0:
        slots = slots[cols["name_lc_id"][slots] == name_lc]
    return dedup_rank_limit(
        zip(cols["trace_id"][slots].tolist(),
            cols["ts_last"][slots].tolist()), limit)


def test_span_scans_answer_as_the_side_row_scans_did():
    """The O(ring) id scans take one candidate a span (a scatter of
    each side row's lap to its span's slot, no gather a row): on lapped
    rings in the stream's arrival order, for every service, with and
    without a span name, and by annotation and binary annotation, the
    top ten traces equal those of the per-row scan they replaced."""
    calls = _calls(seed=41, passes=9)
    store = TpuSpanStore(CFG)
    services = _services(calls)
    names = ("row_gid", "indexable", "name_lc_id", "trace_id", "ts_last",
             "ann_gid", "ann_service_id", "ann_value_id", "bann_gid",
             "bann_key_id")
    checked = nonempty = 0
    for upto, c in enumerate(calls, 1):
        store.apply(c)
        if upto % 9:
            continue
        cols = {n: np.asarray(store._fetch(
            lambda st, n=n: _fresh(getattr(st, n)))) for n in names}
        ann = store.dicts.annotations.get("some custom annotation")
        key = store.dicts.binary_keys.get("http.uri")
        name_lc = int(np.max(cols["name_lc_id"]))
        for svc in services:
            sid = store.dicts.services.get(svc.lower())
            for nl, av, bk, launch in (
                (-1, None, None, lambda st, k: dev.query_trace_ids_by_service(
                    st, sid, -1, END_TS, k)),
                (name_lc, None, None,
                 lambda st, k: dev.query_trace_ids_by_service(
                     st, sid, name_lc, END_TS, k)),
                (-1, ann, None,
                 lambda st, k: dev.query_trace_ids_by_annotation(
                     st, sid, ann, -1, -1, -1, END_TS, k)),
                (-1, None, key,
                 lambda st, k: dev.query_trace_ids_by_annotation(
                     st, sid, -1, key, -1, -1, END_TS, k)),
            ):
                got = topk_ids_with_escalation(
                    10, CFG.capacity, store._scan_fetch(launch))
                assert got == _side_row_scan(cols, sid, nl, av, bk, 10), (
                    upto, svc, nl, av, bk)
                checked += 1
                nonempty += bool(got)
    assert checked >= 4 * len(services) * 3 and nonempty > checked // 2


def test_restored_state_reads_whole_traces_without_the_launch_map():
    """A store whose state was written another way (a checkpoint
    restore, ``adopt_state``) has no launch map for its spans: whole
    traces read through the whole-ring gather (gate
    ``gather_unmapped``), exact, until commits start the map again."""
    calls = _calls(seed=42, passes=2)
    src = TpuSpanStore(CFG)
    for c in calls[:-1]:
        src.apply(c)
    store = TpuSpanStore(CFG)
    store.codec = src.codec
    store.adopt_state(_fresh(src.state), src._wp)
    store._awp, store._bwp = src._awp, src._bwp  # as a restore sets them
    tids = sorted({s.trace_id for s in calls[-2]})[:3]
    assert not store._launch_map_current()
    assert store.get_spans_by_trace_ids(tids) == \
        src.get_spans_by_trace_ids(tids)
    assert store.index_fallback_gates["gather_unmapped"] == 1
    for st in (store, src):
        st.apply(calls[-1])  # the map starts over at this commit
    assert store._launch_map_current() and len(store._launches) == 1
    tids += sorted({s.trace_id for s in calls[-1]})[:3]
    assert store.get_spans_by_trace_ids(tids) == \
        src.get_spans_by_trace_ids(tids)


def test_span_ring_scan_gathers_as_the_whole_ring_gather_does():
    """A whole-trace read whose span bucket fails its gate reads the
    span ring for the trace's spans and the launch map for their side
    rows: on lapped rings in the stream's arrival order, for traces
    whole, cut by the rings' lap and long gone, it answers as the
    whole-ring gather (dev.gather_trace_rows) does, and a read whose
    bucket refuses does not run the whole-ring gather."""
    # Four span buckets of 64 rows for 2,048 span slots: every bucket
    # wraps while what it displaced is still resident, so the gate
    # refuses whole-trace reads as the benchmark's does a few a window.
    cfg = CFG._replace(idx_trace_buckets=4)
    calls = _calls(seed=43, passes=9)
    store = TpuSpanStore(cfg)
    real_gather = dev.gather_trace_rows
    whole = [0]

    def counted(*a, **kw):
        whole[0] += 1
        return real_gather(*a, **kw)

    dev.gather_trace_rows = counted
    try:
        checked = found = 0
        for upto, c in enumerate(calls, 1):
            store.apply(c)
            if upto % 6:
                continue
            # the newest call's traces, one about a lap back (cut by
            # the lap), one whose spans the span ring has overwritten
            back = [calls[max(0, upto - 1 - d)]
                    for d in (0, LAP // CALL_SPANS,
                              2 * cfg.capacity // CALL_SPANS)]
            for call in back:
                tids = sorted({s.trace_id for s in call})
                for ids in ([tids[0]], tids[:3]):
                    qids = store._sorted_qids(ids)
                    payload, gate = store._gather_via_launches(qids, True)
                    assert gate is None, (upto, gate)
                    want = gather_with_escalation(
                        cfg, lambda k_s, k_a, k_b, q=qids: (lambda m: (
                            *(int(x) for x in m[0]),
                            (*(int(x) for x in m[0]), *m[1:])))(
                                store._fetch(lambda st: real_gather(
                                    st, q, k_s, k_a, k_b))))
                    got = store._decode_gathered(*payload)
                    assert got == store._decode_gathered(*want), (upto, ids)
                    checked += 1
                    found += bool(got)
        assert checked >= 3 * 2 * (len(calls) // 6)
        assert checked // 2 < found < checked  # some traces long gone
        spans_before = store.index_fallback_gates["gather_span"]
        for call in calls[-LAP // CALL_SPANS:]:
            store.get_spans_by_trace_ids(sorted({s.trace_id for s in call}))
        refused = store.index_fallback_gates["gather_span"] - spans_before
        assert refused > 0 and whole[0] == 0, (refused, whole[0])
    finally:
        dev.gather_trace_rows = real_gather
