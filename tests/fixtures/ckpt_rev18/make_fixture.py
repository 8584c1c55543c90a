"""How ``tests/fixtures/ckpt_rev18`` was made: run ONCE at commit
9321a67 (checkpoint revision 18, the arena one [slots, 3] i64 leaf),
from the root of that checkout:

    JAX_PLATFORMS=cpu python tests/fixtures/ckpt_rev18/make_fixture.py OUT

It drives a small store through mixed steps (the span ring laps about
2.5 times, so evictions; service buckets overflow inside a batch and
wrap), saves it with that commit's ``checkpoint.save``, and records
what that commit's ``_iq_*`` routes answered (``expected.npz``).
``tests/test_arena_planes.py`` restores the snapshot with today's code
and holds it to both. Not a test; never collected (no ``test_`` name).
"""

import sys

import numpy as np

sys.path.insert(0, ".")

CONFIG = dict(
    capacity=256, ann_capacity=1024, bann_capacity=512, max_services=16,
    max_span_names=32, max_annotation_values=64, max_binary_keys=32,
    cms_width=256, hll_p=6, quantile_buckets=64, window_seconds=60,
)
CHUNK = 96


def spans():
    from zipkin_tpu.tracegen import generate_traces

    return [s for t in generate_traces(n_traces=130, max_depth=4,
                                       n_services=6) for s in t]


def drive(store):
    sp = spans()
    for i in range(0, len(sp), CHUNK):
        store.apply(sp[i:i + CHUNK])
    return sp


def iq_answers(store, sp) -> dict:
    """Every index-read route's raw device answer, as numpy arrays."""
    import jax

    from zipkin_tpu.store import device as dev

    st, d = store.state, store.dicts
    end_ts = max(s.last_timestamp for s in sp if s.last_timestamp) + 1
    out = {}
    svcs = sorted(store.get_all_service_names())
    for i, svc in enumerate(svcs):
        sid = d.services.get(svc)
        out[f"svc{i}"] = dev.iquery_trace_ids_by_service(
            st, sid, None, end_ts, 10)
        for j, name in enumerate(sorted(store.get_span_names(svc))[:3]):
            out[f"name{i}_{j}"] = dev.iquery_trace_ids_by_service(
                st, sid, d.span_names.get(name), end_ts, 10)
        out[f"ann{i}"] = dev.iquery_trace_ids_by_annotation(
            st, sid, d.annotations.get("some custom annotation"),
            -1, -1, -1, end_ts, 10)
        bk = d.binary_keys.get("http.uri")
        out[f"bkey{i}"] = dev.iquery_trace_ids_by_annotation(
            st, sid, -1, bk, -1, -1, end_ts, 10)
        bv = d.binary_values.get(b"/api/widgets")
        out[f"bval{i}"] = dev.iquery_trace_ids_by_annotation(
            st, sid, -1, bk, bv if bv is not None else -1, -1, end_ts, 10)
    tids = np.sort(np.array(
        sorted({s.trace_id for s in sp})[-24:], np.int64))
    out["durations"] = dev.iquery_durations(st, tids)
    out["gather"] = dev.iquery_gather_trace_rows(st, tids[:8], 64, 256, 128)
    lay = store.config.cand_layout[0]
    fam = lay[dev.StoreConfig.CAND_SVC]
    n = len(svcs)
    out["multi"] = dev.iquery_trace_ids_multi(st, dict(
        b_base=np.full(n, fam[0]), s_base=np.full(n, fam[1]),
        n_b=np.full(n, fam[2]), depth=np.full(n, fam[3]),
        key1=np.array([d.services.get(s) for s in svcs]),
        key2=np.zeros(n), key3=np.zeros(n), three=np.zeros(n, bool),
        is_svc=np.ones(n, bool), end_ts=np.full(n, end_ts),
        poison_on=np.zeros(n, bool)), 10)
    flat = {}
    for k, v in out.items():
        for m, leaf in enumerate(jax.tree_util.tree_leaves(
                jax.device_get(v))):
            flat[f"{k}.{m}"] = np.asarray(leaf)
    return flat


if __name__ == "__main__":
    from zipkin_tpu import checkpoint
    from zipkin_tpu.store.device import StoreConfig
    from zipkin_tpu.store.tpu import TpuSpanStore

    store = TpuSpanStore(StoreConfig(**CONFIG))
    sp = drive(store)
    checkpoint.save(store, sys.argv[1])
    np.savez_compressed(sys.argv[1] + "/expected.npz",
                        **iq_answers(store, sp))
    print(len(sp), "spans", store.config.idx_layout[2], "slots")
