"""Arm-by-arm on-chip profiling of the fused ingest step.

Round-4 diagnostic: the first post-rework real-chip stream measured
~2.2 s per 57k-span step vs the ~150 ms the round-3 cost model
predicts. This script times each arm of ingest_step in isolation at
the same shapes so the pathology has a name before we fix it.

Usage (chip must be otherwise idle — one process per chip):
    python scripts/profile_ingest.py [--cap-log2 22] [--traces 16384]

Every timing uses jax.device_get of a scalar as the barrier.
"""

import argparse
import sys
import time
from functools import partial

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cap-log2", type=int, default=22)
    ap.add_argument("--traces", type=int, default=16384)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--batch-spans-sweep", default="",
                    help="comma-separated span counts: re-template the "
                         "full step at each batch size and time it "
                         "(the r12 batch-escalation knee finder, e.g. "
                         "57344,114688,229376,458752)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from zipkin_tpu.store import device as dev
    from zipkin_tpu.store.tpu import TpuSpanStore
    from bench import _tpu_config, _make_template

    print("backend:", jax.default_backend(), flush=True)

    def timeit(name, fn, *a, reps=args.reps, sync=None, **kw):
        # warmup (compile)
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        s = sync(out) if sync else jax.device_get(
            jax.tree_util.tree_leaves(out)[0]
        )
        t1 = time.perf_counter()
        times = []
        for _ in range(reps):
            t2 = time.perf_counter()
            out = fn(*a, **kw)
            s = sync(out) if sync else jax.device_get(
                jax.tree_util.tree_leaves(out)[0]
            )
            times.append(time.perf_counter() - t2)
        del s
        print(f"{name:42s} compile+1st {t1 - t0:8.3f}s   "
              f"steady {min(times) * 1e3:9.1f} ms", flush=True)
        return out

    # 0. dispatch floor today
    one = jnp.ones((8, 128), jnp.float32)
    f_triv = jax.jit(lambda x: x * 2.0 + 1.0)
    timeit("dispatch floor (trivial jit)", f_triv, one, reps=10)

    config = _tpu_config(args.cap_log2, 1024, False)
    store = TpuSpanStore(config)
    db0, fused_chain, pad_spans = _make_template(store, 1024, args.traces)
    state = dev.init_state(config)
    state = jax.device_put(state)
    print(f"shapes: P={pad_spans} PA={db0.ann_ts.shape[0]} "
          f"PB={db0.bann_key_id.shape[0]} cap=2^{args.cap_log2}",
          flush=True)

    c = config
    S = c.max_services
    P = db0.trace_id.shape[0]
    PA = db0.ann_ts.shape[0]
    PB = db0.bann_key_id.shape[0]
    b = db0
    mask = jnp.arange(P) < b.n_spans
    mask_a = jnp.arange(PA) < b.n_anns
    mask_b = jnp.arange(PB) < b.n_banns

    # 1. full single ingest step (donate state copy each call would free
    # it; use a non-donated wrapper so we can repeat on the same state)
    step_once = jax.jit(lambda s, d: dev.ingest_step.__wrapped__(s, d))
    state2 = timeit(
        "ingest_step FULL (1 step)", step_once, state, b,
        sync=lambda s: float(jax.device_get(s.counters["spans_seen"])),
    )
    del state2

    # 2. ring writes only
    def ring_only(st, bb):
        gids = st.write_pos + jnp.arange(P, dtype=jnp.int64)
        slots = (gids % c.capacity).astype(jnp.int32)
        widx = jnp.where(mask, slots, c.capacity)
        outs = []
        for col in ("trace_id", "span_id", "parent_id", "name_id",
                    "ts_cs", "ts_cr", "ts_sr", "ts_ss", "duration"):
            outs.append(getattr(st, col).at[widx].set(
                getattr(bb, col), mode="drop"))
        return outs[0].sum()

    timeit("ring column writes (9 cols)", jax.jit(ring_only), state, b)

    # 3. span_tab insert (4-round scatter-min probe)
    def tab_only(st, bb):
        skey = dev._mix48(bb.trace_id, bb.span_id)
        tab = dev._tab_insert(st.span_tab, skey, bb.service_id, mask)
        return tab.sum()

    timeit("span_tab insert (hash join build)", jax.jit(tab_only), state, b)

    # 4. resolve links + window fold
    def dep_only(st, bb):
        skey = dev._mix48(bb.trace_id, bb.span_id)
        tab = dev._tab_insert(st.span_tab, skey, bb.service_id, mask)
        resolved, link_id, pending, ckey = dev._resolve_links(
            tab, bb.trace_id, bb.span_id, bb.parent_id, bb.service_id,
            bb.service_id, bb.duration, mask, mask & bb.has_parent, S,
        )
        w, wts = dev._window_fold(
            st.dep_window, st.dep_window_ts, bb.duration, link_id,
            resolved, bb.ts_first, bb.ts_last, S,
        )
        return w.sum()

    timeit("dep join (insert+resolve+fold)", jax.jit(dep_only), state, b)

    # 5. combined candidate index write (the concat + rank-sort + scatter)
    from zipkin_tpu.store.device import (
        StoreConfig, _bucket_of, _mixb, _verify_of, _span_host_range,
        FIRST_USER_ANNOTATION_ID,
    )

    def cand_only(st, bb):
        lay, _, _ = c.idx_layout
        a_host = bb.ann_service_id
        a_idx_ok = mask_a & (a_host >= 0) & (a_host < S)
        span_gid_of_ann = st.write_pos + bb.ann_span_idx.astype(jnp.int64)
        gid_a = jnp.where(a_idx_ok, span_gid_of_ann, -1)
        ts_a = bb.ts_last[bb.ann_span_idx]

        def seg(fam, local_bucket, gid, verify, ts, ok):
            b_base, s_base, n_b, depth = lay[fam]
            lb = jnp.clip(local_bucket, 0, n_b - 1)
            n = lb.shape[0]
            return fam, (
                lb.astype(jnp.int32) + jnp.int32(b_base),
                lb.astype(jnp.int64) * depth + jnp.int64(s_base),
                jnp.full(n, depth, jnp.int32),
                jnp.asarray(gid, jnp.int64),
                jnp.asarray(verify, jnp.int64),
                jnp.asarray(ts, jnp.int64),
                ok,
            )

        segments = [seg(StoreConfig.CAND_SVC, a_host, gid_a, a_host,
                        ts_a, a_idx_ok)]
        ann_name_lc_i = bb.name_lc_id[bb.ann_span_idx]
        nm_ok = a_idx_ok & (ann_name_lc_i >= 0)
        nm_mix = _mixb([a_host, ann_name_lc_i])
        segments.append(seg(
            StoreConfig.CAND_NAME, _bucket_of(nm_mix, c.name_buckets),
            gid_a, _verify_of(nm_mix), ts_a, nm_ok,
        ))
        hmin, hmax = _span_host_range(a_host, bb.ann_span_idx, a_idx_ok, P)
        h1 = hmin[bb.ann_span_idx]
        h2 = hmax[bb.ann_span_idx]
        v_ok = (
            mask_a & (bb.ann_value_id >= FIRST_USER_ANNOTATION_ID)
            & (bb.ann_value_id < jnp.int32(1 << 30))
        )
        for h, extra in ((h1, None), (h2, h2 != h1)):
            ok = v_ok & (h >= 0) & (h < S)
            if extra is not None:
                ok &= extra
            mix = _mixb([h, bb.ann_value_id])
            segments.append(seg(
                StoreConfig.CAND_ANN, _bucket_of(mix, c.ann_buckets),
                jnp.where(ok, span_gid_of_ann, -1), _verify_of(mix),
                ts_a, ok,
            ))
        span_gid_of_bann = st.write_pos + bb.bann_span_idx.astype(jnp.int64)
        bh1 = hmin[bb.bann_span_idx]
        bh2 = hmax[bb.bann_span_idx]
        bk_idx_ok = mask_b & (bb.bann_key_id >= 0)
        ts_b = bb.ts_last[bb.bann_span_idx]
        no_val = jnp.full(PB, -1, jnp.int32)
        for h, val, extra in (
            (bh1, bb.bann_value_id, None),
            (bh2, bb.bann_value_id, bh2 != bh1),
            (bh1, no_val, None), (bh2, no_val, bh2 != bh1),
        ):
            ok = bk_idx_ok & (h >= 0) & (h < S)
            if extra is not None:
                ok &= extra
            mix = _mixb([h, bb.bann_key_id, val])
            segments.append(seg(
                StoreConfig.CAND_BANN, _bucket_of(mix, c.bann_buckets),
                jnp.where(ok, span_gid_of_bann, -1), _verify_of(mix),
                ts_b, ok,
            ))
        fams = [f for f, _ in segments]
        assert (fams[0] == StoreConfig.CAND_SVC
                and StoreConfig.CAND_SVC not in fams[1:]), fams
        n_cand_rows = sum(p[0].shape[0] for _, p in segments)
        # Trace-membership segments trail in the SAME unified pass (the
        # r6 arena merge): one rank sort + scatter block for all seven
        # families — this arm now measures the whole index write.
        tb = _bucket_of(_mixb([bb.trace_id]), c.trace_buckets)
        tmix = _verify_of(_mixb([bb.trace_id]))
        gids = st.write_pos + jnp.arange(P, dtype=jnp.int64)
        a_gids = st.ann_write_pos + jnp.arange(PA, dtype=jnp.int64)
        bb_gids = st.bann_write_pos + jnp.arange(PB, dtype=jnp.int64)
        NC = StoreConfig.N_CAND_FAMILIES
        segments.append(seg(NC + StoreConfig.TR_SPAN, tb, gids, tmix,
                            bb.ts_last, mask))
        segments.append(seg(NC + StoreConfig.TR_ANN, tb[bb.ann_span_idx],
                            a_gids, tmix[bb.ann_span_idx], ts_a, mask_a))
        segments.append(seg(NC + StoreConfig.TR_BANN,
                            tb[bb.bann_span_idx], bb_gids,
                            tmix[bb.bann_span_idx], ts_b, mask_b))
        cat = [jnp.concatenate(parts)
               for parts in zip(*(p for _, p in segments))]
        out = dev._index_write(
            st.cand_idx, st.cand_pos, st.cand_wm, st.key_tab, st.key_wm,
            st.ann_poison, *cat,
            keyed_from=segments[0][1][0].shape[0],
            n_cand_rows=n_cand_rows, n_cand_buckets=c.cand_layout[1],
            poison_bucket=a_host, poison_gid=span_gid_of_ann,
            poison_ok=a_idx_ok & (a_host != h1) & (a_host != h2),
        )
        return sum(p.sum() for p in out[0])

    timeit("unified index write (cand+trace, concat+sort+scatter)",
           jax.jit(cand_only), state, b)

    # 7. histogram/counter scatter-adds
    def hist_only(st, bb):
        from zipkin_tpu.store.device import _scatter_add, svc_histogram
        from zipkin_tpu.ops import quantile as Q
        hist = svc_histogram(st)
        svc_ok = mask & (bb.service_id >= 0) & (bb.service_id < S) \
            & (bb.duration >= 0)
        bidx = Q.bucket_index(hist, bb.duration.astype(jnp.float32))
        g = jnp.clip(bb.service_id, 0, S - 1)
        out = _scatter_add(
            st.svc_hist,
            jnp.where(svc_ok, g * c.quantile_buckets + bidx, -1),
            jnp.ones(P, jnp.int32), False,
        )
        return out.sum()

    timeit("svc_hist scatter-add", jax.jit(hist_only), state, b)

    # 8. CMS + HLL
    def sketch_only(st, bb):
        from zipkin_tpu.ops import hll, cms
        from zipkin_tpu.store.device import _scatter_add, dev_split64
        t_hi, t_lo = dev_split64(bb.trace_id)
        regs = hll.update(hll.HyperLogLog(st.hll_traces), t_hi, t_lo,
                          valid=mask).registers
        sk = cms.CountMin(st.cms_trace_spans)
        cms_idx = cms._indices(sk, t_hi, t_lo)
        cms_flat = cms_idx + (
            jnp.arange(c.cms_depth, dtype=jnp.int32) * c.cms_width
        )[:, None]
        cms_flat = jnp.where(mask[None, :], cms_flat, -1).reshape(-1)
        out = _scatter_add(
            st.cms_trace_spans, cms_flat,
            jnp.ones(c.cms_depth * P, jnp.int32), False,
        )
        return out.sum() + regs.sum()

    timeit("HLL + CMS update", jax.jit(sketch_only), state, b)

    # 8b. micro-arms for the remaining _index_write costs: which gather
    # shape is cheapest for the old-entry read, what the rank sort
    # costs alone, and what one full-width war costs.
    NR = 4 * PA + 4 * PB  # concatenated candidate rows
    M_ROWS = config.cand_layout[2]
    # Hash-scattered indices: production gidx values are bucket slots,
    # not sequential — a sequential arm would let the gather coalesce
    # into reads the real access pattern never gets.
    gidx = ((jnp.arange(NR, dtype=jnp.int64) * 2654435761)
            % M_ROWS).astype(jnp.int32)
    ent = jnp.zeros((M_ROWS, 3), jnp.int64)

    def g_cols(e, ix):
        return (e[:, 0][ix] + e[:, 1][ix] + e[:, 2][ix]).sum()

    def g_rows(e, ix):
        return e[ix].sum()

    def g_planes(e, ix):
        p = dev._p32(e)  # [M, 3, 2]
        acc = 0
        for cdx in range(3):
            for pl in range(2):
                acc += p[:, cdx, pl][ix].astype(jnp.int64).sum()
        return acc

    timeit(f"old-entry gather: 3 col i64 ({NR} rows)",
           jax.jit(g_cols), ent, gidx)
    timeit("old-entry gather: row [N,3] i64", jax.jit(g_rows), ent, gidx)
    timeit("old-entry gather: 6 plane i32", jax.jit(g_planes), ent, gidx)

    bkt = (jnp.arange(NR, dtype=jnp.int64) * 2654435761) % (1 << 16)

    def ranks_only(bb):
        return dev._fifo_ranks(bb, jnp.ones(NR, bool), 1 << 16).sum()

    timeit("fifo ranks (sort+cummax+unsort)", jax.jit(ranks_only), bkt)

    wmv = jnp.full(1 << 16, dev.I64_MIN, jnp.int64)

    def war_only(w, bb):
        return dev._war_max64(
            w, bb.astype(jnp.int32), jnp.arange(NR, dtype=jnp.int64),
            jnp.ones(NR, bool),
        ).sum()

    timeit("war_max64 full width", jax.jit(war_only), wmv, bkt)

    # 8c. r12 rank-path arms: the argsort rank vs the segmented
    # counting rank at the step's REAL concatenated shape + bucket
    # count. Counting is scratch-bounded — when no block fits at this
    # geometry the arm reports so (the step then statically keeps
    # argsort; see device.rank_block_for / docs/PERFORMANCE.md).
    n_b_total = config.idx_layout[1]
    rbkt = ((jnp.arange(NR, dtype=jnp.int64) * 2654435761)
            % n_b_total).astype(jnp.int32)
    rvalid = jnp.ones(NR, bool)

    def arg_ranks(bb):
        return dev._fifo_ranks(bb, rvalid, n_b_total).sum()

    timeit(f"rank path: argsort ({NR} rows, {n_b_total} buckets)",
           jax.jit(arg_ranks), rbkt)
    blk = dev.rank_block_for(NR, n_b_total)
    if blk:
        def cnt_ranks(bb):
            return dev._fifo_ranks_counting(bb, rvalid, n_b_total,
                                            blk).sum()

        timeit(f"rank path: counting (block {blk})",
               jax.jit(cnt_ranks), rbkt)
    else:
        print(f"rank path: counting infeasible at {NR} rows x "
              f"{n_b_total} buckets (scratch budget); step keeps "
              "argsort here", flush=True)

    # 8d. r12 arena-scatter arms: the 6-plane XLA scatter vs the fused
    # pallas claim+scatter, at a geometry whose arena fits VMEM (the
    # kernel's own support boundary — the full-size arena stays on the
    # XLA path by the NOTES_r06 §3 roofline).
    from zipkin_tpu.ops import pallas_kernels as PK

    small_nb, small_depth = 1 << 10, 32
    small_S = small_nb * small_depth
    if PK.arena_scatter_supported(small_S, small_nb):
        NS = min(NR, 1 << 17)
        ent = dev._arena_init(small_S)
        sb = ((jnp.arange(NS, dtype=jnp.int64) * 2654435761)
              % small_nb).astype(jnp.int32)
        svals = jnp.stack([jnp.arange(NS, dtype=jnp.int64)] * 3, -1)
        sval = jnp.ones(NS, bool)
        sbase = jnp.zeros(NS, jnp.int32)
        sslot0 = sb.astype(jnp.int64) * small_depth
        sdep = jnp.full(NS, small_depth, jnp.int32)

        def xla_scatter(e):
            rank = dev._fifo_ranks(sb, sval, small_nb)
            slot = sslot0.astype(jnp.int32) + (rank % small_depth)
            keep = sval & (rank >= 0)
            return sum(p.sum() for p in
                       dev._arena_set(e, slot, svals, keep))

        def pallas_scatter(e):
            return sum(p.sum() for p in PK.arena_claim_scatter(
                e, sb, sbase, sslot0, sdep, svals, sval,
                n_buckets=small_nb))

        timeit(f"arena scatter: XLA rank+6-plane ({NS} rows)",
               jax.jit(xla_scatter), ent)
        timeit("arena scatter: pallas claim+scatter (VMEM arena)",
               jax.jit(pallas_scatter), ent)

    # 9a. r12 batch escalation: re-template the full step at each
    # requested batch size and time it — spans/s per batch_spans is
    # the scatter-amortization curve whose knee picks the new
    # StoreConfig.batch_spans / bench --batch-spans default (the old
    # 16384-trace optimum predates the PR 4 pipeline overlap).
    sweep = [int(x) for x in args.batch_spans_sweep.split(",") if x]
    from bench import SPT

    for bs in sweep:
        traces_n = max(1, bs // SPT)
        if traces_n * SPT > (1 << args.cap_log2) // 2:
            print(f"batch_spans {bs}: exceeds half-ring budget at "
                  f"cap 2^{args.cap_log2}; skipped", flush=True)
            continue
        db_s, _, pad_s = _make_template(store, 1024, traces_n)
        st_s = jax.device_put(dev.init_state(config))
        step_s = jax.jit(
            lambda s, d: dev.ingest_step.__wrapped__(s, d))
        t0 = time.perf_counter()
        out_s = step_s(st_s, db_s)
        jax.device_get(out_s.counters["spans_seen"])
        t1 = time.perf_counter()
        times = []
        for _ in range(args.reps):
            t2 = time.perf_counter()
            out_s = step_s(st_s, db_s)
            jax.device_get(out_s.counters["spans_seen"])
            times.append(time.perf_counter() - t2)
        best = min(times)
        print(f"batch_spans {pad_s:7d}: compile+1st {t1 - t0:7.3f}s  "
              f"steady {best * 1e3:9.1f} ms  "
              f"({pad_s / best / 1e3:8.1f}k spans/s)", flush=True)
        del st_s, out_s, db_s

    # 9. chain scaling: is scan amortization working?
    for k in (1, 4, 18):
        st2 = dev.init_state(config)
        st2 = jax.device_put(st2)
        stp = jnp.int64(0)
        fc = fused_chain
        t0 = time.perf_counter()
        st2, stp = fc(st2, b, stp, k, jnp.bool_(False))
        _ = float(jax.device_get(st2.counters["spans_seen"]))
        t1 = time.perf_counter()
        st2, stp = fc(st2, b, stp, k, jnp.bool_(False))
        _ = float(jax.device_get(st2.counters["spans_seen"]))
        t2 = time.perf_counter()
        print(f"fused_chain k={k:3d}: compile+1st {t1 - t0:8.3f}s  "
              f"steady {(t2 - t1) * 1e3:9.1f} ms  "
              f"({(t2 - t1) * 1e3 / k:7.1f} ms/step)", flush=True)
        del st2

    print("done", flush=True)


if __name__ == "__main__":
    main()
