"""On-chip A/B of scatter strategies (round-4 perf diagnosis step 2).

Methodology: the per-launch dispatch floor (~80-120ms when this was
written, round 4) swamps single-launch timings, so every candidate op is chained K times inside
ONE jitted program (output feeds the next iteration's input, values
perturbed by the loop counter so nothing hoists) and the reported
number is (wall - floor) / K. x64 is on (zipkin_tpu import), matching
the real store's dtypes.
"""

import sys
import time

sys.path.insert(0, ".")

import zipkin_tpu  # noqa: F401  (enables x64 like the real workload)
import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

P = 114688
CAP = 1 << 22
S, QB = 1024, 256
K = 16


def chain_timeit(name, step, init, reps=3):
    """step: (carry, i) -> carry, jitted; runs K times per launch."""

    @jax.jit
    def run(carry):
        def body(i, c):
            return step(c, i)
        return jax.lax.fori_loop(jnp.int32(0), jnp.int32(K), body, carry)

    out = run(init)
    jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[0])
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run(out)
        jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[0])
        times.append(time.perf_counter() - t0)
    per = (min(times)) / K * 1e3
    print(f"{name:56s} {per:9.2f} ms/op", flush=True)
    return per


def main():
    print("backend:", jax.default_backend(), "x64:",
          jax.config.jax_enable_x64, flush=True)
    rng = np.random.default_rng(0)

    floor = chain_timeit(
        "floor probe (x*2+1, K-chained)",
        lambda c, i: c * 2.0 + 1.0,
        jnp.ones((8, 128), jnp.float32),
    )

    slots = jnp.asarray(np.arange(P) % CAP, jnp.int32)
    mask = jnp.asarray(rng.random(P) < 0.98)
    col = jnp.asarray(rng.integers(0, 1 << 40, size=P), jnp.int64)
    ring0 = jax.device_put(jnp.zeros(CAP + 1, jnp.int64))

    def mk(v, i):
        return v ^ i.astype(jnp.int64)

    # single i64 ring column write, three ways
    chain_timeit(
        "ring col set: baseline (shared OOB dup)",
        lambda r, i: r.at[jnp.where(mask, slots, CAP)].set(
            mk(col, i), mode="drop"),
        ring0,
    )
    arange_p = jnp.arange(P, dtype=jnp.int32)
    chain_timeit(
        "ring col set: unique_indices (distinct OOB)",
        lambda r, i: r.at[
            jnp.where(mask, slots, CAP + arange_p)
        ].set(mk(col, i), mode="drop", unique_indices=True),
        ring0,
    )
    chain_timeit(
        "ring col set: unique+sorted",
        lambda r, i: r.at[jnp.where(mask, slots, CAP)].set(
            mk(col, i), mode="drop", unique_indices=True,
            indices_are_sorted=True),
        ring0,
    )

    # scatter-ADD into svc_hist geometry
    hidx = jnp.asarray(rng.integers(0, S * QB, size=P), jnp.int32)
    hidx = jnp.where(jnp.asarray(rng.random(P) < 0.97), hidx, -1)
    hist0 = jax.device_put(jnp.zeros(S * QB + 1, jnp.int32))
    ones = jnp.ones(P, jnp.int32)

    chain_timeit(
        "hist add 114k rows: XLA scatter-add",
        lambda h, i: h.at[jnp.where(hidx >= 0, hidx, S * QB)
                          ].add(ones + i * 0, mode="drop"),
        hist0,
    )

    from zipkin_tpu.ops.pallas_kernels import flat_histogram

    def pallas_step(h, i):
        d = flat_histogram(hidx, (ones + i * 0).astype(jnp.float32),
                           S * QB)
        return h + d.astype(jnp.int32)[: S * QB + 1].at[S * QB].set(0) \
            if False else h.at[:S * QB].add(d.astype(jnp.int32))

    chain_timeit("hist add 114k rows: pallas VMEM kernel", pallas_step,
                 hist0)

    # sort+segment+one-unique-scatter
    def sortseg(h, i):
        idx = jnp.where(hidx >= 0, hidx, S * QB)
        order = jnp.argsort(idx)
        si = idx[order]
        cum = jnp.cumsum(jnp.ones(P, jnp.int32))
        nxt = jnp.concatenate([si[1:], jnp.full(1, -7, si.dtype)])
        run_end = si != nxt
        # total per run = cum at run end minus cum at previous run end
        end_cum = jnp.where(run_end, cum, 0)
        prev = jax.lax.cummax(
            jnp.concatenate([jnp.zeros(1, jnp.int32), end_cum[:-1]]))
        total = jnp.where(run_end, cum - prev, 0) * (1 + i * 0)
        tgt = jnp.where(run_end, si, S * QB)
        return h.at[tgt].add(total, mode="drop", unique_indices=False)

    chain_timeit("hist add 114k rows: sort+segsum+scatter", sortseg,
                 hist0)

    # index entries: [N,2] i64 rows, four ways
    NI = 8 * P
    M = 1 << 23
    e2_0 = jax.device_put(jnp.zeros((M + 1, 2), jnp.int64))
    ef_0 = jax.device_put(jnp.zeros(2 * (M + 1), jnp.int64))
    eidx = jnp.asarray(rng.choice(M, size=NI, replace=False), jnp.int32)
    vals = jnp.asarray(rng.integers(0, 1 << 40, size=(NI, 2)), jnp.int64)

    chain_timeit(
        "idx write 917k [N,2]: baseline",
        lambda e, i: e.at[eidx].set(vals ^ i.astype(jnp.int64),
                                    mode="drop"),
        e2_0,
    )
    chain_timeit(
        "idx write 917k [N,2]: unique_indices",
        lambda e, i: e.at[eidx].set(vals ^ i.astype(jnp.int64),
                                    mode="drop", unique_indices=True),
        e2_0,
    )
    chain_timeit(
        "idx write 917k flat 2x1-D unique",
        lambda e, i: e.at[2 * eidx].set(
            vals[:, 0] ^ i.astype(jnp.int64), mode="drop",
            unique_indices=True,
        ).at[2 * eidx + 1].set(
            vals[:, 1] ^ i.astype(jnp.int64), mode="drop",
            unique_indices=True,
        ),
        ef_0,
    )

    # scatter in sorted-index order (gather vals through the sort)
    sorder = jnp.argsort(eidx)
    sidx = eidx[sorder]
    svals = vals[sorder]
    chain_timeit(
        "idx write 917k [N,2]: pre-sorted unique+sorted",
        lambda e, i: e.at[sidx].set(svals ^ i.astype(jnp.int64),
                                    mode="drop", unique_indices=True,
                                    indices_are_sorted=True),
        e2_0,
    )

    # scatter-add small target: bucket counters (cnt/pos pattern)
    NB = 98304
    bidx = jnp.asarray(rng.integers(0, NB, size=NI), jnp.int32)
    cnt0 = jax.device_put(jnp.zeros(NB + 1, jnp.int32))
    chain_timeit(
        "bucket cnt add 917k rows -> 98k buckets: XLA",
        lambda h, i: h.at[bidx].add(jnp.ones(NI, jnp.int32) + i * 0,
                                    mode="drop"),
        cnt0,
    )

    def cnt_sortseg(h, i):
        order = jnp.argsort(bidx)
        si = bidx[order]
        cum = jnp.cumsum(jnp.ones(NI, jnp.int32))
        nxt = jnp.concatenate([si[1:], jnp.full(1, -7, si.dtype)])
        run_end = si != nxt
        end_cum = jnp.where(run_end, cum, 0)
        prev = jax.lax.cummax(
            jnp.concatenate([jnp.zeros(1, jnp.int32), end_cum[:-1]]))
        total = jnp.where(run_end, cum - prev, 0) * (1 + i * 0)
        tgt = jnp.where(run_end, si, NB)
        return h.at[tgt].add(total, mode="drop")

    chain_timeit("bucket cnt add 917k rows: sort+segsum", cnt_sortseg,
                 cnt0)

    # scatter-min (span_tab probe round)
    T = 1 << 22
    tslot = jnp.asarray(rng.integers(0, T, size=P), jnp.int32)
    tval = jnp.asarray(rng.integers(0, 1 << 62, size=P), jnp.int64)
    tab0 = jax.device_put(jnp.full(T, (1 << 63) - 1, jnp.int64))
    chain_timeit(
        "span_tab probe round 114k: scatter-min",
        lambda t, i: t.at[tslot].min(tval ^ i.astype(jnp.int64),
                                     mode="drop"),
        tab0,
    )
    chain_timeit(
        "span_tab probe round 114k: scatter-min unique(lie-free dedup "
        "assumed)",
        lambda t, i: t.at[tslot].min(tval ^ i.astype(jnp.int64),
                                     mode="drop", unique_indices=True),
        tab0,
    )

    # gather cost for comparison (tab lookup reads)
    chain_timeit(
        "gather 114k from 4M table",
        lambda t, i: t.at[tslot].min(
            t[(tslot + i) % T], mode="drop", unique_indices=True),
        tab0,
    )

    # big sort cost at index-write row count
    skey = jnp.asarray(rng.integers(0, 1 << 62, size=NI), jnp.int64)

    def sort_step(c, i):
        out = jnp.sort(skey ^ i.astype(jnp.int64))
        return c + out[0] * 0 + out[-1] * 0

    chain_timeit("argsortable i64 sort 917k rows", sort_step,
                 jnp.int64(0))

    print(f"(floor was {floor:.2f} ms/op amortized)", flush=True)
    print("done", flush=True)


if __name__ == "__main__":
    main()
