"""Pallas kernel parity tests (interpret mode on the CPU backend)."""

import jax.numpy as jnp
import numpy as np
import pytest

from zipkin_tpu.ops import pallas_kernels as pk
from zipkin_tpu.store import device as dev


class TestFlatHistogram:
    def test_matches_xla_scatter(self):
        rng = np.random.default_rng(0)
        m = 1024
        idx = rng.integers(-1, m, size=3000).astype(np.int32)
        w = rng.random(3000).astype(np.float32)
        counts = jnp.zeros(m, jnp.float32)
        got = pk.histogram_update(counts, jnp.asarray(idx), jnp.asarray(w),
                                  tile=256)
        want = pk.scatter_histogram_xla(counts, jnp.asarray(idx),
                                        jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5)

    def test_int_counts(self):
        idx = jnp.asarray([0, 5, 5, 127, 128, -1], jnp.int32)
        counts = jnp.zeros(256, jnp.int32)
        got = pk.histogram_update(counts, idx, tile=128)
        want = np.zeros(256, np.int32)
        for i in [0, 5, 5, 127, 128]:
            want[i] += 1
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_accumulates_across_tiles(self):
        # Same bucket hit from several tiles must sum, not overwrite.
        idx = jnp.full(1000, 7, jnp.int32)
        got = pk.histogram_update(jnp.zeros(128, jnp.float32), idx, tile=128)
        assert float(got[7]) == 1000.0

    def test_2d_counts_shape_preserved(self):
        counts = jnp.zeros((4, 128), jnp.float32)
        idx = jnp.asarray([0, 129, 511], jnp.int32)
        got = pk.histogram_update(counts, idx, tile=128)
        assert got.shape == (4, 128)
        assert float(got[0, 0]) == 1 and float(got[1, 1]) == 1
        assert float(got[3, 127]) == 1


class TestCmsUpdate:
    def test_matches_ops_cms(self):
        from zipkin_tpu.ops import cms
        from zipkin_tpu.ops.hashing import split64

        keys = np.arange(50, dtype=np.int64) * 7919
        hi, lo = split64(keys)
        sk = cms.init(depth=4, width=1 << 10)
        want = cms.update(sk, hi, lo).counts
        idx = cms._indices(sk, jnp.asarray(hi), jnp.asarray(lo))
        got = pk.cms_update(sk.counts, idx, tile=128)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestArenaClaimScatter:
    """r12 fused claim+scatter vs the XLA reference formulation: the
    kernel's sequential cursor walk + write-all-in-arrival-order must
    land the bitwise SAME arena as the rank-gated unique plane scatter
    (_index_write's XLA path) — including under in-batch overflow,
    where the kernel overwrites dropped rows instead of skipping
    them."""

    def _xla_reference(self, entries, bucket, pos, depth, vals, valid,
                       n_b):
        import jax

        rank = dev._fifo_ranks(bucket, valid, n_b)
        pos_lo = jax.lax.bitcast_convert_type(pos, jnp.int32)[:, 0]
        b_c = jnp.clip(bucket, 0, n_b - 1)
        pos_b = pos_lo[b_c]
        oob_b = jnp.where(valid, b_c, n_b)
        cnt = jnp.zeros(n_b + 1, jnp.int32).at[oob_b].add(
            1, mode="drop")[:n_b]
        keep = valid & (rank >= cnt[b_c] - depth)
        slot = (b_c * depth).astype(jnp.int32) + (
            (pos_b + rank) % depth)
        return dev._arena_set(entries, slot, vals, keep)

    @pytest.mark.parametrize("n", [7, 300, 1024])
    def test_matches_xla_path(self, n):
        rng = np.random.default_rng(11 + n)
        n_b, depth = 53, 8
        S = n_b * depth
        rows = rng.integers(-2**62, 2**62, (S, 3))
        entries = tuple(jnp.asarray(p) for p in dev.arena_planes(rows))
        bucket = jnp.asarray(rng.integers(0, n_b, n), jnp.int32)
        pos = jnp.asarray(rng.integers(0, 500, n_b), jnp.int64)
        valid = jnp.asarray(rng.random(n) < 0.8)
        vals = jnp.asarray(
            rng.integers(-2**62, 2**62, (n, 3)), jnp.int64)
        dvec = jnp.full(n, depth, jnp.int32)
        want = self._xla_reference(entries, bucket, pos, depth,
                                   vals, valid, n_b)
        pos_lo = np.asarray(pos).astype(np.uint64) & 0xFFFFFFFF
        base = jnp.asarray(
            pos_lo[np.clip(np.asarray(bucket), 0, n_b - 1)],
            jnp.int32)
        got = pk.arena_claim_scatter(
            entries, bucket, base,
            bucket.astype(jnp.int64) * depth, dvec, vals, valid,
            n_buckets=n_b, tile=256)
        np.testing.assert_array_equal(
            dev.arena_rows64(want), dev.arena_rows64(got))
        # Both writers against a plain numpy FIFO walk over i64 rows:
        # each valid row, in arrival order, lands whole at its
        # bucket's next slot; nothing else moves.
        model = rows.copy()
        cur = np.asarray(pos).copy()
        for i in np.flatnonzero(np.asarray(valid)):
            b = int(bucket[i])
            model[b * depth + cur[b] % depth] = np.asarray(vals)[i]
            cur[b] += 1
        np.testing.assert_array_equal(dev.arena_rows64(got), model)

    def test_overflow_single_bucket(self):
        # 100 rows into one depth-4 bucket: the kernel writes all 100
        # in order; the final 4 slots must hold exactly the newest 4
        # rows at the cursor-aligned positions.
        n_b, depth, n = 4, 4, 100
        S = n_b * depth
        entries = dev._arena_init(S)
        bucket = jnp.zeros(n, jnp.int32)
        vals = jnp.stack(
            [jnp.arange(n, dtype=jnp.int64)] * 3, axis=-1)
        got = pk.arena_claim_scatter(
            entries, bucket, jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.int64), jnp.full(n, depth, jnp.int32),
            vals, jnp.ones(n, bool), n_buckets=n_b)
        got = dev.arena_rows64(got)
        # slots (0+r) % 4 for r=96..99 -> slot r%4 holds row r.
        np.testing.assert_array_equal(got[:4, 0], [96, 97, 98, 99])
        np.testing.assert_array_equal(got[4:, 0], -np.ones(S - 4))

    def test_supported_boundary(self):
        assert pk.arena_scatter_supported(1 << 12, 1 << 10)
        assert not pk.arena_scatter_supported(100_000_000, 800_000)
        assert not pk.arena_scatter_supported(0, 10)
        assert not pk.arena_scatter_supported(1 << 32, 10)

    @pytest.mark.slow
    def test_store_level_identity(self):
        # A use_pallas store must land the bitwise-identical state of
        # the XLA store (the arena fits VMEM at this geometry, so the
        # fused kernel actually engages — counters prove it). Slow
        # lane: the kernel-level fuzz above is the bitwise proof in
        # tier-1; this is the whole-store integration twin.
        from zipkin_tpu.store.tpu import TpuSpanStore
        from zipkin_tpu.testing.crash import states_bitwise_equal
        from zipkin_tpu.tracegen import generate_traces

        base = dict(
            capacity=1 << 10, ann_capacity=1 << 11,
            bann_capacity=1 << 10, max_services=16, max_span_names=32,
            max_annotation_values=64, max_binary_keys=32,
            cms_width=1 << 8, hll_p=6, quantile_buckets=64,
        )
        cfg_x = dev.StoreConfig(**base, rank_path="argsort")
        cfg_p = dev.StoreConfig(**base, rank_path="argsort",
                                use_pallas=True)
        traces = generate_traces(n_traces=28, max_depth=3,
                                 n_services=8)
        spans = [s for t in traces for s in t][:170]
        stores = []
        for cfg in (cfg_x, cfg_p):
            st = TpuSpanStore(cfg)
            for i in range(0, len(spans), 64):
                st.apply(spans[i:i + 64])
            stores.append(st)
        assert states_bitwise_equal(stores[0].state, stores[1].state)
        assert stores[1].counters()["scatter_path_pallas"] == 1.0
        assert stores[0].counters()["scatter_path_pallas"] == 0.0
