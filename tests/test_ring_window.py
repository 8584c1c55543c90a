"""The rings are written as windows (PR 30).

A launch writes every ring at consecutive slots from its cursor, so
``dev._ring_write`` lands them as two slice updates and not as a
scatter. Two things are held here, bit for bit:

- the helper against ``dev._uset`` (the scatter it replaced) on the same
  inputs, over every way a write can lie on the ring;
- whole stores driven through three laps of the span ring with ragged
  launches, every state leaf equal to the same drive through today's
  step traced with the scatter in the helper's place — ring and paged
  layouts, single launches (``ingest_step``) and chained ones
  (``ingest_steps``).

The scatter path is callable from here only: the program has no switch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zipkin_tpu.store import device as dev
from zipkin_tpu.store.tpu import TpuSpanStore
from zipkin_tpu.testing.crash import states_bitwise_equal
from zipkin_tpu.tracegen import generate_traces


def _scatter_write(arr, start, vals, n):
    """What ``_ring_write`` replaced: the same rows through ``_uset``."""
    j = jnp.arange(vals.shape[0], dtype=jnp.int32)
    return dev._uset(arr, (start.astype(jnp.int32) + j) % arr.shape[0],
                     vals, j < n.astype(jnp.int32))


# -- the helper ---------------------------------------------------------------

# name -> (cap, pad, start, n): how the n written slots lie on the ring.
WRITES = {
    "no_lap": (64, 16, 10, 16),
    "ends_at_cap": (64, 16, 48, 16),
    "laps_mid_batch": (64, 16, 57, 16),
    "n_under_pad": (64, 16, 57, 9),
    "n_under_pad_no_lap": (64, 16, 3, 5),
    "n_zero": (64, 16, 60, 0),
    "start_zero": (64, 16, 0, 16),
    "last_slot_first": (64, 16, 63, 16),
    "odd_ring": (50, 16, 41, 13),
    "windows_overlap": (24, 16, 19, 16),
    "pad_is_cap": (16, 16, 5, 16),
    "pad_is_cap_n_under": (16, 16, 11, 7),
    "pad_past_cap": (8, 16, 5, 8),
}


def _column(rng, n, dtype):
    if dtype == np.bool_:
        return rng.integers(0, 2, n).astype(dtype)
    if dtype == np.int64:  # both words carry bits, the high one's sign too
        return rng.integers(-2**62, 2**62, n).astype(dtype)
    return rng.integers(0, np.iinfo(dtype).max, n).astype(dtype)


@pytest.mark.parametrize("dtype", [np.int64, np.int32, np.uint8, np.bool_],
                         ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("case", sorted(WRITES))
def test_ring_write_equals_the_scatter(case, dtype):
    cap, pad, start, n = WRITES[case]
    rng = np.random.default_rng(sorted(WRITES).index(case))
    arr, vals = _column(rng, cap, dtype), _column(rng, pad, dtype)
    args = (jnp.asarray(arr), jnp.int64(start), jnp.asarray(vals),
            jnp.int32(n))
    got = jax.jit(dev._ring_write)(*args)
    want = _scatter_write(*args)
    assert got.dtype == want.dtype == arr.dtype and got.shape == (cap,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # ... and both are the plain statement of the write.
    model = arr.copy()
    model[(start + np.arange(n)) % cap] = vals[:n]
    np.testing.assert_array_equal(np.asarray(got), model)
    assert dev._ring_windowed(cap, pad) == (case != "pad_past_cap")


def test_ring_write_lowers_without_a_scatter():
    text = jax.jit(dev._ring_write).lower(
        jnp.zeros(64, jnp.int64), jnp.int64(57), jnp.ones(16, jnp.int64),
        jnp.int32(16)).as_text()
    assert "scatter" not in text and "gather" not in text
    assert text.count("dynamic_update_slice") == 2


# -- whole stores -------------------------------------------------------------

CONFIG = dict(
    capacity=256, ann_capacity=1024, bann_capacity=512, max_services=16,
    max_span_names=32, max_annotation_values=64, max_binary_keys=32,
    cms_width=256, hll_p=6, quantile_buckets=64, window_seconds=60,
    pend_slots=128,
)
# Spans a ``store.apply``: ragged, and never a multiple of a pad, so the
# cursors stand off every pad's multiples when a ring laps.
RAGGED = (96, 37, 120, 5, 77, 1, 111, 64)


def _spans():
    """Shuffled, so that children arrive launches before their parents
    and wait in the pending ring (128 slots: it laps too)."""
    spans = [s for t in generate_traces(n_traces=190, max_depth=4,
                                        n_services=6) for s in t]
    return [spans[i] for i in np.random.default_rng(30).permutation(
        len(spans))]


def _drive(store, spans, chained: bool):
    if chained:
        third = len(spans) // 3
        for i in range(0, len(spans), third):
            store.apply(spans[i:i + third])
        return
    i = k = 0
    while i < len(spans):
        store.apply(spans[i:i + RAGGED[k % len(RAGGED)]])
        i += RAGGED[k % len(RAGGED)]
        k += 1


@pytest.mark.parametrize("chained", [False, True],
                         ids=["ingest_step", "ingest_steps"])
@pytest.mark.parametrize("layout", ["ring", "paged"])
def test_state_equals_the_scatter_path(layout, chained, monkeypatch):
    # batch_spans 8 makes chunks small enough for four to chain (the
    # paged layout allows a launch an eighth of the ring).
    config = dev.StoreConfig(**dict(
        CONFIG, layout=layout, page_rows=16,
        batch_spans=8 if chained else 0))
    spans = _spans()
    launches = {"ingest_step": 0, "ingest_steps": 0}

    def counted(name, fn):
        def run(state, batch):
            launches[name] += 1
            return fn(state, batch)
        run.__wrapped__ = fn.__wrapped__  # ingest_steps scans the raw step
        return run

    with monkeypatch.context() as m:
        m.setattr(dev, "_ring_write", _scatter_write)
        # Fresh jits: the program's own must never see the scatter.
        for name in launches:
            m.setattr(dev, name, counted(name, jax.jit(
                getattr(dev, name).__wrapped__, donate_argnums=(0,))))
        want = TpuSpanStore(config)
        _drive(want, spans, chained)
        assert launches["ingest_steps" if chained else "ingest_step"] > 3
    assert dev._ring_write is not _scatter_write
    got = TpuSpanStore(config)
    _drive(got, spans, chained)

    assert int(got.state.write_pos) > 3 * config.capacity
    assert int(got.state.ann_write_pos) > 3 * config.ann_capacity
    assert int(got.state.pend_pos) > config.pending_slots
    assert states_bitwise_equal(got.state, want.state)
    for name in dev.StoreState._FIELDS:  # ... and no leaf changed form
        a, b = getattr(got.state, name), getattr(want.state, name)
        assert jax.tree_util.tree_structure(a) == (
            jax.tree_util.tree_structure(b)), name
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            assert x.shape == y.shape and x.dtype == y.dtype, name
    span = "span:scatter" if layout == "paged" else "span:window"
    assert dev.active_paths(config)["ring_write"] == (
        "ann:window", "bann:window", "pend:window", span)


# -- structure ----------------------------------------------------------------

# Every ring a size of its own, shared with no other leaf (``span_tab``
# has 2048 rows here) and with no pad, so a dimension names its ring.
SWEEP_CONFIG = dict(CONFIG, capacity=384, ann_capacity=1536,
                    bann_capacity=768, pend_slots=8192, span_tab_slots=2048)
SWEEP_PADS = (64, 128, 32)


@pytest.mark.parametrize("window_seconds", [0, 60])
@pytest.mark.parametrize("layout", ["ring", "paged"])
def test_step_never_sweeps_a_ring(layout, window_seconds):
    from zipkin_tpu.columnar.schema import SpanBatch
    from zipkin_tpu.store import census

    config = dev.StoreConfig(**dict(
        SWEEP_CONFIG, layout=layout, page_rows=16,
        window_seconds=window_seconds))
    state = jax.eval_shape(lambda: dev.init_state(config))
    rings = {"span": config.capacity, "ann": config.ann_capacity,
             "bann": config.bann_capacity, "pend": config.pending_slots}
    by_dim = {}
    for name in dev.StoreState._FIELDS:
        for leaf in jax.tree_util.tree_leaves(getattr(state, name)):
            for d in leaf.shape:
                by_dim.setdefault(d, set()).add(name)
    # The detector goes by dimension: a ring's size is its columns' alone
    # (span_tab, hashed and scattered, is exempt by having another).
    assert by_dim[rings["span"]] == {*dev._SPAN_RING_COLS, "row_gid"}
    assert by_dim[rings["ann"]] == set(dev.ANN_MAT_COLS)
    assert by_dim[rings["bann"]] == set(dev.BANN_MAT_COLS)
    assert by_dim[rings["pend"]] == {
        "pend_key", "pend_dur", "pend_tsf", "pend_tsl"}
    assert not set(rings.values()) & set(SWEEP_PADS)
    if config.paged_enabled:  # its span slots are the planner's: _uset
        del rings["span"]
    paged = (dict(span_slot=np.zeros(0, np.int32),
                  span_gid=np.zeros(0, np.int64),
                  reclaim_pages=np.zeros(0, np.int32))
             if config.paged_enabled else {})
    batch = dev.make_device_batch(
        SpanBatch.empty(0, 0, 0), np.zeros(0, np.int32), np.zeros(0, bool),
        *SWEEP_PADS, **paged)
    text = dev.ingest_step.lower(state, batch).as_text()
    sweeps = census.stablehlo_ring_sweeps(text, rings.values())
    assert len(sweeps) <= census.RING_SWEEP_OPS, sweeps
    # The detector sees a sweep when there is one: the scatter's planes.
    seen = census.stablehlo_ring_sweeps(jax.jit(_scatter_write).lower(
        jnp.zeros(rings["ann"], jnp.int64), jnp.int64(0),
        jnp.zeros(SWEEP_PADS[1], jnp.int64), jnp.int32(1)).as_text(),
        rings.values())
    assert "bitcast_convert" in seen and "concatenate" in seen, seen
