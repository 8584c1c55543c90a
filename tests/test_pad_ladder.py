"""The pad ladder (``store/tpu.py:_pad_rows``): a launch's annotation
and binary dimensions pad to the half-octave rungs 2^k and 3 * 2^(k-1)
from 4,096 rows up, so a 12,288-row call launches 12,288 rows and not
16,384 (docs/PERFORMANCE.md, "The pad ladder").

What has to hold, and is held here on the CPU: the ladder is a pure,
monotone function of the row count that never pads past the power of
two; the fused step leaves every state leaf bit for bit what the
power-of-two pads leave (the padded rows are masked out of every
write), on both rank paths and chained; the launch-row counters add up
to the units' own counts and exist before the first call; a WAL written
at the ladder's pads replays through ``_pad_unit(..., wal_seq)`` to the
same state; and a sweep over two octaves of annotation rows compiles
two shapes an octave, once.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from zipkin_tpu import obs
from zipkin_tpu.columnar.schema import SpanBatch
from zipkin_tpu.store import device as dev
from zipkin_tpu.store.tpu import (
    _LADDER_MIN, TpuSpanStore, _next_pow2, _pad_rows)
from zipkin_tpu.testing.crash import states_bitwise_equal
from zipkin_tpu.tracegen import ColumnarTraceGen

# Every count up to two rungs past the ladder's start, then every rung
# of the next five octaves with its neighbours.
SAMPLE = sorted(
    set(range(1, 2 * _LADDER_MIN + 2))
    | {r + d for k in range(12, 18) for r in (1 << k, 3 << (k - 1))
       for d in (-1, 0, 1) if r + d <= 1 << 17})
PADS = [_pad_rows(n) for n in SAMPLE]


@pytest.mark.parametrize("n, pad", [
    (1, 64), (64, 64), (65, 128), (2049, 4096), (3072, 4096),
    (4096, 4096), (4097, 6144), (6144, 6144), (6145, 8192),
    (8193, 12288), (12288, 12288), (12289, 16384), (16385, 24576),
    (1 << 17, 1 << 17),
])
def test_rungs(n, pad):
    assert _pad_rows(n) == pad


def _never_under(n, pad):
    return pad >= n


def _idempotent_on_rungs(n, pad):
    return _pad_rows(pad) == pad


def _never_past_pow2(n, pad):
    return pad <= _next_pow2(n)


def _pow2_under_the_ladder(n, pad):
    return pad == _next_pow2(n) or pad >= _LADDER_MIN


def _wastes_a_third_at_most(n, pad):
    return n < _LADDER_MIN or 3 * (pad - n) <= pad


@pytest.mark.parametrize("holds", [
    _never_under, _idempotent_on_rungs, _never_past_pow2,
    _pow2_under_the_ladder, _wastes_a_third_at_most,
], ids=lambda f: f.__name__.strip("_"))
def test_ladder_property(holds):
    bad = [(n, pad) for n, pad in zip(SAMPLE, PADS) if not holds(n, pad)]
    assert not bad, bad[:5]


def test_ladder_is_monotone_and_two_rungs_an_octave():
    assert PADS == sorted(PADS)
    rungs = sorted(set(PADS))
    for k in range(12, 17):
        assert [r for r in rungs if 1 << k <= r < 2 << k] == [
            1 << k, 3 << (k - 1)]
    assert all(r & (r - 1) == 0 for r in rungs if r < _LADDER_MIN)


# -- the fused step at a 3 * 2^k pad -------------------------------------

BASE = dict(
    capacity=1 << 10, ann_capacity=1 << 11, bann_capacity=1 << 10,
    max_services=16, max_span_names=32, max_annotation_values=64,
    max_binary_keys=32, cms_width=1 << 8, hll_p=6, quantile_buckets=64,
    window_seconds=60, window_buckets=8,
)
# 84 spans, 168 annotation rows, 84 binary rows a batch: over the power
# of two below each rung, so the rung's upper rows are written too.
POW2_PADS = dict(pad_spans=128, pad_anns=256, pad_banns=128)
RUNG_PADS = dict(pad_spans=128, pad_anns=192, pad_banns=96)


def _host_batches(n_batches=4, n_traces=12):
    gen = ColumnarTraceGen(TpuSpanStore(dev.StoreConfig(**BASE)).dicts,
                           n_services=8, n_span_names=16,
                           spans_per_trace=7)
    return [gen.next_batch(n_traces) for _ in range(n_batches)]


def _drive(config, batches, pads, chained):
    state = dev.init_state(config)
    dbs = [dev.make_device_batch(*b, **pads) for b in batches]
    if chained:
        return dev.ingest_steps(state, dev.stack_device_batches(dbs))
    for db in dbs:
        state = dev.ingest_step(state, db)
    return state


@pytest.mark.parametrize("rank_path, chained", [
    ("argsort", False), ("counting", False), ("argsort", True),
])
def test_rung_pads_leave_the_pow2_pads_state(rank_path, chained):
    config = dev.StoreConfig(**BASE, rank_path=rank_path)
    batches = _host_batches()
    assert all(128 < b.n_annotations <= 192 and 64 < b.n_binary <= 96
               for b, _, _ in batches)
    want = _drive(config, batches, POW2_PADS, chained)
    got = _drive(config, batches, RUNG_PADS, chained)
    assert states_bitwise_equal(want, got)
    if rank_path == "counting":
        assert "counting" in dev.active_paths(config)["rank"]


# -- whole stores at the ladder's own rungs ------------------------------

STORE = dict(BASE, capacity=1 << 11, ann_capacity=1 << 14,
             bann_capacity=1 << 12)


def _with_ann_rows(base: SpanBatch, n_anns: int) -> SpanBatch:
    """``base`` (two annotation rows a span) with ``n_anns`` annotation
    rows dealt round its spans in span order, each a copy of one of the
    span's own two rows."""
    span = np.sort(np.arange(n_anns, dtype=np.int32) % base.n_spans)
    row = 2 * span + np.arange(n_anns) % 2
    return dataclasses.replace(
        base, ann_span_idx=span,
        **{c: getattr(base, c)[row] for c in SpanBatch.ANN_COLUMNS
           if c != "ann_span_idx"})


def _calls(store, ann_rows, n_traces=64):
    gen = ColumnarTraceGen(store.dicts, n_services=8, n_span_names=16,
                           spans_per_trace=8)
    for n in ann_rows:
        base, _, indexable = gen.next_batch(n_traces)
        yield _with_ann_rows(base, n), indexable


def _sample(reg, name, dim):
    return reg.as_dict()[f'{name}{{dim="{dim}"}}']


def test_counters_add_up_and_a_wal_of_rung_pads_recovers(tmp_path):
    from zipkin_tpu.wal import WriteAheadLog, recover

    reg = obs.Registry()
    store = TpuSpanStore(dev.StoreConfig(**STORE), registry=reg)
    text = reg.render_text()
    for dim in ("span", "annotation", "binary"):
        assert f'zipkin_store_launch_rows_total{{dim="{dim}"}} 0' in text
        assert (f'zipkin_store_launch_pad_rows_total{{dim="{dim}"}} 0'
                in text)
    wal = WriteAheadLog(str(tmp_path / "wal"), fsync="off")
    store.attach_wal(wal)
    ann_rows = (6144, 12288, 6144)
    valid = dict(span=0, annotation=0, binary=0)
    for batch, indexable in _calls(store, ann_rows):
        store.write_batch(batch, indexable)
        valid["span"] += batch.n_spans
        valid["annotation"] += batch.n_annotations
        valid["binary"] += batch.n_binary
    launched = {d: _sample(reg, "zipkin_store_launch_rows_total", d)
                for d in valid}
    padding = {d: _sample(reg, "zipkin_store_launch_pad_rows_total", d)
               for d in valid}
    assert {d: launched[d] - padding[d] for d in valid} == valid
    assert launched["annotation"] == sum(ann_rows)
    assert padding["annotation"] == 0
    wal.sync()

    again = WriteAheadLog(str(tmp_path / "wal"), fsync="off")
    recovered, stats = recover(
        None, again,
        fresh_store=lambda: TpuSpanStore(dev.StoreConfig(**STORE),
                                         registry=obs.Registry()))
    assert stats["applied_seq"] == len(ann_rows)
    assert states_bitwise_equal(store.state, recovered.state)


def test_two_octaves_of_annotation_rows_compile_four_shapes():
    # A geometry of its own: the jit cache is the process's, and a
    # shape another test compiled would not be counted here.
    store = TpuSpanStore(dev.StoreConfig(**dict(STORE, hll_p=7)),
                         registry=obs.Registry())
    sweep = (4097, 5000, 6144, 6145, 8192, 8193, 11000, 12288, 12289,
             16384)
    steps = dev.ingest_step._cache_size()
    for batch, indexable in _calls(store, sweep):
        store.write_batch(batch, indexable)
    assert dev.ingest_step._cache_size() - steps == 4
    compiled = dev.compile_count()
    for batch, indexable in _calls(store, sweep):
        store.write_batch(batch, indexable)
    jax.block_until_ready(store.state.write_pos)
    assert dev.compile_count() == compiled
