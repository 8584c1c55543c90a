"""The index arena as six i32 bit-plane leaves (PR 26).

Three things are held here:

- structure: the fused step's lowering has no op that passes over a
  whole arena plane but the gathers and scatters themselves
  (``census.ARENA_SWEEP_OPS``) — the step costs the batch, not the arena;
- bits: the planes, viewed as the logical [slots, 3] i64 rows, equal a
  plain numpy FIFO-bucket model after mixed steps, and equal the arena
  the revision-18 code (one i64 leaf) built from the same drive;
- checkpoint: a revision-18 snapshot restores bit for bit, answers every
  ``_iq_*`` route as the code that saved it did, and save -> load -> save
  is bit-stable at revision 19.
"""

import importlib.util
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zipkin_tpu import checkpoint
from zipkin_tpu.columnar.schema import SpanBatch
from zipkin_tpu.store import census
from zipkin_tpu.store import device as dev
from zipkin_tpu.store.tpu import TpuSpanStore
from zipkin_tpu.testing.crash import states_bitwise_equal

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "ckpt_rev18")
_spec = importlib.util.spec_from_file_location(
    "make_fixture", os.path.join(FIXTURE, "make_fixture.py"))
fx = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(fx)


# -- structure ---------------------------------------------------------------


@pytest.mark.parametrize("window_seconds", [0, 60])
@pytest.mark.parametrize("layout", ["ring", "paged"])
def test_step_never_sweeps_the_arena(layout, window_seconds):
    store = TpuSpanStore(dev.StoreConfig(**dict(
        fx.CONFIG, layout=layout, page_rows=16,
        window_seconds=window_seconds)))
    slots = store.config.idx_layout[2]
    # The detector goes by dimension: no other leaf may share it.
    other = {d for leaf in jax.tree_util.tree_leaves(store.state)
             for d in leaf.shape} - {slots}
    assert all(p.shape == (slots,) and p.dtype == jnp.int32
               for p in store.state.cand_idx)
    assert len(store.state.cand_idx) == dev.ARENA_PLANES
    paged = (dict(span_slot=np.zeros(0, np.int32),
                  span_gid=np.zeros(0, np.int64),
                  reclaim_pages=np.zeros(0, np.int32))
             if store.config.paged_enabled else {})
    db = dev.make_device_batch(
        SpanBatch.empty(0, 0, 0), np.zeros(0, np.int32), np.zeros(0, bool),
        256, 1024, 512, **paged)
    assert not {256, 1024, 512} & {slots} and slots not in other
    text = dev.ingest_step.lower(store.state, db).as_text()
    sweeps = census.stablehlo_arena_sweeps(text, slots)
    assert len(sweeps) <= census.ARENA_SWEEP_OPS, sweeps
    # The detector sees a sweep when there is one: the logical view.
    seen = census.stablehlo_arena_sweeps(jax.jit(
        lambda p: jnp.stack(p, -1)).lower(store.state.cand_idx).as_text(),
        slots)
    assert "concatenate" in seen, seen


# -- bits: a plain numpy FIFO-bucket model -----------------------------------


def _fifo_model(arena, pos, gbucket, slot0, depth, rows, valid):
    """Every valid row, in arrival order, lands whole at its bucket's
    next FIFO slot (in-batch overflow overwrites: the newest stay)."""
    for i in np.flatnonzero(valid):
        b = gbucket[i]
        arena[slot0[i] + pos[b] % depth[i]] = rows[i]
        pos[b] += 1


@pytest.mark.parametrize("rank_kind", ["argsort", "counting"])
def test_planes_equal_numpy_fifo_model(rank_kind):
    cfg = dev.StoreConfig(**fx.CONFIG)
    lay, n_b, slots = cfg.idx_layout
    st = dev.init_state(cfg)
    entries, pos, wm = st.cand_idx, st.cand_pos, st.cand_wm
    key_tab, key_wm, poison = st.key_tab, st.key_wm, st.ann_poison
    model = np.full((slots, 3), -1, np.int64)
    mpos = np.zeros(n_b, np.int64)
    rng = np.random.default_rng(26)
    # Rows per family and step: the service family (16 buckets of
    # depth 64 here) takes 200, so a bucket overflows inside a batch on
    # some steps and every one wraps within the 12 steps.
    per_fam = [200, 60, 60, 60, 40, 80, 40]
    n_cand = sum(per_fam[:dev.StoreConfig.N_CAND_FAMILIES])
    write = jax.jit(dev._index_write, static_argnames=(
        "keyed_from", "n_cand_rows", "n_cand_buckets", "wm_shift",
        "rank_sel"))
    for step in range(12):
        cols = []
        for (b_base, s_base, nb, depth), n in zip(lay, per_fam):
            lb = rng.integers(0, min(nb, 5), n)  # few buckets: they wrap
            cols.append((lb + b_base, lb * depth + s_base,
                         np.full(n, depth)))
        gbucket, slot0, depth = (np.concatenate(c) for c in zip(*cols))
        n = gbucket.shape[0]
        rows = rng.integers(0, 2**62, (n, 3))
        rows[:, 0] = step * 10_000 + np.arange(n)  # gid: small, rising
        valid = rng.random(n) < 0.9
        blk = dev.rank_block_for(n, n_b) if rank_kind == "counting" else 0
        entries, pos, wm, key_tab, key_wm, poison, _ = write(
            entries, pos, wm, key_tab, key_wm, poison,
            jnp.asarray(gbucket, jnp.int32), jnp.asarray(slot0, jnp.int64),
            jnp.asarray(depth, jnp.int32), jnp.asarray(rows[:, 0]),
            jnp.asarray(rows[:, 1]), jnp.asarray(rows[:, 2]),
            jnp.asarray(valid), keyed_from=per_fam[0], n_cand_rows=n_cand,
            n_cand_buckets=cfg.cand_layout[1], wm_shift=0,
            rank_sel=(rank_kind, blk))
        _fifo_model(model, mpos, gbucket, slot0, depth, rows, valid)
        np.testing.assert_array_equal(dev.arena_rows64(entries), model)
        np.testing.assert_array_equal(np.asarray(pos), mpos)
    assert (mpos[:lay[0][2]] > lay[0][3]).any()  # wrapped, not vacuous
    # The host views invert each other.
    for a, b in zip(dev.arena_planes(model), entries):
        np.testing.assert_array_equal(a, np.asarray(b))


# -- the revision-18 snapshot ------------------------------------------------


@pytest.fixture(scope="module")
def restored():
    return checkpoint.load(FIXTURE)


@pytest.fixture(scope="module")
def saved18():
    return np.load(os.path.join(FIXTURE, "state.npz"))


def test_rev18_snapshot_restores_bit_for_bit(restored, saved18):
    with open(os.path.join(FIXTURE, "meta.json")) as f:
        assert json.load(f)["revision"] == 18
    assert saved18["cand_idx"].dtype == np.int64
    np.testing.assert_array_equal(
        dev.arena_rows64(restored.state.cand_idx), saved18["cand_idx"])
    for name in dev.StoreState._FIELDS:
        if name not in ("cand_idx", "counters"):
            np.testing.assert_array_equal(
                np.asarray(getattr(restored.state, name)), saved18[name],
                err_msg=name)


def test_replayed_drive_builds_the_rev18_arena(restored):
    """The same mixed steps (the span ring laps, service buckets
    overflow and wrap) through today's step land the state the
    revision-18 step saved, arena bits included."""
    fresh = TpuSpanStore(dev.StoreConfig(**fx.CONFIG))
    fx.drive(fresh)
    assert int(fresh.state.write_pos) > 2 * fresh.config.capacity
    assert states_bitwise_equal(fresh.state, restored.state)


ROUTES = ("svc", "name", "ann", "bkey", "bval", "durations", "gather",
          "multi")


@pytest.fixture(scope="module")
def answers(restored):
    return fx.iq_answers(restored, fx.spans())


@pytest.mark.parametrize("route", ROUTES)
def test_rev18_snapshot_answers_as_before(answers, route):
    want = np.load(os.path.join(FIXTURE, "expected.npz"))
    keys = [k for k in want.files if k.startswith(route)]
    assert keys and sorted(keys) == sorted(
        k for k in answers if k.startswith(route))
    for k in keys:
        np.testing.assert_array_equal(answers[k], want[k], err_msg=k)
    # Not vacuous: some candidate matrix of the route holds a trace id.
    assert any(want[k].ndim >= 1 and (want[k] > 0).any() for k in keys)


def test_snapshot_with_options_this_build_lacks_restores(
        restored, answers, tmp_path):
    """A snapshot's meta.json may name options this build does not have
    (one written before an option was deleted, as the first key planted
    here was in PR 31: its two scatter paths were bitwise equal; or one
    a later build adds): they are dropped, and the state and the
    answers are the snapshot's."""
    planted = str(tmp_path / "planted")
    shutil.copytree(FIXTURE, planted)
    with open(os.path.join(planted, "meta.json")) as f:
        meta = json.load(f)
    meta["config"].update(use_pallas=True, option_of_a_later_build=7)
    with open(os.path.join(planted, "meta.json"), "w") as f:
        json.dump(meta, f)
    store = checkpoint.load(planted)
    assert store.config == restored.config
    assert states_bitwise_equal(store.state, restored.state)
    again = fx.iq_answers(store, fx.spans())
    assert sorted(again) == sorted(answers)
    for k in answers:
        np.testing.assert_array_equal(again[k], answers[k], err_msg=k)


def test_save_load_save_is_bit_stable_at_rev19(restored, tmp_path):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    checkpoint.save(restored, a)
    again = checkpoint.load(a)
    assert states_bitwise_equal(again.state, restored.state)
    checkpoint.save(again, b)
    za, zb = (np.load(os.path.join(p, "state.npz")) for p in (a, b))
    assert sorted(za.files) == sorted(zb.files)
    assert "cand_idx" not in za.files
    for j in range(dev.ARENA_PLANES):
        assert za[f"cand_idx.{j}"].dtype == np.int32
    for k in za.files:
        assert za[k].tobytes() == zb[k].tobytes(), k
    for p in (a, b):
        with open(os.path.join(p, "meta.json")) as f:
            assert json.load(f)["revision"] == 19
