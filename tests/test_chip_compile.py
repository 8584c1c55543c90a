"""Chipless compile guards: the served path's device programs, compiled
for a DESCRIBED v5e by the installed TPU compiler (no chip attached).

CPU tests cannot see what the TPU backend refuses (a program that does
not fit HBM, a ring copied where it should be written in place). These
cases compile the fused ingest step (both rank paths), one index-hit
read and the trace gather at the geometry the daemon serves, so every
later PR meets the chip's compiler at no chip time. Nothing runs: a compile that
passes says nothing about results or speed.

The chip takes a branch the CPU suites never take — ``rank_mode``'s
"auto" asks ``jax.default_backend()`` while tracing — so the cases
steer it HERE (explicit ``rank_path``).

The topology is described inside a module-scoped fixture: only one
process may load the TPU library, so no topology call may run at import
or collection time (every xdist worker imports this file; only the one
that is handed it runs the fixture). Keep all such cases in THIS file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zipkin_tpu.columnar.schema import SpanBatch
from zipkin_tpu.store import device as dev

# The daemon's default geometry (main/example.py: --capacity 65536,
# window arena on) and the pow2 pads a 4096-span launch lands in.
CONFIG = dev.StoreConfig(capacity=1 << 16, window_seconds=60)
PADS = (4096, 8192, 4096)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without the chip — keep it off."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _abstract(tree, sharding, lead=()):
    """Shapes placed on the described chip(s) (no arrays: there is no
    device to hold one); ``lead`` prepends the stacked-shard axis."""
    def leaf(x):
        x = x if hasattr(x, "dtype") else np.asarray(x)
        return jax.ShapeDtypeStruct(lead + x.shape, x.dtype,
                                    sharding=sharding)

    return jax.tree_util.tree_map(leaf, tree)


def _state(config, sharding):
    return _abstract(jax.eval_shape(lambda: dev.init_state(config)),
                     sharding)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_on_tpu(lowered):
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("rank_path", ["counting", "argsort"])
def test_ingest_step_compiles(one_chip, rank_path):
    """The fused step, both rank paths: on the chip ``auto`` picks
    counting, and degrades to argsort where the scratch cannot fit."""
    config = CONFIG._replace(rank_path=rank_path)
    batch = dev.make_device_batch(
        SpanBatch.empty(0, 0, 0), np.zeros(0, np.int32),
        np.zeros(0, bool), *PADS, error_flag=np.zeros(0, bool))
    _compiled_on_tpu(dev.ingest_step.lower(
        _state(config, one_chip), _abstract(batch, one_chip)))
    assert dev.active_paths(config)["rank"] == (rank_path,)


# -- the rings are written in place (PR 30) ----------------------------------
#
# "Before the chip" of ISSUE 30 as a test: at the benchmark's geometry
# (the daemon at --capacity 4194304, pads of a 2048-span Log call) the
# optimized step holds no instruction of a ring's size but parameters,
# views, window reads and in-place window writes. Two things of that
# size are known and named: ``span_tab`` (hashed slots; an [H, 2] leaf
# whose planes are sliced, scattered into and stacked back, with the
# compiler's copies between memory spaces: exempt by its scope's name
# and, for the copies, which carry no name, by its size) and the three
# X64 custom calls an i64 leaf costs for any update at all
# (X64SplitLow/High before, X64Combine after: 97 us a 2^22 column on
# the chip, PERF.md 6, PR 30; the cure is the leaf in plane form).


def _daemon_config(capacity):
    from zipkin_tpu.main.example import _side_rings

    return dev.StoreConfig(capacity=capacity, **_side_rings(capacity),
                           window_seconds=60, window_buckets=64)


_FREE = ("parameter", "bitcast", "get-tuple-element", "tuple",
         "dynamic-slice", "dynamic-update-slice")
_MOVES = ("copy-start", "copy-done", "slice-start", "slice-done",
          "ConcatBitcast")
# Scopes whose instructions of such a size are no ring's: span_tab's,
# and the sketch update's one slice of 2^20 counters, which at the 2^22
# ring happens to be the pending ring's size.
_NOT_A_RING = ("ingest.span_table_insert", "ingest.sketch_update")
_INSTRUCTION = re.compile(
    r"^\s*(ROOT\s+)?%\S+ = (\(?[a-z0-9]+\[.*?) ([a-z][a-z0-9\-]*)\(")


def _ring_sized(hlo: str, ring_dims, span_tab_rows: int):
    """(offending, x64) instructions of an optimized HLO module with a
    result dimension in ``ring_dims``: ``x64`` the X64 split/combine
    custom calls, ``offending`` whatever is neither free (a parameter, a
    view, a window read, an in-place window write alone or as a fusion's
    root), nor ``span_tab``'s (its scope's name; a result of its
    ``[H, 2]`` or ``[H, 1]`` form; the compiler's nameless moves of an
    ``H``-row array between memory spaces), nor the one bool column's
    (a byte a row: the sharded program copies it between memory spaces
    and reduces it on its way out of ``shard_map``)."""
    dims = {str(d) for d in ring_dims}
    tab = str(span_tab_rows)
    roots, comp, rows = {}, None, []
    for line in hlo.splitlines():
        if line.startswith(("%", "ENTRY")):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        root, shape, op = m.groups()
        if root:
            roots[comp] = op
        arrays = [(dt, g.split(",")) for dt, g in re.findall(
            r"([a-z0-9]+)\[([0-9,]*)\]", shape) if dims & set(g.split(","))]
        if arrays:
            rows.append((op, arrays, line))
    offending, x64 = [], []
    for op, arrays, line in rows:
        target = re.search(r'custom_call_target="([^"]+)"', line)
        target = target.group(1) if target else ""
        called = re.search(r"calls=(%[^,)\s]+)", line)
        move = op in _MOVES or target in _MOVES
        if op in _FREE or (op == "fusion" and roots.get(
                called.group(1)) == "dynamic-update-slice"):
            continue
        if target.startswith("X64"):
            x64.append(target)
        elif any(scope in line for scope in _NOT_A_RING) or all(
                d[-2:] in ([tab, "2"], [tab, "1"]) or (move and tab in d)
                or dt == "pred" for dt, d in arrays):
            continue
        else:
            offending.append(line.strip()[:200])
    return offending, x64


def _assert_rings_in_place(compiled, config, n_leaves, temp_limit):
    text = compiled.as_text()
    rings = {config.capacity, config.ann_capacity, config.bann_capacity,
             config.pending_slots}
    offending, x64 = _ring_sized(text, rings, config.tab_slots)
    assert not offending, offending[:5]
    # Three a column for the 18 i64 ring columns, and no more.
    n_i64 = sum(
        leaf.dtype == jnp.int64 and leaf.shape in {(d,) for d in rings}
        for leaf in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: dev.init_state(config))))
    assert n_i64 == 18 and len(x64) <= 3 * n_i64, (n_i64, len(x64))
    # The detector sees a pass when there is one.
    seen, _ = _ring_sized(
        "ENTRY %main (p: s32[8]) -> s32[8] {\n"
        f"  %a = s32[{config.capacity}]{{0}} add(%p, %p)\n", rings, 0)
    assert len(seen) == 1
    header = text.split("\n", 1)[0]
    assert len(re.findall(r"(?:may|must)-alias", header)) == n_leaves
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_limit, mem
    assert dev.active_paths(config)["ring_write"] == (
        "ann:window", "bann:window", "pend:window", "span:window")


@pytest.mark.parametrize("pad_anns", [12288, 16384])
def test_ingest_step_writes_rings_in_place(one_chip, pad_anns):
    """The benchmark's step: ring 2^22, and the pads of a 2048-span Log
    call of 12,288 annotation rows: 2048/12288/4096, the served shape
    since the pad ladder (PR 35; ``tests/test_pad_ladder.py`` pins the
    rung), and the rung above it, the power of two served before. The
    temporaries were 60,322,816 B with the rings scattered into (PR 26)
    and 3.34 GB before the arena's planes."""
    config = _daemon_config(1 << 22)
    batch = dev.make_device_batch(
        SpanBatch.empty(0, 0, 0), np.zeros(0, np.int32),
        np.zeros(0, bool), 2048, pad_anns, 4096,
        error_flag=np.zeros(0, bool))
    state = _state(config, one_chip)
    compiled = _compiled_on_tpu(dev.ingest_step.lower(
        state, _abstract(batch, one_chip)))
    _assert_rings_in_place(
        compiled, config, len(jax.tree_util.tree_leaves(state)), 80e6)


def test_sharded_ingest_compiles_for_four_chips(topo):
    """``--shards 4``: the per-shard fused step plus its cross-shard
    summary (psum / pmax / all_gather) as ONE program over the 2x2
    mesh. The TPU lowers only SUM all-reduces over 64-bit types, so a
    64-bit pmax/pmin in the summary is refused here, not on the host
    with four chips (parallel/shard._pmax64). At the four-shard
    fixture's geometry (2^20 rows a shard, its pads), where each
    shard's rings are written in place too."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from zipkin_tpu.parallel.shard import make_sharded_ingest

    # (a pending ring of its own size: the daemon's 2^18 slots at this
    # capacity are also the 512 x 512 links of the cross-shard summary)
    config = _daemon_config(1 << 20)._replace(pend_slots=1 << 19)
    mesh = Mesh(np.array(topo.devices[:4]), ("shard",))
    sharded = NamedSharding(mesh, P("shard"))
    batch = dev.make_device_batch(
        SpanBatch.empty(0, 0, 0), np.zeros(0, np.int32),
        np.zeros(0, bool), 1024, 4096, 2048, error_flag=np.zeros(0, bool))
    state = _abstract(jax.eval_shape(lambda: dev.init_state(config)),
                      sharded, lead=(4,))
    compiled = _compiled_on_tpu(make_sharded_ingest(mesh).lower(
        state, _abstract(batch, sharded, lead=(4,))))
    assert "all-reduce" in compiled.as_text()
    _assert_rings_in_place(
        compiled, config, len(jax.tree_util.tree_leaves(state)), 80e6)


def test_index_read_compiles(one_chip):
    """One index-hit read: the by-service bucket probe."""
    st = _state(CONFIG, one_chip)
    fam = CONFIG.cand_layout[0][dev.StoreConfig.CAND_SVC]
    _compiled_on_tpu(dev._iq_service_impl.lower(
        st.cand_idx, st.cand_pos, st.cand_wm, st.row_gid, st.indexable,
        st.trace_id, st.ts_last, CONFIG.capacity, fam, 16,
        _spec((), jnp.int32, one_chip), _spec((), jnp.int64, one_chip)))


def test_trace_gather_compiles(one_chip):
    """The whole-trace ring gather at its first-try caps."""
    st = _state(CONFIG, one_chip)

    def cols(names):
        return tuple(getattr(st, c) for c in names)

    _compiled_on_tpu(dev._gather_impl.lower(
        cols(dev.SPAN_MAT_COLS), cols(dev.ANN_MAT_COLS),
        cols(dev.BANN_MAT_COLS), _spec((1,), jnp.int64, one_chip),
        st.write_pos, st.ann_write_pos, st.bann_write_pos,
        CONFIG.capacity, CONFIG.ann_capacity, CONFIG.bann_capacity,
        256, 512, 256, False))


@pytest.mark.parametrize("scan", [False, True], ids=["bucket", "scan"])
def test_launch_map_gather_compiles(one_chip, scan):
    """The whole-trace read through the span buckets (or, where a
    bucket refuses, a scan of the span ring) and the launch map (PR
    36): the side rows found by binary search in the runs the launches
    wrote."""
    st = _state(CONFIG, one_chip)
    tlay = CONFIG.trace_layout[0][dev.StoreConfig.TR_SPAN]

    def cols(names):
        return tuple(getattr(st, c) for c in names)

    z = 4096
    launches = tuple(_spec((z,), jnp.int64, one_chip) for _ in range(5)) \
        + (_spec((z,), jnp.bool_, one_chip),)
    _compiled_on_tpu(dev._iq_launch_gather_impl.lower(
        st.cand_idx, st.cand_pos, st.cand_wm,
        cols(dev.SPAN_MAT_COLS), cols(dev.ANN_MAT_COLS),
        cols(dev.BANN_MAT_COLS), _spec((10,), jnp.int64, one_chip),
        st.write_pos, st.ann_write_pos, st.bann_write_pos, launches,
        _spec((), jnp.int64, one_chip),
        (CONFIG.capacity, CONFIG.ann_capacity, CONFIG.bann_capacity,
         tlay, 640, 5120, 2560, 8, 4, CONFIG.ann_capacity.bit_length(),
         CONFIG.bann_capacity.bit_length(), scan)))


def test_id_scans_compile(one_chip):
    """An id lookup's O(ring) scans (PR 36: one candidate a span, a
    scatter of each side row's lap to its span's slot), both kernels."""
    st = _state(CONFIG, one_chip)
    i32, i64 = (_spec((), t, one_chip) for t in (jnp.int32, jnp.int64))
    _compiled_on_tpu(dev._q_by_service_impl.lower(
        st.ann_gid, st.ann_service_id, st.row_gid, st.indexable,
        st.name_lc_id, st.trace_id, st.ts_last, CONFIG.capacity, 64,
        i32, i32, i64))
    _compiled_on_tpu(dev._q_by_annotation_impl.lower(
        st.ann_gid, st.ann_service_id, st.ann_value_id, st.row_gid,
        st.indexable, st.ts_last, st.trace_id, st.bann_gid,
        st.bann_key_id, st.bann_value_id, CONFIG.capacity, 64,
        i32, i32, i32, i32, i32, i64))
