"""Chipless compile guards: the served path's device programs, compiled
for a DESCRIBED v5e by the installed TPU compiler (no chip attached).

Interpret-mode tests cannot see what Mosaic or the TPU backend refuse
(64-bit leaks in a kernel body, unaligned slices, VMEM overruns, a
program that does not fit HBM). These cases compile the fused ingest
step (both rank paths), one index-hit read, the trace gather and the
Pallas kernels at the geometry the daemon serves, so every later PR
meets the chip's compiler at no chip time. Nothing runs: a compile that
passes says nothing about results or speed.

The chip takes branches the CPU suites never take — ``rank_mode``'s
"auto", ``gather_paged_trace_rows`` and ``pallas_kernels._interpret``
all ask ``jax.default_backend()`` while tracing — so the cases steer
them HERE (explicit ``rank_path``, a monkeypatched ``_interpret``),
never through an option of the program.

The topology is described inside a module-scoped fixture: only one
process may load the TPU library, so no topology call may run at import
or collection time (every xdist worker imports this file; only the one
that is handed it runs the fixture). Keep all such cases in THIS file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zipkin_tpu.columnar.schema import SpanBatch
from zipkin_tpu.ops import pallas_kernels as PK
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


@pytest.fixture
def mosaic(monkeypatch):
    """Compile the Pallas kernels for Mosaic, as the chip does."""
    monkeypatch.setattr(PK, "_interpret", lambda: False)


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


def test_sharded_ingest_compiles_for_four_chips(topo):
    """``--shards 4``: the per-shard fused step plus its cross-shard
    summary (psum / pmax / all_gather) as ONE program over the 2x2
    mesh. The TPU lowers only SUM all-reduces over 64-bit types, so a
    64-bit pmax/pmin in the summary is refused here, not on the host
    with four chips (parallel/shard._pmax64)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from zipkin_tpu.parallel.shard import make_sharded_ingest

    mesh = Mesh(np.array(topo.devices[:4]), ("shard",))
    sharded = NamedSharding(mesh, P("shard"))
    batch = dev.make_device_batch(
        SpanBatch.empty(0, 0, 0), np.zeros(0, np.int32),
        np.zeros(0, bool), 512, 1024, 512, error_flag=np.zeros(0, bool))
    compiled = _compiled_on_tpu(make_sharded_ingest(mesh).lower(
        _abstract(jax.eval_shape(lambda: dev.init_state(CONFIG)),
                  sharded, lead=(4,)),
        _abstract(batch, sharded, lead=(4,))))
    assert "all-reduce" in compiled.as_text()


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


def test_flat_histogram_compiles(one_chip, mosaic):
    """The per-service latency histogram (256 services x 2048 buckets,
    m = 524288 f32) at the cert launch's 114688 rows."""
    n, m = 114688, 524288
    _compiled_on_tpu(PK.flat_histogram.lower(
        _spec((n,), jnp.int32, one_chip),
        _spec((n,), jnp.float32, one_chip), m=m))


def test_cms_update_compiles(one_chip, mosaic):
    """Count-min update at the default sketch: 4 x 65536 i32."""
    d, w, n = 4, 65536, PADS[0]
    _compiled_on_tpu(jax.jit(PK.cms_update).lower(
        _spec((d, w), jnp.int32, one_chip),
        _spec((d, n), jnp.int32, one_chip)))


def test_paged_page_gather_compiles(one_chip, mosaic):
    """The paged trace-assembly block gather at the cert ring: 2^22
    rows in 128-row pages, 12 columns as 24 bit-planes, 64 pages."""
    capacity, page_rows, w, k = 1 << 22, 128, 24, 64
    assert PK.paged_gather_supported(capacity, page_rows, w // 2, k)
    _compiled_on_tpu(PK.paged_page_gather.lower(
        _spec((w, capacity), jnp.int32, one_chip),
        _spec((k,), jnp.int32, one_chip), page_rows=page_rows))


def test_arena_claim_scatter_compiles(one_chip, mosaic):
    """The fused claim + entry scatter (behind --use-pallas) at a
    VMEM-resident arena: 2^15 slots, 2^10 buckets, 4096 rows."""
    s, nb, n = 1 << 15, 1 << 10, 4096
    assert PK.arena_scatter_supported(s, nb)
    i32 = _spec((n,), jnp.int32, one_chip)
    planes = (_spec((s,), jnp.int32, one_chip),) * dev.ARENA_PLANES
    _compiled_on_tpu(PK.arena_claim_scatter.lower(
        planes, i32, i32, i32, i32,
        _spec((n, 3), jnp.int64, one_chip),
        _spec((n,), jnp.bool_, one_chip), n_buckets=nb))
