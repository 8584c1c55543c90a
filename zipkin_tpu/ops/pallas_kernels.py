"""Pallas TPU kernels for the scatter-heavy ingest ops.

The fused ingest step is dominated by scatter-adds into modest-size
count arrays (per-service histograms [S*B], count-min rows [D*W],
presence matrices). XLA lowers scatter-add to a sort+segment pipeline
through HBM; these kernels instead keep the whole count array resident
in VMEM and apply updates with on-chip scalar stores — grid steps run
sequentially on a TPU core, so the output block accumulates across
tiles without atomics (pallas_guide.md: grids are sequential; revisited
blocks stay in VMEM).

The count array must fit VMEM (~16MB): S*B = 256×2048 f32 = 2MB and
CMS 4×65536 i32 = 1MB both do. On CPU the kernels run in interpreter
mode (tests); on TPU they compile natively. ``flat_histogram`` is the
generic primitive; ``cms_update`` reuses it per sketch row.

Why the INDEX-FAMILY scatter block was NOT a Pallas kernel at bench
geometry (the r6 decision, NOTES_r06.md §3 carries the arithmetic):
the VMEM-residency trick above is what makes these kernels win, and it
does not transfer to arenas that dwarf VMEM. The unified index arena
at the bench geometry is ~0.5-1.6 GB ([slots, 3] i64 entries) —
30-100x VMEM — and the destination slots are hash-scattered across ALL
of it, so a Pallas version must stream HBM tiles exactly like XLA's
scatter does, with no reuse to amortize: each of the ~1.4M batch rows
touches 24 bytes of a ~1 GB array once. The measured fast path
(unique-index i32 plane scatters at ~4.5 ns/row,
scripts/profile_scatter*.py) already runs within ~2x of the pure HBM
write-bandwidth bound for that access pattern; the remaining gap is
random-access DMA latency, which a hand-rolled kernel pays
identically.

r12 re-opens the SMALL-arena half of that question with
``arena_claim_scatter``: when the whole [slots, 3] arena (as six i32
bit-planes) plus the per-bucket cursor walk DOES fit VMEM, a
grid-sequential kernel fuses the FIFO slot claim (a running cursor
histogram — the work the XLA path buys with a rank sort) and the
six-plane entry scatter into one pass with zero atomics (TPU grids run
sequentially, pallas_guide.md). ``arena_scatter_supported`` is the
VMEM-fit oracle; bigger arenas keep the XLA plane-scatter path and the
r6 roofline conclusion stands for them unchanged. Gated behind
``StoreConfig.use_pallas`` (default OFF) until the profile arms
(scripts/profile_ingest.py --arena-arm, bench.py --ingest-matrix)
prove it on-chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_TILE = 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _hist_kernel(idx_ref, w_ref, out_ref):
    # idx_ref/w_ref are SMEM-resident rank-1 blocks of ``tile`` scalars:
    # SMEM is the TPU memory built for data-dependent SCALAR reads, so
    # ``idx_ref[t]`` with a loop-carried ``t`` lowers cleanly — the
    # round-3 VMEM variant's dynamic LANE index was what Mosaic rejected
    # ("cannot statically prove index in dimension 2 is a multiple of
    # 128"), and a rank-2 (1, tile) SMEM block trips
    # the block-shape rule (second-to-last dim must be divisible by 8 or
    # equal the array dim). Rank-1 blocks only constrain the LAST dim
    # (tile % 128 == 0, asserted by the caller). The output stays
    # VMEM-resident across the whole grid (same block for every step);
    # updates are row-granular read-modify-writes with a one-hot lane
    # add — dynamic SUBLANE indexing is legal.
    i = pl.program_id(0)
    tile = idx_ref.shape[0]

    @pl.when(i == 0)
    def _():
        out_ref[:, :] = jnp.zeros_like(out_ref)

    # Shift/mask instead of //,% — LANES is 128 — int32 loop bounds and
    # a None carry: pallas TPU has no 64-bit lowering, and x64 mode
    # would make a plain python-int bound or carry int64 (Mosaic then
    # fails to legalize the loop's i64 func.return).
    def body(t, carry):
        b = idx_ref[t]

        @pl.when(b >= 0)
        def _():
            r = b >> 7
            c = b & 127
            row = out_ref[pl.ds(r, 1), :]
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
            onehot = (lane == c).astype(row.dtype) * w_ref[t]
            out_ref[pl.ds(r, 1), :] = row + onehot

        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(tile), body, None)


@functools.partial(jax.jit, static_argnames=("m", "tile"))
def flat_histogram(idx, weights, m: int, tile: int = DEFAULT_TILE):
    """Scatter-add ``weights`` at flat positions ``idx`` into a length-m
    array (m must be a multiple of 128). Negative idx rows are dropped.

    Returns the [m] histogram delta (caller adds it to running state).
    """
    assert m % LANES == 0, "histogram size must be a multiple of 128"
    assert tile % LANES == 0, "tile must be a multiple of 128"
    n = idx.shape[0]
    if n == 0:
        # Zero-length SMEM operands fail Mosaic layout verification, and
        # a (0,) grid would skip the i==0 output zeroing anyway.
        return jnp.zeros(m, jnp.asarray(weights).dtype)
    n_tiles = -(-n // tile)
    pad = n_tiles * tile - n
    idx = jnp.pad(jnp.asarray(idx, jnp.int32), (0, pad), constant_values=-1)
    weights = jnp.pad(jnp.asarray(weights), (0, pad))
    # Index maps must return i32: with jax_enable_x64 on (package-wide),
    # a literal python 0 traces as i64 and Mosaic fails to legalize the
    # map's func.return. ``i - i`` stays in the i32 program-id type.
    out = pl.pallas_call(
        _hist_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tile,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((m // LANES, LANES), lambda i: (i - i, i - i)),
        out_shape=jax.ShapeDtypeStruct((m // LANES, LANES), weights.dtype),
        interpret=_interpret(),
    )(idx, weights)
    return out.reshape(m)


def histogram_update(counts, idx, weights=None, tile: int = DEFAULT_TILE):
    """counts[m] += scatter(idx, weights) via the VMEM-resident kernel."""
    m = counts.shape[-1] if counts.ndim == 1 else counts.size
    flat = counts.reshape(-1)
    if weights is None:
        weights = jnp.ones(idx.shape, flat.dtype)
    delta = flat_histogram(idx, weights.astype(flat.dtype), int(m), tile)
    return (flat + delta).reshape(counts.shape)


def cms_update(counts, idx_rows, weights=None, tile: int = DEFAULT_TILE):
    """Count-min update: counts [D, W] += per-row scatter of idx_rows
    [D, N] (bucket per key per row). One flat histogram over D*W."""
    d, w = counts.shape
    n = idx_rows.shape[1]
    flat_idx = (
        idx_rows + (jnp.arange(d, dtype=jnp.int32) * w)[:, None]
    ).reshape(-1)
    flat_idx = jnp.where(idx_rows.reshape(-1) >= 0, flat_idx, -1)
    if weights is None:
        wts = jnp.ones(d * n, counts.dtype)
    else:
        wts = jnp.broadcast_to(weights, (d, n)).reshape(-1).astype(counts.dtype)
    delta = flat_histogram(flat_idx, wts, d * w, tile)
    return counts + delta.reshape(d, w)


# ---------------------------------------------------------------------------
# Fused index-arena claim + entry scatter (r12)
# ---------------------------------------------------------------------------

# VMEM budget for the arena kernel's resident state: 6 input + 6 output
# entry planes + the cursor histogram, all i32. ~10 MB leaves headroom
# for the SMEM row tiles and compiler temporaries inside the ~16 MB
# core budget.
ARENA_VMEM_BUDGET = 10 << 20
# SMEM row tile. 1024 matches the T(1024) layout XLA gives 1-D i32
# operands; Mosaic refused 512 ("XLA layout does not match Mosaic
# layout") the first time the kernel met the TPU compiler (PR 22).
ARENA_TILE = 1024


def arena_scatter_supported(total_slots: int, n_buckets: int) -> bool:
    """True when the unified arena fits the kernel's VMEM-resident
    model (the r6 roofline boundary: past this, any kernel degenerates
    to the same random-access HBM DMA XLA already issues). Also guards
    the kernel's i32 slot arithmetic."""
    if total_slots <= 0 or total_slots >= (1 << 31):
        return False
    if n_buckets <= 0 or n_buckets >= (1 << 31):
        return False
    sp = -(-total_slots // LANES) * LANES
    bp = -(-n_buckets // LANES) * LANES
    return (12 * sp + bp) * 4 <= ARENA_VMEM_BUDGET


def _arena_kernel(bucket_ref, base_ref, slot0_ref, dmask_ref, valid_ref,
                  v0, v1, v2, v3, v4, v5,
                  e0, e1, e2, e3, e4, e5,
                  o0, o1, o2, o3, o4, o5,
                  cur_ref):
    # Same Mosaic discipline as _hist_kernel: per-row scalars from
    # rank-1 SMEM blocks, VMEM state updated by row-granular RMWs with
    # one-hot lane selects (dynamic SUBLANE indexing is legal, dynamic
    # LANE indexing is not), i32 everywhere (no 64-bit lowering on TPU
    # pallas — the arena travels as bit-planes).
    i = pl.program_id(0)
    tile = bucket_ref.shape[0]
    vins = (v0, v1, v2, v3, v4, v5)
    eins = (e0, e1, e2, e3, e4, e5)
    outs = (o0, o1, o2, o3, o4, o5)

    @pl.when(i == 0)
    def _():
        # The cursor walk starts from zero: ``base`` already carries
        # each row's bucket cursor (pos low word), so the kernel only
        # counts THIS launch's same-bucket predecessors — exactly the
        # FIFO rank the argsort/counting paths compute.
        cur_ref[:, :] = jnp.zeros_like(cur_ref)
        for e, o in zip(eins, outs):
            o[:, :] = e[:, :]

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(t, carry):
        @pl.when(valid_ref[t] != 0)
        def _():
            b = bucket_ref[t]
            at_b = lane == (b & 127)
            crow = cur_ref[pl.ds(b >> 7, 1), :]
            # Lane extract by masked MAX, not sum: cursors are >= 0, and
            # an i32 sum promotes to i64 under the package's x64 mode,
            # which Mosaic refuses ("64-bit types are not supported").
            c = jnp.max(jnp.where(at_b, crow, jnp.int32(0)))
            cur_ref[pl.ds(b >> 7, 1), :] = crow + at_b.astype(jnp.int32)
            # The claim: this row's FIFO slot, from the bucket's live
            # cursor. Writes land in arrival order, so an in-batch
            # overflow row is overwritten by its newest same-slot
            # successor — the final arena equals the rank-gated unique
            # scatter's bitwise (store/device._index_write).
            slot = slot0_ref[t] + ((base_ref[t] + c) & dmask_ref[t])
            hit = lane == (slot & 127)
            for v, o in zip(vins, outs):
                row = o[pl.ds(slot >> 7, 1), :]
                o[pl.ds(slot >> 7, 1), :] = jnp.where(hit, v[t], row)

        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(tile), body, None)


@functools.partial(jax.jit, static_argnames=("n_buckets", "tile"))
def arena_claim_scatter(entries, bucket, base, slot0, depth, vals,
                        valid, n_buckets: int, tile: int = ARENA_TILE):
    """Fused FIFO claim + entry-row scatter over the unified index
    arena, handed over as the store keeps it: ``entries`` is the tuple
    of six [slots] i32 planes (store/device: plane 2c the low word of
    column c, 2c + 1 its high word) and so is the result. Per valid
    row: claim the bucket's next FIFO slot (``slot0 + ((base +
    cursor++) & (depth - 1))``) and store the row's three i64 columns
    (``vals`` [N, 3] i64) into the planes. Grid steps run sequentially on
    a TPU core, so the cursor walk needs no atomics and write order is
    arrival order — the final arena is bitwise-identical to the XLA
    path's rank-gated unique scatter (fuzz-gated by
    tests/test_pallas_kernels.py).

    ``bucket`` must be clipped to [0, n_buckets); ``base`` is each
    row's bucket cursor low word (pos_lo[bucket], already gathered by
    the caller); ``depth`` per-row powers of two; callers check
    ``arena_scatter_supported`` first (whole-arena VMEM residency).
    """
    S = entries[0].shape[0]
    n = bucket.shape[0]
    if n == 0:
        return entries
    sp = -(-S // LANES) * LANES
    bp = -(-n_buckets // LANES) * LANES
    n_tiles = -(-n // tile)
    pad = n_tiles * tile - n
    # Each plane lane-padded to a [rows, LANES] VMEM block, so each
    # kernel write is one contiguous VMEM row RMW.
    planes = [jnp.pad(p, (0, sp - S)).reshape(sp // LANES, LANES)
              for p in entries]
    v = jax.lax.bitcast_convert_type(
        jnp.asarray(vals, jnp.int64), jnp.int32).reshape(n, 6)

    def padi(x, dtype=jnp.int32):
        return jnp.pad(jnp.asarray(x, dtype), (0, pad))

    row_ins = [
        padi(bucket), padi(base), padi(slot0), padi(
            jnp.asarray(depth, jnp.int32) - 1),
        padi(jnp.asarray(valid).astype(jnp.int32)),
    ] + [padi(v[:, j]) for j in range(6)]
    smem = pl.BlockSpec((tile,), lambda i: (i,),
                        memory_space=pltpu.SMEM)
    vblock = pl.BlockSpec((sp // LANES, LANES),
                          lambda i: (i - i, i - i))
    outs = pl.pallas_call(
        _arena_kernel,
        grid=(n_tiles,),
        in_specs=[smem] * 11 + [vblock] * 6,
        out_specs=[vblock] * 6,
        out_shape=[
            jax.ShapeDtypeStruct((sp // LANES, LANES), jnp.int32)
        ] * 6,
        scratch_shapes=[pltpu.VMEM((bp // LANES, LANES), jnp.int32)],
        interpret=_interpret(),
    )(*row_ins, *planes)
    return tuple(o.reshape(sp)[:S] for o in outs)


# ---------------------------------------------------------------------------
# Paged trace-assembly block gather (r19)
# ---------------------------------------------------------------------------

# VMEM model for the page gather: the kernel streams one (W, R) i32
# page block per grid step (double-buffered in/out DMA), so residency
# is a handful of blocks, not the pool — but keep an explicit ceiling
# so absurd page_rows (or a plane count change) degrade to the XLA
# take fallback instead of a Mosaic allocation failure, mirroring the
# arena_claim_scatter gate.
PAGED_GATHER_VMEM_BUDGET = 10 << 20


def paged_gather_supported(capacity: int, page_rows: int,
                           n_cols: int, n_pages_req: int) -> bool:
    """True when the paged trace gather may take the Pallas block
    kernel. Lane alignment: the (W, page_rows) block's last dim must be
    a multiple of 128 and the plane matrix [W, capacity] must tile
    evenly into page blocks. VMEM: ~4 in+out blocks resident
    (double-buffered DMA) under the ceiling."""
    W = 2 * n_cols
    if page_rows % LANES != 0 or capacity % page_rows != 0:
        return False
    if n_pages_req <= 0:
        return False
    return 4 * W * page_rows * 4 <= PAGED_GATHER_VMEM_BUDGET


def _paged_gather_kernel(pages_ref, in_ref, out_ref):
    # One grid step per requested page: the scalar-prefetched page list
    # drives the INPUT block index map (a block-level gather — no
    # in-kernel dynamic slicing, so no Mosaic divisibility proofs
    # beyond the lane-aligned block shape), and the body just forwards
    # the block. Holes (-1 pages, the pad) are clamped to block 0 by
    # the index map and zero-filled here so both gather paths mask
    # identically downstream.
    i = pl.program_id(0)

    @pl.when(pages_ref[i] < 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(pages_ref[i] >= 0)
    def _():
        out_ref[...] = in_ref[...]


@functools.partial(jax.jit, static_argnames=("page_rows",))
def paged_page_gather(planes, pages, page_rows: int):
    """Gather page blocks out of the plane matrix.

    ``planes`` [W, capacity] i32 — the span columns as lo/hi bit-planes
    (W = 2 * n_cols, built by the caller with one free bitcast);
    ``pages`` [K] i32 page ids, -1 for holes. Returns [W, K *
    page_rows] i32: output block i is page ``pages[i]``'s rows (zeros
    for holes). The W axis rides the "second-to-last dim equals the
    array dim" Mosaic block rule, so any lane-aligned page_rows works.
    Callers check ``paged_gather_supported`` first."""
    W, _ = planes.shape
    K = pages.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K,),
        in_specs=[pl.BlockSpec(
            (W, page_rows),
            lambda i, pages: (i - i, jnp.maximum(pages[i], 0)),
        )],
        out_specs=pl.BlockSpec((W, page_rows), lambda i, pages: (i - i, i)),
    )
    return pl.pallas_call(
        _paged_gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, K * page_rows), jnp.int32),
        interpret=_interpret(),
    )(jnp.asarray(pages, jnp.int32), planes)


def scatter_histogram_xla(counts, idx, weights=None):
    """XLA reference path (what store/device.py uses today); kept for
    benchmarking the pallas kernel against on real hardware."""
    flat = counts.reshape(-1)
    m = flat.shape[0]
    if weights is None:
        weights = jnp.ones(idx.shape, flat.dtype)
    safe = jnp.where(idx >= 0, idx, m)
    out = jnp.concatenate([flat, jnp.zeros(1, flat.dtype)])
    out = out.at[safe].add(weights.astype(flat.dtype))
    return out[:m].reshape(counts.shape)
