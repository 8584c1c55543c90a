"""The ONE home of the fused-ingest StableHLO census ceilings.

Per-kernel overhead dominates the target device class (NOTES_r03 §3),
so the scatter/gather/sort counts of the compiled ingest step are the
portable proxy for its TPU cost — the r6 unified index arena exists to
hold them down, and the tier-1 lane gates them every CI run. These
ceilings used to live as three hard-coded copies (bench_smoke docs,
the tier-1 test, the notes); a path change now updates exactly one
number here, consumed by ``scripts/bench_smoke.py`` and
``tests/test_bench_smoke.py``.

r19 restructures the constants into a LOWERING TABLE: the base counts
plus one explicit gated bump per optional layout/feature, so a new
layout cannot ride ungated — adding one REQUIRES adding its ``+NAME``
row here, and ``expected_census`` composes any feature combination
(bench_smoke's windows and paged phases both gate at exact equality
against the composed row).

History of the measured counts at the smoke shapes:

- r5 split index design: 101 scatters / 6 sorts / 80 gathers;
- r6 unified arena:       95 / 5 / 79;
- r12 counting-sort rank:  95 / 4 / 79 — the ``_fifo_ranks`` argsort
  is replaced by a segmented counting rank (one duplicate-index
  scatter-add + cumsum + one gather, spending exactly the scatter and
  gather the argsort path's unsort freed), deleting the last hot-path
  ``stablehlo.sort`` the index write owned. The argsort path remains
  selectable (``StoreConfig.rank_path``) and bitwise-identical; its
  lowering sits at ARGSORT_STEP_SORTS.
- r13 windowed arena:     +5 scatters / +0 sorts / +2 gathers — the
  EXPLICIT GATED BUMP that buys the windowed Moments-sketch
  (service × time-bucket) cell grid inside the fused step
  (aggregate/windows.py) when ``window_seconds > 0``: +2 scatters
  +1 gather for the exact epoch plane-war, +1 i32 count scatter (3P
  rows), +1 i64 power-sum scatter (4P rows — the only
  serialized-class scatter the feature adds), +1 i32 min/max
  scatter-max (2P rows), +1 gather for the live-epoch check. The
  arena is OPT-IN at the library layer (``StoreConfig`` default 0 —
  the daemon turns it on via ``--window-seconds``), so the BASE
  lowering stays 95/4/79 and the window-on lowering sits exactly at
  BASE + WINDOW_BUMP (bench_smoke's windows phase gates both).
- r19 paged layout:       +2 scatters / +0 sorts / +2 gathers —
  ``layout="paged"`` (store/paged): the reclaimed-page row_gid
  invalidation is ONE i64 ring write (= 2 i32 plane scatters through
  the same _uset discipline as every other plane pair), and the
  side-ring index segments gather their owning span's planner gid
  from the batch column (+1 gather each for ann/bann) instead of
  deriving it from write_pos arithmetic. Slot/gid assignment itself
  moves HOST-side into the page planner, so the step spends nothing
  on allocation. Additive with the window bump (measured: paged+win
  == BASE + WINDOW + PAGED exactly).

- PR 26 arena planes:     +0 scatters / +0 sorts / +5 gathers — the
  index arena became six [slots] i32 bit-plane leaves (device "the
  index arena's plane form"), so the displaced-entry read is no longer
  ONE [N, 3] i64 row gather but six word gathers of the rows each
  section needs (ts lo/hi on the candidate prefix, gid lo/hi from the
  keyed slice on, verify lo/hi on the keyed slice): 79 -> 84. What it
  bought: the entry write's six 1-D scatters now land in place in
  donated leaves; the 29 whole-arena ops beside them (ARENA_SWEEP_OPS
  below) are gone, and with them 3.3 GB of step temporaries at the
  2^22 ring (chipless compile, PR 26: 60 MB).

- PR 30 ring windows:     -41 scatters / +0 sorts / +0 gathers on BASE,
  +27 scatters on +PAGED — a launch writes the span, annotation and
  binary rings at consecutive slots, so their 35 column writes (27 + 7
  + 7 scatters: two plane scatters an i64 column, one for the others)
  are two slice updates each (device ``_ring_write``) and no scatter:
  95 -> 54. The pending ring's eight stay, now as 2048-row packing
  scatters (its rows go to the front of a batch-sized buffer by their
  running count, then in as a window). The paged layout's span rows
  keep the planner's slots and so ``_uset``: the span ring's 27
  scatters moved from BASE into +PAGED (2 -> 29; paged on is 83 as
  before). What it bought: the 162 whole-column ops beside those
  scatters (RING_SWEEP_OPS below) are gone, a quarter of the step on
  the chip at the 2^22 ring.

Raise a ceiling only with a note here explaining what bought the
extra launches.
"""

import re

# The per-layout lowering table: (scatters, sorts, gathers) — "BASE"
# is the default ring/window-off lowering; every "+NAME" row is the
# explicit gated bump one optional feature may spend inside the fused
# step. New layouts MUST add a row (test_bench_smoke gates the table's
# composed rows at exact equality, so an ungated path shows up as a
# census mismatch, not a silent regression).
LOWERING_TABLE = {
    "BASE": (54, 4, 84),
    "+WINDOW": (5, 0, 2),   # r13 windowed Moments-sketch arena
    "+PAGED": (29, 0, 2),   # r19 paged span layout (PR 30: + its 27
                            # span-ring scatters, off BASE)
}


def expected_census(*bumps: str):
    """(scatters, sorts, gathers) ceiling for BASE plus the named
    bumps, e.g. ``expected_census("+WINDOW", "+PAGED")``. Unknown bump
    names raise — the "can't ride ungated" contract."""
    s, o, g = LOWERING_TABLE["BASE"]
    for b in bumps:
        if b == "BASE":
            continue
        bs, bo, bg = LOWERING_TABLE[b]
        s, o, g = s + bs, o + bo, g + bg
    return s, o, g


# Fused-step BASE ceilings: the default (window-off) lowering, gated
# in tier-1 against the main smoke stream (tests/test_bench_smoke.py).
BASE_STEP_SCATTERS, BASE_STEP_SORTS, BASE_STEP_GATHERS = (
    LOWERING_TABLE["BASE"])

# The r13 windowed-arena bump (window_seconds > 0): the gated extra
# launches the feature is allowed to spend inside the fused step.
WINDOW_BUMP_SCATTERS, _, WINDOW_BUMP_GATHERS = LOWERING_TABLE["+WINDOW"]

# The r19 paged-layout bump (layout="paged"): see the history note.
PAGED_BUMP_SCATTERS, _, PAGED_BUMP_GATHERS = LOWERING_TABLE["+PAGED"]

# Overall ceilings — every optional path engaged (window + paged);
# bench_smoke's feature phases gate each on-lowering at EXACTLY its
# composed table row, so these are pure upper bounds for coarse gates.
MAX_STEP_SCATTERS, MAX_STEP_SORTS, MAX_STEP_GATHERS = expected_census(
    "+WINDOW", "+PAGED")

# The argsort rank path's sort count — the pre-r12 ceiling, still the
# expected lowering when rank_path="argsort" (or the wm_shift == 0 /
# scratch-infeasible fallbacks) is active.
ARGSORT_STEP_SORTS = 5

# Ops of the fused step that pass over a whole index-arena plane, other
# than the gathers and scatters that touch the batch's rows
# (stablehlo_arena_sweeps below; tests/test_arena_planes.py gates every
# layout, window on and off). PR 26: the arena is six 1-D i32 plane
# leaves precisely so that this is 0 — held as one [slots, 3] i64 leaf
# the entry write cost 29 such ops at the smoke shapes (bitcasts, strided
# slices, stacks), 60 % of the step on the chip at a 1.99 GB arena.
# Nothing buys a raise: a new reader gathers words at the slots it
# probes; a new writer scatters into the donated planes.
ARENA_SWEEP_OPS = 0


def stablehlo_arena_sweeps(stablehlo_text: str, slots: int) -> list:
    """Ops of a StableHLO lowering that pass over a whole arena plane:
    every op other than ``gather``/``scatter`` (and the entry
    function's own arguments and results) with an operand or result
    that has a dimension of ``slots`` (= ``idx_layout[2]``, chosen by
    the caller to coincide with no other dimension). A step that costs
    the batch and not the arena has none (ARENA_SWEEP_OPS). Returns the
    offending op names, in order."""
    return _stablehlo_sweeps(stablehlo_text, {slots}, ("gather", "scatter"))


# Ops of the fused step that pass over a whole ring column (span,
# annotation, binary or pending ring), other than the ops that touch the
# batch's rows: gathers, scatters and, since PR 30, the window reads and
# writes (stablehlo_ring_sweeps below; tests/test_ring_window.py gates
# ring and paged layouts, window arena on and off). A launch writes a
# ring at consecutive slots, so its write is two slice updates on the
# donated leaf in its own dtype; scattered into through ``_uset`` an i64
# column was bitcast to planes, sliced, scattered and stacked back: 162
# whole-column ops at the gate's shapes (nine for each of 18 i64
# columns) and a quarter of the step on the chip at the 2^22 ring
# (PERF.md 6, PR 30). Exempt, by the caller's choice of dimensions:
# ``span_tab`` (hashed slots, an [H, 2] leaf whose planes are still
# sliced and stacked: its cure is the leaf's form) and the paged
# layout's span ring (the planner's slots, ``_uset``). Nothing buys a
# raise: consecutive slots are written as a window; hashed slots want
# the leaf in plane form.
RING_SWEEP_OPS = 0


def stablehlo_ring_sweeps(stablehlo_text: str, dims) -> list:
    """Ops of a StableHLO lowering that pass over a whole ring column:
    every op other than ``gather``, ``scatter``, ``dynamic_slice`` and
    ``dynamic_update_slice`` (and the entry function's own arguments
    and results) with an operand or result that has a dimension in
    ``dims`` (the rings' capacities, chosen by the caller to coincide
    with no other dimension, ``span_tab``'s among them). Returns the
    offending op names, in order (RING_SWEEP_OPS)."""
    return _stablehlo_sweeps(
        stablehlo_text, set(dims),
        ("gather", "scatter", "dynamic_slice", "dynamic_update_slice"))


def _stablehlo_sweeps(stablehlo_text: str, sizes: set, row_ops) -> list:
    sizes = {str(d) for d in sizes}
    dims = re.compile(r"tensor<((?:\d+x)+)[a-z]")
    name = re.compile(r'"?\b(?:stablehlo|chlo|func)\.([a-z_]+)"?|\b(call) @')
    out, open_ops = [], []
    for line in stablehlo_text.splitlines():
        ln = line.strip()
        m = name.search(ln)
        op = (m.group(1) or m.group(2)) if m else None
        if ln.startswith("return "):
            op = "return"
        if ln.startswith("})"):
            op = open_ops.pop() if open_ops else None
        elif ln.endswith("({"):
            open_ops.append(op)
            continue  # a region op's types come on its closing line
        if op in row_ops or op == "return" or (
                op == "func" and "public @main" in ln):
            continue
        if any(sizes & set(d.split("x")) for d in dims.findall(ln)):
            out.append(op or ln[:60])
    return out
