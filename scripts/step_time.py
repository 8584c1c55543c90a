"""Device time of the fused ingest step against the ring's capacity.

The one-number experiment of ROADMAP S1: the daemon's geometry
(``main/example.py``: side rings from ``_side_rings``, window arena on)
at each ``--capacity``, at each launch shape ``--pads`` names (none:
the pads the store gives a 2048-span ``Log`` call of the benchmark),
``--steps`` donated steps chained back to back and one barrier at the
end. A step whose cost follows the batch reads the same at every
capacity; one that sweeps a state leaf grows with it; one whose cost is
the launch's rows follows the pads.

Usage (the chip must be otherwise idle; fails without one):
    python scripts/step_time.py --capacity 1048576,4194304
    python scripts/step_time.py --capacity 4194304 \
        --pads 2048,16384,4096 --pads 2048,12288,4096

Prints one JSON line per capacity and shape. ``ms_per_step`` is wall
time over the chained steps; ``enqueue_ms_per_step`` is what the host
needed to launch them (where the two are close, the host bound the run
and ``ms_per_step`` is an upper bound of the device's).

The batches have the shape of the benchmark's stream
(``benchmark/gen.py``: 6 annotations a span on two hosts, 2 of them
user annotations, and 2 binary annotations), so a launch carries 12,288
valid annotation rows and 4,096 binary rows whatever its pads, as a
served 2048-span ``Log`` call does. ``--pads S,A,B`` (repeatable, run
in the order given) pads them to another shape, each at least the
valid rows: 2048,16384,4096 is the power-of-two shape the daemon
launched until PR 35.

``--profile DIR`` captures ``--profile-steps`` more steps at each
capacity and shape with the JAX profiler and writes
``DIR/ops_<capacity>_<S>-<A>-<B>.json``: the device time of
``jit_ingest_step`` a run and of every device op summed by name (the
name carries the result shape), longest first. Run it from another
checkout's root to time that checkout's step.
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, ".")

# A benchmark Log call's valid rows: spans, annotations, binary
# annotations. The launch's pads are the store's own for these counts
# (``served_pads``) unless ``--pads`` gives others.
ROWS = (2048, 12288, 4096)
SPANS_PER_TRACE = 8


def served_pads():
    """The shape ``TpuSpanStore._pad_unit`` launches ``ROWS`` at."""
    from zipkin_tpu.store.tpu import _next_pow2, _pad_rows

    return (_next_pow2(ROWS[0]), _pad_rows(ROWS[1]), _pad_rows(ROWS[2]))


def benchmark_shape(gen, n_traces):
    """A ``ColumnarTraceGen`` batch (2 annotations and 1 binary
    annotation a span) widened to the benchmark's rows a span: cs and cr
    on the caller's host (the parent's service; a root calls itself),
    sr, two user annotations and ss on the span's own; two binary
    annotations on the span's own host."""
    from zipkin_tpu.columnar.schema import SpanBatch

    base, name_lc, indexable = gen.next_batch(n_traces)
    n = base.n_spans
    spt = gen.spans_per_trace
    j = np.arange(n) % spt
    caller = np.where(j > 0, np.arange(n) - j + (j - 1) // 2, np.arange(n))
    ep_of = dict(zip(gen.service_ids.tolist(), gen.endpoint_ids.tolist()))
    own_ep = base.ann_endpoint_id[0::2]
    caller_svc = base.service_id[caller]
    caller_ep = np.array([ep_of[s] for s in caller_svc.tolist()], np.int32)
    word = gen.dicts.annotations.encode
    user = np.array([word(f"word-{i:03d}") for i in range(256)], np.int32)
    mid = base.ts_first + base.duration // 2
    rows = (  # (ts, value id, service, endpoint) of a span's six rows
        (base.ts_cs, 0, caller_svc, caller_ep),
        (base.ts_sr, 2, base.service_id, own_ep),
        (mid, user[gen.rng.integers(0, len(user), n)], base.service_id,
         own_ep),
        (mid + 1, gen.custom_ann_id, base.service_id, own_ep),
        (base.ts_ss, 3, base.service_id, own_ep),
        (base.ts_cr, 1, caller_svc, caller_ep),
    )
    wide = SpanBatch.empty(n, len(rows) * n, 2 * n)
    for f in ("trace_id", "span_id", "parent_id", "name_id", "service_id",
              "flags", "ts_cs", "ts_cr", "ts_sr", "ts_ss", "ts_first",
              "ts_last", "duration"):
        getattr(wide, f)[:] = getattr(base, f)
    k = len(rows)
    for i, (ts, value, svc, ep) in enumerate(rows):
        wide.ann_span_idx[i::k] = np.arange(n)
        wide.ann_ts[i::k] = ts
        wide.ann_value_id[i::k] = value
        wide.ann_service_id[i::k] = svc
        wide.ann_endpoint_id[i::k] = ep
    bkey = gen.dicts.binary_keys.encode
    bval = gen.dicts.binary_values.encode
    keys = np.array([bkey(f"key-{i:03d}") for i in range(256)], np.int32)
    vals = np.array([bval(b"value-%03d" % i) for i in range(256)], np.int32)
    for f in ("bann_span_idx", "bann_key_id", "bann_value_id", "bann_type",
              "bann_service_id", "bann_endpoint_id"):
        getattr(wide, f)[0::2] = getattr(base, f)
        getattr(wide, f)[1::2] = getattr(base, f)
    wide.bann_key_id[0::2] = keys[gen.rng.integers(0, len(keys), n)]
    wide.bann_value_id[0::2] = vals[gen.rng.integers(0, len(vals), n)]
    return wide, name_lc, indexable


def ops_by_name(profile_dir):
    """{"step_ms": [...], "ops": [[name, runs, seconds], ...]} of the
    newest capture under ``profile_dir``: the device plane's
    ``jit_ingest_step`` runs and every device op summed by name."""
    from jax.profiler import ProfileData

    from trace_stages import DEVICE_PLANE, newest_xplane  # scripts/

    steps, ops = [], {}
    for plane in ProfileData.from_file(newest_xplane(profile_dir)).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if line.name == "XLA Modules" and "ingest_step" in e.name:
                    steps.append(e.duration_ns / 1e6)
                elif line.name == "XLA Ops":
                    runs, s = ops.get(e.name, (0, 0.0))
                    ops[e.name] = (runs + 1, s + e.duration_ns / 1e9)
    return {"step_ms": steps,
            "ops": sorted(([k, r, s] for k, (r, s) in ops.items()),
                          key=lambda row: -row[2])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--capacity", default="1048576,4194304")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--pads", action="append", default=[],
                    help="S,A,B: a launch shape (repeatable; none: "
                         "the store's own pads for the benchmark's "
                         "rows)")
    ap.add_argument("--batches", type=int, default=8,
                    help="distinct batches cycled through (new trace "
                         "ids each, so index buckets differ by step)")
    ap.add_argument("--profile", default="",
                    help="directory for ops_<capacity>_<S>-<A>-<B>.json")
    ap.add_argument("--profile-steps", type=int, default=8)
    args = ap.parse_args()

    import jax

    from zipkin_tpu.columnar.dictionary import DictionarySet
    from zipkin_tpu.main.example import _side_rings
    from zipkin_tpu.store import device as dev
    from zipkin_tpu.tracegen.gen import ColumnarTraceGen

    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(f"no chip: {d.platform}")
    gen = ColumnarTraceGen(DictionarySet(), n_services=64,
                           spans_per_trace=SPANS_PER_TRACE, topology=True)
    shapes = [tuple(int(x) for x in p.split(",")) for p in args.pads]
    host = [benchmark_shape(gen, ROWS[0] // SPANS_PER_TRACE)
            for _ in range(args.batches)]
    runs = [(int(cap), pads) for cap in args.capacity.split(",")
            for pads in shapes or [served_pads()]]
    for cap, pads in runs:
        batches = [jax.device_put(dev.make_device_batch(*b, *pads))
                   for b in host]
        valid = {f: int(getattr(batches[0], f))
                 for f in ("n_spans", "n_anns", "n_banns")}
        config = dev.StoreConfig(capacity=cap, **_side_rings(cap),
                                 window_seconds=60, window_buckets=64)
        state = dev.init_state(config)
        t0 = time.perf_counter()
        for i in range(3):  # compile (or load) and settle
            state = dev.ingest_step(state, batches[i % len(batches)])
        jax.block_until_ready(state.write_pos)
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for i in range(args.steps):
            state = dev.ingest_step(state, batches[i % len(batches)])
        t1 = time.perf_counter()
        jax.block_until_ready(state.write_pos)
        t2 = time.perf_counter()
        print(json.dumps({
            "capacity": cap, "pads": pads, "valid_rows": valid,
            "steps": args.steps,
            "arena_slots": config.idx_layout[2],
            "ms_per_step": (t2 - t0) / args.steps * 1e3,
            "enqueue_ms_per_step": (t1 - t0) / args.steps * 1e3,
            "warmup_s": warm_s,
            "device": f"{d.platform} {d.device_kind}",
            "paths": dev.active_paths(config),
        }), flush=True)
        if args.profile:
            with tempfile.TemporaryDirectory() as raw:
                jax.profiler.start_trace(raw)
                for i in range(args.profile_steps):
                    state = dev.ingest_step(state, batches[i % len(batches)])
                jax.block_until_ready(state.write_pos)
                jax.profiler.stop_trace()
                table = ops_by_name(raw)
            os.makedirs(args.profile, exist_ok=True)
            name = f"ops_{cap}_{'-'.join(map(str, pads))}.json"
            with open(os.path.join(args.profile, name), "w") as f:
                json.dump({"capacity": cap, "pads": pads, **table}, f)
            print(json.dumps({
                "capacity": cap, "pads": pads,
                "profiled_steps": len(table["step_ms"]),
                "device_ms_per_step": float(np.mean(table["step_ms"])),
            }), flush=True)
        del state, batches


if __name__ == "__main__":
    main()
