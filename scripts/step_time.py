"""Device time of the fused ingest step against the ring's capacity.

The one-number experiment of ROADMAP S1: the daemon's geometry
(``main/example.py``: side rings from ``_side_rings``, window arena on)
at each ``--capacity``, one launch shape (the benchmark's pads for a
2048-span ``Log`` call), ``--steps`` donated steps chained back to back
and one barrier at the end. A step whose cost follows the batch reads
the same at every capacity; one that sweeps a state leaf grows with it.

Usage (the chip must be otherwise idle; fails without one):
    python scripts/step_time.py --capacity 1048576,4194304

Prints one JSON line per capacity. ``ms_per_step`` is wall time over
the chained steps; ``enqueue_ms_per_step`` is what the host needed to
launch them (where the two are close, the host bound the run and
``ms_per_step`` is an upper bound of the device's).
"""

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

PADS = (2048, 16384, 4096)  # spans, annotations, binary annotations
SPANS_PER_TRACE = 8


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--capacity", default="1048576,4194304")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batches", type=int, default=8,
                    help="distinct batches cycled through (new trace "
                         "ids each, so index buckets differ by step)")
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
    batches = [
        jax.device_put(dev.make_device_batch(
            *gen.next_batch(PADS[0] // SPANS_PER_TRACE), *PADS))
        for _ in range(args.batches)
    ]
    for cap in (int(x) for x in args.capacity.split(",")):
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
            "capacity": cap, "pads": PADS, "steps": args.steps,
            "arena_slots": config.idx_layout[2],
            "ms_per_step": (t2 - t0) / args.steps * 1e3,
            "enqueue_ms_per_step": (t1 - t0) / args.steps * 1e3,
            "warmup_s": warm_s,
            "device": f"{d.platform} {d.device_kind}",
            "paths": dev.active_paths(config),
        }), flush=True)
        del state


if __name__ == "__main__":
    main()
