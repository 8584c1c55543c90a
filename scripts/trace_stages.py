"""What the host was doing while the device was idle, from one capture.

    python scripts/trace_stages.py <profile dir or .xplane.pb> [--gaps N]

Reads a ``POST /debug/profile`` capture with ``jax.profiler.ProfileData``
(no backend comes up) and prints one JSON object:

- ``stages``: per span name of ``obs.stage`` (``<layer>.<stage>``) on
  the host planes: events, seconds, and the seconds as a share of
  ``ingest.call``'s (the call's spans nest on the receiver's thread).
  The profiler keeps a span only if it began AND ended inside the
  capture, so long waits at its edges are missing: shares over a whole
  run come from the sketches on /metrics, not from here;
- ``device``: the traced window, busy seconds, and the idle gaps
  between the device's programs (``XLA Modules`` of ``/device:TPU:0``);
- ``gaps``: the N longest idle gaps, each with the host spans that
  overlapped it (name, host line, seconds of overlap, ``unit``), so a
  gap is named for what the host was doing, not for the program that
  ran before it;
- ``idle_by_span``: every idle second put down to the stage spans that
  overlapped it, widest first (a second under two threads' spans
  counts for both).

By hand, for PERF.md 5; ``benchmark/readers/trace_reduce.py`` is the
benchmark's own reduction and does not read host lines yet.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# obs.stage's names; XLA's own host events (copy.163) look alike
STAGE = re.compile(
    r"^(ingest|collector|store|wal|pipeline|lineage)\.[a-z0-9_]+$")


def newest_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise SystemExit(f"no .xplane.pb under {path}")
    return files[-1]


def read(path: str):
    """(host spans [(name, line, start_ns, dur_ns, stats)], device
    modules [(name, start_ns, dur_ns)])."""
    from jax.profiler import ProfileData

    spans, modules = [], []
    for plane in ProfileData.from_file(newest_xplane(path)).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if STAGE.match(e.name):
                        spans.append((e.name, f"{line.name}#{i}",
                                      e.start_ns, e.duration_ns,
                                      dict(e.stats)))
    return spans, sorted(modules, key=lambda m: m[1])


def reduce(spans, modules, n_gaps: int = 12) -> dict:
    stages = {}
    for name, _, _, dur, _ in spans:
        n, s = stages.get(name, (0, 0.0))
        stages[name] = (n + 1, s + dur / 1e9)
    call_s = stages.get("ingest.call", (0, 0.0))[1]
    out = {"stages": {
        name: {"events": n, "seconds": s,
               "share_of_call": (s / call_s if call_s else None)}
        for name, (n, s) in sorted(stages.items(), key=lambda kv: -kv[1][1])}}
    gaps = []
    for (n0, s0, d0), (_, s1, _) in zip(modules, modules[1:]):
        if s1 > s0 + d0:
            gaps.append((s1 - s0 - d0, s0 + d0, s1, n0))
    if modules:
        lo, hi = modules[0][1], max(s + d for _, s, d in modules)
        out["device"] = {
            "window_s": (hi - lo) / 1e9,
            "program_s": sum(d for _, _, d in modules) / 1e9,
            "programs": len(modules),
            "idle_s": sum(g[0] for g in gaps) / 1e9,
            "idle_gaps": len(gaps)}
    by_span = {}
    detail = []
    for k, (length, a, b, after) in enumerate(
            sorted(gaps, reverse=True)):
        over = []
        for name, line, s, d, stats in spans:
            lap = min(b, s + d) - max(a, s)
            if lap > 0:
                by_span[name] = by_span.get(name, 0.0) + lap / 1e9
                over.append({"span": name, "line": line,
                             "overlap_s": lap / 1e9,
                             **({"unit": stats["unit"]}
                                if "unit" in stats else {})})
        if k < n_gaps:
            detail.append({
                "idle_s": length / 1e9, "after": after[:60],
                "at_s": (a - modules[0][1]) / 1e9,
                "host": sorted(over, key=lambda o: -o["overlap_s"])[:8]})
    out["gaps"] = detail
    out["idle_by_span"] = sorted(
        ([k, v] for k, v in by_span.items()), key=lambda kv: -kv[1])
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("path")
    p.add_argument("--gaps", type=int, default=12)
    args = p.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # reading needs no chip
    json.dump(reduce(*read(args.path), n_gaps=args.gaps), sys.stdout,
              indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
