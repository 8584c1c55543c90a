"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Read in the parent with ``jax.profiler.ProfileData`` (no backend comes
up). A device plane is named ``/device:TPU:<n>``; its line ``XLA
Modules`` holds one event per executed program, named
``<module>(<fingerprint>)``, and ``XLA Ops`` one per operation. Busy
time is the union of the operation intervals (the module intervals
where a trace has no operation line), averaged over the device planes.
Every number is reckoned per chip: a program that runs on n chips at
once (one launch, one event on every plane) counts as one run of the
mean plane's length, and seconds are the mean plane's. One plane in,
the numbers are that plane's.
A metric of ``{"reader": "trace"}`` picks a ``stat`` below.
"""

from __future__ import annotations

import glob
import json
import os
import re

import prom_delta
import roofline

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def union_s(intervals) -> float:
    """Seconds covered by [(start_ns, end_ns)], overlaps counted once."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        elif b > cur_b:
            cur_b = b
    if cur_b is not None:
        total += cur_b - cur_a
    return total / 1e9


def _top10(seconds_by_name: dict) -> list:
    return [[k, v] for k, v in sorted(
        seconds_by_name.items(), key=lambda kv: -kv[1])[:10]]


class Trace:
    def __init__(self, planes: dict):
        """planes: {plane name: {"modules": [(name, start, dur)],
        "ops": [(name, start, dur)]}} of the device planes. The traced
        window is what the device planes span, first event to last: the
        capture's own start and stop stall the host for seconds (the
        python tracer), and the device records nothing then."""
        self.planes = planes
        busy, lo, hi = [], None, None
        for p in planes.values():
            ev = p["ops"] or p["modules"]
            busy.append(union_s((s, s + d) for _, s, d in ev))
            for _, s, d in p["ops"] + p["modules"]:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
        self.window_s = (hi - lo) / 1e9 if busy else 0.0
        self.busy_s = sum(busy) / len(busy) if busy else 0.0

    def module_events(self, patterns) -> list:
        """[(name, start_ns, dur_ns)] of the programs whose module name
        matches any pattern, over all device planes."""
        rx = [re.compile(p) for p in patterns]
        return [e for p in self.planes.values() for e in p["modules"]
                if any(r.search(e[0]) for r in rx)]

    def module_s(self, patterns) -> float:
        """Seconds these programs ran, on the mean plane."""
        n = max(1, len(self.planes))
        return sum(d for _, _, d in self.module_events(patterns)) / 1e9 / n

    def breakdown(self) -> dict:
        """Seconds per plane (the mean over the device planes, as
        ``busy_s`` is), by operation and by the program an idle gap
        came after: on four chips an operation's seconds are what one
        chip spent in it, not four chips' sum."""
        ops, gaps = {}, {}
        n = len(self.planes)
        for p in self.planes.values():
            for name, _, d in (p["ops"] or p["modules"]):
                name = name[:160]
                ops[name] = ops.get(name, 0.0) + d / 1e9 / n
            mods = sorted(p["modules"], key=lambda e: e[1])
            for (n0, s0, d0), (_, s1, _) in zip(mods, mods[1:]):
                if s1 > s0 + d0:
                    key = f"after {n0}"
                    gaps[key] = gaps.get(key, 0.0) + (s1 - s0 - d0) / 1e9 / n
        return {"device_ops": _top10(ops), "idle_gaps": _top10(gaps)}


def load(profile_dir: str):
    """The newest .xplane.pb under ``profile_dir`` as a Trace; None
    where it holds no device plane (a CPU rehearsal)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {profile_dir}")
    return reduce_planes(ProfileData.from_file(files[-1]))


def reduce_planes(data):
    planes = {}
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        keep = {"modules": [], "ops": []}
        for line in plane.lines:
            which = {MODULE_LINE: "modules", OP_LINE: "ops"}.get(line.name)
            for e in line.events:
                s, d = float(e.start_ns), float(e.duration_ns)
                if is_dev and which:
                    keep[which].append(
                        (_FINGERPRINT.sub("", e.name), s, d))
        if is_dev and (keep["modules"] or keep["ops"]):
            planes[plane.name] = keep
    if not planes:
        return None
    return Trace(planes)


def dump(trace, path: str, keep_modules: int = 3) -> None:
    """A text summary to look at by hand."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        if trace is None:
            f.write("no device plane\n")
            return
        f.write(f"window_s {trace.window_s} busy_s {trace.busy_s}\n")
        for name, p in trace.planes.items():
            f.write(f"PLANE {name}: {len(p['modules'])} module events, "
                    f"{len(p['ops'])} op events\n")
            agg = {}
            for n, _, d in p["modules"]:
                c, t = agg.get(n, (0, 0.0))
                agg[n] = (c + 1, t + d / 1e9)
            for n, (c, t) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
                f.write(f"  module {n}: {c} runs, {t:.6f}s\n")
            for n, s, d in sorted(p["modules"], key=lambda e: e[1])[:60]:
                f.write(f"  at {s / 1e9:.6f}s for {d / 1e9:.6f}s {n}\n")
    # a cut-down copy for tests/recorded_trace.json: the first modules
    # of each plane with the operations inside their span
    cut = {}
    for name, p in trace.planes.items():
        mods = sorted(p["modules"], key=lambda e: e[1])[:keep_modules]
        if not mods:
            continue
        lo, hi = mods[0][1], mods[-1][1] + mods[-1][2]
        cut[name] = {
            "modules": [[n, s - lo, d] for n, s, d in mods],
            "ops": [[n[:40], s - lo, d] for n, s, d in p["ops"]
                    if lo <= s and s + d <= hi]}
    with open(path + ".cut.json", "w") as f:
        json.dump({"planes": cut}, f)


# -- the reader: one per-layer metric from a Trace ---------------------------


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace")
    if trace is None:
        return None
    stat = spec["stat"]
    if stat == "idle_pct":
        if trace.window_s <= 0:
            return None
        return 100.0 * (1.0 - trace.busy_s / trace.window_s)
    events = trace.module_events(spec["patterns"])
    if not events:
        return None
    seconds = trace.module_s(spec["patterns"])  # on the mean plane
    if stat == "module_ms_per_unit":
        # device ms of these programs per unit of work (a read, a
        # thousand spans): the units the traced window held are the whole
        # window's count scaled to the traced window's length, since the
        # client cannot see which of its requests fell inside the capture.
        count = ctx["client"].get(spec["per"])
        whole = ctx["client"].get("window_s")
        if not count or not whole or trace.window_s <= 0:
            return None
        units = count * trace.window_s / whole / spec.get("per_unit", 1.0)
        return 1e3 * seconds / units
    n_planes = len(trace.planes)
    if stat == "module_ms_per_event_unit":
        # device ms of one run of these programs on one chip (every
        # plane's events over every plane's seconds), times runs per
        # unit over the whole window (from the program's own launch
        # counter: a launch over n chips is one run)
        runs_per_unit = prom_delta.read(spec["events_per_unit"], ctx)
        if runs_per_unit is None:
            return None
        total_s = sum(d for _, _, d in events) / 1e9
        return 1e3 * total_s / len(events) * runs_per_unit
    if stat == "hbm_roofline_pct":
        with open(os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "peaks.json")) as f:
            peaks = json.load(f)["device_kinds"]
        kind = ctx["device_kind"]
        if kind not in peaks:
            raise KeyError(f"device kind {kind!r} is not in peaks.json")
        # the bytes once per launch (a launch is one event on every
        # plane), over all the planes' peak and the launch's seconds
        need = (getattr(roofline, spec["bytes"])(ctx["traffic"])
                * len(events) / n_planes)
        least_s = need / (peaks[kind]["hbm_bytes_per_s"] * n_planes)
        return 100.0 * least_s / seconds
    raise ValueError(f"unknown trace stat {stat!r}")
