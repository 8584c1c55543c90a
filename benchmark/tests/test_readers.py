"""The readers' arithmetic: prom_delta on two canned scrapes, the trace
reduction on a small recorded trace, roofline bytes for one
hand-worked batch."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "readers"))

import prom_delta  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402


def test_prom_delta_on_two_canned_scrapes():
    before = {"wal_sum": 1.0, 'serve_sum{tier="a"}': 2.0,
              'serve_sum{tier="b"}': 1.0, 'serve_count{tier="a"}': 10.0,
              'serve_count{tier="b"}': 5.0}
    after = {"wal_sum": 1.5, 'serve_sum{tier="a"}': 3.0,
             'serve_sum{tier="b"}': 1.5, 'serve_count{tier="a"}': 20.0,
             'serve_count{tier="b"}': 10.0, "late_sum": 7.0}
    ctx = {"before": before, "after": after,
           "client": {"acked": 4000, "none": 0}}
    ms_per_kspan = prom_delta.read(
        {"num": [{"prom": "wal_sum"}], "den": [{"client": "acked"}],
         "scale": 1e6}, ctx)
    assert abs(ms_per_kspan - 125.0) < 1e-9     # 0.5 s over 4 kspans
    serve_ms = prom_delta.read(
        {"num": [{"prom": "serve_sum"}], "den": [{"prom": "serve_count"}],
         "scale": 1e3}, ctx)
    assert abs(serve_ms - 100.0) < 1e-9         # 1.5 s over 15 reads
    # a sample that appeared during the window counts from 0
    assert prom_delta.read({"num": [{"prom": "late_sum"}]}, ctx) == 7.0
    # nothing to read is None, never 0
    assert prom_delta.read({"num": [{"prom": "absent"}]}, ctx) is None
    assert prom_delta.read({"num": [{"prom": "wal_sum"}],
                            "den": [{"client": "none"}]}, ctx) is None


def test_union_counts_overlaps_once():
    assert trace_reduce.union_s([(0, 10), (5, 20), (30, 40)]) == 30e-9
    assert trace_reduce.union_s([]) == 0.0


def test_trace_reduction_on_the_recorded_trace():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    t = trace_reduce.Trace(
        {n: {k: [tuple(e) for e in v] for k, v in p.items()}
         for n, p in rec["planes"].items()})
    want = rec["by_hand"]
    assert abs(t.window_s - want["window_s"]) < 1e-9
    assert abs(t.busy_s - want["busy_s"]) < 1e-9
    assert abs(t.module_s(["^jit_ingest_step"])
               - want["ingest_step_s"]) < 1e-9
    assert len(t.module_events(["^jit_ingest_step"])) == want["ingest_steps"]
    ctx = {"trace": t, "client": {"traced_acked_spans": want["spans"]},
           "device_kind": "TPU v5 lite", "traffic": rec["traffic"]}
    idle = trace_reduce.read({"stat": "idle_pct"}, ctx)
    assert abs(idle - 100 * (1 - want["busy_s"] / want["window_s"])) < 1e-9
    ctx["client"].update(window_s=4 * want["window_s"], reads_ok=8)
    per = trace_reduce.read(
        {"stat": "module_ms_per_unit", "patterns": ["^jit_ingest_step"],
         "per": "reads_ok", "per_unit": 1.0}, ctx)
    # 8 units over a window four times the traced one: 2 inside the trace
    assert abs(per - 1e3 * want["ingest_step_s"] / 2) < 1e-6
    ctx.update(before={"launches_count": 10.0}, after={"launches_count": 30.0})
    ctx["client"]["acked_spans"] = 40960
    per = trace_reduce.read(
        {"stat": "module_ms_per_event_unit", "patterns": ["^jit_ingest_step"],
         "events_per_unit": {"num": [{"prom": "launches_count"}],
                             "den": [{"client": "acked_spans"}],
                             "scale": 1000.0}}, ctx)
    # 20 launches for 40.96 kspans; two runs in the trace
    assert abs(per - 1e3 * want["ingest_step_s"] / 2 * 20 / 40.96) < 1e-6
    share = trace_reduce.read(
        {"stat": "hbm_roofline_pct", "patterns": ["^jit_ingest_step"],
         "bytes": "ingest_step"}, ctx)
    assert 0 < share < 100
    assert trace_reduce.read({"stat": "module_ms_per_unit",
                              "patterns": ["^no_such_module"],
                              "per": "reads_ok"}, ctx) is None
    assert trace_reduce.read({"stat": "idle_pct"}, {"trace": None}) is None
    b = t.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_roofline_bytes_for_one_hand_worked_batch():
    traffic = {"call_spans": 2, "annotations_per_span": 6,
               "binary_per_span": 2, "services_per_span": 2,
               "indexed_annotations_per_span": 2}
    # batch: 2*89 + 12*24 + 4*21 = 550 B, read once and written once
    # index rows: 2*2*2 + 4 + 4*2 + (2 + 12 + 4) = 38, 24 B read + written
    assert roofline.index_rows(2, 12, 4, 2, 4) == 38
    assert roofline.ingest_step(traffic) == 2 * 550 + 2 * 24 * 38


def test_a_device_kind_not_in_the_table_is_an_error():
    with open(os.path.join(os.path.dirname(HERE), "readers",
                           "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5 lite" in peaks["device_kinds"] and peaks["source"]


def test_reduce_planes_keeps_device_planes_and_strips_fingerprints():
    class E:
        def __init__(self, name, start_ns, duration_ns):
            self.name, self.start_ns, self.duration_ns = (
                name, start_ns, duration_ns)

    class L:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class P:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class D:
        planes = [
            P("/host:CPU", [L("python", [E("x", 0, 1e9)])]),
            P("/device:TPU:0", [
                L("XLA Modules", [E("jit_ingest_step(123456)", 10, 100),
                                  E("jit__iq_probe(77)", 200, 50)]),
                L("XLA Ops", [E("fusion.1", 10, 60), E("fusion.2", 50, 60),
                              E("copy.3", 200, 50)]),
                L("Steps", [E("0", 0, 1000)])]),
        ]

    t = trace_reduce.reduce_planes(D)
    assert list(t.planes) == ["/device:TPU:0"]
    assert [e[0] for e in t.module_events(["^jit_ingest_step$"])] == [
        "jit_ingest_step"]
    assert abs(t.busy_s - 150e-9) < 1e-15       # 10..110 and 200..250
    assert abs(t.window_s - 240e-9) < 1e-15     # the device plane's span
    D.planes = D.planes[:1]
    assert trace_reduce.reduce_planes(D) is None  # a CPU rehearsal


def test_four_planes_are_reckoned_per_chip():
    """A launch over four chips is one event on every plane. The time of
    a run is one chip's; the bytes of a ``Log`` call are moved once a
    launch with four chips' bandwidth, so four planes that each take as
    long as the one read a quarter of its roofline share. One plane in,
    the floats are those of the single-device arithmetic, to the bit."""
    steps = [("jit_ingest_step", 1_000 + 50_000 * i, 30_000 + 1_000 * i)
             for i in range(5)]
    ops = [("fusion.1", s, d // 2) for _, s, d in steps]
    plane = {"modules": steps + [("jit_other", 400_000, 7_000)], "ops": ops}
    # the other chips start a little later and take a little longer
    skew = [{k: [(n, s + 100 * c, d + 10 * c) for n, s, d in v]
             for k, v in plane.items()} for c in range(4)]
    one = trace_reduce.Trace({"/device:TPU:0": skew[0]})
    four = trace_reduce.Trace(
        {f"/device:TPU:{c}": skew[c] for c in range(4)})
    traffic = {"call_spans": 2048, "annotations_per_span": 6,
               "binary_per_span": 2, "services_per_span": 2,
               "indexed_annotations_per_span": 2}
    spec_ms = {"stat": "module_ms_per_event_unit",
               "patterns": ["^jit_ingest_step"],
               "events_per_unit": {"num": [{"prom": "launches"}],
                                   "den": [{"client": "acked_spans"}],
                                   "scale": 1000.0}}
    spec_roof = {"stat": "hbm_roofline_pct", "bytes": "ingest_step",
                 "patterns": ["^jit_ingest_step"]}

    def read(trace, spec):
        return trace_reduce.read(spec, {
            "trace": trace, "before": {"launches": 0.0},
            "after": {"launches": 5.0}, "client": {"acked_spans": 10240},
            "device_kind": "TPU v5 lite", "traffic": traffic})

    durs = [d for _, _, d in steps]
    seconds = sum(durs) / 1e9 / 1
    launches_per_kspan = 1000.0 * 5.0 / 10240
    # one plane: the single-device formulas as they stood, bit for bit
    assert read(one, spec_ms) == 1e3 * seconds / 5 * launches_per_kspan
    need = roofline.ingest_step(traffic) * 5
    assert read(one, spec_roof) == 100.0 * (need / 819e9) / (seconds * 1)
    # four planes: a run is the mean chip's 30 + 2 + 0.015 us
    mean_run_s = (sum(durs) / 5 + 15) / 1e9
    assert abs(read(four, spec_ms)
               - 1e3 * mean_run_s * launches_per_kspan) < 1e-12
    assert abs(read(four, spec_ms) / read(one, spec_ms)
               - mean_run_s / (sum(durs) / 5e9)) < 1e-12
    want = 100.0 * roofline.ingest_step(traffic) / (4 * 819e9) / mean_run_s
    assert abs(read(four, spec_roof) - want) < 1e-12
    # were all four planes the one plane, the time of a run would be the
    # same and the share a quarter
    same = trace_reduce.Trace(
        {f"/device:TPU:{c}": skew[0] for c in range(4)})
    assert abs(read(same, spec_ms) - read(one, spec_ms)) < 1e-12
    assert abs(read(same, spec_roof) - read(one, spec_roof) / 4) < 1e-15
    assert abs(same.busy_s - one.busy_s) < 1e-15
    # the breakdown is per plane too: one chip's seconds in an operation
    b1, b4 = one.breakdown(), same.breakdown()
    assert b1["device_ops"][0][0] == b4["device_ops"][0][0] == "fusion.1"
    assert abs(b4["device_ops"][0][1] - b1["device_ops"][0][1]) < 1e-15
    assert abs(b4["idle_gaps"][0][1] - b1["idle_gaps"][0][1]) < 1e-15
    acc = 0.0  # one plane: added up as it always was, to the bit
    for d in durs:
        acc = acc + d // 2 / 1e9
    assert b1["device_ops"][0][1] == acc
