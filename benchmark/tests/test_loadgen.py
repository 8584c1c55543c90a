"""The read side of the load generator, which no cell of BENCHMARK.json
drives yet (PERF.md, Open questions): every seed sends the same reads,
in another order."""

import os
import sys
from collections import Counter

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import gen  # noqa: E402
from loadgen import Reads, percentile  # noqa: E402

SPEC = {
    "per_s": 2.0, "workers": 4, "cycle_reads": 20, "schedule_cycles": 3,
    "trace_lag_calls": 1, "trace_recent_calls": 2,
    "mix": [{"route": "query_service", "share": 0.7},
            {"route": "trace", "share": 0.25},
            {"route": "services", "share": 0.05}],
    "routes": {
        "query_service": {"path": "/api/query",
                          "params": {"serviceName": "{service}", "limit": 10}},
        "trace": {"path": "/api/trace/{trace}"},
        "services": {"path": "/api/services"}},
}


class Acked:
    last_acked = 5


def test_every_seed_sends_the_same_reads_in_another_order():
    s = gen.Stream(3, 512, 64, 8, 10_000_000)
    s.close()
    a, b = (Reads(0, s, SPEC, np.random.default_rng([seed, 0xBEAD]), Acked())
            for seed in (1, 2))
    for r in (a, b):
        for cycle in range(3):
            kinds = r.kind[cycle * 20:(cycle + 1) * 20]
            assert Counter(r.names[k] for k in kinds) == {
                "query_service": 14, "trace": 5, "services": 1}
    assert list(a.kind) != list(b.kind)
    paths = [a.path_of(j) for j in range(20)]
    assert sum(p.startswith("/api/query?serviceName=") for p in paths) == 14
    # a trace read asks for a trace of a call acked a little while ago
    recent = {f"{s.trace_id_at(f * 64 + j):x}"
              for f in (3, 4) for j in range(64)}
    for p in paths:
        if p.startswith("/api/trace/"):
            assert p.rsplit("/", 1)[1] in recent


def test_percentile_is_by_rank_over_every_value():
    assert percentile([], 0.95) is None
    assert percentile([5, 1, 3], 0.5) == 3
    assert percentile(list(range(1, 101)), 0.95) == 95
