"""What a daemon's ``--shards n`` changes: a lap is n per-shard laps,
each shard holds whole the newest spans of the traces it owns, and the
re-stated ``shard_of`` routes as the program's does."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import gen  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from reference import Reference  # noqa: E402

TRAFFIC = {"call_spans": 2048, "annotations_per_span": 6,
           "binary_per_span": 2, "ingest": {"connections": 8}}
CONFIG = {"ring_rows_per_capacity_row": {"span": 1, "annotation": 2,
                                         "binary": 1},
          "retained_whole_share": 0.9}


@pytest.mark.parametrize("n", [1, 2, 3, 4, 8, 7])
def test_shard_of_routes_as_the_program_does(n):
    from zipkin_tpu.parallel.multihost import shard_of

    ids = np.random.default_rng(27).integers(
        0, 2**64, size=10_000, dtype=np.uint64)
    ids[:4] = (0, 1, 2**63, 2**64 - 1)
    got = reference.shard_of(ids.view(np.int64), n)
    assert got.min() >= 0 and got.max() < n
    for unsigned, signed, g in zip(ids.tolist(), ids.view(np.int64).tolist(),
                                   got.tolist()):
        assert g == shard_of(unsigned, n) == shard_of(signed, n)
    assert reference.shard_of(int(ids.view(np.int64)[7]), n) == got[7]


def test_a_lap_is_every_shards_lap():
    # no --shards among the flags: the accepted cells' numbers (PERF.md 4)
    flags = ["--capacity", "4194304", "--pipeline-depth", "4"]
    assert run.shards_of(flags) == 1
    lap = run.lap_spans(CONFIG, TRAFFIC, 4194304)
    assert lap == 1_398_101
    assert run.retained_spans(CONFIG, TRAFFIC, lap) == 1_241_906
    assert run.lap_spans(CONFIG, TRAFFIC, 4194304, 1) == lap
    # --capacity sizes each shard: four shards lap at four times one's
    assert run.shards_of(["--shards", "4"] + flags) == 4
    assert run.lap_spans(CONFIG, TRAFFIC, 1048576, 4) == 4 * 349_525
    assert run.lap_spans(CONFIG, TRAFFIC, 4194304, 4) == 4 * 1_398_101
    assert run.retained_spans(CONFIG, TRAFFIC, 4 * 349_525) == \
        int(0.9 * 4 * 349_525) - 8 * 2048


def test_the_fixture_lays_its_cell_over_the_root_files_metrics():
    """The fixture names its configuration and its cell and nothing
    else: metrics and bounds are the root file's, in one place."""
    import json

    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(HERE, "sharded", "BENCHMARK.json")) as f:
        fixture = json.load(f)
    assert sorted(fixture) == ["configs", "workloads"]
    with open(os.path.join(root, fixture["configs"][0]["file"])) as f:
        config = json.load(f)
    assert run.shards_of(config["daemon_flags"]) == \
        fixture["workloads"][0]["chips"] == 4
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = {w["name"] for w in json.load(f)["workloads"]}
    assert fixture["workloads"][0]["name"] not in names


def test_each_shard_holds_the_newest_spans_of_its_own_traces():
    s = gen.Stream(11, 512, 64, 8, 10_000_000)
    s.close()
    n, c, retained = 1024, 64, 600
    one = Reference(s, range(16), retained=retained)
    assert one.first_retained == n - (retained // c) * c  # as it stood
    assert list(one.held_from) == [one.first_retained]
    ref = Reference(s, range(16), retained=retained, shards=4)
    tids, _, _ = ref.span_keys()
    owner = reference.shard_of(tids, 4)
    assert len(set(owner.tolist())) == 4
    for shard in range(4):
        cut = int(ref.held_from[shard])
        assert cut % c == 0  # a call's edge
        held = int((owner[cut:] == shard).sum())
        assert held <= retained // 4 < int((owner[cut - c:] == shard).sum())
    assert ref.first_retained == ref.held_from.max()
    assert len(set(ref.held_from.tolist())) > 1  # the shards evict apart
    # a trace is asked for whole only while its own shard holds it
    early = int(ref.held_from.min())
    for j in range(early, ref.first_retained):
        tid = ref.trace_id_of(j)
        shard = int(reference.shard_of(tid, 4))
        first = min(k for k in range(n) if tids[k] == tid)
        assert ref._before_retained(tid) == (first < ref.held_from[shard])
    longest = ref.longest_trace()
    assert not ref._before_retained(longest)
    # what is kept for all time still counts every acked span
    assert ref.dependency_calls() == one.dependency_calls()


def test_a_shard_that_holds_nothing_has_every_span_older():
    """So little retained that a shard's first held span lies in the
    last acked call: rounded up to the call's edge, it holds none."""
    s = gen.Stream(11, 512, 64, 8, 10_000_000)
    s.close()
    ref = Reference(s, range(16), retained=8, shards=4)
    n = ref.n_spans()
    assert n in ref.held_from.tolist()
    tids, _, _ = ref.span_keys()
    for j in (0, n - 1):
        assert ref._before_retained(int(tids[j]))
