"""A whole run with the timed path broken underneath comes out
``correct: false``; the same run unbroken comes out true.

Drives run.py end to end with the child on the CPU at a small ring (the
harness's look for a chip is skipped by ``--platform cpu``); the daemon
is the program itself with one of faults.py's broken guarantees planted.
About a minute a case, on rings that lap (2^18 rows). The sharded cases
drive the fixture cell of ``sharded/`` on four forced CPU devices, four
shards of 2^17 rows, about two minutes a case. Run by hand:

    python -m pytest benchmark/tests/test_faults.py -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def last_line(r) -> dict:
    return json.loads(r.stdout.strip().splitlines()[-1])


def run_cell(workload: str, fault: str, env: dict = None,
             want_rc: int = None, capacity: int = 262144, more=()):
    """The result line of a run that exits 0 or, where ``want_rc`` is
    given, the finished process itself."""
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", "2147483659", "--seconds", "2",
           "--trace", "0", "--platform", "cpu", "--capacity", str(capacity),
           *more]
    if fault:
        cmd += ["--fault", fault]
    r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=900, env={**os.environ, **(env or {})})
    assert r.returncode == (want_rc or 0), r.stderr[-3000:]
    return r if want_rc is not None else last_line(r)


def first_cell(loop: str) -> str:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            if json.load(f)["ingest"]["loop"] == loop:
                return w["name"]
    pytest.skip(f"no cell with a {loop} ingest loop")


@pytest.mark.parametrize("fault,numbers", [
    ("", ()),
    ("lost_write", ("acked_spans_not_in_wal", "dependency_calls_off")),
    ("ack_before_fsync", ("acks_before_durable",)),
    ("stored_then_pushed_back", ("dependency_calls_off",)),
    ("not_whole", ("answers_wrong",)),
    ("stale_query", ("answers_wrong",)),
])
def test_a_planted_fault_reads_not_correct(fault, numbers):
    line = run_cell(first_cell("closed"), fault)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    if not fault:
        assert line["correct"] is True
        assert all(v["value"] <= v["limit"]
                   for v in line["compared"].values())
        assert line["metrics"]["setup_s"]["value"] > 0
    else:
        assert line["correct"] is False
        over = {k for k, v in line["compared"].items()
                if v["value"] > v["limit"]}
        assert over >= set(numbers)


def test_a_pushed_back_prefill_is_done_again_on_a_fresh_daemon(tmp_path):
    """A call stored and then answered TRY_LATER is resent and stored
    twice: the run may not go on with that daemon."""
    r = run_cell(first_cell("closed"), "prefill_pushed_back",
                 {"BENCH_FAULT_MARK": str(tmp_path / "fired")}, want_rc=0)
    assert "pre-fill 1 was pushed back" in r.stderr
    assert "pre-fill 2 was pushed back" not in r.stderr
    line = last_line(r)
    assert line["correct"] is True
    assert all(v["value"] == 0 for v in line["compared"].values())


def test_prefills_pushed_back_in_a_row_fail_the_run():
    r = run_cell(first_cell("closed"), "prefill_pushed_back", want_rc=1)
    assert "pre-fills in a row were pushed back" in r.stderr
    assert not r.stdout.strip()


SHARDED = os.path.join("benchmark", "tests", "sharded", "BENCHMARK.json")


@pytest.mark.parametrize("fault,numbers", [
    ("", ()),
    ("ack_on_epoch_only", ("acks_before_durable",)),
    ("lost_write", ("acked_spans_not_in_wal", "dependency_calls_off")),
])
def test_a_fault_planted_in_the_sharded_daemon_reads_not_correct(
        fault, numbers):
    """The fixture cell: four shards over four forced CPU devices (the
    daemon child inherits XLA_FLAGS), rings that lap, the log a tree."""
    with open(os.path.join(ROOT, SHARDED)) as f:
        cell = json.load(f)["workloads"][0]
    assert cell["chips"] == 4
    line = run_cell(
        cell["name"], fault,
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        capacity=131072, more=("--benchmark-file", SHARDED))
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    assert line["correct"] is (not fault)
    assert over >= set(numbers) and bool(over) == bool(fault)
