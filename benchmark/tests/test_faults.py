"""A whole run with the timed path broken underneath comes out
``correct: false``; the same run unbroken comes out true.

Drives run.py end to end with the child on the CPU at a small ring (the
harness's look for a chip is skipped by ``--platform cpu``); the daemon
is the program itself with one of faults.py's broken guarantees planted.
About a minute and a half a case. Run by hand:

    python -m pytest benchmark/tests/test_faults.py -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def run_cell(workload: str, fault: str, extra=()) -> dict:
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, *extra, "--seed", "2147483659", "--seconds", "2",
           "--trace", "0", "--platform", "cpu", "--capacity", "262144",
           "--prefill-spans", "4096", "--stream-spans", "81920",
           "--ingest-rate", "4000", "--read-rate", "4"]
    if fault:
        cmd += ["--fault", fault]
    # lost_write drops the 4th durable Log call: the 2nd of the window
    env = {**os.environ, "BENCH_FAULT_AT": "4"}
    r = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def first_cell(loop: str) -> str:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            if json.load(f)["ingest"]["loop"] == loop:
                return w["name"]
    pytest.skip(f"no cell with a {loop} ingest loop")


@pytest.mark.parametrize("fault,number", [
    ("", None),
    ("lost_write", "dependency_calls_off"),
    ("not_whole", "answers_wrong"),
    ("stale_query", "answers_wrong"),
])
def test_a_planted_fault_reads_not_correct(fault, number):
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
        assert line["compared"][number]["value"] > \
            line["compared"][number]["limit"]


def test_a_fault_shows_under_reads_too():
    # the read mix kept for a later cell (PERF.md, Open questions)
    line = run_cell("ui-reads-live", "lost_write",
                    ("--config", "allinone-wal-ring22",
                     "--traffic", "ui-reads-live"))
    assert line["correct"] is False
    c = line["compared"]
    assert (c["dependency_calls_off"]["value"] > 0
            or c["acked_calls_never_readable"]["value"] > 0)
