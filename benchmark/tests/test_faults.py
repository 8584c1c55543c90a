"""A whole run with the timed path broken underneath comes out
``correct: false``; the same run unbroken comes out true.

Drives run.py end to end with the child on the CPU at a small ring (the
harness's look for a chip is skipped by ``--platform cpu``); the daemon
is the program itself with one of faults.py's broken guarantees planted.
About a minute a case, on rings that lap (2^18 rows). Run by hand:

    python -m pytest benchmark/tests/test_faults.py -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def run_cell(workload: str, fault: str) -> dict:
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", workload, "--seed", "2147483659", "--seconds", "2",
           "--trace", "0", "--platform", "cpu", "--capacity", "262144"]
    if fault:
        cmd += ["--fault", fault]
    r = subprocess.run(cmd, cwd=ROOT, capture_output=True,
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


@pytest.mark.parametrize("fault,numbers", [
    ("", ()),
    ("lost_write", ("acked_spans_not_in_wal", "dependency_calls_off")),
    ("ack_before_fsync", ("acks_before_durable",)),
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
