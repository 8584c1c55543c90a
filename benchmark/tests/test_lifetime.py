"""However ``run.py`` ends, nothing it started outlives it; and a run
whose set-up cannot be done within its budget stops by itself.

Drives run.py end to end with the child on the CPU at a small ring, as
``test_faults.py`` does, and ends it from outside: SIGKILL, SIGTERM,
SIGHUP and SIGINT during the pre-fill, SIGKILL and SIGTERM during the
window, SIGKILL on the sharded fixture (four forced CPU devices), whose
daemon is the one that was once left. Five seconds after run.py is gone
the daemon's pid and its whole process group are gone and both ports can
be bound again. Then the set-up's budget, with ``faults.py``'s
``slow_store`` planted: a fill that would take ten times the budget is
stopped a look after its 32nd ack; one that fits is not. Half a minute
to a minute a signal case, two for the sharded one and for the fill
that fits. Run by hand:

    python -m pytest benchmark/tests/test_lifetime.py -q -p no:cacheprovider
"""

import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

SHARDED = os.path.join("benchmark", "tests", "sharded", "BENCHMARK.json")
SMALL_CALLS = os.path.join("benchmark", "tests", "lifetime", "BENCHMARK.json")
SPAWNED = re.compile(r"daemon spawned pid (\d+) ports (\d+) (\d+); "
                     r"workdir (\S+)")
GONE_WITHIN_S = 5.0


def processes() -> dict:
    """{pid: (parent, process group, states of its threads)} of /proc."""
    out = {}
    for name in os.listdir("/proc"):
        if name.isdigit():
            try:
                states = ""
                for task in os.listdir(f"/proc/{name}/task"):
                    with open(f"/proc/{name}/task/{task}/stat") as f:
                        state, ppid, pgrp = f.read().rpartition(
                            ")")[2].split()[:3]
                    states += state
            except OSError:
                continue  # gone between the listing and the read
            out[int(name)] = (int(ppid), int(pgrp), states)
    return out


def alive(pids, table: dict) -> list:
    """Those of which any thread still runs (a killed process keeps its
    ports until its last thread is gone; a zombie holds nothing)."""
    return [p for p in pids if p in table and table[p][2].strip("ZX")]


def descendants(pid: int, table: dict) -> list:
    kids = [p for p, (parent, _, _) in table.items() if parent == pid]
    return kids + [d for k in kids for d in descendants(k, table)]


class Run:
    """run.py as a child, its standard error read as it comes."""

    def __init__(self, tmp_path, workload="", more=(), env=None,
                 seconds=8, capacity=262144):
        if not workload:
            with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
                workload = json.load(f)["workloads"][0]["name"]
        cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
               "--workload", workload, "--seed", "2147483693", "--seconds",
               str(seconds), "--trace", "0", "--platform", "cpu",
               "--capacity", str(capacity), *more]
        self.lines = []
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env={**os.environ, "TMPDIR": str(tmp_path),
                            **(env or {})})
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stderr:
            self.lines.append((time.monotonic(), line))

    def wait_for(self, pattern: str, timeout_s: float = 300.0):
        """The first line of standard error that matches, with the
        clock at which it was read."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            for t, line in list(self.lines):
                m = re.search(pattern, line)
                if m:
                    return t, m
            assert self.proc.poll() is None, self.stderr()[-3000:]
            time.sleep(0.05)
        raise AssertionError(f"no {pattern!r} in {timeout_s}s:\n"
                             + self.stderr()[-3000:])

    def stderr(self) -> str:
        return "".join(line for _, line in self.lines)

    def end(self, timeout_s: float) -> tuple:
        """(exit code, standard output, the clock when it was gone)."""
        try:
            self.proc.wait(timeout=timeout_s)
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
        t_gone = time.monotonic()
        out = self.proc.stdout.read()
        self._reader.join(10.0)
        return self.proc.returncode, out, t_gone


def assert_nothing_left(daemon_pid: int, ports, started: list,
                        t_gone: float) -> None:
    """Within five seconds of run.py's end: the daemon, whatever run.py
    or the daemon had started, and the daemon's process group are gone,
    and a new daemon could bind both ports."""
    while True:
        table = processes()
        left = alive(set(started) | {daemon_pid}, table) + alive(
            [p for p, (_, pgrp, _) in table.items() if pgrp == daemon_pid],
            table)
        if not left or time.monotonic() - t_gone > GONE_WITHIN_S:
            break
        time.sleep(0.1)
    assert not left, [(p, table[p]) for p in left]
    for port in ports:
        with socket.socket() as s:  # as a restarted daemon binds it
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", port))


def spawned(r: Run) -> tuple:
    """(the daemon's pid, its two ports, the run's workdir), off the
    line run.py prints when it has spawned the daemon."""
    _, m = r.wait_for(SPAWNED.pattern)
    return int(m.group(1)), (int(m.group(2)), int(m.group(3))), m.group(4)


def end_from_outside(tmp_path, sig, phase: str, **kw) -> None:
    r = Run(tmp_path, **kw)
    try:
        daemon_pid, ports, workdir = spawned(r)
        if phase == "prefill":
            r.wait_for(r"boot line after")
            time.sleep(3.0)  # some calls acked, most still to send
            assert "pre-fill:" not in r.stderr()
        else:
            r.wait_for(r"window starts")
            time.sleep(1.0)
            assert "window closed" not in r.stderr()
        table = processes()
        assert alive([daemon_pid], table)
        assert table[daemon_pid][:2] == (r.proc.pid, daemon_pid)
        started = descendants(r.proc.pid, table)
        # The daemon starts no process of its own in a run (run.py built
        # the codec before it): a SIGKILL of run.py orphans nothing.
        assert descendants(daemon_pid, table) == []
        r.proc.send_signal(sig)
        rc, out, t_gone = r.end(30.0)
    finally:
        if r.proc.poll() is None:
            r.proc.kill()
    assert_nothing_left(daemon_pid, ports, started, t_gone)
    assert not out.strip()  # no result line
    if sig == signal.SIGKILL:
        assert rc == -signal.SIGKILL
        shutil.rmtree(workdir, ignore_errors=True)  # nobody else could
    else:
        assert rc == 128 + sig, r.stderr()[-3000:]
        err = r.stderr()
        assert f"ended by {signal.Signals(sig).name}" in err
        assert "---- daemon stdout (tail) ----" in err
        assert "---- daemon stderr (tail) ----" in err
        assert not os.path.exists(workdir)


@pytest.mark.parametrize("sig,phase", [
    (signal.SIGKILL, "prefill"), (signal.SIGTERM, "prefill"),
    (signal.SIGHUP, "prefill"), (signal.SIGINT, "prefill"),
    (signal.SIGKILL, "window"), (signal.SIGTERM, "window"),
], ids=lambda v: v.name if isinstance(v, signal.Signals) else v)
def test_the_daemon_does_not_outlive_run_py(tmp_path, sig, phase):
    end_from_outside(tmp_path, sig, phase)


def test_the_sharded_daemon_does_not_outlive_run_py(tmp_path):
    with open(os.path.join(ROOT, SHARDED)) as f:
        cell = json.load(f)["workloads"][0]["name"]
    end_from_outside(
        tmp_path, signal.SIGKILL, "prefill", workload=cell, capacity=131072,
        more=("--benchmark-file", SHARDED),
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})


def test_a_daemon_whose_parent_is_gone_does_not_start(tmp_path):
    """The race that the kernel's tie leaves open: a parent that died
    before the child asked for it."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "daemon_entry.py"),
         "--parent-pid", "1", "--memory-report", str(tmp_path / "m"),
         "--fsync-journal", str(tmp_path / "f"), "--", "--platform", "cpu"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode != 0 and "not starting" in r.stderr
    assert not (tmp_path / "f").exists()  # before anything else


# -- the set-up's budget --------------------------------------------------


def small_calls(tmp_path, delay_s: float, budget_s: float) -> Run:
    """A pre-fill of 683 calls of 64 spans (2^17 rows) on a store that
    takes ``delay_s`` a call before its own work."""
    with open(os.path.join(ROOT, SMALL_CALLS)) as f:
        cell = json.load(f)["workloads"][0]["name"]
    return Run(tmp_path, workload=cell, capacity=131072, seconds=2,
               more=("--benchmark-file", SMALL_CALLS, "--fault", "slow_store"),
               env={"BENCH_FAULT_DELAY_S": str(delay_s),
                    "BENCH_FAULT_SETUP_BUDGET_S": str(budget_s)})


def test_a_fill_that_cannot_make_the_budget_stops_the_run(tmp_path):
    budget_s, delay_s = 60.0, 1.0
    r = small_calls(tmp_path, delay_s, budget_s)
    try:
        daemon_pid, ports, _ = spawned(r)
        r.wait_for(r"boot line after")
        t_boot = time.monotonic()
        started = descendants(r.proc.pid, processes())
        rc, out, t_gone = r.end(budget_s + 30.0)
    finally:
        if r.proc.poll() is None:
            r.proc.kill()
    err = r.stderr()
    assert rc == 1 and not out.strip(), err[-3000:]
    m = re.search(r"the set-up stops itself: (\d+) of (\d+) pre-fill calls "
                  r"acked, (\d+) spans/s .* would end (\d+)s into the run; "
                  r"the set-up's budget is 60s", err)
    assert m, err[-3000:]
    acked, calls, rate, end_s = map(int, m.groups())
    assert calls == 683 and rate <= 64 / delay_s
    # ten times the budget, seen a look (1 s) after the 32nd ack and
    # ended at once: the 32nd ack cannot come before 31 delays are over
    assert calls * delay_s > 10 * budget_s and end_s > 10 * budget_s
    assert run.PROJECT_ACKS <= acked <= run.PROJECT_ACKS + 8
    assert t_gone - t_boot < (acked - 1) * (delay_s + 0.25) + 1.0 + 5.0
    assert "---- daemon stderr (tail) ----" in err
    assert_nothing_left(daemon_pid, ports, started, t_gone)


def test_a_fill_that_fits_is_not_stopped(tmp_path):
    r = small_calls(tmp_path, 0.02, 400.0)
    rc, out, _ = r.end(800.0)
    err = r.stderr()
    assert rc == 0, err[-3000:]
    assert re.search(r"\d+ of 683 pre-fill calls acked, .* the set-up's "
                     r"budget is 400s", err)
    assert "stops itself" not in err
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ack_p95_ms", "setup_s"}


def test_without_a_fault_the_budget_is_the_constant(tmp_path):
    """The environment shrinks the budget of a run with a fault planted
    and of no other: a driver cannot set it."""
    r = Run(tmp_path, env={"BENCH_FAULT_SETUP_BUDGET_S": "1"})
    try:
        r.wait_for(rf"the set-up's budget is {run.SETUP_BUDGET_S:.0f}s")
        r.proc.send_signal(signal.SIGTERM)
        rc, _, _ = r.end(30.0)
    finally:
        if r.proc.poll() is None:
            r.proc.kill()
    assert rc == 128 + signal.SIGTERM


def test_the_projection_takes_the_median_period():
    now = run.T_START + 100.0
    even = [now - 31 + i for i in range(32)]           # an ack a second
    assert run.projected_fill(even[:-1], 10, now) is None
    period, end_s = run.projected_fill(even, 500, now)
    assert period == pytest.approx(1.0) and end_s == pytest.approx(600.0)
    # one call that took a compile's 98 s does not decide it
    stalled = [t - 98.0 for t in even[:10]] + even[10:]
    period, _ = run.projected_fill(stalled, 500, now)
    assert period == pytest.approx(1.0)
    # only the newest acks count, in whatever order they were recorded
    old = [now - 5000 + 100 * i for i in range(40)]
    period, _ = run.projected_fill(even[::-1] + old, 500, now)
    assert period == pytest.approx(1.0)
    # eight connections acked at once, every eight seconds: reads too
    # fast, so such a fill is stopped by the budget itself and no sooner
    bursts = [now - 8.0 * (3 - i // 8) for i in range(32)]
    period, end_s = run.projected_fill(bursts, 500, now)
    assert period == 0.0 and end_s == pytest.approx(100.0)
