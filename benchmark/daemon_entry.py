"""Entry of the daemon child: ``zipkin_tpu.main.example.main`` itself,
with a few things round it that only the process on the chip can do.

- First of all it ties its life to its parent's (``die_with_parent``):
  however ``run.py`` ends, this process does not outlive it.
- At exit it writes the device's memory statistics (the peak on the
  fullest chip) where the parent asked: the parent never touches JAX.
- It keeps a journal of every ``os.fsync`` that returned (monotonic
  seconds, inode, the file's size when the fsync began), as ``strace``
  would from outside: ``walcheck.py`` holds the acks against it.
- ``--fault NAME[,NAME]`` plants one or more of ``benchmark/tests/faults.py``'s broken
  guarantees in the program before it starts. Only the control runs and
  the tests pass it; a benchmark run never does.
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import signal

PR_SET_PDEATHSIG = 1  # <linux/prctl.h>


def die_with_parent(parent_pid: int) -> None:
    """The kernel kills this process when its parent dies, whatever the
    parent died of: a SIGKILL runs no handler of the parent's, so the tie
    is made from the child's side (and not in a ``preexec_fn``, which is
    unsafe once ``run.py`` has threads). The signal is sent when the
    THREAD that forked this process exits: ``run.py`` makes its ``Daemon``
    on its main thread. A parent that died before the ``prctl`` took hold
    left this process to another parent: ``getppid`` tells."""
    libc = ctypes.CDLL(None, use_errno=True)
    libc.prctl.argtypes = [ctypes.c_int, ctypes.c_ulong]
    libc.prctl.restype = ctypes.c_int
    if libc.prctl(PR_SET_PDEATHSIG, signal.SIGKILL) != 0:
        raise OSError(ctypes.get_errno(), "prctl(PR_SET_PDEATHSIG)")
    if os.getppid() != parent_pid:
        raise SystemExit(f"the parent {parent_pid} is gone (now "
                         f"{os.getppid()}): not starting")


def write_memory_report(path: str) -> None:
    import jax

    peaks, kinds = [], []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        kinds.append(d.device_kind)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"memory_peak_bytes": max(peaks, default=0),
                   "per_device": peaks, "kinds": kinds}, f)
    os.replace(tmp, path)


def journal_fsyncs(path: str) -> None:
    import time

    real = os.fsync
    # one write a line to a file opened for appending: lines stay whole
    # however many group-commit threads fsync at once
    out = os.open(path, os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)

    def fsync(fd):
        st = os.fstat(fd)
        real(fd)
        os.write(out, f"{time.monotonic():.6f} {st.st_ino} "
                      f"{st.st_size}\n".encode())

    os.fsync = fsync


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--parent-pid", type=int, required=True)
    p.add_argument("--memory-report", required=True)
    p.add_argument("--fsync-journal", required=True)
    p.add_argument("--fault", default="")
    p.add_argument("rest", nargs=argparse.REMAINDER)
    args = p.parse_args()
    die_with_parent(args.parent_pid)
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    if args.fault:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "bench_faults", os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "tests", "faults.py"))
        faults = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(faults)
        for name in args.fault.split(","):
            faults.plant(name)
    journal_fsyncs(args.fsync_journal)
    from zipkin_tpu.main import example

    try:
        example.main(rest)
    finally:
        write_memory_report(args.memory_report)


if __name__ == "__main__":
    main()
