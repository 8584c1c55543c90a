"""The daemon child: the one process on the chip.

Copied from ``chip_smoke.py``'s ``Daemon`` (spawn, boot line, HTTP GET,
/metrics scrape, SIGTERM) and made to take its flags from a
configuration file. stdout/stderr go to files so a chatty child never
blocks on a full pipe.

The child cannot outlive the process that made it: ``daemon_entry.py``
asks the kernel to kill it when this process dies, and it leads a
process group of its own, which ``kill`` takes down whole.
"""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.parse

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BOOT_RE = re.compile(
    r"serving on .* device=(\S+) kind=(.+) count=(\d+) state_bytes=(\d+)")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            lines = [ln for ln in f if "cpu_aot_loader.cc" not in ln]
    except OSError:
        return ""
    return "".join(lines[-n:])


class Daemon:
    def __init__(self, flags: list, platform: str, workdir: str,
                 fault: str = ""):
        self.http_port, self.scribe_port = free_port(), free_port()
        self.out_path = os.path.join(workdir, "daemon.out")
        self.err_path = os.path.join(workdir, "daemon.err")
        self.mem_path = os.path.join(workdir, "device_memory.json")
        self.fsync_path = os.path.join(workdir, "fsyncs.txt")
        cmd = [sys.executable, os.path.join(HERE, "daemon_entry.py"),
               "--parent-pid", str(os.getpid()),
               "--memory-report", self.mem_path,
               "--fsync-journal", self.fsync_path]
        if fault:
            cmd += ["--fault", fault]
        cmd += ["--", "--platform", platform, "--host", "127.0.0.1",
                "--port", str(self.http_port),
                "--scribe-port", str(self.scribe_port)]
        cmd += [f.replace("{workdir}", workdir) for f in flags]
        # The child alone may use the chip: it must not inherit the
        # parent's pin to the CPU.
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        self.t_spawn = time.monotonic()
        # Made on the caller's MAIN thread: the child's tie to this
        # process (daemon_entry.die_with_parent) follows the thread that
        # forked it. A group of its own, so that kill() reaches whatever
        # the child started (its native.py can have a compiler running).
        with open(self.out_path, "wb") as out, \
                open(self.err_path, "wb") as err:
            self.proc = subprocess.Popen(
                cmd, cwd=ROOT, env=env, stdin=subprocess.DEVNULL,
                stdout=out, stderr=err, process_group=0)

    def check_alive(self, when: str = "early") -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise RuntimeError(f"daemon exited {when} with code {rc}")

    def wait_boot(self, deadline_s: float) -> dict:
        """Block until the boot line; returns the device the store's
        state lives on, as the daemon reported it. A daemon that died
        and one that hangs read differently."""
        while time.monotonic() - self.t_spawn < deadline_s:
            self.check_alive("before its boot line")
            with open(self.out_path, errors="replace") as f:
                m = BOOT_RE.search(f.read())
            if m:
                return {"platform": m.group(1), "kind": m.group(2),
                        "count": int(m.group(3)),
                        "state_bytes": int(m.group(4))}
            time.sleep(0.2)
        raise TimeoutError(
            f"no boot line within {deadline_s:.0f}s of the spawn: the "
            f"daemon (pid {self.proc.pid}) is alive and silent")

    def request(self, method: str, path: str, params: dict = None,
                timeout_s: float = 900.0):
        """(status, body bytes); one connection a request, as the
        daemon's HTTP/1.0 server closes each."""
        if params:
            path += "?" + urllib.parse.urlencode(params)
        conn = http.client.HTTPConnection("127.0.0.1", self.http_port,
                                          timeout=timeout_s)
        try:
            conn.request(method, path)
            r = conn.getresponse()
            return r.status, r.read()
        finally:
            conn.close()

    def get_json(self, path: str, params: dict = None):
        status, body = self.request("GET", path, params)
        if status != 200:
            raise RuntimeError(
                f"GET {path} {params} -> {status}: {body[:300]!r}")
        return json.loads(body)

    def scrape(self) -> dict:
        """Prometheus text -> {sample name with labels: value}."""
        status, body = self.request("GET", "/metrics")
        if status != 200:
            raise RuntimeError(f"GET /metrics -> {status}")
        out = {}
        for line in body.decode().splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                out[name] = float(value)
        return out

    def terminate(self, deadline_s: float) -> int:
        """SIGTERM to the child alone, which saves and reports on its
        way out; its exit code. What it may have left in its group goes
        after it."""
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(timeout=deadline_s)
        self.kill()
        return rc

    def kill(self) -> None:
        """SIGKILL to the child's whole group, the child gone or not:
        what it started may have outlived it."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()

    def memory_report(self) -> dict:
        """What daemon_entry.py wrote at exit: the device's own memory
        statistics, read in the one process that may ask."""
        try:
            with open(self.mem_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}
