"""The daemon child: the one process on the chip.

Copied from ``chip_smoke.py``'s ``Daemon`` (spawn, boot line, HTTP GET,
/metrics scrape, SIGTERM) and made to take its flags from a
configuration file. stdout/stderr go to files so a chatty child never
blocks on a full pipe.
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
        with open(self.out_path, "wb") as out, \
                open(self.err_path, "wb") as err:
            self.proc = subprocess.Popen(
                cmd, cwd=ROOT, env=env, stdin=subprocess.DEVNULL,
                stdout=out, stderr=err)

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise RuntimeError(f"daemon exited early with code {rc}")

    def wait_boot(self, deadline_s: float) -> dict:
        """Block until the boot line; returns the device the store's
        state lives on, as the daemon reported it."""
        while time.monotonic() - self.t_spawn < deadline_s:
            self.check_alive()
            with open(self.out_path, errors="replace") as f:
                m = BOOT_RE.search(f.read())
            if m:
                return {"platform": m.group(1), "kind": m.group(2),
                        "count": int(m.group(3)),
                        "state_bytes": int(m.group(4))}
            time.sleep(0.2)
        raise TimeoutError(f"no boot line within {deadline_s:.0f}s")

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
        self.proc.send_signal(signal.SIGTERM)
        return self.proc.wait(timeout=deadline_s)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()

    def memory_report(self) -> dict:
        """What daemon_entry.py wrote at exit: the device's own memory
        statistics, read in the one process that may ask."""
        try:
            with open(self.mem_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}
