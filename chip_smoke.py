"""Chip smoke: the served path, once, on the attached accelerator.

Starts the all-in-one daemon (``python -m zipkin_tpu.main.example``) as
the ONLY process that touches the chip, feeds it tracegen spans over
scribe TCP in three waves with the WAL on, reads every listed route
back over HTTP, and compares the answers for equality against the
in-memory oracle (``store/memory.InMemorySpanStore`` behind the same
``ApiServer`` route table) holding the same spans.

    python chip_smoke.py               # one chip, 2^22 ring, 2^18 spans
    python chip_smoke.py --shards 4    # four chips, 2^20 ring per shard
    python chip_smoke.py --rehearse    # CPU child, tiny: the rehearsal

This parent NEVER initialises a JAX backend (a chip belongs to one
process at a time): it pins ``JAX_PLATFORMS=cpu`` for itself, builds
the child's environment without that variable, and asserts at the end
that no backend came up. Any failed phase raises — the script exits
non-zero with the daemon's last stderr lines shown, and prints the
result line only when everything held. The device fields of that line
are copied from the daemon's boot line, never read here.

The earlier lines are counts and wall seconds, each labelled for what
it is. This script states no rate and no performance result.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request

os.environ["JAX_PLATFORMS"] = "cpu"  # the parent stays off the chip

import numpy as np  # noqa: E402

from zipkin_tpu import native  # noqa: E402
from zipkin_tpu.aggregate.job import aggregate_spans  # noqa: E402
from zipkin_tpu.api.server import ApiServer  # noqa: E402
from zipkin_tpu.ingest.receiver import ResultCode  # noqa: E402
from zipkin_tpu.ingest.scribe_server import ScribeClient  # noqa: E402
from zipkin_tpu.query.service import QueryService  # noqa: E402
from zipkin_tpu.store.memory import InMemorySpanStore  # noqa: E402
from zipkin_tpu.tracegen import generate_traces  # noqa: E402
from zipkin_tpu.wire.thrift import span_to_scribe_message  # noqa: E402

ROOT = os.path.dirname(os.path.abspath(__file__))
SELF_SERVICE = "zipkin-tpu"  # the daemon's self-trace service (+ "-<name>")
# Most spans per scribe Log call. Every call of a run carries the same
# number, so the daemon pads every launch into ONE compiled ingest shape
# (tracegen spans have a fixed 6 annotations + 2 binary annotations).
CHUNK = 2048
N_WAVES = 3
N_SERVICES = 64  # inside the daemon's default max_services=256
END_TS = 2_000_000_000_000  # fixed query horizon, past every span
QUERY_LIMIT = 10
# Ids per /api/traces_exist probe: a 35 KB URL (the server caps a
# request line at 64 KB); fewer, wider probes — each may scan the ring.
EXIST_BATCH = 2048
CHECK_SERVICES = 16  # services compared per route after the last wave
CHECK_TRACES = 32  # whole traces compared after the last wave
BOOT_DEADLINE_S = 600.0  # generous: backend init + state allocation
STOP_DEADLINE_S = 300.0  # SIGTERM -> drain, fsync, exit 0
BOOT_RE = re.compile(
    r"serving on .* device=(\S+) kind=(.+) count=(\d+) state_bytes=(\d+)")


def say(label: str, value) -> None:
    print(f"{label}: {value}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 40) -> str:
    """Last lines of a daemon log, minus XLA:CPU's page-long notices
    about cached AOT results (rehearsals only; they bury the cause)."""
    with open(path, errors="replace") as f:
        lines = [ln for ln in f if "cpu_aot_loader.cc" not in ln]
    return "".join(lines[-n:])


# -- the daemon child --------------------------------------------------------


class Daemon:
    """The one process on the chip. stdout/stderr go to files so a
    chatty child can never block on a full pipe."""

    def __init__(self, args, workdir: str):
        self.http_port = free_port()
        self.scribe_port = free_port()
        self.out_path = os.path.join(workdir, "daemon.out")
        self.err_path = os.path.join(workdir, "daemon.err")
        cmd = [
            sys.executable, "-m", "zipkin_tpu.main.example",
            "--platform", "cpu" if args.rehearse else "tpu",
            "--capacity", str(args.capacity),
            "--host", "127.0.0.1",
            "--port", str(self.http_port),
            "--scribe-port", str(self.scribe_port),
            "--wal-dir", os.path.join(workdir, "wal"),
            "--wal-fsync", "interval",
            "--pipeline-depth", "4",
        ]
        if args.shards:
            cmd += ["--shards", str(args.shards)]
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        if args.rehearse and args.shards:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
                f"device_count={args.shards}").strip()
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
                say("boot_seconds", round(time.monotonic() - self.t_spawn, 1))
                say("state_bytes", int(m.group(4)))
                return {"platform": m.group(1), "kind": m.group(2),
                        "count": int(m.group(3))}
            time.sleep(0.5)
        raise TimeoutError(f"no boot line within {deadline_s:.0f}s")

    def get(self, path: str, params: dict = None, timeout_s: float = 900.0):
        """HTTP GET → (status, body bytes). The timeout is generous: a
        route's first call may compile its device kernel."""
        self.check_alive()
        url = f"http://127.0.0.1:{self.http_port}{path}"
        if params:
            url += "?" + urllib.parse.urlencode(params)
        try:
            with urllib.request.urlopen(url, timeout=timeout_s) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def get_json(self, path: str, params: dict = None):
        status, body = self.get(path, params)
        if status != 200:
            raise RuntimeError(f"GET {path} {params} -> {status}: "
                               f"{body[:300]!r}")
        return json.loads(body)

    def terminate(self, deadline_s: float) -> int:
        self.proc.send_signal(signal.SIGTERM)
        return self.proc.wait(timeout=deadline_s)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


# -- traffic -----------------------------------------------------------------


def make_spans(seed: int, total: int, chunk: int) -> list:
    """≥ ``total`` tracegen spans (trees of depth ≤ 7, ~7 spans each),
    flattened in trace order and cut to a whole number of chunks."""
    rng = np.random.default_rng(seed)
    spans: list = []
    target = total + chunk
    while len(spans) < target:
        for trace in generate_traces(n_traces=target // 6 + 16, rng=rng,
                                     n_services=N_SERVICES):
            spans.extend(trace)
    return spans[:-(-total // chunk) * chunk]


def send_wave(daemon: Daemon, oracle: InMemorySpanStore, spans: list,
              chunk_spans: int, counts: dict) -> None:
    """One scribe connection per wave (the server drops a connection
    idle past its io timeout, and reads between waves may compile)."""
    client = ScribeClient("127.0.0.1", daemon.scribe_port, timeout_s=900.0)
    try:
        for i in range(0, len(spans), chunk_spans):
            chunk = spans[i:i + chunk_spans]
            entries = [("zipkin", span_to_scribe_message(s)) for s in chunk]
            for attempt in range(50):
                daemon.check_alive()
                if client.log(entries) is ResultCode.OK:
                    break
                counts["try_later"] += 1
                time.sleep(0.1 * (attempt + 1))
            else:
                raise RuntimeError("scribe kept answering TRY_LATER")
            counts["acked"] += len(chunk)
            oracle.apply(chunk)
    finally:
        client.close()


def wait_visible(daemon: Daemon, oracle_api: ApiServer, last_span,
                 deadline_s: float = 900.0) -> None:
    """An ack means durably appended, not yet committed: the pipelined
    write path lands units FIFO, so once the LAST acked span's trace
    reads back whole, everything acked before it is visible too."""
    path = f"/api/trace/{hex_id(last_span.trace_id)}"
    _, want = oracle_api.handle("GET", path, {})
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        status, body = daemon.get(path)
        if status == 200 and len(json.loads(body)) == len(want):
            return
        if status not in (200, 404):
            raise RuntimeError(f"GET {path} -> {status}: {body[:300]!r}")
        time.sleep(0.2)
    raise TimeoutError("acked spans did not become readable")


# -- the oracle comparison ---------------------------------------------------


def hex_id(trace_id: int) -> str:
    return f"{trace_id & (2**64 - 1):x}"


def compare_reads(daemon: Daemon, oracle: InMemorySpanStore,
                  oracle_api: ApiServer, rng: np.random.Generator,
                  n_services: int, n_traces: int) -> dict:
    """Every listed route, daemon vs oracle, for equality. Returns the
    number of non-empty answers per route; raises on any difference."""
    nonempty = {"services": 0, "spans": 0, "query_service": 0,
                "query_annotation": 0, "query_binary": 0, "trace": 0,
                "traces_exist": 0, "dependencies": 0}

    def same(path: str, params: dict = None):
        got = daemon.get_json(path, params)
        status, want = oracle_api.handle("GET", path, dict(params or {}))
        want = json.loads(json.dumps(want))
        if status != 200 or got != want:
            raise AssertionError(
                f"{path} {params}: daemon differs from the oracle\n"
                f" daemon: {json.dumps(got)[:600]}\n"
                f" oracle: {status} {json.dumps(want)[:600]}")
        return got

    services = [s for s in daemon.get_json("/api/services")
                if not s.startswith(SELF_SERVICE)]
    if services != sorted(oracle.get_all_service_names()):
        raise AssertionError(f"/api/services differs: {services}")
    nonempty["services"] += bool(services)

    picked = [services[i] for i in sorted(rng.choice(
        len(services), size=min(n_services, len(services)),
        replace=False))]
    for svc in picked:
        nonempty["spans"] += bool(
            same("/api/spans", {"serviceName": svc}))
        base = {"serviceName": svc, "endTs": END_TS, "limit": QUERY_LIMIT}
        for route, extra in (
            ("query_service", {}),
            ("query_annotation",
             {"annotationQuery": "some custom annotation"}),
            ("query_binary", {"annotationQuery": "http.uri=/api/widgets"}),
        ):
            got = same("/api/query", {**base, **extra})
            nonempty[route] += bool(got["traceIds"])

    trace_ids = sorted({s.trace_id for s in oracle.spans})
    for i in rng.choice(len(trace_ids), size=min(n_traces, len(trace_ids)),
                        replace=False):
        nonempty["trace"] += bool(
            same(f"/api/trace/{hex_id(trace_ids[i])}"))

    # Every acked span's trace must be readable: batched membership
    # probes over ALL trace ids (fixed batch size → one compiled shape;
    # the last window overlaps the previous one instead of shrinking).
    width = min(EXIST_BATCH, len(trace_ids))
    for lo in range(0, len(trace_ids), width):
        lo = min(lo, len(trace_ids) - width)
        batch = sorted(hex_id(t) for t in trace_ids[lo:lo + width])
        got = daemon.get_json("/api/traces_exist",
                              {"traceIds": ",".join(batch)})
        if got["exist"] != batch:
            missing = set(batch) - set(got["exist"])
            raise AssertionError(
                f"/api/traces_exist: {len(missing)} acked traces "
                f"unreadable, e.g. {sorted(missing)[:3]}")
        nonempty["traces_exist"] += bool(got["exist"])

    # Dependencies: link set + call counts against the batch-job
    # oracle (the in-memory store aggregates nothing itself).
    deps = daemon.get_json("/api/dependencies")
    got_links = {
        (l["parent"], l["child"]): l["durationMoments"]["count"]
        for l in deps["links"]
        if not (l["parent"].startswith(SELF_SERVICE)
                or l["child"].startswith(SELF_SERVICE))
    }
    want_links = {
        (l.parent, l.child): l.duration_moments.count
        for l in aggregate_spans(oracle.spans).links
    }
    if got_links != want_links:
        diff = set(got_links.items()) ^ set(want_links.items())
        raise AssertionError(
            f"/api/dependencies: {len(diff)} link/count differences, "
            f"e.g. {sorted(diff)[:4]}")
    nonempty["dependencies"] += bool(got_links)
    return nonempty


# -- /metrics ----------------------------------------------------------------


def scrape(daemon: Daemon) -> dict:
    """Prometheus text → {sample name with labels: value}."""
    status, body = daemon.get("/metrics")
    if status != 200:
        raise RuntimeError(f"GET /metrics -> {status}")
    out = {}
    for line in body.decode().splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def report_metrics(daemon: Daemon, shards: int) -> None:
    """Print the listed observables; a sample /metrics lacks is a
    KeyError naming it."""
    m = scrape(daemon)

    def counter(name):
        return m[f'zipkin_store_counter{{name="{name}"}}']

    if not shards:
        # Single-device store observables (the sharded store exports
        # neither): compiles so far, and which rank and ring-write
        # implementations its compiled steps took (dev.active_paths).
        say("jit_compiles_total", int(m["zipkin_store_jit_compiles_total"]))
        say("rank_path_counting", int(counter("rank_path_counting")))
        say("ring_write_window", int(counter("ring_write_window")))
        say("ring_write_scatter", int(counter("ring_write_scatter")))
    say("ring_occupancy", int(counter("ring_occupancy")))
    say("wal_records_total", int(m["zipkin_wal_records_total"]))
    for k in range(shards):
        # State on every device, not N shards on the first one.
        occ = m[f'zipkin_shard_occupancy{{shard="{k}"}}']
        say(f"shard_occupancy[{k}]", int(occ))
        if occ <= 0:
            raise AssertionError(f"shard {k} holds no spans")


# -- main --------------------------------------------------------------------


def build_native() -> None:
    """Force-build the codec from native/span_codec.cc (the file git
    commits) and load it: a stale or copied-in .so proves nothing, and
    a daemon without it would silently measure the python decoder."""
    native.build(force=True)
    native.get_lib()
    say("native_codec", "built")


def run(args) -> dict:
    build_native()
    t0 = time.monotonic()
    chunk = CHUNK
    while chunk > 64 and chunk * 2 * N_WAVES > args.spans:
        chunk //= 2
    spans = make_spans(args.seed, args.spans, chunk)
    say("tracegen_seconds", round(time.monotonic() - t0, 1))
    per_wave = -(-len(spans) // chunk // N_WAVES) * chunk
    waves = [spans[i:i + per_wave] for i in range(0, len(spans), per_wave)]

    oracle = InMemorySpanStore()
    oracle_api = ApiServer(QueryService(oracle), None)
    rng = np.random.default_rng(args.seed + 1)
    counts = {"acked": 0, "try_later": 0}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        daemon = Daemon(args, workdir)
        try:
            device = daemon.wait_boot(BOOT_DEADLINE_S)
            say("device", f"{device['platform']} / {device['kind']} "
                          f"x{device['count']}")
            if not args.rehearse and device["platform"] != "tpu":
                raise RuntimeError(
                    f"the store's state is on {device['platform']!r}, "
                    "not on the chip")
            if args.shards and device["count"] != args.shards:
                raise RuntimeError(
                    f"--shards {args.shards} but the state spans "
                    f"{device['count']} device(s)")
            nonempty = {}
            for w, wave in enumerate(waves):
                t0 = time.monotonic()
                send_wave(daemon, oracle, wave, chunk, counts)
                wait_visible(daemon, oracle_api, wave[-1])
                say(f"wave{w}_seconds", round(time.monotonic() - t0, 1))
                last = w == len(waves) - 1
                t0 = time.monotonic()
                nonempty = compare_reads(
                    daemon, oracle, oracle_api, rng,
                    n_services=CHECK_SERVICES if last else 2,
                    n_traces=CHECK_TRACES if last else 4)
                say(f"reads{w}_seconds", round(time.monotonic() - t0, 1))
            empty = [r for r, n in nonempty.items() if not n]
            if empty:
                raise AssertionError(f"no non-empty answer for {empty}")
            say("nonempty_answers", json.dumps(nonempty))
            say("spans_acked", counts["acked"])
            say("try_later", counts["try_later"])
            if counts["acked"] < args.spans:
                raise AssertionError("fewer spans acked than asked for")
            report_metrics(daemon, args.shards)
            rc = daemon.terminate(STOP_DEADLINE_S)
            say("daemon_exit_code", rc)
            if rc != 0:
                raise RuntimeError(f"daemon exited {rc} on SIGTERM")
        except BaseException:
            daemon.kill()
            sys.stderr.write("---- daemon stdout (tail) ----\n"
                             + tail(daemon.out_path)
                             + "---- daemon stderr (tail) ----\n"
                             + tail(daemon.err_path))
            raise
    return device


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearse", action="store_true",
                   help="CPU child at a small ring and span count: the "
                        "chipless rehearsal (the result line then names "
                        "the cpu device the child reported)")
    p.add_argument("--shards", type=int, default=0,
                   help="run ONLY the sharded phase: the same daemon "
                        "with --shards N (four chips: N=4)")
    p.add_argument("--capacity", type=int, default=None,
                   help="span ring rows (per shard with --shards); "
                        "default 2^22, 2^20 per shard, 2^16 rehearsing")
    p.add_argument("--spans", type=int, default=None,
                   help="spans to send at least (default 2^18; 6000 "
                        "rehearsing)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.capacity is None:
        args.capacity = (1 << 16 if args.rehearse
                         else 1 << 20 if args.shards else 1 << 22)
    if args.spans is None:
        args.spans = 6000 if args.rehearse else 1 << 18

    device = run(args)

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError("the parent initialised a JAX backend")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
