"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent makes the span stream from the seed, starts the daemon child
(the only process on the chip), warms the cell's shapes, drives the
window through the daemon's two front doors (scribe TCP ``Log`` and
HTTP GET), then compares what is read back with the plain reference
and prints one JSON line. This parent never initialises a JAX backend.
Nothing here names a cell: configurations, traffic mixes and per-layer
metrics are files found by the names in BENCHMARK.json.

``--platform cpu --capacity N`` is the rehearsal (README.md): the child
on the CPU at a small ring; the last line then says ``cpu``.
``--benchmark-file PATH`` lays another file's keys over the root file's
(the fixture of tests/sharded/ brings its own ``configs`` and
``workloads``; a traffic mix is looked for beside such a file first);
the driver passes none of these.

However a run ends, the daemon ends with it (README.md, "How a run
ends"): SIGTERM, SIGHUP and SIGINT are raised on the main thread, so the
one ``except`` below kills the daemon's group; a SIGKILL of this process
kills the daemon through the kernel (``daemon_entry.die_with_parent``).
And a set-up that cannot be done within ``SETUP_BUDGET_S`` stops itself.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import threading
import time

os.environ["JAX_PLATFORMS"] = "cpu"  # the parent stays off the chip

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(HERE, "readers")]

import numpy as np  # noqa: E402

import compare as compare_mod  # noqa: E402
import walcheck  # noqa: E402
from daemon import Daemon, tail  # noqa: E402
from gen import Stream  # noqa: E402
from loadgen import Ingest, Reads, percentile  # noqa: E402
from reference import Reference, hex_id  # noqa: E402

BOOT_DEADLINE_S = 600.0
STOP_DEADLINE_S = 300.0
MAX_FILLS = 3  # pre-fills tried before a run gives up (each a fresh daemon)
# Process start -> window start, every pre-fill together. Above every
# set-up that has passed (537 s, ledger PR 24; 569 s, the four-shard
# fixture compiling, PR 27) and under what a compiling run may take
# (1200 s) less the window (40 s) and the longest comparison and log
# check on record (160 s + 3 s). README.md, "How a run ends".
SETUP_BUDGET_S = 900.0
PROJECT_ACKS = 32  # acks whose median period projects the pre-fill's end
ENDING = (signal.SIGTERM, signal.SIGHUP, signal.SIGINT)
T_START = time.monotonic()


class Ended(SystemExit):
    """This process was told to end: exit code 128 + the signal's number."""

    def __init__(self, signum: int):
        super().__init__(128 + signum)
        self.signal = signal.Signals(signum).name


def end_on(signum, frame) -> None:
    """One exception on the main thread for every signal that asks the
    run to end, so that ``run_cell``'s ``except`` kills the daemon; a
    second signal does not cut that short."""
    for s in ENDING:
        signal.signal(s, signal.SIG_IGN)
    raise Ended(signum)


def say(msg: str) -> None:
    print(f"[{time.monotonic() - T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def build_codec(deadline_s: float) -> None:
    """Build the native codec from native/span_codec.cc where the shared
    object is missing or older than the source (the program's own rule;
    a checkout has none, so its first run builds what git committed), in
    a child, so that this process imports nothing of the program and
    the daemon finds it built and starts no compiler of its own."""
    r = subprocess.run(
        [sys.executable, "-c",
         "from zipkin_tpu import native; native.build(); native.get_lib()"],
        cwd=ROOT, capture_output=True, text=True, timeout=deadline_s,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": ROOT + os.pathsep
             + os.environ.get("PYTHONPATH", "")})
    if r.returncode != 0:
        raise RuntimeError("native codec did not build:\n" + r.stderr[-2000:])


def budget_left(budget_s: float, before: str) -> float:
    """Seconds of the set-up's budget not yet spent: the deadline of the
    set-up's next wait. A run past its budget stops."""
    left = budget_s - (time.monotonic() - T_START)
    if left <= 0:
        raise TimeoutError(
            f"the set-up budget of {budget_s:.0f}s was passed before "
            f"{before}")
    return left


def projected_fill(acks: list, calls_left: int, now: float):
    """(seconds a call, seconds into the run at which the pre-fill would
    end), from the median period of the last ``PROJECT_ACKS`` acks: one
    slow call (a compile) does not decide it, and acks that come in
    bursts read too fast, never too slow. None before that many acks."""
    if len(acks) < PROJECT_ACKS:
        return None
    last = sorted(acks)[-PROJECT_ACKS:]
    period = statistics.median(b - a for a, b in zip(last, last[1:]))
    return period, now - T_START + calls_left * period


def fill(ingest: Ingest, first: int, n_calls: int, budget_s: float) -> None:
    """Send the pre-fill and wait for its last ack, looking once a second
    at the acks so far (the senders are not touched): a fill that would
    end past the set-up's budget, or a budget already passed, stops the
    run here and not at somebody else's ``kill``."""
    c = ingest.stream.call_spans
    ingest.run(first, first + n_calls)
    said = False
    for t in ingest.threads:
        while t.is_alive():
            t.join(1.0)
            with ingest.lock:
                acks = [r[3] for r in ingest.records]
            now = time.monotonic()
            seen = projected_fill(acks, n_calls - len(acks), now)
            if seen is None:
                budget_left(budget_s, f"the pre-fill's ack {PROJECT_ACKS}")
                continue
            period, end_s = seen
            line = (f"{len(acks)} of {n_calls} pre-fill calls acked, "
                    f"{c / max(period, 1e-9):.0f} spans/s by the median "
                    f"period of the last {PROJECT_ACKS} acks: the fill "
                    f"would end {end_s:.0f}s into the run; the set-up's "
                    f"budget is {budget_s:.0f}s")
            if end_s > budget_s:  # at the latest when the budget is passed
                say("the set-up stops itself")
                raise TimeoutError("the set-up stops itself: " + line)
            if not said:
                say(line)
                said = True
    ingest.join()


def wait_visible(daemon, ref: Reference, frames: list,
                 deadline_s: float = 60.0) -> tuple:
    """An ack means durably appended, not yet committed. Wait, up to a
    minute, until the last span of each of the newest acked calls reads
    back whole. Returns (seconds waited, calls that never did): late is
    late, but an acked span that never comes is for ``correct``."""
    c = ref.stream.call_spans
    t0 = time.monotonic()
    never = 0
    for f in frames:
        tid = ref.stream.trace_id_at(f * c + c - 1)
        want = len(ref.trace(tid))
        while True:
            status, body = daemon.request("GET", f"/api/trace/{hex_id(tid)}")
            if status == 200 and len(json.loads(body)) >= want:
                break
            if status not in (200, 404):
                raise RuntimeError(f"trace read -> {status}: {body[:300]!r}")
            if time.monotonic() - t0 > deadline_s:
                never += 1
                break
            daemon.check_alive()
            time.sleep(0.1)
    return time.monotonic() - t0, never


def warm_reads(reads: Reads, spec: dict) -> int:
    """Each route of the mix, through the window's own request path,
    until its latency stops falling."""
    w = spec.get("warm", {"min": 2, "max": 8})
    n = 0
    by_route = {}
    for j in range(len(reads.kind)):
        by_route.setdefault(reads.names[reads.kind[j]], []).append(j)
    for route, js in by_route.items():
        prev = None
        for i, j in enumerate(js[:w["max"]]):
            took = reads.get(j)
            n += 1
            if i + 1 >= w["min"] and prev is not None and took > 0.7 * prev:
                break
            prev = took
    bad = [r for r in reads.records if r[5] != 200]
    if bad:
        raise RuntimeError(f"warm-up read failed: {bad[:3]}")
    reads.records.clear()
    return n


def profile(daemon, seconds: float, out: dict) -> None:
    try:
        status, body = daemon.request(
            "POST", "/debug/profile", {"seconds": seconds})
        if status != 200:
            raise RuntimeError(f"/debug/profile -> {status}: {body[:300]!r}")
        out["dir"] = json.loads(body)["profileDir"]
    except Exception as e:
        out["error"] = e


def end_to_end(ingest, reads, w0: float, t_end: float, setup_s: float,
               call_spans: int, say) -> dict:
    """Every end-to-end metric this traffic yields, over all the work of
    the window and all its time: every call sent within ``--seconds``,
    from the window's start until the last of them was answered."""
    out = {"setup_s": (setup_s, "s")}
    done = [r for r in ingest.records if r[5]]
    if done:
        lat = [(r[3] - (r[1] if r[1] is not None else r[2])) * 1e3
               for r in done]
        out["ack_p95_ms"] = (percentile(lat, 0.95), "ms")
        # The connections queue on one device step, so the latencies
        # stand on rungs a step apart and the 95th percentile can fall
        # in the thin part between two (PERF.md 2): its neighbours are
        # printed beside it.
        say("ack latency percentiles 90..99 ms: " + " ".join(
            f"{percentile(lat, q / 100):.1f}" for q in range(90, 100)))
        work_s = max(r[3] for r in done) - w0
        out["acked_spans_per_s"] = (len(done) * call_spans / work_s,
                                    "spans/s")
        # The daemon acks in bursts (it runs ahead of the device, then
        # waits for it), so the count inside a fixed number of seconds
        # turns on where the last burst is cut; it is printed beside.
        inside = sum(1 for r in done if r[3] <= t_end)
        say(f"{len(done)} calls acked in {work_s:.3f}s; {inside} of them "
            f"inside the first {t_end - w0:.0f}s, "
            f"{inside * call_spans / (t_end - w0):.1f} spans/s")
    if reads is not None and reads.records:
        lat = [(r[4] - r[2]) * 1e3 for r in reads.records if r[5] == 200]
        out["read_p50_ms"] = (percentile(lat, 0.5), "ms")
        out["read_p95_ms"] = (percentile(lat, 0.95), "ms")
    return out


def client_counts(ingest, reads, w0: float, seconds: float,
                  call_spans: int) -> dict:
    """The load generator's own counts and clocks, for the per-layer
    readers (``{"client": name}`` terms): over the same calls and the
    same seconds as the end-to-end rate, which the two /metrics scrapes
    bracket."""
    done = [r for r in ingest.records if r[5]]
    c = {
        "window_s": max((r[3] for r in done), default=w0 + seconds) - w0,
        "log_calls_sent": ingest.sent_calls,
        "try_later": ingest.try_later,
        "acked_calls": len(done),
        "acked_spans": len(done) * call_spans,
        "offered_calls": (math.ceil(seconds / ingest.interval)
                          if ingest.interval else len(ingest.records)),
    }
    if reads is not None:
        c["reads_ok"] = sum(1 for r in reads.records if r[5] == 200)
    return c


def per_layer(bench: dict, workload: str, ctx: dict) -> dict:
    """Each per-layer metric of BENCHMARK.json that this cell reports;
    how it is read is in the metric's own file, and the reader named
    there is found by name under readers/."""
    out = {}
    for m in bench["per_layer"]:
        if not reports(m, bench, workload):
            continue
        spec = load_json(os.path.join(
            bench["paths"][0], "layer_metrics", m["name"] + ".json"))
        reader = importlib.import_module(spec["source"]["reader"])
        value = reader.read(spec["source"], ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            say(f"per-layer {m['name']}: nothing to read")
    return out


def ring_fill(scrape: dict) -> str:
    """The rings' occupancy as /metrics has it, for the run's log."""
    return ", ".join(
        f"{k.split(chr(34))[1]} {int(v)}" for k, v in sorted(scrape.items())
        if k.startswith("zipkin_store_counter{")
        and ("ring_occupancy" in k or "ring_laps" in k))


def seconds_split(before: dict, after: dict) -> str:
    """Every timing sketch of /metrics that moved over the window
    (``obs.stage``'s and the layers' own), most seconds first, as
    ``name seconds/runs``: where a call's time went, for the run's log."""
    rows = []
    for k, v in after.items():
        if "_seconds_sum" in k and v > before.get(k, 0.0):
            n = k.replace("_seconds_sum", "_seconds_count")
            rows.append((v - before.get(k, 0.0), k.replace(
                "_seconds_sum", "").replace("zipkin_", ""),
                after.get(n, 0.0) - before.get(n, 0.0)))
    return ", ".join(f"{k} {d:.3f}s/{int(n)}"
                     for d, k, n in sorted(rows, reverse=True))


def reports(metric: dict, bench: dict, workload: str) -> bool:
    """Whether this cell reports the metric: the cells its entry lists
    or, where it lists none, every cell that reports the end-to-end
    metric it moves (all cells, for an end-to-end metric)."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    if "moves" not in metric:
        return True
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == metric["moves"])
    return reports(moved, bench, workload)


def shards_of(flags: list) -> int:
    """How many sets of rings the daemon keeps: its ``--shards``, else 1."""
    return int(flags[flags.index("--shards") + 1]) if "--shards" in flags \
        else 1


def lap_spans(config: dict, traffic: dict, capacity: int,
              shards: int = 1) -> int:
    """Spans of this traffic that fill the ring that fills first, in
    every shard: ``--capacity`` sizes each shard's rings, and the
    traces spread evenly over the shards."""
    per_span = {"span": 1, "annotation": traffic["annotations_per_span"],
                "binary": traffic["binary_per_span"]}
    return shards * min(
        capacity * rows // per_span[ring]
        for ring, rows in config["ring_rows_per_capacity_row"].items())


def retained_spans(config: dict, traffic: dict, lap: int) -> int:
    """Held to be whole: the configuration's share of a lap (the rest is
    room for the daemon's own self-trace rows), less the calls that
    can be in flight at once, which may be committed out of send order."""
    return (int(config["retained_whole_share"] * lap)
            - traffic["ingest"]["connections"] * traffic["call_spans"])


def run_cell(args) -> dict:
    bench = load_json("BENCHMARK.json")
    if args.benchmark_file:
        bench.update(load_json(args.benchmark_file))
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no workload {args.workload!r} in "
                         + (args.benchmark_file or "BENCHMARK.json"))
    config = load_json(next(c["file"] for c in bench["configs"]
                            if c["name"] == cell["config"]))
    traffic_file = os.path.join(
        bench["paths"][0], "traffic", cell["traffic"] + ".json")
    if args.benchmark_file:  # a fixture may bring a mix of its own
        beside = os.path.join(os.path.dirname(args.benchmark_file),
                              "traffic", cell["traffic"] + ".json")
        if os.path.exists(os.path.join(ROOT, beside)):
            traffic_file = beside
    traffic = load_json(traffic_file)
    flags = list(config["daemon_flags"])
    if args.capacity:
        flags[flags.index("--capacity") + 1] = str(args.capacity)
    shards = shards_of(flags)
    platform = args.platform or config["platform"]
    seconds = float(args.seconds)
    c = traffic["call_spans"]
    # The window runs on full rings: the pre-fill is as many laps of the
    # ring that fills first as the traffic file says, in whole calls.
    lap = lap_spans(config, traffic,
                    int(flags[flags.index("--capacity") + 1]), shards)
    n_prefill = math.ceil(traffic["prefill_laps"] * lap / c)
    ing_spec, rd_spec = traffic["ingest"], traffic.get("reads")
    retained = retained_spans(config, traffic, lap)
    # where a control drops a call, it drops one of the window's
    os.environ.setdefault("BENCH_FAULT_AT", str(n_prefill + 6))
    # Only a run with a fault planted (controls and tests) may have
    # another budget, from the environment the faults are set by.
    budget_s = float(os.environ.get("BENCH_FAULT_SETUP_BUDGET_S",
                                    SETUP_BUDGET_S)
                     if args.fault else SETUP_BUDGET_S)

    build_codec(budget_left(budget_s, "the codec's build"))
    daemon = stream = None
    try:
        # -- boot, pre-fill and warm-up: this cell's shapes, through the
        # window's own doors, until the rings are full. The window runs on
        # a state that the reference describes exactly: a call answered
        # TRY_LATER is resent, and the daemon may have stored it before it
        # pushed back (it does where the ack's wait for the fsync times
        # out), so a daemon whose pre-fill was pushed back is stopped and
        # a fresh one is filled with the stream's next calls. --------------
        first = 0
        for attempt in range(1, MAX_FILLS + 1):
            # Inside TMPDIR, the driver's per-side directory: the WAL of a
            # run is some hundreds of MB.
            workdir = tempfile.mkdtemp(prefix="bench_run_")
            daemon = Daemon(flags, platform, workdir, fault=args.fault)
            say(f"{args.workload} seed {args.seed} seconds {seconds} trace "
                f"{args.trace}; daemon spawned pid {daemon.proc.pid} ports "
                f"{daemon.http_port} {daemon.scribe_port}; workdir "
                f"{workdir}")
            if stream is None:  # made while the daemon boots
                t0 = time.monotonic()
                # Three passes over the pool fit in what is held whole, so
                # the newest traces by timestamp (the last two passes') are
                # all held: the traffic file's pool, cut only where a
                # rehearsal's ring is too small for it.
                stream = Stream(
                    args.seed,
                    min(traffic["pool_spans"], retained // 3 // c * c), c,
                    traffic["n_services"], traffic["pass_shift_us"])
                say(f"stream: calls of {c} spans; one lap is {lap} spans, "
                    f"pre-fill {n_prefill} calls, held whole {retained}; "
                    f"pool made in {time.monotonic() - t0:.1f}s")
            device = daemon.wait_boot(min(BOOT_DEADLINE_S, budget_left(
                budget_s, "the daemon's boot")))
            say(f"boot line after {time.monotonic() - daemon.t_spawn:.1f}s: "
                f"{device}")
            if device["platform"] != platform:
                raise RuntimeError(
                    f"the store's state is on {device['platform']!r}, "
                    f"not on {platform!r}")
            if device["count"] != cell["chips"]:
                raise RuntimeError(
                    f"the cell asks for {cell['chips']} chip(s); "
                    f"the state spans {device['count']}")
            ingest = Ingest(daemon.scribe_port, stream, ing_spec,
                            daemon.check_alive)
            t0 = time.monotonic()
            fill(ingest, first, n_prefill, budget_s)
            if any(not r[5] for r in ingest.records):
                raise RuntimeError("a pre-fill Log call was never acked")
            ack_time = {r[0]: r[3] for r in ingest.records}
            ref = Reference(stream, ack_time, retained, shards)
            _, never = wait_visible(
                daemon, ref, sorted(ack_time)[-ing_spec["connections"]:],
                deadline_s=min(600.0, budget_left(
                    budget_s, "the pre-fill's spans were readable")))
            if never:
                raise RuntimeError(
                    "the pre-fill's spans never became readable")
            # The daemon gives up an ack's wait for the fsync after 30 s:
            # the slowest call says how near a stall (a program loaded or
            # compiled under the store's lock) came to that.
            slowest = max(ingest.records, key=lambda r: r[3] - r[2])
            say(f"pre-fill: {n_prefill} calls acked and visible in "
                f"{time.monotonic() - t0:.1f}s (try_later "
                f"{ingest.try_later}; slowest call {slowest[0]} "
                f"{slowest[3] - slowest[2]:.1f}s)")
            if not ingest.try_later:
                break
            say(f"pre-fill {attempt} was pushed back: calls "
                f"{sorted(r[0] for r in ingest.records if r[4] > 1)} were "
                f"resent; a fresh daemon is filled from call "
                f"{first + n_prefill} on")
            rc = daemon.terminate(min(STOP_DEADLINE_S, budget_left(
                budget_s, f"pre-fill {attempt + 1}")))
            if rc != 0:
                raise RuntimeError(f"daemon exited {rc} on SIGTERM")
            shutil.rmtree(workdir, ignore_errors=True)
            first += n_prefill
        else:
            raise RuntimeError(
                f"{MAX_FILLS} pre-fills in a row were pushed back "
                "(TRY_LATER): no daemon state that the reference describes")
        ingest.records.clear()
        ingest.try_later = ingest.sent_calls = 0
        rng = np.random.default_rng([int(args.seed), 0xBEAD])
        reads = None
        if rd_spec:
            reads = Reads(daemon.http_port, stream, rd_spec, rng, ingest)
            t0 = time.monotonic()
            n = warm_reads(reads, rd_spec)
            say(f"read warm-up: {n} reads in {time.monotonic() - t0:.1f}s")

        # -- the window ----------------------------------------------------
        before = daemon.scrape()
        starved0 = stream.starved_s
        budget_left(budget_s, "the window")
        setup_s = time.monotonic() - T_START
        say(f"window starts; setup_s {setup_s:.3f}; rings "
            + ring_fill(before))
        w0, w_end = ingest.run(
            first + n_prefill, None, seconds,
            ing_spec.get("spans_per_s") if ing_spec["loop"] == "open"
            else None)
        if reads is not None:
            reads.run(seconds, rd_spec["per_s"])
        prof = {}
        prof_thread = None
        if args.trace:
            trace_s = min(traffic.get("trace_seconds", 3.0), seconds / 2)
            time.sleep(max(0.0, (seconds - trace_s) / 2))
            prof_thread = threading.Thread(
                target=profile, args=(daemon, trace_s, prof), daemon=True)
            prof_thread.start()
        ingest.join()
        if reads is not None:
            reads.join()
        if prof_thread is not None:
            prof_thread.join()
            if "error" in prof:
                raise prof["error"]
        after = daemon.scrape()
        say(f"window closed: {len(ingest.records)} calls, try_later "
            f"{ingest.try_later}; rings " + ring_fill(after)
            + (f", {len(reads.records)} reads" if reads else ""))
        say("seconds by sketch over the window: "
            + seconds_split(before, after))
        acks = sorted(r[3] - w0 for r in ingest.records if r[5])
        say("acks by second of the window: " + " ".join(
            str(sum(1 for a in acks if k <= a < k + 1))
            for k in range(math.ceil(seconds))))
        gaps = sorted(((b - a, a) for a, b in zip([0.0] + acks, acks)),
                      reverse=True)[:3]
        say("longest waits for the next ack: " + ", ".join(
            f"{g * 1e3:.0f} ms from {a:.2f}s" for g, a in gaps))
        starved = stream.starved_s - starved0
        if starved > 0.01 * seconds:
            raise RuntimeError(
                f"the senders waited {starved:.2f}s for frames: the load "
                "generator, not the daemon, set this window's rate")

        # -- results of the window -------------------------------------------
        e2e = end_to_end(ingest, reads, w0, w_end, setup_s, c, say)
        attempted = len(ingest.records) + (len(reads.records) if reads else 0)
        failed = sum(1 for r in ingest.records if not r[5]) + (
            sum(1 for r in reads.records if r[5] != 200) if reads else 0)
        for r in (reads.records if reads else []):
            if r[5] != 200:
                say(f"failed read {r[1]} status {r[5]}")
                break

        # -- the comparison that decides `correct` ---------------------------
        ack_time.update({r[0]: r[3] for r in ingest.records if r[5]})
        ref = Reference(stream, ack_time, retained, shards)
        newest = sorted(r[0] for r in ingest.records if r[5])[
            -ing_spec["connections"]:]
        lag, never = wait_visible(daemon, ref, newest or sorted(ack_time)[-1:])
        say(f"last acked spans visible {lag:.2f}s after the window"
            + (f"; {never} call(s) NEVER" if never else ""))
        t0 = time.monotonic()
        numbers = compare_mod.compare(
            daemon, ref, rng, traffic.get("compare", {}), say)
        numbers["acked_calls_never_readable"] = never
        say(f"comparison over HTTP took {time.monotonic() - t0:.1f}s")
        rc = daemon.terminate(STOP_DEADLINE_S)
        if rc != 0:
            raise RuntimeError(f"daemon exited {rc} on SIGTERM")
        t0 = time.monotonic()
        wal_dir = flags[flags.index("--wal-dir") + 1].replace(
            "{workdir}", workdir)
        numbers.update(walcheck.check(
            wal_dir, daemon.fsync_path, ref, ack_time,
            traffic["annotations_per_span"], traffic["binary_per_span"], say))
        say(f"the log held against the acks in "
            f"{time.monotonic() - t0:.1f}s")
    except BaseException as e:
        if isinstance(e, Ended):
            say(f"ended by {e.signal}")
        if daemon is not None:
            daemon.kill()
            sys.stderr.write("---- daemon stdout (tail) ----\n"
                             + tail(daemon.out_path)
                             + "---- daemon stderr (tail) ----\n"
                             + tail(daemon.err_path))
            shutil.rmtree(workdir, ignore_errors=True)
        raise
    finally:
        if stream is not None:
            stream.close()

    mem = daemon.memory_report()
    say(f"memory peak by device: {mem.get('per_device')}")
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": mem.get("memory_peak_bytes", 0),
           "state_bytes": device["state_bytes"]}
    result = {"attempted": attempted, "failed": failed}
    if args.trace:
        import trace_reduce

        trace = trace_reduce.load(prof["dir"])
        shutil.rmtree(prof["dir"], ignore_errors=True)
        ctx = {"before": before, "after": after, "trace": trace,
               "device_kind": device["kind"], "traffic": traffic,
               "client": client_counts(ingest, reads, w0, seconds, c)}
        metrics = per_layer(bench, args.workload, ctx)
        if trace is not None:
            dev["busy_s"] = trace.busy_s
            dev["window_s"] = trace.window_s
            result["breakdown"] = trace.breakdown()
        if args.dump_trace:
            trace_reduce.dump(trace, args.dump_trace)
    else:
        metrics = {}
        for m in bench["end_to_end"]:
            if not reports(m, bench, args.workload):
                continue
            if m["name"] not in e2e or e2e[m["name"]][0] is None:
                raise RuntimeError(f"this traffic yields no {m['name']}")
            metrics[m["name"]] = {"value": e2e[m["name"]][0],
                                  "unit": m["unit"]}
    for name, (value, unit) in e2e.items():
        say(f"{name} = {value} {unit}")
    if reads is not None:
        for route in reads.names:
            lat = [(r[4] - r[2]) * 1e3 for r in reads.records
                   if r[1] == route]
            say(f"reads {route}: n {len(lat)} p50 {percentile(lat, 0.5)} "
                f"p95 {percentile(lat, 0.95)} ms")
    shutil.rmtree(workdir, ignore_errors=True)

    compared = {k: {"value": v, "limit": compare_mod.LIMITS[k]}
                for k, v in numbers.items()}
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    return {"correct": correct, **result, "metrics": metrics,
            "device": dev, "compared": compared}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, default=0, choices=(0, 1))
    # the rehearsal; the driver passes neither
    p.add_argument("--platform", choices=("cpu", "tpu"), default=None)
    p.add_argument("--capacity", type=int, default=0)
    p.add_argument("--benchmark-file", default="",
                   help="a file whose keys stand in for BENCHMARK.json's, "
                        "relative to the checkout's root (fixtures only)")
    p.add_argument("--fault", default="",
                   help="plant a fault of tests/faults.py in the daemon "
                        "(controls and tests only)")
    p.add_argument("--dump-trace", default="",
                   help="write a text summary of the trace's planes here")
    args = p.parse_args(argv)
    for s in ENDING:  # but one that the caller had ignored stays ignored
        if signal.getsignal(s) is not signal.SIG_IGN:
            signal.signal(s, end_on)
    result = run_cell(args)
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise RuntimeError("the parent initialised a JAX backend")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
