"""All-in-one daemon: collector + device store + query + HTTP API.

Usage:
    python -m zipkin_tpu.main.example --port 9411 [--seed-traces 10]
        [--sample-rate 1.0] [--adaptive-target N] [--checkpoint DIR]
        [--memory-store]

Reference shape: zipkin-example's Main (scribe receiver + store + query
+ web in one process) and zipkin-deployment-collector's sampler wiring.
"""

from __future__ import annotations

import argparse
import signal
import threading
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=9411)
    p.add_argument("--scribe-port", type=int, default=9410,
                   help="framed-thrift Scribe.Log TCP port (0 disables)")
    p.add_argument("--memory-store", action="store_true",
                   help="use the in-memory reference store instead of TPU")
    p.add_argument("--shards", type=int, default=0,
                   help="serve from an N-shard ShardedSpanStore over the "
                        "device mesh (0 = single-device store); needs N "
                        "visible devices — use --platform cpu with "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                        "to simulate")
    p.add_argument("--capacity", type=int, default=1 << 16,
                   help="span ring capacity (device store)")
    p.add_argument("--layout", default="ring",
                   choices=("ring", "paged"),
                   help="span-plane layout: 'ring' = the FIFO ring "
                        "(default); 'paged' = fixed-size device pages "
                        "with per-trace chaining and LRW page reclaim, "
                        "so one hot 10k-span trace can't evict a "
                        "thousand cold 1-span traces "
                        "(docs/STORAGE_TIERS.md; single-device stores "
                        "only; echoed at /vars/layout)")
    p.add_argument("--page-rows", type=int, default=128,
                   help="rows per page for --layout paged (power of "
                        "two dividing --capacity; echoed at "
                        "/vars/pageRows)")
    p.add_argument("--batch-spans", type=int, default=0,
                   help="ingest batch escalation: max spans per device "
                        "launch (0 = the store's legacy 4096 default; "
                        "the ring guards still clamp to capacity/2 — "
                        "see docs/PERFORMANCE.md for picking the knee)")
    p.add_argument("--rank-path", default="auto",
                   choices=("auto", "argsort", "counting"),
                   help="index-write FIFO rank implementation (both "
                        "are bitwise-identical; auto picks the "
                        "counting sort when its scratch fits — "
                        "docs/PERFORMANCE.md)")
    p.add_argument("--window-seconds", type=int, default=60,
                   help="windowed-analytics time-bucket width for the "
                        "(service × time) Moments-sketch arena behind "
                        "/api/windowed_quantiles, /api/slo_burn and "
                        "/api/latency_heatmap (0 disables the arena; "
                        "echoed at /vars/windowSeconds — "
                        "docs/OBSERVABILITY.md)")
    p.add_argument("--window-buckets", type=int, default=64,
                   help="windowed-analytics ring length: retention is "
                        "window_seconds × window_buckets of cells per "
                        "service; stale slots self-clear on reuse "
                        "(echoed at /vars/windowBuckets)")
    p.add_argument("--sample-rate", type=float, default=1.0)
    p.add_argument("--adaptive-target", type=float, default=0.0,
                   help="target stored spans/minute; 0 disables adaptive")
    p.add_argument("--queue-max", type=int, default=500)
    p.add_argument("--queue-workers", type=int, default=10)
    p.add_argument("--no-self-trace-ingest", action="store_true",
                   help="disable the per-ingest-step zipkin-tpu self "
                        "spans (API-request self-tracing stays on; "
                        "see docs/OBSERVABILITY.md)")
    p.add_argument("--no-fleet-obs", action="store_true",
                   help="disable the fleet-observability surface: "
                        "batch-lineage tracing (WAL-stamped causal "
                        "spans across ship/apply), metrics federation "
                        "(/metrics?fleet=1, /api/fleet), and the stall "
                        "watchdog behind /api/health + /debug/events "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--lineage-sample-every", type=int, default=0,
                   help="trace 1-in-N launch units end-to-end through "
                        "WAL append → fsync → ship → follower apply "
                        "(0 = the default 64; 1 traces every unit — "
                        "bench/debug only)")
    p.add_argument("--cold-tier", action="store_true",
                   help="capture ring evictions into the compressed "
                        "segment archive and federate queries across "
                        "hot + cold (store/archive; single-device "
                        "stores only)")
    p.add_argument("--pipeline-depth", type=int, default=0,
                   help="pipelined ingest: overlap host encode + H2D "
                        "staging with device compute behind a bounded "
                        "prefetch queue of this depth (0 = serial "
                        "write path; on --shards N the pipeline feeds "
                        "every shard's fused commit — see "
                        "docs/INGEST_PIPELINE.md)")
    p.add_argument("--capture-backlog", type=int, default=4,
                   help="cold-tier async sealer: bound on pulled-but-"
                        "unsealed eviction capture windows; a full "
                        "backlog is the only way capture can stall "
                        "ingest (0 = seal inline on the write path)")
    p.add_argument("--wal-dir", default=None,
                   help="write-ahead log dir: journal every ingest "
                        "batch before commit, replay the tail at boot, "
                        "and switch scribe/kafka receivers to "
                        "ack-after-durable-append; on --shards N this "
                        "is a per-shard + group-commit-epoch log tree "
                        "(see docs/DURABILITY.md, docs/SHARDING.md)")
    p.add_argument("--wal-fsync", default="interval",
                   choices=("batch", "interval", "off"),
                   help="WAL fsync policy: per-batch, group-commit "
                        "interval (default), or off (page-cache only)")
    p.add_argument("--wal-fsync-interval", type=float, default=0.05,
                   help="group-commit fsync cadence in seconds "
                        "(--wal-fsync interval)")
    p.add_argument("--wal-segment-bytes", type=int, default=64 << 20,
                   help="roll WAL segment files at this size; whole "
                        "segments are deleted once a checkpoint "
                        "covers them")
    p.add_argument("--wal-retain-bytes", type=int, default=0,
                   help="shipping retention floor: keep at least this "
                        "many newest WAL bytes on disk even when a "
                        "checkpoint covers them, so reconnecting "
                        "followers catch up from the log instead of "
                        "re-anchoring (0 = truncate everything "
                        "covered; registered follower cursors always "
                        "pin regardless — docs/REPLICATION.md)")
    p.add_argument("--ship-port", type=int, default=0,
                   help="serve sealed WAL records to replication "
                        "followers on this framed-TCP port (0 "
                        "disables; requires --wal-dir — "
                        "docs/REPLICATION.md)")
    p.add_argument("--follow", default=None, metavar="HOST:PORT",
                   help="run as a replication follower of the primary "
                        "at HOST:PORT instead of a collector daemon: "
                        "no ingest ports open, reads serve from the "
                        "replicated store, staleness is exposed at "
                        "/api/replication")
    p.add_argument("--follow-mode", default="replica",
                   choices=("replica", "standby"),
                   help="follower role: 'replica' = device-free CPU "
                        "read replica (SketchMirror + cold segments, "
                        "no TPU); 'standby' = full device store "
                        "replaying through the normal commit body, "
                        "ready for failover")
    p.add_argument("--follow-poll-ms", type=float, default=20.0,
                   help="follower fetch-poll cadence when the primary "
                        "has nothing new (each fetch is also the ack "
                        "that advances the primary's retention pin)")
    p.add_argument("--follower-name", default=None,
                   help="stable follower identity for the primary's "
                        "cursor registry (default: <mode>-<hostname> — "
                        "STABLE across restarts, so a restarted "
                        "follower reuses its retention pin instead of "
                        "leaking a dead one; set explicitly when "
                        "running several same-mode followers per host)")
    p.add_argument("--query-window-ms", type=float, default=None,
                   help="resident query executor micro-batch window "
                        "(ms): how long an idle-entry request waits "
                        "for company before its coalesced device "
                        "launch (default: 2 ms on device stores, 0 on "
                        "the memory store; runtime-adjustable via "
                        "/vars/queryWindowMs — docs/QUERY_ENGINE.md)")
    p.add_argument("--seed-traces", type=int, default=0,
                   help="generate N synthetic traces at startup")
    p.add_argument("--checkpoint", default=None,
                   help="checkpoint dir: restore at boot, save on exit "
                        "and every --checkpoint-interval seconds")
    p.add_argument("--checkpoint-interval", type=float, default=300.0)
    p.add_argument("--platform", default=None, choices=("cpu", "tpu"),
                   help="force the jax backend; without it JAX picks "
                        "one, and the boot line names the device the "
                        "store's state landed on")
    return p


def _side_rings(capacity: int) -> dict:
    """Annotation / binary-annotation ring rows for a ``--capacity``
    span ring. The library defaults are sized for the default 2^16
    ring; a bigger span ring with those side rings serves spans whose
    annotations were lapped long before the span rows were (a 2^22
    ring kept whole annotations for ~43k tracegen spans). Past the
    defaults the side rings grow with the span ring at the cert
    geometry's ratio: 2 annotation rows and 1 binary row per span row.
    No flag sizes them; up to --capacity 2^17 nothing changes."""
    from zipkin_tpu.store.device import StoreConfig

    base = StoreConfig()
    return {
        "ann_capacity": max(base.ann_capacity, 2 * capacity),
        "bann_capacity": max(base.bann_capacity, capacity),
    }


def build_app(args):
    from zipkin_tpu.api.server import ApiServer
    from zipkin_tpu.ingest.collector import Collector
    from zipkin_tpu.query.service import QueryService
    from zipkin_tpu.sampler.adaptive import AdaptiveConfig
    from zipkin_tpu.sampler.core import Sampler

    if args.checkpoint and args.memory_store:
        raise SystemExit(
            "--checkpoint requires a device store (the in-memory "
            "reference store has no snapshot support)"
        )
    if args.layout != "ring":
        # The paged planner is per-store host state; the sharded
        # store's stacked states have no per-shard planner yet, and
        # the memory store has no device layout at all.
        if args.memory_store:
            raise SystemExit(
                "--layout paged requires a device store (the "
                "in-memory reference store has no span planes)"
            )
        if args.shards:
            raise SystemExit(
                "--layout paged requires the single-device store "
                "(the sharded store's per-shard page planner is not "
                "wired yet)"
            )
    store = None
    if args.checkpoint:
        from zipkin_tpu import checkpoint

        if checkpoint.exists(args.checkpoint):
            # A sharded snapshot restores a ShardedSpanStore (shard
            # count from the snapshot; must match --shards if given).
            # exists() includes the .old mid-swap fallback — booting
            # FRESH after a crashed save would replay the WAL tail
            # against empty dictionaries (lineage error at best,
            # silent loss of checkpoint-covered spans at worst).
            # config_defaults: a pre-rev-14 snapshot (no window keys)
            # restores with an EMPTY window arena at the flag
            # geometry; a rev-14+ snapshot's saved geometry wins.
            store = checkpoint.load(args.checkpoint, config_defaults={
                "window_seconds": args.window_seconds,
                "window_buckets": args.window_buckets,
            })
            n = getattr(store, "n", 0)
            if args.shards and n != args.shards:
                raise SystemExit(
                    f"checkpoint has {n or 1} shard(s); --shards "
                    f"{args.shards} does not match"
                )
    if store is None:
        if args.memory_store:
            from zipkin_tpu.store.memory import InMemorySpanStore

            store = InMemorySpanStore()
            # Exact-scan windowed analytics use the same bucket width
            # the device arena would (0 keeps the 60s default — the
            # scan path has no arena to disable).
            if args.window_seconds > 0:
                store.window_seconds = args.window_seconds
        elif args.shards:
            import jax
            import numpy as np
            from jax.sharding import Mesh

            from zipkin_tpu.parallel.shard import ShardedSpanStore
            from zipkin_tpu.store.device import StoreConfig

            devices = jax.devices()
            if len(devices) < args.shards:
                raise SystemExit(
                    f"--shards {args.shards} but only {len(devices)} "
                    f"devices visible (see --shards help)"
                )
            mesh = Mesh(np.array(devices[:args.shards]),
                        axis_names=("shard",))
            # Windowed analytics runs per shard (the fused step bumps
            # every shard's cell census); reads merge the shard
            # mirrors' arenas lazily into the fleet view
            # (store/mirror.FleetMirror) with zero device round-trips
            # — docs/SHARDING.md.
            store = ShardedSpanStore(
                mesh, StoreConfig(
                    capacity=args.capacity,
                    **_side_rings(args.capacity),
                    batch_spans=args.batch_spans,
                    rank_path=args.rank_path,
                    window_seconds=args.window_seconds,
                    window_buckets=args.window_buckets,
                ),
                dispatch_window_s=(
                    args.query_window_ms / 1000.0
                    if args.query_window_ms is not None else 0.0),
            )
        else:
            from zipkin_tpu.store.device import StoreConfig
            from zipkin_tpu.store.tpu import TpuSpanStore

            store = TpuSpanStore(StoreConfig(
                capacity=args.capacity,
                **_side_rings(args.capacity),
                batch_spans=args.batch_spans,
                rank_path=args.rank_path,
                window_seconds=args.window_seconds,
                window_buckets=args.window_buckets,
                layout=args.layout,
                page_rows=args.page_rows,
            ))
    if args.cold_tier:
        if hasattr(store, "archive"):
            # Restored tiered checkpoint: already wrapped, but the
            # daemon still wants compaction off the ingest write path.
            store.archive.start_compactor()
        else:
            if args.memory_store or getattr(store, "n", 0):
                raise SystemExit(
                    "--cold-tier requires the single-device store "
                    "(the sharded store's per-shard capture is not "
                    "wired yet)"
                )
            from zipkin_tpu.store.archive import TieredSpanStore

            store = TieredSpanStore(store, background_compaction=True)
    # The async capture sealer takes effect the first time a capture
    # window is pulled, so the knob just needs to be set before writes.
    hot = getattr(store, "hot", store)
    if hasattr(hot, "capture_backlog"):
        hot.capture_backlog = max(0, args.capture_backlog)
    if args.wal_dir:
        if not hasattr(hot, "attach_wal"):
            raise SystemExit(
                "--wal-dir requires a device store (the in-memory "
                "reference store has no journaled commit path)"
            )
        from zipkin_tpu.wal import ShardedWal, WriteAheadLog, replay_into

        n_shards = getattr(hot, "n", 0)
        if n_shards:
            # Per-shard segment logs + a group-commit epoch log: one
            # journal entry per fused launch unit, recovery replays
            # only COMPLETE epochs (wal/sharded.py).
            if args.ship_port or args.wal_retain_bytes:
                raise SystemExit(
                    "--ship-port/--wal-retain-bytes are single-log "
                    "features; the sharded group-commit log does not "
                    "ship to followers yet"
                )
            wal = ShardedWal(
                args.wal_dir, n_shards, fsync=args.wal_fsync,
                interval_s=args.wal_fsync_interval,
                segment_bytes=args.wal_segment_bytes,
            )
        else:
            wal = WriteAheadLog(
                args.wal_dir, fsync=args.wal_fsync,
                interval_s=args.wal_fsync_interval,
                segment_bytes=args.wal_segment_bytes,
                retain_bytes=args.wal_retain_bytes,
            )
        # Boot-time recovery: the checkpoint (restored above, or a
        # fresh store) is the base; every WAL record past its applied
        # sequence replays through the normal ingest path — capture,
        # sealing, and sweep cadence included — BEFORE the collector's
        # pipeline starts and the ports open.
        hot.attach_wal(wal)
        stats = replay_into(store, wal)
        if stats["replayed_records"]:
            print(f"wal: replayed {stats['replayed_records']} records "
                  f"({stats['replayed_spans']} spans) in "
                  f"{stats['replay_s']}s")
    adaptive = (
        AdaptiveConfig(target_store_rate=args.adaptive_target)
        if args.adaptive_target > 0 else None
    )
    collector = Collector(
        store, sampler=Sampler(args.sample_rate), adaptive=adaptive,
        max_queue=args.queue_max, concurrency=args.queue_workers,
        self_trace=not args.no_self_trace_ingest,
        pipeline_depth=args.pipeline_depth,
    )
    tracker = None
    watchdog = None
    recorder = None
    if not args.no_fleet_obs:
        from zipkin_tpu import obs
        from zipkin_tpu.obs import fleet as fobs

        reg = obs.default_registry()
        # Batch-lineage tracing: spans land through store.apply so they
        # live in the system's own store (and ride the WAL/ship path
        # like any span). attach_lineage is a no-op journal-wise until
        # a single-log WAL is attached; the sharded group-commit log
        # does not stamp lineage yet, but the tracker still collects
        # dispatcher + API-parented spans there.
        tracker = fobs.LineageTracker(
            store.apply, registry=reg,
            sample_every=args.lineage_sample_every or None)
        if hasattr(hot, "attach_lineage"):
            hot.attach_lineage(tracker)
        disp = getattr(hot, "dispatcher", None)
        if disp is not None:
            disp.span_sink = tracker
        recorder = fobs.FlightRecorder()
        watchdog = fobs.Watchdog(recorder=recorder, registry=reg)
        watchdog.add_probe("pipeline", fobs.pipeline_stall_probe(hot))
        watchdog.add_probe("sealer", fobs.sealer_backlog_probe(hot))
        wal_obj = getattr(store, "wal", None)
        if wal_obj is not None and hasattr(wal_obj, "sync_error"):
            watchdog.add_probe("wal_fsync",
                               fobs.fsync_parked_probe(wal_obj))
        if disp is not None:
            watchdog.add_probe("dispatcher",
                               fobs.dispatcher_stuck_probe(disp))
    shipper = None
    if args.ship_port:
        if getattr(store, "wal", None) is None:
            raise SystemExit("--ship-port requires --wal-dir (sealed "
                             "WAL records are what gets shipped)")
        from zipkin_tpu.replicate import WalShipper

        shipper = WalShipper(store, tracker=tracker)
        if watchdog is not None:
            from zipkin_tpu.obs import fleet as fobs

            def _worst_follower_lag():
                st = shipper.status()
                lags = [f["lagRecords"]
                        for f in st.get("followers", {}).values()]
                return {"lagRecords": max(lags) if lags else 0}

            watchdog.add_probe(
                "follower_lag",
                fobs.follower_lag_probe(_worst_follower_lag))
    fleet = None
    if not args.no_fleet_obs:
        from zipkin_tpu import obs
        from zipkin_tpu.obs import fleet as fobs

        fleet = fobs.FleetObs(
            role="primary", registry=obs.default_registry(),
            tracker=tracker, watchdog=watchdog, recorder=recorder,
            remote_sources=(shipper.fleet_sources
                            if shipper is not None else None),
            replication=(shipper.status
                         if shipper is not None else None),
        )
    window_s = (args.query_window_ms / 1000.0
                if args.query_window_ms is not None else None)
    api = ApiServer(
        QueryService(store, coalesce_window_s=window_s), collector,
        replication=shipper.status if shipper is not None else None,
        fleet=fleet,
    )
    return store, collector, api, shipper


def build_follower_app(args):
    """Follower daemon (--follow): connect to the primary's ship port,
    build the local store from the primary's config, and serve the
    read API from it — no ingest ports, no collector. Returns
    (store, follower, api)."""
    import socket as _socket

    from zipkin_tpu.api.server import ApiServer
    from zipkin_tpu.query.service import QueryService
    from zipkin_tpu.replicate import (
        Follower,
        ReplicaTarget,
        ShipClient,
        StandbyTarget,
    )
    from zipkin_tpu.store.device import config_from_dict

    host, _, port = args.follow.rpartition(":")
    if not host or not port.isdigit():
        raise SystemExit(f"--follow wants HOST:PORT, got {args.follow!r}")
    # No PID in the default: the name keys the primary's retention pin,
    # and a per-process name would leak one pinned cursor per restart
    # (truncation blocked at the dead cursor forever).
    name = args.follower_name or (
        f"{args.follow_mode}-{_socket.gethostname()}")
    client = ShipClient(host, int(port), name, mode=args.follow_mode)
    hello = client.connect()
    config = config_from_dict(hello["config"])
    if args.follow_mode == "standby":
        from zipkin_tpu.store.tpu import TpuSpanStore

        store = None
        if args.checkpoint:
            from zipkin_tpu import checkpoint

            # Anchor bootstrap for a standby is a CHECKPOINT of the
            # primary lineage: the shipped tail replays on top of it
            # exactly like crash recovery would.
            if checkpoint.exists(args.checkpoint):
                store = checkpoint.load(args.checkpoint)
        if store is None:
            store = TpuSpanStore(config)
        target = StandbyTarget(store)
    else:
        from zipkin_tpu.store.replica import ReplicaSpanStore

        store = ReplicaSpanStore(config)
        target = ReplicaTarget(store)
    lineage = None
    fleet = None
    if not args.no_fleet_obs:
        from zipkin_tpu import obs
        from zipkin_tpu.obs import fleet as fobs

        reg = obs.default_registry()
        lineage = fobs.FollowerLineage(name, mode=args.follow_mode,
                                       registry=reg)
    follower = Follower(target, client,
                        poll_interval_s=args.follow_poll_ms / 1000.0,
                        lineage=lineage)
    if lineage is not None:
        recorder = fobs.FlightRecorder()
        watchdog = fobs.Watchdog(recorder=recorder, registry=reg)
        watchdog.add_probe("replication_lag",
                           fobs.follower_lag_probe(follower.status))
        fleet = fobs.FleetObs(
            role=args.follow_mode, name=name, registry=reg,
            follower=lineage, watchdog=watchdog, recorder=recorder,
            replication=follower.status,
        )
    window_s = (args.query_window_ms / 1000.0
                if args.query_window_ms is not None else None)
    api = ApiServer(
        QueryService(store, coalesce_window_s=window_s), None,
        replication=follower.status,
        fleet=fleet,
    )
    return store, follower, api


def seed(collector, n_traces: int) -> None:
    from zipkin_tpu.tracegen import generate_traces

    for spans in generate_traces(n_traces=n_traces):
        collector.accept(spans)
    collector.flush()


def follower_main(args) -> None:
    """The --follow serving loop: read-only API over the replicated
    store; SIGTERM/SIGINT stop the follower cleanly (a standby with
    --checkpoint snapshots on the same cadence as a primary, so its
    own recovery base stays fresh)."""
    from zipkin_tpu.api.server import make_server, serve_forever_in_thread

    store, follower, api = build_follower_app(args)
    follower.start()
    server = make_server(api, args.host, args.port)
    serve_forever_in_thread(server)
    print(f"zipkin-tpu {args.follow_mode} following {args.follow}, "
          f"serving reads on {args.host}:{args.port}")
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    can_checkpoint = (args.follow_mode == "standby" and args.checkpoint)
    last_ckpt = time.time()
    try:
        while not stop.is_set():
            stop.wait(1.0)
            err = follower.error()
            if err is not None and not follower.status()["connected"]:
                # Transient disconnects retry inside the loop; only a
                # terminal lineage error lands here with the thread
                # stopped.
                if follower._thread is None or not \
                        follower._thread.is_alive():
                    print(f"follower stopped: {err!r}")
                    break
            if (can_checkpoint
                    and time.time() - last_ckpt
                    > args.checkpoint_interval):
                from zipkin_tpu import checkpoint

                # Captured BEFORE the save: the snapshot covers at
                # least this frontier (records applied mid-save only
                # push the manifest higher), so acking it after a
                # successful save is always conservative.
                seq = follower.target.applied_seq()
                checkpoint.save(store, args.checkpoint)
                # The standby's retention ack is its CHECKPOINTED
                # frontier — only now may the primary truncate the
                # covered records (replicate/follow.StandbyTarget).
                follower.target.note_checkpointed(seq)
                last_ckpt = time.time()
    finally:
        server.shutdown()
        follower.close()
        if can_checkpoint:
            try:
                from zipkin_tpu import checkpoint

                checkpoint.save(store, args.checkpoint)
            except Exception:
                import traceback

                traceback.print_exc()
        store.close()


def _device_summary(store) -> str:
    """Boot-line suffix naming the device(s) the store's STATE lives on
    — read off the state leaves themselves, not ``jax.devices()``, so a
    store that silently landed on the CPU says so. Empty for the
    in-memory reference store (no device state)."""
    hot = getattr(store, "hot", store)
    state = getattr(hot, "state", getattr(hot, "states", None))
    if state is None:
        return ""
    import jax

    leaves = jax.tree_util.tree_leaves(state)
    devices = sorted(set().union(*(leaf.devices() for leaf in leaves)),
                     key=lambda d: d.id)
    return (f" device={devices[0].platform}"
            f" kind={devices[0].device_kind} count={len(devices)}"
            f" state_bytes={sum(leaf.nbytes for leaf in leaves)}")


def start_scribe(args, store, collector, api):
    """The scribe TCP door on ``--scribe-port``, serving in a thread;
    its receiver's entry accounting joins /metrics beside the HTTP
    route's (``zipkin_scribe_entries{transport="tcp"}``:
    ``pushed_back`` is the program's own count of TRY_LATER)."""
    from zipkin_tpu.ingest.receiver import ScribeReceiver
    from zipkin_tpu.ingest.scribe_server import ScribeServer

    # Ack contract: with a WAL, scribe's OK means "durably appended" —
    # the receiver processes synchronously through the durable entries
    # instead of acking from the async queue.
    if getattr(store, "wal", None) is not None:
        receiver = ScribeReceiver(
            collector.ingest_durable,
            process_thrift=collector.ingest_thrift_durable,
        )
    else:
        receiver = ScribeReceiver(
            collector.accept,
            process_thrift=collector.accept_thrift,
        )
    receiver.export_stats(api.registry, "tcp")
    scribe_srv = ScribeServer(receiver, args.host, args.scribe_port)
    scribe_srv.serve_in_thread()
    return scribe_srv


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from zipkin_tpu import compile_cache

    compile_cache.configure()
    if args.follow:
        follower_main(args)
        return
    store, collector, api, shipper = build_app(args)
    if args.seed_traces:
        seed(collector, args.seed_traces)

    from zipkin_tpu.api.server import make_server, serve_forever_in_thread

    server = make_server(api, args.host, args.port)
    serve_forever_in_thread(server)
    ship_srv = None
    if shipper is not None:
        from zipkin_tpu.replicate import ShipServer

        ship_srv = ShipServer(shipper, args.host, args.ship_port)
        ship_srv.serve_in_thread()
    scribe_srv = (start_scribe(args, store, collector, api)
                  if args.scribe_port else None)
    print(f"zipkin-tpu example serving on {args.host}:{args.port}"
          + (f" (scribe tcp :{args.scribe_port})" if scribe_srv else "")
          + (f" (wal-ship tcp :{args.ship_port})" if ship_srv else "")
          + _device_summary(store), flush=True)

    stop = threading.Event()
    # SIGINT and SIGTERM share the graceful-save path: both land in
    # the ordered shutdown below (drain → seal → WAL-fsync →
    # checkpoint) instead of an interpreter teardown mid-write.
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())

    def checkpoint_now():
        if args.checkpoint:
            from zipkin_tpu import checkpoint

            checkpoint.save(store, args.checkpoint)

    last_ckpt = time.time()
    try:
        while not stop.is_set():
            stop.wait(1.0)
            collector.control_tick()
            if (args.checkpoint
                    and time.time() - last_ckpt > args.checkpoint_interval):
                checkpoint_now()
                last_ckpt = time.time()
    finally:
        # Graceful-save ordering (docs/DURABILITY.md): stop intake
        # first, then drain-pipeline → seal-barrier → WAL-fsync
        # (collector.flush enforces that order), THEN checkpoint — so
        # the snapshot's sealed frontier and applied WAL sequence
        # cover everything accepted, and its success truncates the
        # covered log segments. close() comes last.
        if scribe_srv is not None:
            scribe_srv.shutdown()
        if ship_srv is not None:
            ship_srv.shutdown()
        server.shutdown()
        try:
            collector.flush()
        except Exception:
            # A failed drain must not block the checkpoint — but it
            # must be SEEN (graftlint swallowed-exception).
            import traceback

            traceback.print_exc()
        try:
            checkpoint_now()
        except Exception:
            # A failed final save (disk full, suspect store) must not
            # skip the drain/fsync below: the WAL still covers what
            # the snapshot was meant to, so close() losing its final
            # fsync would be the only way to actually lose data here.
            import traceback

            traceback.print_exc()
        collector.close()
        if shipper is not None:
            shipper.close()
        if api.fleet is not None and api.fleet.tracker is not None:
            # Flush buffered lineage spans before the WAL's final
            # fsync so the self-trace tail is durable too.
            try:
                api.fleet.tracker.flush()
            except Exception:
                import traceback

                traceback.print_exc()
        wal = getattr(store, "wal", None)
        if wal is not None:
            wal.close()


if __name__ == "__main__":
    main()
