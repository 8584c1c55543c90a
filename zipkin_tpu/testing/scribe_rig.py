"""The served write path in one object, for tests: a device store
with a write-ahead log, the collector's durable entries, a scribe TCP
server on a free port and a client on its socket — the daemon's own
wiring (``main/example.py:start_scribe``) at a tiny geometry.
"""

from __future__ import annotations

import base64
from typing import List, Sequence, Tuple

from zipkin_tpu import obs
from zipkin_tpu.ingest.collector import Collector
from zipkin_tpu.ingest.receiver import ResultCode, ScribeReceiver
from zipkin_tpu.ingest.scribe_server import ScribeClient, ScribeServer
from zipkin_tpu.models.span import Span
from zipkin_tpu.obs.fleet import LineageTracker
from zipkin_tpu.store import device as dev
from zipkin_tpu.store.tpu import TpuSpanStore
from zipkin_tpu.wal import WriteAheadLog
from zipkin_tpu.wire.thrift import span_to_bytes

# Same geometry as tests/test_determinism.py — shares its jit cache.
CONFIG = dev.StoreConfig(
    capacity=256, ann_capacity=1024, bann_capacity=512,
    max_services=16, max_span_names=32, max_annotation_values=64,
    max_binary_keys=16, cms_width=256, hll_p=6, quantile_buckets=128,
)


def log_entries(spans: Sequence[Span]) -> List[Tuple[str, str]]:
    return [("zipkin", base64.b64encode(span_to_bytes(s)).decode())
            for s in spans]


class ScribeRig:
    def __init__(self, wal_dir: str, pipeline_depth: int = 0,
                 fsync: str = "interval", lineage: bool = False):
        self.registry = obs.Registry()
        self.store = TpuSpanStore(CONFIG, registry=self.registry)
        self.wal = WriteAheadLog(wal_dir, fsync=fsync,
                                 registry=self.registry)
        self.store.attach_wal(self.wal)
        self.tracker = None
        if lineage:
            self.tracker = LineageTracker(
                self.store.apply, registry=self.registry, sample_every=1)
            self.store.attach_lineage(self.tracker)
        self.collector = Collector(
            self.store, registry=self.registry,
            pipeline_depth=pipeline_depth)
        self.receiver = ScribeReceiver(
            self.collector.ingest_durable,
            process_thrift=self.collector.ingest_thrift_durable)
        self.server = ScribeServer(self.receiver, host="127.0.0.1", port=0)
        self.server.serve_in_thread()
        # A first call compiles the fused step under the store's lock:
        # on a loaded test host that takes longer than the client's
        # default 10 s.
        self.client = ScribeClient(*self.server.server_address,
                                   timeout_s=120.0)

    def log(self, spans: Sequence[Span]) -> ResultCode:
        return self.client.log(log_entries(spans))

    def close(self) -> None:
        self.client.close()
        self.server.shutdown()
        self.server.server_close()
        self.collector.close()
        self.wal.close()
