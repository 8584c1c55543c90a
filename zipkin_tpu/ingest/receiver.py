"""Span receivers: transport payloads → spans → the collector pipeline.

Reference: SpanReceiver (zipkin-collector/.../SpanReceiver.scala:27) and
the scribe receiver's decode/whitelist/pushback behavior
(ScribeSpanReceiver.scala:78-141). The kafka receiver's consumer loop is
a transport concern; its decode path is identical to scribe's minus the
base64 (KafkaProcessor.scala:25) and is covered by ``decode_thrift``.
"""

from __future__ import annotations

import base64
import enum
import json
import threading
from typing import Callable, Dict, Iterable, List, Optional, Sequence

from zipkin_tpu import native, obs
from zipkin_tpu.ingest.queue import QueueFullException
from zipkin_tpu.models.span import (
    Annotation,
    AnnotationType,
    BinaryAnnotation,
    Endpoint,
    Span,
)
from zipkin_tpu.wire.thrift import (
    scribe_message_to_span,
    spans_from_bytes,
)


class ResultCode(enum.Enum):
    """Scribe result codes (scribe.thrift): TRY_LATER = backpressure."""

    OK = 0
    TRY_LATER = 1


class ScribeReceiver:
    """Scribe Log() endpoint: base64-thrift LogEntries → spans → process.

    ``process`` is typically Collector.accept (→ ItemQueue.add); a
    QueueFullException surfaces as TRY_LATER so scribe clients buffer
    and retry (ScribeSpanReceiver.scala:133-141).
    """

    def __init__(
        self,
        process: Callable[[Sequence[Span]], None],
        categories: Iterable[str] = ("zipkin",),
        process_thrift: Optional[Callable[[bytes], None]] = None,
    ):
        self.process = process
        self.process_thrift = process_thrift
        self.categories = {c.lower() for c in categories}
        # Bumped from every API handler thread; unlocked += would lose
        # increments under concurrent Log() calls.
        self._stats_lock = threading.Lock()  # lock-order: 82 receiver-stats
        self.stats: Dict[str, int] = {
            "received": 0, "ignored": 0, "bad": 0, "pushed_back": 0,
        }
        # How the TCP door's frames were decoded (``decode_frame``), and
        # the entries whose base64 went back to python for a verdict.
        self.frames: Dict[str, int] = {
            "native": 0, "python": 0, "sent_back": 0,
        }

    def _bump(self, key: str, n: int = 1) -> None:
        with self._stats_lock:
            self.stats[key] += n

    def count_frame(self, decode: str, received: int = 0, ignored: int = 0,
                    bad: int = 0, sent_back: int = 0) -> None:
        """One frame's accounting under one lock."""
        with self._stats_lock:
            self.frames[decode] += 1
            self.frames["sent_back"] += sent_back
            self.stats["received"] += received
            self.stats["ignored"] += ignored
            self.stats["bad"] += bad

    def export_stats(self, registry, transport: str) -> None:
        """This receiver's entry accounting on /metrics, read at
        scrape: ``zipkin_scribe_entries{transport, result}``, one
        family for every scribe door of the process (the HTTP route's
        receiver and the TCP server's are different objects)."""
        def family(name: str, help: str, *labelnames: str):
            return registry.get(name) or registry.register(
                obs.Gauge(name, help, labelnames=labelnames))

        fam = family("zipkin_scribe_entries",
                     "Scribe receiver entry accounting "
                     "(received/ignored/bad/pushed_back) per transport",
                     "transport", "result")
        for key in self.stats:
            fam.labels(transport=transport, result=key).set_function(
                lambda k=key: self.stats[k])
        fam = family("zipkin_scribe_frames",
                     "Scribe Log frames by the decode that took them: "
                     "native (one call off the GIL) or python",
                     "transport", "decode")
        for key in ("native", "python"):
            fam.labels(transport=transport, decode=key).set_function(
                lambda k=key: self.frames[k])
        family("zipkin_scribe_entries_sent_back",
               "Entries of natively decoded frames whose base64 was "
               "not canonical and went to python for its verdict",
               "transport").labels(transport=transport).set_function(
                   lambda: self.frames["sent_back"])

    def log(self, entries: Sequence[tuple]) -> ResultCode:
        """entries: (category, message) pairs — the Scribe.Log call."""
        return self.deliver(self.decode(entries))

    def decode(self, entries: Sequence[tuple]) -> list:
        """Category filter + payload decode: what ``deliver`` takes.

        With ``process_thrift`` wired (Collector.accept_thrift),
        decoded payloads stay raw thrift bytes end-to-end and the
        columnar native parser runs behind ``deliver`` — span objects
        are never built on the hot path (the scrooge-decode role,
        ScribeSpanReceiver.scala:96-107). Segments keep entry
        boundaries so the collector can isolate a thrift-corrupt entry
        instead of dropping the whole batch."""
        fast = self.process_thrift is not None
        out: list = []
        for category, message in entries:
            self._bump("received")
            if category.lower() not in self.categories:
                self._bump("ignored")
                continue
            try:
                if not fast:
                    out.append(scribe_message_to_span(message))
                    continue
                if isinstance(message, str):
                    message = message.encode("ascii")
                out.append(base64.b64decode(message, validate=False))
            except ValueError:  # ThriftError, binascii.Error, non-ascii
                self._bump("bad")
        return out

    def decode_frame(self, frame: bytes, pos: int):
        """``decode`` for a whole ``Log`` frame (``pos``: the byte after
        the message header) in one native call that runs without the
        GIL: no python object per entry, and what comes back keeps the
        entry boundaries (``native.LogSegments``). Taken where the
        payloads stay raw thrift and the library loads; None otherwise,
        and where the strict native walk cannot decide the frame: the
        caller then runs ``_parse_log_args`` + ``decode``, which stay
        the definition. A message that is not canonical base64 gets
        ``decode``'s own verdict, entry by entry."""
        if self.process_thrift is None or not native.available():
            return None
        got = native.decode_log(frame, pos, self.categories)
        if got is None:
            return None
        segments, received, ignored, undecided = got
        bad = 0
        if undecided:
            # Rare by construction (a client that wraps or strips its
            # base64): back to a list of per-entry payloads.
            payloads = list(segments)
            for i, message in reversed(undecided):
                try:
                    payloads[i] = base64.b64decode(
                        message.decode("utf-8", "replace").encode("ascii"),
                        validate=False)
                except ValueError:
                    del payloads[i]
                    bad += 1
            segments = payloads
        self.count_frame("native", received, ignored, bad, len(undecided))
        return segments

    def deliver(self, payloads: list) -> ResultCode:
        """Hand decoded payloads on; any failure is TRY_LATER."""
        if not payloads:
            return ResultCode.OK
        try:
            (self.process_thrift or self.process)(payloads)
        except Exception:
            # Queue full (QueueFullException) and not-yet-durable
            # (WalDurabilityError) are the same answer on the wire:
            # don't ack, client retries (the ack-after-durable-append
            # contract, docs/DURABILITY.md). The durable entries run
            # the whole store write path on this handler thread, so
            # its exception surface (suspect store, closing store)
            # lands here too; any of it maps to TRY_LATER — a torn
            # connection would read as a lost batch to clients that
            # only retry on the wire code.
            self._bump("pushed_back")
            return ResultCode.TRY_LATER
        return ResultCode.OK


def decode_thrift(payload: bytes) -> List[Span]:
    """Raw thrift span sequence → spans (the kafka message decode path)."""
    return spans_from_bytes(payload)


class JsonReceiver:
    """JSON span receiver for HTTP-posted spans (the tracegen/web feed).

    Accepts a list of span dicts in the shape the web API emits; not a
    reference transport, but the natural REST ingest door for a modern
    deployment.
    """

    def __init__(self, process: Callable[[Sequence[Span]], None]):
        self.process = process

    def post(self, body: bytes) -> ResultCode:
        spans = [span_from_json(d) for d in json.loads(body)]
        try:
            self.process(spans)
        except QueueFullException:
            return ResultCode.TRY_LATER
        return ResultCode.OK


def _endpoint_from_json(d: Optional[dict]) -> Optional[Endpoint]:
    if not d:
        return None
    return Endpoint(
        ipv4=int(d.get("ipv4", 0)),
        port=int(d.get("port", 0)),
        service_name=d.get("serviceName", "unknown"),
    )


def span_from_json(d: dict) -> Span:
    anns = tuple(
        Annotation(
            timestamp=int(a["timestamp"]),
            value=a["value"],
            host=_endpoint_from_json(a.get("endpoint")),
        )
        for a in d.get("annotations", ())
    )
    banns = []
    for b in d.get("binaryAnnotations", ()):
        t = AnnotationType[b.get("type", "STRING")]
        value = b.get("value", "")
        if t == AnnotationType.BYTES and isinstance(value, str):
            value = base64.b64decode(value)
        banns.append(
            BinaryAnnotation(
                key=b["key"], value=value, annotation_type=t,
                host=_endpoint_from_json(b.get("endpoint")),
            )
        )
    def _id(v):
        """Hex string (the wire form) or number → canonical SIGNED
        int64 — keeps span_to_json → span_from_json an exact round
        trip for ids with the top bit set."""
        u = int(v, 16) if isinstance(v, str) else int(v)
        return u - (1 << 64) if u >= (1 << 63) else u

    return Span(
        trace_id=_id(d["traceId"]),
        name=d.get("name", ""),
        id=_id(d["id"]),
        parent_id=(
            None if d.get("parentId") in (None, "")
            else _id(d["parentId"])
        ),
        annotations=anns,
        binary_annotations=tuple(banns),
        debug=bool(d.get("debug", False)),
    )


def _hex_id(v: int) -> str:
    return f"{v & (2**64 - 1):x}"


def endpoint_to_json(e: Optional[Endpoint]):
    if e is None:
        return None
    return {"ipv4": e.ipv4, "port": e.port, "serviceName": e.service_name}


def binary_annotation_to_json(b) -> dict:
    value = b.value
    if isinstance(value, (bytes, bytearray)):
        if b.annotation_type == AnnotationType.BYTES:
            value = base64.b64encode(bytes(value)).decode("ascii")
        else:
            value = bytes(value).decode("utf-8", "replace")
    return {
        "key": b.key, "value": value,
        "type": b.annotation_type.name,
        "endpoint": endpoint_to_json(b.host),
    }


def span_to_json(s: Span) -> dict:
    ep = endpoint_to_json
    banns = [binary_annotation_to_json(b) for b in s.binary_annotations]
    # Ids serialize as unsigned hex STRINGS (upstream zipkin JSON
    # convention, and span_from_json's string interpretation): a JSON
    # number round-trips through JS float64, which silently rounds ids
    # above 2^53 — the UI would then fetch the wrong trace.
    return {
        "traceId": _hex_id(s.trace_id),
        "name": s.name,
        "id": _hex_id(s.id),
        "parentId": None if s.parent_id is None else _hex_id(s.parent_id),
        "annotations": [
            {"timestamp": a.timestamp, "value": a.value,
             "endpoint": ep(a.host)}
            for a in s.annotations
        ],
        "binaryAnnotations": banns,
        "debug": s.debug,
    }
