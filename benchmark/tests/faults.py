"""Broken guarantees, planted in the program by ``daemon_entry.py
--fault NAME`` before the daemon starts. Each breaks one guarantee the
configurations state (step 2 of "how correct is decided": the system
states no precision, so the control breaks a guarantee), in the way a
later PR would be tempted to: ack before the write is safe, answer from
something cheaper than the whole truth.

- ``lost_write``: one Log call of the window is acked OK and dropped
  (the durability / read-your-acks guarantee).
- ``ack_before_fsync``: a Log call is acked once appended, without
  waiting for the group commit's fsync (durability).
- ``ack_on_epoch_only``: on a sharded daemon the ack waits for the
  fsync of the epoch log, the group commit's record, and not for the
  shard logs' that hold the spans: the tempting shortcut of the sharded
  barrier (durability; no effect on a single log).
- ``stored_then_pushed_back``: one Log call of the window is stored and
  then answered TRY_LATER, as the program itself does where an ack's
  wait for the fsync times out; the client resends it, so it is stored
  twice (exact answers: every dependency link of the call counts double).
- ``prefill_pushed_back``: the same to the third call of the pre-fill,
  in the first daemon of a run only (the file ``BENCH_FAULT_MARK`` names
  is made when it fires; without the variable, in every daemon). This is
  no control: run.py has to see the push-back and fill a fresh daemon.
- ``slow_store``: every durable Log call first waits
  ``BENCH_FAULT_DELAY_S`` seconds, one call at a time, as a store whose
  write path is slow under its lock. No control either: run.py has to
  project the pre-fill's end and stop a run that cannot make its set-up
  budget (``BENCH_FAULT_SETUP_BUDGET_S``, read only where a fault is
  planted), and may not stop one that can.
- ``not_whole``: a trace read drops the last annotation of one span
  (read back whole).
- ``stale_query``: an index query leaves out the newest trace (exact
  answers).
"""

import os


def plant(name: str) -> None:
    {"lost_write": _lost_write, "ack_before_fsync": _ack_before_fsync,
     "stored_then_pushed_back": _stored_then_pushed_back,
     "prefill_pushed_back": _prefill_pushed_back,
     "not_whole": _not_whole, "stale_query": _stale_query,
     "ack_on_epoch_only": _ack_on_epoch_only,
     "slow_store": _slow_store}[name]()


def _lost_write() -> None:
    from zipkin_tpu.ingest.collector import Collector

    # Which durable Log call to drop: run.py names one of the window's.
    at = int(os.environ["BENCH_FAULT_AT"])
    real = Collector.ingest_thrift_durable
    seen = [0]

    def ingest_thrift_durable(self, payload):
        seen[0] += 1
        if seen[0] == at:
            return 0  # acked by the receiver, never written
        return real(self, payload)

    Collector.ingest_thrift_durable = ingest_thrift_durable


def _push_back_after_storing(at: int) -> None:
    from zipkin_tpu.ingest.collector import Collector
    from zipkin_tpu.wal.log import WalDurabilityError

    real = Collector.ingest_thrift_durable
    seen = [0]

    def ingest_thrift_durable(self, payload):
        seen[0] += 1
        mine = seen[0]  # other calls come in while this one is stored
        stored = real(self, payload)
        if mine == at:  # the receiver maps this to TRY_LATER
            raise WalDurabilityError("planted: stored, then pushed back")
        return stored

    Collector.ingest_thrift_durable = ingest_thrift_durable


def _stored_then_pushed_back() -> None:
    _push_back_after_storing(int(os.environ["BENCH_FAULT_AT"]))


def _prefill_pushed_back() -> None:
    mark = os.environ.get("BENCH_FAULT_MARK")
    if mark:
        if os.path.exists(mark):
            return
        open(mark, "w").close()
    _push_back_after_storing(3)


def _slow_store() -> None:
    import threading
    import time

    from zipkin_tpu.ingest.collector import Collector

    delay = float(os.environ["BENCH_FAULT_DELAY_S"])
    turn = threading.Lock()
    real = Collector.ingest_thrift_durable

    def ingest_thrift_durable(self, payload):
        with turn:
            time.sleep(delay)
        return real(self, payload)

    Collector.ingest_thrift_durable = ingest_thrift_durable


def _ack_before_fsync() -> None:
    from zipkin_tpu.ingest.collector import Collector

    Collector._wal_barrier = lambda self: None


def _ack_on_epoch_only() -> None:
    from zipkin_tpu.wal.sharded import ShardedWal

    ShardedWal.wait_durable = (
        lambda self, seq, timeout=30.0: self.epoch.wait_durable(seq, timeout))


def _not_whole() -> None:
    from zipkin_tpu.api.server import ApiServer

    real = ApiServer._trace

    def _trace(self, trace_id, params):
        status, spans = real(self, trace_id, params)
        if status == 200 and spans and spans[0]["annotations"]:
            spans[0] = dict(spans[0],
                            annotations=spans[0]["annotations"][:-1])
        return status, spans

    ApiServer._trace = _trace


def _stale_query() -> None:
    from zipkin_tpu.api.server import ApiServer

    real = ApiServer._query

    def _query(self, params):
        status, body = real(self, params)
        if status == 200 and len(body.get("traceIds", [])) > 1:
            body = dict(body, traceIds=body["traceIds"][1:])
        return status, body

    ApiServer._query = _query
