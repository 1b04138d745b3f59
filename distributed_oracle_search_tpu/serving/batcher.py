"""Per-shard adaptive micro-batcher: one thread forms a batch when it
is free to run it.

The shard's one thread (the **runner**) loops: take the next batch off
the shard's :class:`~.queue.ShardQueue` with ``get_batch`` (flush at the
power-of-two ``max_batch`` or on ``max_wait_ms`` expiry), then run the
dispatch callback on it. No batch forms ahead of the runner: requests
that arrive while a batch is in flight stay in the queue, and when the
runner frees it pops them at once (their oldest has already waited past
``max_wait_ms``, so ``get_batch`` flushes immediately, up to
``max_batch``). That is what makes the batching *adaptive*: under load
the batch is what arrived during the last dispatch, so batch size
tracks load; at light load a batch is its first request plus whatever
comes within ``max_wait_ms``. The dispatch is synchronous for every
backend, so a batch formed earlier could only wait; and the queue's
``queue_depth`` bounds every request waiting on the shard.

Each batch gets a shard-local sequence number when it forms, stamped on
its requests (``ServeRequest.batch``). The runner's wait for a batch
(``get_batch``) is the span ``serve.wait`` with ``batch=``, and it
dispatches inside :func:`obs.trace.tagged` ``(batch=, size=)``, so the
frontend's and the engine's spans name the same batch. The histograms
``serve_queue_wait_seconds`` (each request, enqueued until popped into
a batch) and ``serve_handoff_wait_seconds`` (each batch, flushed until
its dispatch starts: the runner's own bookkeeping, microseconds) time
the waits in front of a dispatch.

The thread is named ``dos-serve-*`` — the test suite's leak check
(tests/conftest.py) holds every ``dos-*`` thread to the
joined-on-shutdown contract, and :meth:`MicroBatcher.stop` joins it.
"""

from __future__ import annotations

import threading
import time

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..utils.log import get_logger
from .queue import ShardQueue
from .request import ERROR, ServeRequest, ServeResult

log = get_logger(__name__)

M_BATCHES = obs_metrics.counter(
    "serve_batches_total", "batches dispatched by the micro-batchers")
M_FLUSH_FULL = obs_metrics.counter(
    "serve_flush_full_total", "flushes triggered by max_batch")
M_FLUSH_WAIT = obs_metrics.counter(
    "serve_flush_wait_total", "flushes triggered by max_wait_ms expiry")
# dos-lint: disable=metric-registry -- serve_batch_fill is a
#   dimensionless batch-SIZE histogram, not a latency: the power-of-two
#   buckets are the unit, a _seconds suffix would misdescribe it
H_FILL = obs_metrics.histogram(
    "serve_batch_fill", "dispatched batch size (requests)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024))
H_FLUSH = obs_metrics.histogram(
    "serve_time_to_flush_seconds",
    "first request enqueued until its batch flushed")
H_QUEUE_WAIT = obs_metrics.histogram(
    "serve_queue_wait_seconds",
    "each request, enqueued until popped into a batch")
H_HANDOFF_WAIT = obs_metrics.histogram(
    "serve_handoff_wait_seconds",
    "each batch, flushed until the runner starts its dispatch")
H_DISPATCH = obs_metrics.histogram(
    "serve_dispatch_seconds", "batch dispatch (engine call or wire "
    "round-trip) as seen by the runner thread")
G_INFLIGHT = obs_metrics.gauge(
    "serve_batches_in_flight", "batches currently executing")


class MicroBatcher:
    """One shard's batcher. ``dispatch(batch)`` must complete every
    request's future; the runner backstops a raising dispatch so no
    future is ever left pending."""

    def __init__(self, wid: int, shard_queue: ShardQueue, dispatch,
                 max_batch: int, max_wait_s: float):
        self.wid = wid
        self.queue = shard_queue
        self.dispatch = dispatch
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_s)
        self._stop = threading.Event()
        #: THIS batcher's dispatch-in-progress flag — stop() must drain
        #: on it, not on the process-global in-flight gauge, or one busy
        #: shard (or a second frontend) would stall every other shard's
        #: shutdown for the full drain budget
        self._dispatching = False
        self._runner = threading.Thread(
            target=self._run_loop, daemon=True,
            name=f"dos-serve-dispatch-w{wid}")

    def start(self) -> None:
        self._runner.start()

    # ----------------------------------------------------------- thread
    def _run_loop(self) -> None:
        seq = 0
        # once stop() gave up draining, what is still queued is its to
        # fail: take no further batch
        while not self._stop.is_set():
            with obs_trace.span("serve.wait", shard=self.wid, batch=seq):
                batch = self.queue.get_batch(self.max_batch,
                                             self.max_wait_s, self._stop)
            if not batch:
                # a closed, drained queue is terminal (try_put refuses
                # once closed): exit instead of spinning on instant
                # empty get_batch returns until stop() gets to us
                if self.queue.closed:
                    return
                continue
            self._dispatching = True
            flushed = time.monotonic()
            for r in batch:
                r.batch = seq
                H_QUEUE_WAIT.observe(flushed - r.t_enqueue)
            H_FILL.observe(len(batch))
            H_FLUSH.observe(flushed - batch[0].t_enqueue)
            (M_FLUSH_FULL if len(batch) >= self.max_batch
             else M_FLUSH_WAIT).inc()
            H_HANDOFF_WAIT.observe(time.monotonic() - flushed)
            G_INFLIGHT.add(1)
            t0 = time.perf_counter()
            try:
                with obs_trace.tagged(batch=seq, size=len(batch)):
                    self.dispatch(batch)
            except Exception as e:  # noqa: BLE001 — a dispatch bug must
                # never strand waiters or kill the shard's runner
                log.exception("shard w%d batch dispatch raised: %s",
                              self.wid, e)
            finally:
                G_INFLIGHT.add(-1)
                self._dispatching = False
                H_DISPATCH.observe(time.perf_counter() - t0)
                M_BATCHES.inc()
                _fail_batch(batch, "dispatch-raised")  # only undone ones
            seq += 1

    # --------------------------------------------------------- shutdown
    def stop(self, drain_s: float = 5.0) -> None:
        """Close the queue, give in-flight/queued work ``drain_s`` to
        finish, then stop the runner and fail anything left — every
        admitted request still terminates."""
        self.queue.close()
        deadline = time.monotonic() + max(drain_s, 0.0)
        while time.monotonic() < deadline:
            if len(self.queue) == 0 and not self._dispatching:
                break
            time.sleep(0.01)
        self._stop.set()
        if self._runner.is_alive():
            self._runner.join(timeout=drain_s + 1.0)
        _fail_batch(self.queue.drain(), "shutdown")


def _fail_batch(batch: list[ServeRequest], detail: str) -> None:
    """Complete every still-pending request with ERROR (idempotent:
    completed futures are skipped)."""
    now = time.monotonic()
    for r in batch:
        if not r.future.done():
            r.future.set(ServeResult(ERROR, r.s, r.t, detail=detail,
                                     t_done=now))
