"""Async SLO-aware request scheduling: futures + deadline-driven flush.

This is the ROADMAP item "async request queue + latency SLO accounting in
MicroBatcher". `MicroBatcher.drain()` is synchronous and deterministic by
design — every caller blocks until the whole coalesced batch runs.
`AsyncBatcher` keeps that exact compute path (flushes are literally
`MicroBatcher.submit()* + drain()`, so results are bit-identical by
construction) and puts a latency-aware front door on it:

    submit(Xq) -> Future     returns immediately; the request joins the
                             pending window and its enqueue timestamp is
                             taken
    flush trigger            whichever fires first:
                               - the pending window reaches max_bucket
                                 query columns (a full steady-state batch
                                 is ready -> flushing now costs nothing),
                                 checked at submit time;
                               - the OLDEST pending request has waited
                                 max_wait_ms (the latency deadline),
                                 checked by poll()/the pump thread.
    completion               the flushed batch runs through the bucketed
                             assignment path; each request's Future
                             resolves to its (labels, d2) slice and its
                             enqueue->flush->complete timestamps land in
                             a LatencyStats (serve/latency.py)

Determinism: all scheduling state lives behind one lock and the clock is
injectable, so tests drive deadline semantics with a fake clock and
explicit poll() calls — no sleeps, no flaky timing. A background pump
thread (`start()`/`stop()`, or the context manager) is available for real
deployments where nobody polls.

Batch membership does not affect results: query columns are independent
through the whole extension matmul and the bucketed path pads to the same
pow-2 widths regardless of how requests were grouped (see
serve/batcher.py), so any interleaving of flushes yields the same labels
as one big drain. tests/test_scheduler.py pins this.

Compute-path selection (Pallas kernels, mesh sharding) arrives as a
`policy=ComputePolicy(...)` kwarg forwarded verbatim to the underlying
MicroBatcher — AsyncBatcher adds no knobs of its own (the deprecated
fused=/embed_fused=/mesh= spellings forward the same way).
"""
from __future__ import annotations

import contextlib
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from repro.serve.artifact import FittedModel
from repro.serve.batcher import MicroBatcher, bucket_size
from repro.serve.latency import LatencyStats
from repro.spans import span


class _Pending(NamedTuple):
    """One queued request: payload + future + its enqueue timestamp +
    its request id (the batcher's submit counter; ids are FIFO, so a
    flush's first id and request count name every request in it)."""
    Xq: np.ndarray
    future: Future
    enqueue_ts: float
    rid: int


class AsyncBatcher:
    """Deadline-driven async front door over MicroBatcher's bucketed path.

    max_wait_ms: latency deadline — the longest any request may sit in the
        pending window before a flush is forced. Lower = lower p99, less
        coalescing; higher = bigger batches, better throughput.
    slo_ms: end-to-end latency SLO recorded per request (None disables).
    clock: monotonic-seconds callable; injectable for deterministic tests.
    Remaining kwargs (block, min_bucket, max_bucket, fused, embed_fused,
    interpret, mesh, mesh_axis) go straight to the inner MicroBatcher —
    embed_fused/interpret pick the fused extend_embed Pallas stripe
    engine exactly as in the sync path.
    """

    def __init__(self, model: FittedModel, *, max_wait_ms: float = 5.0,
                 slo_ms: Optional[float] = None,
                 clock=time.monotonic, latency: Optional[LatencyStats] = None,
                 **batcher_kwargs):
        self.batcher = MicroBatcher(model, **batcher_kwargs)
        self.max_wait_ms = float(max_wait_ms)
        self.clock = clock
        self.latency = latency if latency is not None \
            else LatencyStats(slo_ms=slo_ms)
        # lock-order: _flush_lock -> _lock
        # flush() nests the window lock inside the drain lock; nothing
        # may acquire the pair inverted (taking _flush_lock while
        # holding _lock would deadlock against a concurrent flush).
        # repro.analysis reads this contract and the guarded-by
        # annotations below; mutations of annotated fields outside
        # `with self._lock` are build failures (rules L001/L002).
        self._queue: List[_Pending] = []      # guarded-by: _lock
        self._next_rid = 0                    # guarded-by: _lock
        # Per-bucket deadline overrides (milliseconds), keyed by the pow-2
        # execution bucket the CURRENT pending window would coalesce into.
        # This is the knob the fleet tier's AdaptiveWaitController turns:
        # a bucket whose latency breakdown shows deadline pressure gets a
        # shorter wait (less batching, more headroom); a comfortably-fast
        # bucket earns a longer one. Unset buckets fall back to
        # max_wait_ms. Read by due(); written via set_bucket_wait().
        self._bucket_wait: Dict[int, float] = {}  # guarded-by: _lock
        self._lock = threading.Lock()         # guards the pending window
        self._flush_lock = threading.Lock()   # serializes inner drains
        self._thread: Optional[threading.Thread] = None  # guarded-by: _lock
        self._stop_event = threading.Event()
        self._stopped = False                 # guarded-by: _lock
        # Pump-thread health: a flush that raises has already delivered
        # the exception to that batch's futures; the pump must survive to
        # serve later requests. Counter + last error are the monitoring
        # surface.
        self.pump_errors = 0
        self.last_pump_error: Optional[BaseException] = None

    # -- request side ----------------------------------------------------

    def submit(self, Xq) -> "Future[Tuple[np.ndarray, np.ndarray]]":
        """Enqueue one (p, b) request; resolves to (labels (b,), d2 (b,)).

        Flushes inline when this submit fills the window to max_bucket —
        the full-batch trigger — so a saturating client never waits on the
        deadline.
        """
        with span("serve.submit") as submit_span:
            Xq = self.batcher.validate_request(Xq)
            fut: Future = Future()
            with self._lock:
                # Checked under the lock so a submit racing stop() either
                # lands in the queue stop() is about to flush, or raises —
                # it can never enqueue into a retired, pump-less batcher
                # where the future would be stranded forever.
                if self._stopped:
                    raise RuntimeError(
                        "submit() on a stopped AsyncBatcher: nothing would "
                        "ever flush this request (after a hot-swap, get the "
                        "current scheduler from the registry)")
                rid = self._next_rid
                self._next_rid += 1
                self._queue.append(_Pending(Xq, fut, self.clock(), rid))
                full = (self._pending_width_locked()
                        >= self.batcher.max_bucket)
            submit_span.set_metadata(rid=rid)
            if full:
                self._flush("full")
            return fut

    def _pending_width_locked(self) -> int:
        return sum(p.Xq.shape[1] for p in self._queue)

    @property
    def pending_requests(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def pending_width(self) -> int:
        """Total query columns currently waiting for a flush."""
        with self._lock:
            return self._pending_width_locked()

    # -- flush side ------------------------------------------------------

    def set_bucket_wait(self, bucket: int, max_wait_ms: float) -> None:
        """Override the flush deadline for one pow-2 execution bucket.

        The AdaptiveWaitController's write path: buckets not overridden
        keep the constructor's max_wait_ms."""
        if max_wait_ms <= 0:
            raise ValueError(f"max_wait_ms must be positive, "
                             f"got {max_wait_ms!r}")
        with self._lock:
            self._bucket_wait[int(bucket)] = float(max_wait_ms)

    def bucket_wait(self, bucket: int) -> float:
        """Effective flush deadline (ms) for one pow-2 bucket."""
        with self._lock:
            return self._bucket_wait.get(int(bucket), self.max_wait_ms)

    def due(self, now: Optional[float] = None) -> bool:
        """True when the oldest pending request has hit the deadline.

        The deadline is per execution bucket when overridden
        (set_bucket_wait): the wait that applies is the one for the
        bucket the CURRENT pending window would coalesce into — as the
        window grows into a larger bucket, that bucket's (usually
        longer) wait takes over, which is exactly the batching-vs-
        deadline trade the adaptive controller tunes."""
        now = self.clock() if now is None else now
        with self._lock:
            if not self._queue:
                return False
            if self._bucket_wait:
                b = bucket_size(self._pending_width_locked(),
                                self.batcher.min_bucket,
                                self.batcher.max_bucket)
                wait = self._bucket_wait.get(b, self.max_wait_ms)
            else:
                wait = self.max_wait_ms
            return (now - self._queue[0].enqueue_ts) * 1e3 >= wait

    def poll(self) -> int:
        """Flush if the deadline trigger fires; returns requests completed.

        This is the cooperative scheduling entry point: an event loop (or
        test) calls poll() at whatever cadence it likes; the pump thread
        is just poll() in a loop.
        """
        return self._flush("deadline") if self.due() else 0

    def flush(self) -> int:
        """Run all pending requests now; returns requests completed.

        The batch is handed to the inner MicroBatcher exactly as drain()
        would see it, so async results are bit-identical to a synchronous
        drain of the same requests. Futures resolve in submission order;
        on compute failure every future in the batch carries the
        exception instead of the batch dying silently.
        """
        return self._flush("manual")

    def _flush(self, trigger: str) -> int:
        """flush(), with what set it off ("full", "deadline", "stop" or
        "manual") named on its serve.flush span."""
        # serve.flush and serve.resolve close after the futures resolve,
        # outside the flush lock, so they are held on an exit stack.
        with contextlib.ExitStack() as spans:
            with self._flush_lock:
                with self._lock:
                    batch, self._queue = self._queue, []
                if not batch:
                    return 0
                # The pow-2 execution bucket this flush runs through: the
                # coalesced width, bucketed by the inner batcher's policy
                # (oversized batches chunk into max_bucket pieces, so the
                # clamp is also the dominant executable). Keys the
                # per-bucket latency breakdown.
                width = sum(p.Xq.shape[1] for p in batch)
                bucket = bucket_size(width, self.batcher.min_bucket,
                                     self.batcher.max_bucket)
                spans.enter_context(span(
                    "serve.flush", trigger=trigger, first_rid=batch[0].rid,
                    requests=len(batch), width=width, bucket=bucket))
                flush_ts = self.clock()
                try:
                    for p in batch:
                        self.batcher.submit(p.Xq)
                    results = self.batcher.drain()
                except Exception as exc:             # pragma: no cover
                    for p in batch:
                        if p.future.set_running_or_notify_cancel():
                            p.future.set_exception(exc)
                    raise
                # drain() must return exactly one result per request
                # handed to it; a mismatch means something enqueued on
                # the inner batcher directly and a silent zip would
                # scatter results to the wrong futures.
                if len(results) != len(batch):       # pragma: no cover
                    exc = RuntimeError(
                        f"flush expected {len(batch)} results, drained "
                        f"{len(results)}: the inner MicroBatcher had "
                        f"foreign pending requests")
                    for p in batch:
                        if p.future.set_running_or_notify_cancel():
                            p.future.set_exception(exc)
                    raise exc
                complete_ts = self.clock()
                spans.enter_context(span("serve.resolve"))
                # LatencyStats mutation stays inside the flush lock:
                # record() is read-modify-write on histogram counts, and
                # a pump-thread flush can overlap a submit-triggered
                # inline flush.
                for p in batch:
                    self.latency.record(p.enqueue_ts, flush_ts,
                                        complete_ts, queries=p.Xq.shape[1],
                                        bucket=bucket)
            # A client may have cancel()ed its future while the request
            # sat in the pending window; set_result on a cancelled future
            # raises InvalidStateError and would strand every LATER
            # future in the batch unresolved.
            # set_running_or_notify_cancel() claims the future atomically
            # (False = it was cancelled -> drop the result).
            for p, res in zip(batch, results):
                if p.future.set_running_or_notify_cancel():
                    p.future.set_result(res)
        return len(batch)

    # -- background pump -------------------------------------------------

    def _pump_period(self) -> float:
        """Pump poll period: a quarter of the SHORTEST active deadline."""
        with self._lock:
            waits = list(self._bucket_wait.values())
        return max(min(waits + [self.max_wait_ms]) / 4e3, 1e-4)

    @property
    def running(self) -> bool:
        """True while the background pump thread is alive."""
        return self._thread is not None

    @property
    def stopped(self) -> bool:
        """True once stop() retired this batcher (submits now raise)."""
        return self._stopped

    def start(self) -> "AsyncBatcher":
        """Spawn the daemon pump thread (poll() every max_wait_ms / 4).

        The check-and-spawn is one critical section: two concurrent
        start() calls must not both see `_thread is None` and leak a
        second pump.
        """

        def pump():
            # Re-read the period every cycle: the adaptive controller may
            # shorten a bucket's wait below the constructor deadline, and
            # a pump polling at the stale (longer) quarter-period would
            # miss the new deadline by up to the difference.
            while not self._stop_event.wait(self._pump_period()):
                try:
                    self.poll()
                except Exception as exc:   # batch futures carry the error
                    self.pump_errors += 1
                    self.last_pump_error = exc

        with self._lock:
            if self._stopped:
                raise RuntimeError("cannot start a stopped AsyncBatcher")
            if self._thread is not None:
                raise RuntimeError("pump thread already running")
            self._stop_event.clear()
            thread = threading.Thread(target=pump, daemon=True,
                                      name="AsyncBatcher-pump")
            self._thread = thread
        thread.start()
        return self

    def stop(self) -> int:
        """Retire this batcher: stop the pump, flush pending, reject
        all later submits. Idempotent — a second stop() is a no-op that
        flushes an empty queue. Returns the requests flushed by THIS
        call (what a hot-swap drained into the outgoing model).

        The thread handle is claimed under _lock (two concurrent
        stop() calls must not both join-and-clear it), but join()
        happens OUTSIDE: the pump's poll()->flush() takes _lock, so
        joining while holding it would deadlock.
        """
        with self._lock:
            self._stopped = True
            thread, self._thread = self._thread, None
        if thread is not None:
            self._stop_event.set()
            thread.join()
        return self._flush("stop")

    def __enter__(self) -> "AsyncBatcher":
        return self.start()

    def __exit__(self, *exc_info) -> None:
        self.stop()
