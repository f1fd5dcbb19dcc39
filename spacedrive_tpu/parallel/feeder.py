"""Host→device feeding — overlap disk reads with device compute.

SURVEY §7 hard part #2 ("feeding the beast"): on a 1M-file library the
sampled reads (~56 KiB/file) dominate wall-clock, so the host must be
reading batch N+1 while the device hashes batch N.

`WindowPipeline` is the mechanism: a producer thread walks a
cursor-chained fetch function back-to-back (window N+1's reads start
the moment N's reads finish, not when the consumer takes N) into a
bounded queue of `depth` windows. Because each window's fetch also
*dispatches* its device batch asynchronously, up to `depth` transfers
ride the host→device link while earlier compute completes.

`PipelineStats` records overlap so jobs can report read vs compute time
honestly (the reference's RunMetadata timing discipline,
ref:indexer/indexer_job.rs:76-88).
"""

from __future__ import annotations

import collections
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Generic, TypeVar

from ..telemetry import metrics as _tm
from ..telemetry import span as _span
from ..telemetry import trace as _trace

T = TypeVar("T")


def pipeline_depth(n_devices: int, base: int = 3, cap: int = 8) -> int:
    """Prefetch depth that keeps an n-device dp dispatch fed: one extra
    in-flight window per doubling of the chip count (each window drains
    n× faster, so the producer needs more read-ahead to hide the same
    disk latency), capped so host memory stays bounded. 1→3, 2→4,
    4→5, 8→6."""
    return min(cap, base + max(0, int(n_devices).bit_length() - 1))


@dataclass
class PipelineStats:
    prefetch_hits: int = 0
    prefetch_misses: int = 0
    read_time: float = 0.0  # time the consumer WAITED on reads
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)


class WindowPipeline(Generic[T]):
    """Bounded multi-window producer pipeline.

    `fetch(key)` returns `(next_key, window)` — or `None` when the
    cursor is exhausted. A daemon producer thread chains fetches
    back-to-back and parks up to `depth` ready windows; `take()` hands
    them to the consumer in order (`None` = end of stream). `close()`
    stops the producer promptly (it also aborts any blocked put), so
    pause/cancel paths can't leak the thread; re-reading the in-flight
    windows after a resume is the caller's contract (fetches must be
    side-effect-free)."""

    def __init__(
        self,
        fetch: Callable[[Any], "tuple[Any, T] | None"],
        start_key: Any,
        depth: "int | Callable[[], int]" = 3,
        measure: Callable[[T], int] | None = None,
    ):
        # `measure(window) -> bytes` attributes each fetched window's
        # host→device payload to sd_feeder_h2d_bytes_total
        self._measure = measure
        self.stats = PipelineStats()
        # unbounded deque + condition (NOT a bounded Queue): close()
        # must wake a blocked consumer IMMEDIATELY. A bounded queue
        # could be full when close() tried to enqueue its wake-up
        # sentinel, leaving take() to discover shutdown only via a
        # 0.1 s poll; here `depth` only throttles the producer, and
        # close() just flips the flag under the condition and notifies.
        self._buf: collections.deque = collections.deque()
        self._cond = threading.Condition()
        # `depth` may be a callable (the autotuner's live policy read):
        # _put re-evaluates it per parked window, so a mid-job depth
        # adjustment takes effect on the very next fetch
        self._depth = depth if callable(depth) else None
        self._static_depth = 1 if callable(depth) else max(1, depth)
        self._stop = threading.Event()
        self._done = False
        self._fetch = fetch
        self._error: BaseException | None = None
        # one restart is cheap insurance against a transient producer
        # crash (fetches are side-effect-free, so re-reading the failed
        # window is safe); a second crash surfaces to the consumer
        self._restarts_left = 1
        # the producer thread starts with empty contextvars — carry the
        # constructing task's trace across so feeder.fetch spans join it
        self._trace_ctx = _trace.current()
        self._thread = threading.Thread(
            target=self._run, args=(start_key,), name="sd-window-pipeline",
            daemon=True,
        )
        self._thread.start()

    def _run(self, key: Any) -> None:
        from ..utils import faults as _faults

        if self._trace_ctx is not None:
            _trace.set_current(self._trace_ctx)
        try:
            while not self._stop.is_set():
                spec = _faults.hit("feeder.fetch")
                if spec is not None:
                    if spec.mode == "stall":
                        time.sleep(spec.delay_s)
                    elif spec.mode == "crash":
                        raise _faults.InjectedFault(
                            "injected feeder producer crash"
                        )
                with _span("feeder.fetch") as fetch_span:
                    item = self._fetch(key)
                with self.stats._lock:
                    self.stats.read_time += fetch_span.duration
                _tm.FEEDER_FETCH_SECONDS.observe(fetch_span.duration)
                if item is None:
                    self._put(None)
                    return
                key, window = item
                if self._measure is not None:
                    try:
                        _tm.FEEDER_H2D_BYTES.inc(self._measure(window))
                    except Exception:  # measurement must never kill reads
                        pass
                if not self._put(window):
                    return
        except BaseException as e:
            if self._restart(key, e):
                return
            # restart budget spent: surfaced to the consumer on take().
            # Published under the condition BEFORE the sentinel is
            # parked, so the consumer that pops the sentinel (under the
            # same condition) always observes the error with it.
            with self._cond:
                self._error = e
            self._put(None)

    def _restart(self, key: Any, exc: BaseException) -> bool:
        """Re-spawn the producer once after a crash, resuming at the
        window whose fetch failed (fetches are side-effect-free per the
        class contract). Returns False when the budget is spent — the
        caller then surfaces the error."""
        from ..telemetry.events import RESILIENCE_EVENTS

        if self._stop.is_set() or self._restarts_left <= 0:
            return False
        self._restarts_left -= 1
        _tm.FEEDER_RESTARTS.inc()
        RESILIENCE_EVENTS.emit(
            "feeder_restart", error=str(exc)[:200],
        )
        replacement = threading.Thread(
            target=self._run, args=(key,), name="sd-window-pipeline",
            daemon=True,
        )
        # the handle swap races close()'s join of the old thread: both
        # sides go through the pipeline condition so close() always
        # joins the replacement, never a corpse
        with self._cond:
            self._thread = replacement
        replacement.start()
        return True

    def _depth_now(self) -> int:
        """Current read-ahead bound; a broken policy callable degrades
        to depth 1 (throttled, never wedged or unbounded)."""
        if self._depth is None:
            return self._static_depth
        try:
            return max(1, int(self._depth()))
        except Exception:  # noqa: BLE001 - policy reads must never kill reads
            return 1

    def _put(self, item) -> bool:
        """Park one window (or the end-of-stream sentinel) for the
        consumer; blocks while `depth` windows are already parked and
        aborts promptly when close() is called. The sentinel never
        blocks — the deque is unbounded, depth only throttles real
        windows, so end-of-stream (and a producer error) reaches the
        consumer even when the buffer is full."""
        with self._cond:
            while (
                item is not None
                and len(self._buf) >= self._depth_now()
                and not self._stop.is_set()
            ):
                self._cond.wait()
            if self._stop.is_set():
                return False
            self._buf.append(item)
            _tm.FEEDER_INFLIGHT.set(len(self._buf))
            self._cond.notify_all()
            return True

    def take(self) -> T | None:
        """Next window in order; None at end of stream (raises if the
        producer died) or after close(). The time the consumer spent
        blocked is recorded as a prefetch miss; instant handoffs count
        as hits. Once the end-of-stream sentinel has been consumed every
        further take() returns None immediately — the producer thread
        has exited and there is only one sentinel, so without this latch
        an extra take() (steps outnumbering windows, e.g. the orphan set
        shrank mid-run) would spin forever."""
        if self._done:
            with self._cond:
                err = self._error
            if err is not None:
                raise err
            return None
        t0 = time.perf_counter()
        with _span("feeder.wait"):
            with self._cond:
                while not self._buf and not self._stop.is_set():
                    self._cond.wait()
                if self._buf:
                    window = self._buf.popleft()
                    self._cond.notify_all()  # free the producer's slot
                else:  # closed: wake immediately, no sentinel needed
                    window = None
                inflight = len(self._buf)
                # producer publishes _error under this condition before
                # parking the sentinel — capture it under the same lock
                err = self._error
        waited = time.perf_counter() - t0
        hit = waited < 0.002
        with self.stats._lock:
            if hit:
                self.stats.prefetch_hits += 1
            else:
                self.stats.prefetch_misses += 1
        _tm.FEEDER_WAIT_SECONDS.observe(waited)
        _tm.FEEDER_PREFETCH.inc(result="hit" if hit else "miss")
        _tm.FEEDER_INFLIGHT.set(inflight)
        if window is None:
            self._done = True
            if err is not None:
                raise err
        return window

    def close(self) -> None:
        with self._cond:
            self._stop.set()
            # one notify wakes BOTH sides instantly: a producer blocked
            # on a full buffer and a consumer blocked on an empty one
            self._cond.notify_all()
            # snapshot under the condition: _restart() swaps the handle
            # under the same lock, so this is the live producer
            producer = self._thread
        producer.join(timeout=5)
