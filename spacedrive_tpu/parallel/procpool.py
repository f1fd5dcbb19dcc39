"""Multi-process execution plane — escape the GIL for CPU-bound stages.

Two in-process nodes scale poorly, and the PR 13 host profiler names
why: one shared GIL serializes every per-entry Python between the
spans — journal payload decode, chunk-cache digesting, linking SQL
prep, image decode/webp encode, pHash planes. The reference's
execution layer is a work-stealing multi-threaded Rust task system
(``crates/task-system``) that simply uses the cores; this Python
mirror needs **processes**.

This module is the owner-side half: a persistent pool of worker
processes (each a fresh ``python -m spacedrive_tpu.parallel.procworker``
interpreter — the slim in-worker runtime; length-prefixed msgpack
frames over its own stdio pipe, no fork, no pickled state, no
re-imported ``__main__``) that the task system's execute leg
dispatches CPU-bound stages onto:

- **lifecycle**: spawn-started with the Node and refcounted like the
  host profiler (two in-process nodes share one pool; the first stop
  must not kill the survivor's workers). ``SD_PROCS`` sizes the pool;
  ``SD_PROCS=0`` (the default) is the golden single-process path —
  every call site falls through to its inline implementation,
  bit-identical to the pre-pool tree;
- **shared-nothing batches**: ``submit()`` msgpack-serializes the
  payload *before* it crosses the boundary — a non-plain object
  (Database, connection, loop, Node, policy) fails loudly at the call
  site, and sdlint SD022 (``process-boundary-purity``) rejects it at
  review time. The shard plane already defines the serializable unit
  (journal-keyed entries + stat identity);
- **single-writer telemetry**: each result carries the worker's
  additive counter/histogram delta; the per-worker reader merges it
  into the owner registry (``registry.merge_delta``) so metrics,
  spans, and flight rings keep exactly one writer per process. A
  batch whose worker died never shipped a delta — the retry counts
  once;
- **crash recovery**: a worker that dies mid-batch is restarted once
  and its in-flight batches are re-dispatched (each batch retries at
  most once — a twice-fatal batch fails its future, and every call
  site degrades to its inline path on pool failure, so a broken pool
  can slow a pass but never wrong it). The ``procpool.worker`` fault
  point (modes ``crash``/``stall``) drives this path deterministically
  in the chaos tier;
- **IPC amortization**: callers size batches through the per-workload
  ``PipelinePolicy.procpool_batch_rows()`` seam (parallel/autotune.py)
  so the serialize+frame tax is paid per quantum, not per row.

Evidence plane: ``sd_procpool_*`` (workers alive, dispatch/roundtrip
seconds, batch rows, restarts, job outcomes) and the attribution
report's ``gap``/``gil_wait`` shares (docs/performance.md
"Multi-process execution plane").
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import Future
from typing import Any

from ..telemetry import metrics as _tm
from ..telemetry.registry import REGISTRY
from ..utils import faults as _faults
from . import procworker as _wire

logger = logging.getLogger(__name__)

#: hard cap on SD_PROCS — a fat-fingered value must not fork-bomb a host
MAX_PROCS = 64

#: per-batch result timeout floor for sync waiters (seconds); generous —
#: a stalled worker is recovered by the watchdog, not by waiters
REQUEST_TIMEOUT_S = 120.0

#: a worker holding any batch older than this is WEDGED (hung C call —
#: e.g. a decompression bomb inside PIL), not slow: the watchdog kills
#: it so the normal death path (restart + re-dispatch-once) reclaims
#: the capacity. Far above every sane batch (callers' own timeouts
#: give up long before), so it can only fire on a genuine hang.
WEDGE_TIMEOUT_S = 300.0
#: watchdog poll cadence
_WATCHDOG_INTERVAL_S = 5.0


def procs() -> int:
    """``SD_PROCS`` worker count. 0 (default) disables the plane —
    the golden bit-identical single-process path."""
    raw = os.environ.get("SD_PROCS", "0")
    try:
        n = int(raw)
    except ValueError:
        return 0
    return max(0, min(MAX_PROCS, n))


def enabled() -> bool:
    return procs() > 0


class ProcPoolError(RuntimeError):
    """A pool-side failure (worker error, death past the retry budget,
    pool stopped). Call sites catch this and fall back inline — the
    pool may only ever make a pass FASTER, never wrong."""


class _Job:
    __slots__ = ("id", "stage", "blob", "rows", "stall_s", "future",
                 "t_submit", "retried")

    def __init__(self, job_id: int, stage: str, blob: bytes, rows: int):
        self.id = job_id
        self.stage = stage
        self.blob = blob
        self.rows = rows
        self.stall_s = 0.0
        self.future: Future = Future()
        self.t_submit = time.monotonic()
        self.retried = False


class _Worker:
    """One subprocess + its reader thread + its write lock."""

    __slots__ = ("index", "proc", "reader", "wlock", "inflight", "gen")

    def __init__(self, index: int):
        self.index = index
        self.proc: subprocess.Popen | None = None
        self.reader: threading.Thread | None = None
        self.wlock = threading.Lock()
        self.inflight: set[int] = set()
        self.gen = 0  # bumped per restart so stale readers exit


class ProcPool:
    """The process-wide pool (:data:`POOL`); ``start``/``stop`` are
    refcounted because two in-process nodes (the loopback test mesh)
    share one interpreter."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._refs = 0
        self._running = False
        self._workers: list[_Worker] = []
        self._jobs: dict[int, _Job] = {}
        self._job_seq = itertools.count(1)
        self._size = 0
        self._watchdog: threading.Thread | None = None
        self._stop_event = threading.Event()

    # -- lifecycle --------------------------------------------------------

    def start(self) -> bool:
        """Add one hold; the first hold spawns the workers. Returns
        True when the pool is running after the call (False under
        ``SD_PROCS=0`` — a true no-op)."""
        n = procs()
        if n <= 0:
            return False
        with self._lock:
            self._refs += 1
            if self._running:
                return True
            self._size = n
            self._workers = [_Worker(i) for i in range(n)]
            self._running = True
            for w in self._workers:
                self._spawn_locked(w)
            self._stop_event.clear()
            self._watchdog = threading.Thread(
                target=self._watch, name="sd-procpool-watchdog",
                daemon=True,
            )
            self._watchdog.start()
            _tm.PROCPOOL_WORKERS.set(n)
            return True

    def _spawn_locked(self, w: _Worker) -> None:
        """(Re)launch one worker subprocess and its reader thread."""
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # workers never own an accelerator
        env.pop("SD_FAULTS", None)  # the owner drives worker faults
        env["PYTHONPATH"] = pkg_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        w.proc = subprocess.Popen(
            # -c (not -m): the parallel package imports procworker for
            # the frame helpers, and runpy would re-execute an already-
            # imported module with a noisy RuntimeWarning
            [sys.executable, "-c",
             "from spacedrive_tpu.parallel import procworker; "
             "procworker.main()"],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=None,  # worker logs/tracebacks pass through
            env=env,
        )
        w.gen += 1
        w.reader = threading.Thread(
            target=self._read_loop, args=(w, w.proc, w.gen),
            name=f"sd-procpool-r{w.index}", daemon=True,
        )
        w.reader.start()

    def stop(self) -> None:
        """Release one hold; the last release stops workers and fails
        any still-outstanding futures (call sites fall back inline)."""
        with self._lock:
            self._refs = max(0, self._refs - 1)
            if self._refs > 0:
                return
            self._running = False
            self._stop_event.set()
            workers, self._workers = self._workers, []
            jobs, self._jobs = dict(self._jobs), {}
        for w in workers:
            proc = w.proc
            if proc is None:
                continue
            try:
                proc.stdin.close()  # EOF = clean worker shutdown
            except OSError:
                pass
            try:
                proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=2.0)
        for w in workers:
            if w.reader is not None and w.reader.is_alive():
                w.reader.join(timeout=2.0)
        watchdog, self._watchdog = self._watchdog, None
        if watchdog is not None and watchdog.is_alive():
            watchdog.join(timeout=2.0)
        for job in jobs.values():
            if not job.future.done():
                job.future.set_exception(ProcPoolError("pool stopped"))
        _tm.PROCPOOL_WORKERS.set(0)

    def running(self) -> bool:
        # start()/stop() flip this under _lock from the loop; readers
        # include the watchdog thread — read under the same lock
        with self._lock:
            return self._running

    def worker_count(self) -> int:
        with self._lock:
            return sum(
                1 for w in self._workers
                if w.proc is not None and w.proc.poll() is None
            )

    # -- dispatch ---------------------------------------------------------

    def submit(self, stage: str, payload: Any, rows: int = 1) -> Future:
        """Ship one shared-nothing batch; returns a concurrent Future
        resolving to the stage result dict. The payload is serialized
        HERE (msgpack-plain or it fails loudly, matching sdlint SD022);
        raises :class:`ProcPoolError` when the pool is not running."""
        import msgpack

        t0 = time.perf_counter()
        try:
            blob = msgpack.packb(payload, use_bin_type=True)
        except (TypeError, ValueError) as exc:
            raise ProcPoolError(
                f"procpool payload for {stage!r} is not msgpack-plain: {exc}"
            ) from exc
        with self._lock:
            if not self._running:
                raise ProcPoolError("pool not running")
            job = _Job(next(self._job_seq), stage, blob, rows)
            w = self._pick_locked()
            spec = _faults.hit("procpool.worker")
            if spec is not None and spec.mode == "stall":
                job.stall_s = spec.delay_s
            self._jobs[job.id] = job
            w.inflight.add(job.id)
            kill = w.proc if spec is not None and spec.mode == "crash" \
                else None
        self._send(w, job)
        if kill is not None:
            # simulated process death mid-batch: the reader sees EOF,
            # restarts the worker once and re-dispatches its batches
            kill.kill()
        _tm.PROCPOOL_DISPATCH_SECONDS.observe(time.perf_counter() - t0)
        _tm.PROCPOOL_BATCH_ROWS.observe(rows)
        return job.future

    def _pick_locked(self) -> _Worker:
        return min(self._workers, key=lambda w: len(w.inflight))

    def _send(self, w: _Worker, job: _Job) -> None:
        """Frame one job onto a worker's stdin. A write failure means
        the worker is dead or dying — its reader owns the recovery, so
        the job just stays in-flight until the reaper re-dispatches."""
        import msgpack

        frame = msgpack.packb(
            [job.id, job.stage, job.blob, job.stall_s], use_bin_type=True,
        )
        try:
            with w.wlock:
                if w.proc is not None and w.proc.stdin is not None:
                    _wire.write_frame(w.proc.stdin, frame)
        except (OSError, ValueError):
            pass  # reader-side reaper re-dispatches this job

    def request(self, stage: str, payload: Any, rows: int = 1,
                timeout: float | None = None) -> Any:
        """Synchronous round-trip (worker-thread call sites — shard
        execution runs in ``to_thread``). Raises ProcPoolError on any
        pool-side failure so callers can fall back inline."""
        fut = self.submit(stage, payload, rows)
        try:
            return fut.result(timeout or REQUEST_TIMEOUT_S)
        except ProcPoolError:
            raise
        except Exception as exc:  # noqa: BLE001 - timeout/cancel → pool error
            raise ProcPoolError(f"procpool {stage} failed: {exc}") from exc

    async def run(self, stage: str, payload: Any, rows: int = 1) -> Any:
        """Event-loop-side round-trip (thumbnail actor, duplicates)."""
        fut = self.submit(stage, payload, rows)
        try:
            return await asyncio.wrap_future(fut)
        except ProcPoolError:
            raise
        except Exception as exc:  # noqa: BLE001 - normalize for callers
            raise ProcPoolError(f"procpool {stage} failed: {exc}") from exc

    # -- per-worker reader (results + recovery) ---------------------------

    def _read_loop(self, w: _Worker, proc: subprocess.Popen,
                   gen: int) -> None:
        import msgpack

        def _decode(frame: bytes) -> list | None:
            try:
                parsed = msgpack.unpackb(frame, raw=False)
            except (TypeError, ValueError):
                return None
            return parsed if isinstance(parsed, list) \
                and len(parsed) == 4 else None

        try:
            while True:
                frame = _wire.read_frame(proc.stdout)
                if frame is None:
                    break  # EOF: worker exited (or was killed)
                parsed = _decode(frame)
                if parsed is None:
                    # a torn frame means the stream is unframed from
                    # here on — treat as death, don't spin on garbage
                    break
                job_id, ok, body, delta_blob = parsed
                self._finish(w, job_id, ok, body, delta_blob)
        except (EOFError, OSError, ValueError):
            pass
        self._reap(w, proc, gen)

    def _finish(self, w: _Worker, job_id: int, ok: bool, body: bytes,
                delta_blob: bytes) -> None:
        import msgpack

        with self._lock:
            job = self._jobs.pop(job_id, None)
            w.inflight.discard(job_id)
        if job is None:
            return  # late duplicate of a re-dispatched batch
        try:
            REGISTRY.merge_delta(msgpack.unpackb(delta_blob, raw=False))
        except Exception:  # noqa: BLE001 - delta drift must not kill results
            logger.exception("procpool telemetry delta merge failed")
        _tm.PROCPOOL_ROUNDTRIP_SECONDS.observe(
            time.monotonic() - job.t_submit)
        try:
            result = msgpack.unpackb(body, raw=False)
        except Exception:  # noqa: BLE001 - torn body → job error
            result, ok = {"error": "undecodable result"}, False
        if ok:
            _tm.PROCPOOL_JOBS.inc(result="ok")
            if not job.future.done():
                job.future.set_result(result)
        else:
            _tm.PROCPOOL_JOBS.inc(result="error")
            if not job.future.done():
                job.future.set_exception(ProcPoolError(
                    f"worker {w.index} failed {job.stage}: "
                    f"{result.get('error')}"
                ))

    def _reap(self, w: _Worker, proc: subprocess.Popen, gen: int) -> None:
        """The worker behind ``gen`` is gone: restart it (if the pool
        is still running) and re-dispatch its in-flight batches, once
        per batch."""
        try:
            proc.wait(timeout=5.0)
        except subprocess.TimeoutExpired:
            proc.kill()
        redispatch: list[_Job] = []
        failed: list[_Job] = []
        with self._lock:
            if not self._running or w.gen != gen:
                return  # pool stopping, or a newer generation owns `w`
            for job_id in sorted(w.inflight):
                job = self._jobs.get(job_id)
                if job is None:
                    continue
                if job.retried:
                    self._jobs.pop(job_id, None)
                    failed.append(job)
                else:
                    job.retried = True
                    redispatch.append(job)
            w.inflight.clear()
            self._spawn_locked(w)
            _tm.PROCPOOL_RESTARTS.inc()
            _tm.PROCPOOL_WORKERS.set(self._size)
            targets: list[tuple[_Worker, _Job]] = []
            for job in redispatch:
                tgt = self._pick_locked()
                tgt.inflight.add(job.id)
                targets.append((tgt, job))
                _tm.PROCPOOL_JOBS.inc(result="retried")
        logger.warning(
            "procpool worker %d died; restarted (re-dispatching %d, "
            "failing %d)", w.index, len(redispatch), len(failed),
        )
        for tgt, job in targets:
            self._send(tgt, job)
        for job in failed:
            if not job.future.done():
                job.future.set_exception(ProcPoolError(
                    f"batch {job.stage} died twice; giving up"
                ))

    # -- watchdog (wedged-worker recovery) --------------------------------

    def _watch(self) -> None:
        """Kill any worker that has held a batch past WEDGE_TIMEOUT_S —
        a hung C call (decompression bomb in PIL, a pathological read)
        never returns to the frame loop, so the reader's EOF-driven
        reap can't see it. Killing converts the wedge into an ordinary
        death: restart + re-dispatch-once, and a batch that wedges its
        retry worker too fails its future (callers fall back inline)."""
        while not self._stop_event.wait(_WATCHDOG_INTERVAL_S):
            now = time.monotonic()
            wedged: list[Any] = []
            with self._lock:
                if not self._running:
                    return
                for w in self._workers:
                    if w.proc is None or w.proc.poll() is not None:
                        continue  # dead already: the reader owns it
                    oldest = min(
                        (self._jobs[jid].t_submit
                         for jid in w.inflight if jid in self._jobs),
                        default=None,
                    )
                    if oldest is not None \
                            and now - oldest > WEDGE_TIMEOUT_S:
                        wedged.append(w.proc)
            for proc in wedged:
                logger.warning(
                    "procpool worker wedged past %.0fs; killing",
                    WEDGE_TIMEOUT_S,
                )
                proc.kill()

    # -- warmup -----------------------------------------------------------

    def warm(self, timeout: float = 30.0) -> None:
        """Block until every worker answered one echo — bench arms call
        this so spawn/import cost never lands inside a timed window."""
        futs = [self.submit("echo", {"i": i}) for i in range(self._size)]
        for f in futs:
            try:
                f.result(timeout)
            except Exception:  # noqa: BLE001 - a dead worker reaps later
                pass


#: the process-wide pool — Node.start() takes a refcounted hold
#: (parallel to telemetry.sampler.SAMPLER), tests may hold it directly
POOL = ProcPool()


def get() -> ProcPool | None:
    """The running pool, or None — the one call-site gate: every
    consumer does ``pool = procpool.get()`` and falls through to its
    inline implementation when this is None (SD_PROCS=0, pool not
    started, or already stopped)."""
    return POOL if POOL.running() else None
