"""The node-wide thumbnailer actor.

Parity: ref:core/src/object/media/thumbnail/{actor.rs,worker.rs,
process.rs} — a node-global actor outside the job system; jobs dispatch
batches and only await counts. Foreground batches are a priority LIFO
stack, background a FIFO queue (state.rs:23-32); background work is
throttled to `background_processing_percentage`% of cores
(process.rs:105-128); each thumb gets a 30s timeout (process.rs:172);
queues persist across crashes (state.rs); `NewThumbnail` events flow to
the node event bus (ref:core/src/api/mod.rs:54).

TPU shape: a batch is processed as [decode on host threads] →
[ONE device resize call per size bucket] → [webp encode on host
threads]; "pause/preempt" maps to batch-boundary draining, the leftover
pattern the reference uses for its queues.
"""

from __future__ import annotations

import asyncio
import collections
import functools
import itertools
import logging
import os
import secrets
import time
from typing import Any, Callable, Sequence

from ....parallel import autotune as _autotune
from ....telemetry import metrics as _tm
from ....telemetry import span
from ....telemetry import trace as _trace
from ....utils import faults as _faults
from .process import (
    Decoded,
    ThumbError,
    can_generate,
    chunk_len,
    decode,
    decodes_full_size,
    finish,
    host_resize_reason,
    resize_cpu,
    resize_decoded,
)
from .state import Batch, load_state, save_state
from .store import ThumbnailStore, get_shard_hex

logger = logging.getLogger(__name__)

GENERATION_TIMEOUT_S = 30  # ref:process.rs:172
# images per device dispatch per accelerator: autotune.THUMB_DEVICE_BATCH
# via the "thumbnail" PipelinePolicy (read live in _device_chunk)


ThumbKey = tuple[str, str, str]  # (namespace, shard, cas_id)
# What a batch's submitter may hang on the decode stage: called on the
# decode worker thread, once per still image decoded, with the cas_id,
# the frame (process.FrameTap's: a PIL image or a uint8 array, RGB or
# RGBA) and the DCT scale it was decoded at.
FrameSink = Callable[[str, Any, int], None]


def _offer(sink: FrameSink, cas_id: str, frame: Any, scale: int) -> None:
    """The tap `decode` calls for a batch that has a sink, inside the
    decode stage's `_timed`: the sink's work is decode work. A sink
    that fails costs its owner the frame, never the thumbnail."""
    try:
        sink(cas_id, frame, scale)
    except Exception:  # noqa: BLE001 - the sink is a guest here
        logger.exception("frame sink failed for %s", cas_id)


class Thumbnailer:
    """`Node.thumbnailer` — see module docstring for the contract."""

    def __init__(
        self,
        data_dir: str | os.PathLike,
        event_bus: Any = None,
        background_processing_percentage: int = 50,  # ref:actor.rs:98
        use_device: bool = True,
    ):
        self.data_dir = os.fspath(data_dir)
        os.makedirs(self.data_dir, exist_ok=True)
        self.store = ThumbnailStore(self.data_dir)
        self.event_bus = event_bus
        self.use_device = use_device
        cores = os.cpu_count() or 1
        self._fg_parallelism = cores
        self.background_percentage = max(
            0, min(100, background_processing_percentage)
        )
        self._bg_parallelism = max(1, cores * self.background_percentage // 100)
        self._fg: collections.deque[Batch] = collections.deque()  # LIFO
        self._bg: collections.deque[Batch] = collections.deque()  # FIFO
        self._current: Batch | None = None  # in-flight (for persistence)
        # random base so a batch id persisted in a resumed job's state
        # can't collide with a fresh id from this process
        self._batch_ids = itertools.count((secrets.randbits(40) << 20) | 1)
        self._batch_pending: collections.Counter[int] = collections.Counter()
        self._pending: collections.Counter[str] = collections.Counter()
        self._cond: asyncio.Condition | None = None
        self._wake: asyncio.Event | None = None
        self._chunk_rows: int | None = None  # explicit override (tests);
        # None → read the live "thumbnail" PipelinePolicy per batch
        self._accel: int | None = None  # cached accelerator count
        self._worker: asyncio.Task | None = None
        self._stopped = False
        self.generated = 0
        self.skipped = 0
        self.errors = 0
        # Crash recovery: previously queued batches resume as background,
        # and are re-persisted at once so a second crash before the first
        # batch completes still loses nothing (the load deleted the file).
        # Entries whose thumbnail already landed in the store are dropped
        # here: a crash between chunk store and journal write leaves the
        # stored prefix inside the persisted batch, and re-decoding /
        # re-resizing it would redo device work the store already holds.
        for b in load_state(self.data_dir):
            kept = [
                e for e in b.entries
                if not self.store.exists(b.library_id, e[0])
            ]
            already = len(b.entries) - len(kept)
            if already:
                self.skipped += already
                _tm.THUMB_FILES.inc(already, result="skipped")
            if not kept:
                continue
            b.entries = kept
            b.background = True
            b.id = next(self._batch_ids)
            self._bg.append(b)
            self._pending[self._ns(b.library_id)] += len(b.entries)
            self._batch_pending[b.id] = len(b.entries)
        self._save()

    # ---- lifecycle -----------------------------------------------------
    def _ns(self, library_id: str | None) -> str:
        return self.store.namespace(library_id)

    def _save(self) -> None:
        batches = list(self._fg) + list(self._bg)
        if self._current is not None and self._current.entries:
            batches.insert(0, self._current)
        save_state(self.data_dir, batches)

    def _ensure_started(self) -> None:
        """Lazily bind to the running loop (actor model: one worker)."""
        if self._stopped:
            return
        if self._worker is None or self._worker.done():
            self._cond = self._cond or asyncio.Condition()
            self._wake = self._wake or asyncio.Event()
            self._loop = asyncio.get_running_loop()
            self._worker = self._loop.create_task(
                self._run(), name="thumbnailer"
            )
            if self._fg or self._bg:
                self._wake.set()

    def _kick(self) -> None:
        """Start/wake the worker. Raises RuntimeError off-loop — the
        caller then schedules `_kick_on_loop` via call_soon_threadsafe
        (asyncio.Event.set is NOT thread-safe, and enqueues arrive from
        to_thread workers — e.g. the non-indexed walker queueing
        on-the-fly thumbnails)."""
        self._ensure_started()
        assert self._wake is not None
        self._wake.set()

    def _kick_on_loop(self) -> None:
        try:
            self._kick()
        except RuntimeError:
            pass  # loop shutting down

    async def shutdown(self) -> None:
        """Persist unprocessed batches (including the in-flight
        remainder) and stop (ref:state.rs:47-75)."""
        self._stopped = True
        if self._wake is not None:
            self._wake.set()
        if self._worker is not None:
            try:
                await asyncio.wait_for(asyncio.shield(self._worker), timeout=60)
            except (asyncio.TimeoutError, asyncio.CancelledError):
                self._worker.cancel()
                try:
                    await self._worker
                except (asyncio.CancelledError, Exception):
                    pass
        self._save()
        # unblock rendezvous waiters: with the actor stopped their work
        # will never drain, and hanging a job forever is worse
        if self._cond is not None:
            async with self._cond:
                self._cond.notify_all()

    # ---- dispatch API (ref:actor.rs new_*_thumbnails_batch) ------------
    def set_background_percentage(self, pct: int) -> None:
        """Re-derive background parallelism from a percentage of cores
        (ref:actor.rs:98 `background_processing_percentage` update)."""
        cores = os.cpu_count() or 1
        self.background_percentage = max(0, min(100, pct))
        self._bg_parallelism = max(1, cores * self.background_percentage // 100)

    def new_indexed_thumbnails_batch(
        self,
        library_id: str,
        entries: Sequence[tuple[str, str] | tuple[str, str, str]],
        background: bool = False,
        sink: FrameSink | None = None,
    ) -> int:
        """entries: (cas_id, path[, extension]); returns a batch id for
        `wait_batch`, or 0 if nothing was queued. `sink` is offered
        every still frame the batch decodes, so a submitter that needs
        the pixels too (the media job's embed step) does not open the
        file again. It lives with this process's batch only: a batch
        reloaded after a restart, and the pooled software path, offer
        nothing, and the submitter decodes for itself."""
        return self._enqueue(library_id, entries, background, sink)

    def new_ephemeral_thumbnails_batch(
        self, entries: Sequence[tuple[str, str] | tuple[str, str, str]]
    ) -> int:
        return self._enqueue(None, entries, background=False)

    def _enqueue(self, library_id, entries, background, sink=None) -> int:
        library_id = str(library_id) if library_id is not None else None
        norm: list[tuple[str, str, str]] = []
        for e in entries:
            cas_id, path = e[0], e[1]
            ext = (
                e[2]
                if len(e) > 2
                else os.path.splitext(path)[1].lstrip(".").lower()
            )
            if not cas_id or not can_generate(ext):
                continue
            if self.store.exists(library_id, cas_id):
                self.skipped += 1
                _tm.THUMB_FILES.inc(result="skipped")
                continue
            norm.append((cas_id, path, ext))
        if not norm:
            return 0
        batch = Batch(library_id=library_id, entries=norm,
                      background=background, sink=sink)
        batch.id = next(self._batch_ids)
        # the actor worker is a separate task: the batch carries the
        # enqueueing trace (media job, watcher, ephemeral walk) across
        batch.trace = _trace.wire_current()
        if background:
            self._bg.append(batch)
        else:
            self._fg.appendleft(batch)  # LIFO priority stack
        self._pending[self._ns(library_id)] += len(norm)
        self._batch_pending[batch.id] = len(norm)
        self._save()
        # which thread are we on? asyncio.Event.set is only safe on the
        # owning loop — and once the worker is pre-started (Node.start),
        # _kick would NOT raise off-loop, so the check must be explicit
        try:
            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        owner = getattr(self, "_loop", None)
        if running is not None and (owner is None or running is owner):
            self._kick()
        elif owner is not None and owner.is_running():
            # off-loop caller (a to_thread worker) or a foreign loop:
            # hand the kick to the owning loop
            owner.call_soon_threadsafe(self._kick_on_loop)
        # with no loop bound yet, the batch is persisted and processed
        # on first await/start()
        return batch.id

    def delete_thumbnails(self, library_id: str | None, cas_ids: list[str]) -> int:
        return self.store.remove(library_id, cas_ids)

    # ---- rendezvous (ref:job.rs WaitThumbnails) ------------------------
    async def wait_batch(self, batch_id: int) -> None:
        """Wait for one dispatched batch (ids are per-process; an
        unknown/finished id — e.g. after an actor restart — is done)."""
        if batch_id <= 0:
            return
        self._ensure_started()
        assert self._cond is not None
        async with self._cond:
            await self._cond.wait_for(
                lambda: self._stopped or self._batch_pending[batch_id] == 0
            )

    async def wait_library_batch(self, library_id: str | None) -> None:
        """Wait for a whole namespace to drain (coarser than
        `wait_batch`; unrelated background work counts too)."""
        self._ensure_started()
        ns = self._ns(library_id)
        assert self._cond is not None
        async with self._cond:
            await self._cond.wait_for(
                lambda: self._stopped or self._pending[ns] == 0
            )

    def pending_count(self, library_id: str | None) -> int:
        return self._pending[self._ns(library_id)]

    # ---- worker --------------------------------------------------------
    async def _run(self) -> None:
        assert self._wake is not None and self._cond is not None
        while not self._stopped:
            if not self._fg and not self._bg:
                self._wake.clear()
                try:
                    await asyncio.wait_for(self._wake.wait(), timeout=1.0)
                except asyncio.TimeoutError:
                    continue
            if self._stopped:
                break
            if self._fg:
                batch = self._fg.popleft()
            elif self._bg:
                batch = self._bg.popleft()
            else:
                continue
            self._current = batch
            try:
                await self._process_batch(batch)
            except asyncio.CancelledError:
                # shutdown cancelled us mid-batch: requeue the remainder
                # so shutdown's _save persists it (waiters unblock via
                # the _stopped clause in their predicates)
                self._current = None
                if batch.entries:
                    self._fg.appendleft(batch)
                raise
            except Exception:
                logger.exception("thumbnail batch failed")
                self.errors += len(batch.entries)
                await self._account(batch, len(batch.entries))
                batch.entries = []
            self._current = None
            if batch.entries:
                # drained early because _stopped flipped mid-batch
                self._fg.appendleft(batch)
            self._save()

    async def _account(self, batch: Batch, n: int) -> None:
        assert self._cond is not None
        async with self._cond:
            ns = self._ns(batch.library_id)
            self._pending[ns] -= n
            if self._pending[ns] <= 0:
                # drop zeroed keys: a Counter with zero values is still
                # truthy, which turns `while thumbnailer._pending` polls
                # into infinite loops
                del self._pending[ns]
            self._batch_pending[batch.id] -= n
            if self._batch_pending[batch.id] <= 0:
                del self._batch_pending[batch.id]
            self._cond.notify_all()

    async def _process_batch(self, batch: Batch) -> None:
        with _trace.use(_trace.TraceContext.from_wire(batch.trace)):
            await self._process_batch_traced(batch)

    def _device_chunk(self) -> int:
        """Images per device dispatch: the live "thumbnail"
        PipelinePolicy scaled by the accelerator count (a dp-sharded
        resize splits the chunk over every chip, so each still sees the
        per-device batch). CPU-only hosts keep the parity base (virtual
        devices share cores — bigger host chunks would only add
        latency). Read per batch, so an autotuner adjustment lands on
        the next batch; an explicit ``_chunk_rows`` (tests, chaos
        harness) always wins."""
        if self._chunk_rows is not None:
            return self._chunk_rows
        if self._accel is None:
            n = 1
            if self.use_device:
                from ....parallel.mesh import accelerator_count

                n = accelerator_count()
            self._accel = n
        return _autotune.policy("thumbnail").thumb_chunk_rows(self._accel)

    async def _process_batch_traced(self, batch: Batch) -> None:
        """Stage-overlapped chunk loop.

        The per-chunk stages — host decode → device resize → host webp
        encode + store — are independent across chunks, so they run as
        a 3-deep software pipeline: while chunk N rides the device,
        chunk N+1 is decoding on the thread pool and chunk N−1 is
        encoding/storing. Encode tasks are chained (at most one
        outstanding, awaited before the next starts), so entries are
        consumed strictly in order — the persisted resume state only
        ever drops a prefix whose thumbnails are already on disk.
        """
        parallelism = (
            self._bg_parallelism if batch.background else self._fg_parallelism
        )
        sem = asyncio.Semaphore(parallelism)
        chunk_rows = self._device_chunk()

        def _timed(work: list[float], fn, *args):
            """Run one image's `fn` on this worker thread and add the
            seconds spent INSIDE it to the chunk's `work` sum (appends
            are atomic): work done, where the chunk's span is wall time
            with semaphore queueing and pipeline overlap in it."""
            t0 = time.perf_counter()
            try:
                return fn(*args)
            finally:
                work.append(time.perf_counter() - t0)

        async def _decode(entry: tuple[str, str, str],
                          work: list[float]) -> Decoded | None:
            cas_id, path, ext = entry
            tap = None
            if batch.sink is not None:
                tap = functools.partial(_offer, batch.sink, cas_id)
            async with sem:
                try:
                    return await asyncio.wait_for(
                        asyncio.to_thread(
                            _timed, work, decode, path, ext, tap),
                        timeout=GENERATION_TIMEOUT_S,
                    )
                except (ThumbError, asyncio.TimeoutError, OSError) as e:
                    logger.debug("thumb decode failed %s: %s", path, e)
                    return None

        entries = list(batch.entries)
        done = 0  # entries fully stored+accounted (a prefix of `entries`)

        async def _decode_chunk(start: int):
            """→ (the chunk that starts at entry `start`, its frames):
            `chunk_rows` entries, or fewer where the frames of stills
            that decode at full size would pass the host's byte bound
            (`process.chunk_len` reads their headers; no other chunk
            pays for a look)."""
            chunk = entries[start:start + chunk_rows]
            work: list[float] = []
            async with span("thumbnail.decode") as decode_span:
                if any(decodes_full_size(ext) for _c, _p, ext in chunk):
                    chunk = chunk[:await asyncio.to_thread(chunk_len, chunk)]
                decoded = await asyncio.gather(
                    *(_decode(e, work) for e in chunk))
            _tm.THUMB_STAGE_SECONDS.observe(
                decode_span.duration, stage="decode")
            _tm.THUMB_WORK_SECONDS.observe(sum(work), stage="decode")
            _tm.PIPELINE_HOST_SECONDS.observe(
                decode_span.duration, pipeline="thumbnail")
            return chunk, decoded

        async def _encode_chunk(chunk, decoded, device_idx, ds, resized):
            """Final stage for one chunk: webp-encode device outputs,
            run host-path stragglers, store, account, and release the
            chunk from the batch's persisted remainder."""
            nonlocal done
            for d in decoded:
                if d is None:
                    self.errors += 1
                    _tm.THUMB_FILES.inc(result="error")
            # host-path stragglers (an aspect over 16:1 / no device),
            # concurrent now that they ride their own pipeline stage
            fallback = {
                i: host_resize_reason(d) if self.use_device else "no_device"
                for i, d in enumerate(decoded)
                if d is not None and i not in device_idx
            }
            if device_idx and resized is None:
                # the device stage failed past the degradation ladder:
                # degrade the chunk to the CPU reference resize instead
                # of erroring it — slower pixels beat missing thumbnails
                fallback.update((i, "device_failed") for i in device_idx)
                device_idx = []
                ds = []

            async def _one_fallback(i):
                if self.use_device:
                    # a still a device node resized on the host, whatever
                    # the reason: `cli.device_report` counts these, so a
                    # pass cannot say "tpu" over one
                    from ....telemetry.events import RESILIENCE_EVENTS

                    RESILIENCE_EVENTS.emit(
                        "thumbnail_cpu_fallback", reason=fallback[i],
                        cas_id=chunk[i][0],
                    )
                async with sem:  # same host-thread budget as decode
                    try:
                        webp = await asyncio.wait_for(
                            asyncio.to_thread(
                                resize_cpu, decoded[i], fallback[i]),
                            timeout=GENERATION_TIMEOUT_S,
                        )
                        self._store_one(batch.library_id, chunk[i][0], webp)
                    except Exception:
                        self.errors += 1
                        _tm.THUMB_FILES.inc(result="error")

            work: list[float] = []

            async def _one_finish(d, r):
                async with sem:
                    return await asyncio.to_thread(_timed, work, finish, d, r)

            async with span("thumbnail.encode") as encode_span:
                await asyncio.gather(*(_one_fallback(i) for i in fallback))
                # device_idx is non-empty only when the device stage
                # produced output — a wholesale failure was rerouted to
                # the CPU fallback above
                if device_idx:
                    try:
                        webps = await asyncio.gather(
                            *(
                                _one_finish(d, r)
                                for d, r in zip(ds, resized)
                            )
                        )
                        for i, webp in zip(device_idx, webps):
                            self._store_one(
                                batch.library_id, chunk[i][0], webp)
                    except Exception:
                        logger.exception("thumbnail encode chunk failed")
                        self.errors += len(device_idx)
                        _tm.THUMB_FILES.inc(
                            len(device_idx), result="error")
            _tm.THUMB_STAGE_SECONDS.observe(
                encode_span.duration, stage="encode")
            _tm.THUMB_WORK_SECONDS.observe(sum(work), stage="encode")
            _tm.PIPELINE_HOST_SECONDS.observe(
                encode_span.duration, pipeline="thumbnail")
            if _faults.hit("thumbnail.persist") is not None:
                # simulated process death in the window between "chunk
                # stored" and "journal dropped it": InjectedCrash is a
                # BaseException, so no recovery path below can absorb it
                # — only a fresh actor (standing in for a fresh process)
                # resumes, and the resume filter must skip this chunk
                raise _faults.InjectedCrash(
                    "injected crash between chunk store and journal write"
                )
            done += len(chunk)
            # only now may the resume state drop this chunk
            batch.entries = entries[done:]
            await self._account(batch, len(chunk))

        pos = 0  # decode cursor
        decode_task: asyncio.Task | None = None
        encode_task: asyncio.Task | None = None
        try:
            while pos < len(entries) and not self._stopped:
                if decode_task is None:
                    decode_task = asyncio.ensure_future(_decode_chunk(pos))
                chunk, decoded = await decode_task
                decode_task = None
                pos += len(chunk)
                if pos < len(entries) and not self._stopped:
                    # chunk N+1 decodes while chunk N rides the device
                    decode_task = asyncio.ensure_future(_decode_chunk(pos))
                _tm.THUMB_BATCH_FILL.observe(len(chunk) / chunk_rows)
                device_idx = [
                    i for i, d in enumerate(decoded)
                    if d is not None and self.use_device
                    and host_resize_reason(d) is None
                ]
                ds = [decoded[i] for i in device_idx]
                resized = None
                if ds:
                    try:
                        async with span(
                            "thumbnail.device",
                            nbytes=sum(d.array.nbytes for d in ds),
                        ) as device_span:
                            resized = await asyncio.to_thread(
                                resize_decoded, ds)
                            # the frames have done their work: two
                            # chunks' worth stay on the host, not three
                            # (unmapping a gigabyte takes the stage
                            # some tens of milliseconds more)
                            for d in ds:
                                d.array = None
                        _tm.THUMB_STAGE_SECONDS.observe(
                            device_span.duration, stage="device")
                        _tm.PIPELINE_DEVICE_SECONDS.observe(
                            device_span.duration, pipeline="thumbnail")
                    except Exception:
                        logger.exception("device resize batch failed")
                        resized = None
                if encode_task is not None:
                    await encode_task  # chunk N−1 finishes storing first
                encode_task = asyncio.ensure_future(
                    _encode_chunk(
                        chunk, decoded, device_idx, ds, resized)
                )
            if encode_task is not None:
                await encode_task
                encode_task = None
        finally:
            # cancel the read-ahead and retrieve it so no orphan warns;
            # the trailing encode (started work) must complete so its
            # thumbnails are stored before the remainder persists
            if decode_task is not None:
                decode_task.cancel()
                try:
                    await decode_task
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
            while encode_task is not None and not encode_task.done():
                # started encode work MUST finish before the remainder
                # persists (its chunk's entries are dropped by done+=),
                # so keep re-awaiting across repeated cancellations —
                # the shield keeps each cancel from reaching the encode
                try:
                    await asyncio.shield(encode_task)
                except asyncio.CancelledError:
                    continue
                except Exception:  # noqa: BLE001 - logged in the task
                    break

    def _store_one(self, library_id: str | None, cas_id: str, webp: bytes) -> None:
        self.store.write(library_id, cas_id, webp)
        self.generated += 1
        _tm.THUMB_FILES.inc(result="generated")
        if self.event_bus is not None:
            self.event_bus.emit(
                {
                    "type": "NewThumbnail",
                    "thumb_key": (
                        self._ns(library_id),
                        get_shard_hex(cas_id),
                        cas_id,
                    ),
                }
            )


async def distribute_thumbnails(
    node: Any, library: Any, location_id: int, **kwargs: Any,
) -> dict[str, Any]:
    """Distribute one location's thumbnail pass across library peers as
    stage-typed WORK shards (parallel/scheduler.py STAGE_THUMB): every
    executor consults its own journal + store first, encodes the rest,
    and ships the webp bytes back so the
    coordinator's store converges bit-identical. With no P2P runtime
    this IS a local pass in shard clothing."""
    from ....location.indexer.mesh import distribute_location_stages
    from ....parallel import scheduler as _scheduler

    return await distribute_location_stages(
        node, library, location_id, [_scheduler.STAGE_THUMB], **kwargs
    )
