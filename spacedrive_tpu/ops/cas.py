"""Content-addressing (cas_id) — sampling layout + batched TPU pipeline.

Bit-parity with the reference algorithm (ref:core/src/object/cas.rs:23-62):

    message = u64_le(size) || payload
    payload = whole file                          if size <= 100 KiB
            = file[0:8K]
              || file[8K + k*J : +10K]  k=0..3    J = (size - 16K) // 4
              || file[size-8K : size]             otherwise
    cas_id  = blake3(message).hex()[:16]

Large files therefore produce a *fixed* 57,352-byte message (57 chunks)
— the TPU hot bucket. Small files bucket by chunk count into a handful
of compiled shapes (ragged lengths are masked in-kernel).
"""

from __future__ import annotations

import hashlib
import os
import struct
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Sequence

import numpy as np

from .blake3_ref import (
    CHUNK_LEN,
    StreamingBlake3,
    chunk_chaining_value,
    parent_chaining_value,
    root_digest_from_pair,
)
# blake3_jax (and with it jax) loads lazily inside the device dispatch
# paths: the CPU halves (read_message / chunk caches / cas_ids "cpu")
# stay importable without jax, so hashing on host pays no jax import.

SAMPLE_COUNT = 4
SAMPLE_SIZE = 10 * 1024
HEADER_OR_FOOTER_SIZE = 8 * 1024
MINIMUM_FILE_SIZE = 100 * 1024

LARGE_MSG_LEN = 8 + 2 * HEADER_OR_FOOTER_SIZE + SAMPLE_COUNT * SAMPLE_SIZE  # 57,352
LARGE_CHUNKS = (LARGE_MSG_LEN + 1023) // 1024  # 57
MAX_SMALL_MSG_LEN = 8 + MINIMUM_FILE_SIZE  # 102,408
# Small-file buckets by chunk count; compiled once each.
SMALL_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 101)


def sample_ranges(size: int) -> list[tuple[int, int]]:
    """(offset, length) reads composing the payload, matching the
    reference's read/seek sequence exactly."""
    if size <= MINIMUM_FILE_SIZE:
        return [(0, size)]
    jump = (size - 2 * HEADER_OR_FOOTER_SIZE) // SAMPLE_COUNT
    ranges = [(0, HEADER_OR_FOOTER_SIZE)]
    for k in range(SAMPLE_COUNT):
        ranges.append((HEADER_OR_FOOTER_SIZE + k * jump, SAMPLE_SIZE))
    ranges.append((size - HEADER_OR_FOOTER_SIZE, HEADER_OR_FOOTER_SIZE))
    return ranges


def message_from_bytes(content: bytes, size: int | None = None) -> bytes:
    """Assemble the hashed message for in-memory content."""
    size = len(content) if size is None else size
    parts = [struct.pack("<Q", size)]
    for off, ln in sample_ranges(size):
        parts.append(content[off:off + ln])
    return b"".join(parts)


def read_message(path: str | os.PathLike, size: int | None = None) -> bytes:
    """Read the sampling layout from disk: the path form of
    `read_message_fd` (at the end of this module), for callers that
    hold no descriptor. Opens `path`, reads through the descriptor and
    closes it on every exit. `size` None: whatever a stat of the path
    says."""
    if size is None:
        size = os.stat(path).st_size
    fd = os.open(path, os.O_RDONLY)
    try:
        return read_message_fd(fd, size)
    finally:
        os.close(fd)


def message_len(size: int) -> int:
    """Length of the hashed message for a file of `size` bytes."""
    return 8 + size if size <= MINIMUM_FILE_SIZE else LARGE_MSG_LEN


# --- dirty-range rehash (incremental indexing, location/indexer/journal) ---
#
# The cas_id message is hashed by BLAKE3 as a Merkle tree over 1024-byte
# chunks. Caching a cheap content digest per chunk plus the tree's
# chaining values lets a warm pass on a modified file recompute only the
# chunks whose bytes actually changed (and their log-depth path of
# parents) — bit-identical to a full rehash, with zero bytes shipped to
# the device. Unchanged chunks cost one blake2b per 1 KiB (C-speed);
# only dirty chunks pay the BLAKE3 compression.
#
# Cache shape per file: `digests` (16-byte blake2b per chunk, built on
# EVERY journal record — cheap enough for the cold/device path) and
# `levels` (the CV tree, built the first time a file takes the host
# dirty-range path — the device path cannot observe interior CVs).

CHUNK_DIGEST_LEN = 16


def _split_chunks(message: bytes) -> list[bytes]:
    return [message[i:i + CHUNK_LEN] for i in range(0, len(message), CHUNK_LEN)]


def chunk_digests(message: bytes) -> list[bytes]:
    """Cheap per-chunk content digests (blake2b-128, C-speed) — the
    dirty detector, NOT part of the cas_id itself."""
    return [
        hashlib.blake2b(c, digest_size=CHUNK_DIGEST_LEN).digest()
        for c in _split_chunks(message)
    ]


@dataclass
class ChunkCache:
    """Per-file dirty-range state carried by the index journal."""

    msg_len: int
    digests: list[bytes]
    # CV tree: levels[0] = per-chunk CVs, each upper level the pairwise
    # parents (odd node carried up), topmost level exactly 2 nodes.
    # None until the file first takes the host dirty-range path.
    levels: list[list[bytes]] | None = None

    def to_payload(self) -> dict:
        return {
            "len": self.msg_len,
            "dig": self.digests,
            "cvs": self.levels,
        }

    @classmethod
    def from_payload(cls, obj: Any) -> "ChunkCache | None":
        """Strict validation: anything malformed returns None (the
        caller degrades to a cold rehash — never a wrong cas_id)."""
        if not isinstance(obj, dict):
            return None
        msg_len, digests, levels = obj.get("len"), obj.get("dig"), obj.get("cvs")
        if not isinstance(msg_len, int) or msg_len <= 0:
            return None
        n = (msg_len + CHUNK_LEN - 1) // CHUNK_LEN
        if (
            not isinstance(digests, list) or len(digests) != n
            or any(
                not isinstance(d, bytes) or len(d) != CHUNK_DIGEST_LEN
                for d in digests
            )
        ):
            return None
        if levels is not None:
            if not isinstance(levels, list) or not levels:
                return None
            want = n
            for i, level in enumerate(levels):
                if (
                    not isinstance(level, list) or len(level) != want
                    or any(not isinstance(cv, bytes) or len(cv) != 32 for cv in level)
                ):
                    return None
                want = (want + 1) // 2
            if len(levels[-1]) != 2:
                return None
        return cls(msg_len, list(digests), levels)


def build_chunk_cache(message: bytes) -> ChunkCache:
    """Digest-only cache (cheap) — recorded alongside a device-hashed
    cas_id so the FIRST in-place modification can already diff chunks."""
    return ChunkCache(len(message), chunk_digests(message))


def _build_levels(cvs: list[bytes]) -> list[list[bytes]]:
    levels = [cvs]
    while len(levels[-1]) > 2:
        cur = levels[-1]
        nxt = [
            parent_chaining_value(cur[j], cur[j + 1])
            for j in range(0, len(cur) - 1, 2)
        ]
        if len(cur) % 2:
            nxt.append(cur[-1])
        levels.append(nxt)
    return levels


def _root_cas_id(levels: list[list[bytes]]) -> str:
    top = levels[-1]
    return root_digest_from_pair(top[0], top[1], 8).hex()


def host_rehash_with_cache(message: bytes) -> tuple[str, ChunkCache]:
    """Full host rehash that CAPTURES the CV tree, so the next
    modification of this file pays only for its dirty chunks. Only
    valid for multi-chunk messages (single chunks use the ROOT flag)."""
    chunks = _split_chunks(message)
    if len(chunks) < 2:
        raise ValueError("host_rehash_with_cache needs >= 2 chunks")
    cvs = [chunk_chaining_value(c, i) for i, c in enumerate(chunks)]
    levels = _build_levels(cvs)
    cache = ChunkCache(len(message), chunk_digests(message), levels)
    return _root_cas_id(levels), cache


def dirty_range_rehash(
    message: bytes, cache: ChunkCache
) -> tuple[str, ChunkCache, int, int]:
    """Rehash `message` reusing `cache` from its previous version.
    Returns (cas_id, refreshed cache, dirty_chunks, bytes_rehashed) —
    the cas_id is bit-identical to a full rehash (golden-tested).

    Requires an unchanged message length (a size change moves every
    sample offset, so the whole message is new — callers full-rehash).
    """
    if len(message) != cache.msg_len:
        raise ValueError("message length changed; dirty-range does not apply")
    chunks = _split_chunks(message)
    if len(chunks) < 2:
        raise ValueError("dirty-range needs >= 2 chunks")
    digests = chunk_digests(message)
    dirty = [i for i, d in enumerate(digests) if d != cache.digests[i]]
    if cache.levels is None:
        # no CV tree yet (cas came off the device): one full host rehash
        # builds it; every later modification pays only its dirty chunks
        cas, fresh = host_rehash_with_cache(message)
        return cas, fresh, len(dirty), len(message)
    levels = [list(level) for level in cache.levels]
    hashed = 0
    for i in dirty:
        levels[0][i] = chunk_chaining_value(chunks[i], i)
        hashed += len(chunks[i])
    # bubble the dirty paths up: parent j covers children 2j / 2j+1;
    # an unpaired last node is carried (copied), not compressed
    dirty_nodes = set(dirty)
    for depth in range(len(levels) - 1):
        cur, nxt = levels[depth], levels[depth + 1]
        parents = set()
        for i in dirty_nodes:
            j = i // 2
            if j in parents:
                continue
            if 2 * j + 1 < len(cur):
                nxt[j] = parent_chaining_value(cur[2 * j], cur[2 * j + 1])
            else:
                nxt[j] = cur[2 * j]
            parents.add(j)
        dirty_nodes = parents
    return (
        _root_cas_id(levels),
        ChunkCache(cache.msg_len, digests, levels),
        len(dirty),
        hashed,
    )


def cas_id_cpu(path: str | os.PathLike, size: int | None = None) -> str:
    """Host-only cas_id (the reference's exact behavior), used as the
    default/fallback implementation and for parity tests."""
    msg = read_message(path, size)
    return StreamingBlake3().update(msg).hexdigest()[:16]


def cas_id_from_bytes_cpu(content: bytes) -> str:
    return StreamingBlake3().update(message_from_bytes(content)).hexdigest()[:16]


# The pad ladder and per-device dispatch cap live in the autotuner's
# policy module (parallel/autotune.py) — the ONE home for pipeline
# sizing constants (sdlint SD013). Re-exported here because the ladder
# is also the compiled-shape vocabulary this module packs against.
from ..parallel.autotune import BATCH_LADDER

DEVICE_BATCH = BATCH_LADDER[-1]  # max rows per dispatch PER DEVICE


def batch_ladder(n_devices: int = 1) -> tuple[int, ...]:
    """Global pad ladder for an n-device dp dispatch: every rung is the
    per-device warm rung × device count, so each chip always sees one
    of the SAME three compiled shapes (32/256/1024 rows) regardless of
    how many chips share the batch — tracing cost stays bounded at 3
    programs per (bucket, device count)."""
    n = max(1, n_devices)
    return BATCH_LADDER if n == 1 else tuple(r * n for r in BATCH_LADDER)


def device_batch(n_devices: int = 1) -> int:
    """Max rows per dispatch: DEVICE_BATCH per participating device."""
    return DEVICE_BATCH * max(1, n_devices)


def pack_canonical_batch(
    messages: Sequence[bytes], max_chunks: int, n_devices: int = 1
) -> tuple[np.ndarray, np.ndarray]:
    """The ONE batch-shape policy for device hashing: ≤device_batch(n)
    messages pack into a `(ladder_size, max_chunks*1024)` uint8 array +
    int32 lengths, the ladder scaled by `n_devices` (batch_ladder) so a
    dp-sharded dispatch divides evenly with warm per-device shapes. A
    fresh shape costs a compile (seconds for the XLA body, minutes for
    the wide-tile Pallas kernel) while a warm shape only runs, so every
    caller (cas_ids_begin, the validator) MUST pack through here. Pad
    rows hash 1 junk byte and get sliced off by the caller.

    The array starts uninitialized (np.empty) and each row writes its
    message + explicit zero tail — one pass over the buffer instead of
    a full zero-fill followed by prefix overwrites (the zero-fill was
    ~half the pack time at the 57 MB hot-bucket batch size)."""
    n = len(messages)
    cap = device_batch(n_devices)
    if n > cap:
        raise ValueError(f"pack at most {cap} messages, got {n}")
    n_pad = next(s for s in batch_ladder(n_devices) if s >= n)
    arr = np.empty((n_pad, max_chunks * 1024), np.uint8)
    lens = np.ones((n_pad,), np.int32)
    for j, msg in enumerate(messages):
        ln = len(msg)
        arr[j, :ln] = np.frombuffer(msg, np.uint8)
        arr[j, ln:] = 0
        lens[j] = ln
    arr[n:] = 0  # pad rows (length 1) must hash a zero byte
    return arr, lens


def _bucket_for(msg_len: int) -> int:
    chunks = max(1, (msg_len + 1023) // 1024)
    for b in SMALL_BUCKETS:
        if chunks <= b:
            return b
    raise ValueError(f"message too large for small buckets: {msg_len}")


@dataclass
class _Bucket:
    chunks: int
    indices: list[int]
    messages: list[bytes]


def shard_occupancy(n_real: int, n_pad: int, n_dev: int) -> list[float]:
    """Per-device real-row fraction of one sharded dispatch (device d
    owns rows [d*r, (d+1)*r) of the contiguously packed batch) — the
    caller observes these under its own literal `op` label."""
    r = n_pad // n_dev
    return [
        min(max(n_real - d * r, 0), r) / r for d in range(n_dev)
    ]


def cas_ids_begin(
    messages: Sequence[bytes], devices: Sequence[Any] | None = None,
    _depth: int = 0,
) -> Callable[[], list[str]]:
    """Dispatch device hashing WITHOUT blocking: batches go to the
    accelerator asynchronously (JAX dispatch) and the returned finisher
    materializes the hex ids. Splitting dispatch from completion lets a
    pipeline queue window N+1's transfer while N is still in flight
    (SURVEY §7 hard part #2).

    With >1 local device each batch is dp-sharded so ONE dispatch feeds
    every chip (blake3_jax.hash_batch devices=...). Explicitly passed
    `devices` always shard; the default policy shards a batch only when
    it fills at least half of the smallest sharded ladder rung
    (BATCH_LADDER[0] × n_devices ÷ 2) — tiny tails stay on one device
    where their warm 32-row shape is cheapest.

    Auto dispatches ride the degradation ladder (parallel.mesh.LADDER):
    a device failure demotes the NEXT attempt — full mesh → surviving
    chip subset → host reference path — and the failed batch is re-run
    at the demoted rung inside the same `finish()` call instead of
    failing the window (the host path is bit-identical, golden-tested).
    Explicit `devices` stay strict and re-raise."""
    from . import blake3_jax
    from ..parallel import mesh as _mesh
    from ..telemetry import metrics as _tm
    from ..telemetry import span as _span

    if devices is not None:
        devs = list(devices)
        explicit = True
        level: int | None = None
    else:
        explicit = False
        if _depth >= 3:
            # recursion cap: go straight to the host path WITHOUT
            # consulting the ladder — ladder_devices() could hand this
            # doomed call the half-open probe and strand it
            _tm.CAS_BACKEND_FALLBACK.inc()
            return lambda: cas_ids(messages, "cpu")
        devs, level = _mesh.ladder_devices()
        if level == _mesh.LEVEL_HOST:
            # demoted to (or stuck on) the host reference path — count
            # the degradation so a node quietly hashing on CPU shows up
            _tm.CAS_BACKEND_FALLBACK.inc()
            return lambda: cas_ids(messages, "cpu")
    n_dev = len(devs)

    def _retry_demoted(exc: Exception) -> Callable[[], list[str]]:
        from ..telemetry import events as _events

        _mesh.LADDER.record_failure(level, devs)
        _tm.CAS_BACKEND_FALLBACK.inc()
        _events.record_error("cas.ladder", exc)
        # bounded re-dispatch at the demoted rung (depth caps probe
        # oscillation when a test-sized reset_timeout is in effect)
        return cas_ids_begin(messages, _depth=_depth + 1)

    # host seconds of this call by stage, observed once at its end: what
    # it costs to bucket and pack a window and to hand it to the device
    # (H2D and enqueue), apart from the wait for digests in `finish`
    pack_s = dispatch_s = 0.0

    buckets: dict[int, _Bucket] = {}
    with _span("cas.pack") as sp:
        for i, msg in enumerate(messages):
            c = LARGE_CHUNKS if len(msg) == LARGE_MSG_LEN else _bucket_for(len(msg))
            b = buckets.setdefault(c, _Bucket(c, [], []))
            b.indices.append(i)
            b.messages.append(msg)
    pack_s += sp.duration

    # dispatch quantum: the autotuner's current per-device rung × device
    # count (static top rung = device_batch, bit-identical to the
    # pre-autotune path). Smaller rungs keep every compiled shape warm —
    # parts still pack through the same ladder (pack_canonical_batch).
    from ..parallel import autotune as _autotune

    step = min(
        device_batch(n_dev),
        _autotune.policy("identify").dispatch_rows_per_device()
        * max(1, n_dev),
    )
    in_flight: list[tuple[_Bucket, int, Any]] = []
    used_devices = False  # did any part actually shard over `devs`?
    try:
        for c, bucket in sorted(buckets.items()):
            for off in range(0, len(bucket.messages), step):
                part = bucket.messages[off : off + step]
                # shard-declined parts MUST fit the single-device pack cap:
                # with step = DEVICE_BATCH × n_dev a part can exceed
                # DEVICE_BATCH, so anything over the cap shards regardless
                # of the occupancy heuristic (only reachable at >64 devices)
                shard = n_dev > 1 and (
                    explicit
                    or len(part) * 2 >= n_dev * BATCH_LADDER[0]
                    or len(part) > DEVICE_BATCH
                )
                # at the SUBSET rung an unsharded tail must still land
                # on a SURVIVING chip, not the (possibly dead) default
                # device — pin it to the subset's first device
                single = (
                    devs[:1]
                    if not shard and not explicit
                    and level == _mesh.LEVEL_SUBSET and devs
                    else None
                )
                used_devices = used_devices or shard or single is not None
                with _span("cas.pack") as sp:
                    arr, lens = pack_canonical_batch(
                        part, c, n_devices=n_dev if shard else 1
                    )
                pack_s += sp.duration
                if shard:
                    for frac in shard_occupancy(len(part), arr.shape[0], n_dev):
                        _tm.DEVICE_DISPATCH_OCCUPANCY.observe(frac, op="blake3")
                with _span("cas.enqueue", nbytes=arr.nbytes) as sp:
                    words = blake3_jax.hash_batch(
                        arr, lens, max_chunks=c,
                        devices=devs if shard else single,
                    )
                dispatch_s += sp.duration
                # which compiled shape the part took and what crossed the
                # link for it: the padded array, not the messages alone
                bucket_l, rung_l = str(c), str(arr.shape[0])
                _tm.CAS_DISPATCH_ROWS.inc(len(part), chunks=bucket_l, rung=rung_l)
                _tm.CAS_DISPATCH_BYTES.inc(arr.nbytes, chunks=bucket_l, rung=rung_l)
                in_flight.append((bucket, off, words))
        _tm.IDENTIFIER_STAGE_SECONDS.observe(pack_s, stage="pack")
        _tm.IDENTIFIER_STAGE_SECONDS.observe(dispatch_s, stage="dispatch")
    except Exception as exc:  # noqa: BLE001 - dispatch failure → demote
        if explicit:
            raise
        return _retry_demoted(exc)

    def finish() -> list[str]:
        out: list[str | None] = [None] * len(messages)
        try:
            for bucket, off, words in in_flight:
                part = bucket.indices[off : off + step]
                if getattr(words, "ndim", 2) != 2 or words.shape[1] != 8 \
                        or words.shape[0] < len(part):
                    raise ValueError(
                        f"device returned wrong-shaped digest batch "
                        f"{getattr(words, 'shape', '?')} for {len(part)} rows"
                    )
                for j, hx in enumerate(
                    blake3_jax.words_to_hex(words, 16)[: len(part)]
                ):
                    out[part[j]] = hx
        except Exception as exc:  # noqa: BLE001 - materialization → demote
            if explicit:
                raise
            return _retry_demoted(exc)()
        if not explicit:
            if used_devices:
                _mesh.LADDER.record_success(level)
            else:
                # the whole call ran unsharded on the default device —
                # it proved nothing about the rung's chips, so a held
                # half-open probe is released, never promoted
                _mesh.LADDER.probe_inconclusive(level)
        return out  # type: ignore[return-value]

    return finish


def cas_ids_batched(messages: Sequence[bytes]) -> list[str]:
    """cas_ids for pre-assembled messages, batched per chunk-bucket and
    hashed on the accelerator. Order-preserving."""
    return cas_ids_begin(messages)()


def cas_ids_for_paths(paths: Iterable[tuple[str, int]]) -> list[str]:
    """Batched cas_ids for (path, size) pairs: sampled reads on host,
    BLAKE3 on device."""
    msgs = [read_message(p, s) for p, s in paths]
    return cas_ids_batched(msgs)


def cas_ids_native_cpu(messages: Sequence[bytes]) -> list[str] | None:
    """Threaded C BLAKE3 path; None when the native lib is unavailable."""
    from .. import native

    digests = native.blake3_many(list(messages))
    if digests is None:
        return None
    return [d[:8].hex() for d in digests]


def cas_ids(messages: Sequence[bytes], backend: str = "auto") -> list[str]:
    """Backend-selected batched cas_ids.

    - "tpu"/"device": JAX accelerator batch (falls back if jax is
      unusable only under "auto").
    - "cpu": native C (threaded), then pure Python.
    - "auto": device if a non-CPU jax backend is live, else native C,
      else Python — the same default-with-fallback contract the
      north-star requires.
    """
    if not messages:
        return []
    if backend in ("tpu", "device"):
        return cas_ids_batched(messages)
    if backend == "cpu":
        got = cas_ids_native_cpu(messages)
        if got is not None:
            return got
        return [StreamingBlake3().update(m).hexdigest()[:16] for m in messages]
    # auto
    if _device_available():
        try:
            return cas_ids_batched(messages)
        except Exception as exc:  # noqa: BLE001 - fall back to host hashing
            # the degradation must be observable, not silent: count it
            # and put the bounded traceback on the flight recorder so a
            # node quietly hashing on CPU shows up in the debug bundle
            from ..telemetry import events as _events
            from ..telemetry import metrics as _tm

            _tm.CAS_BACKEND_FALLBACK.inc()
            _events.record_error("cas.auto", exc)
    return cas_ids(messages, "cpu")


_DEVICE_STATE: list[bool] | None = None


def _device_available() -> bool:
    """Is a non-CPU JAX backend live? Only "auto" asks, and only to
    choose between two working paths — a backend that cannot
    initialise (a chip held by another process) raises instead of
    reading as "CPU only"."""
    global _DEVICE_STATE
    if _DEVICE_STATE is None:
        import jax

        _DEVICE_STATE = [jax.devices()[0].platform != "cpu"]
    return _DEVICE_STATE[0]


# --- descriptor-based reads (added below everything else: the hash
# programs' cache keys hold this module's line numbers, PERF.md §6) ---


def read_message_fd(fd: int, size: int) -> bytes:
    """The sampling layout of the file open behind `fd`, one `pread` a
    range: no seek, and the descriptor's own offset stays where it was.
    The caller owns the descriptor (the file identifier opens a file
    once, takes its identity from the same descriptor, reads, closes).
    A range that comes back short is an OSError, as from any read."""
    parts = [struct.pack("<Q", size)]
    for off, ln in sample_ranges(size):
        buf = os.pread(fd, ln, off)
        if len(buf) != ln:
            raise OSError(f"short read at {off}: {len(buf)} of {ln} bytes")
        parts.append(buf)
    return b"".join(parts)
