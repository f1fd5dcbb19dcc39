"""Full-file BLAKE3 checksums — native C streaming on the host, batched
XLA kernel on device for small-file fleets.

Parity: ref:core/src/object/validation/hash.rs:9-25 — 1 MiB read
blocks, 64-hex digest. Memory stays bounded over unbounded file sizes:
files stream through the incremental hasher block by block.

TPU-first: a validation pass over a library is mostly many small
files. Those are padded into power-of-two buckets and hashed as one
device batch (ops/blake3_jax); files above DEVICE_MAX_BYTES stream
through the native C hasher instead.
"""

from __future__ import annotations

import os
from collections.abc import Sequence

from ... import native
from ...ops import blake3_jax
from ...ops.blake3_ref import StreamingBlake3

BLOCK_LEN = 1 << 20  # ref:hash.rs:9
DEVICE_MAX_BYTES = 256 * 1024  # larger files stream on the host
_MIN_DEVICE_BATCH = 16


def file_checksum(path: str | os.PathLike) -> str:
    """64-hex full BLAKE3 of one file, streamed in 1 MiB blocks
    (ref:hash.rs:11-25)."""
    hasher = native.StreamingHasher() if native.available() else StreamingBlake3()
    with open(path, "rb") as f:
        while True:
            block = f.read(BLOCK_LEN)
            if not block:
                break
            hasher.update(block)
    return hasher.digest(32).hex()


def _bucket(n: int) -> int:
    chunks = max(1, (n + 1023) // 1024)
    b = 1
    while b < chunks:
        b *= 2
    return b


def file_checksums(paths: Sequence[str | os.PathLike], backend: str = "auto") -> list[str]:
    """Checksum many files; small files go to the device as padded
    batches bucketed by size, everything else streams on the host.
    Unreadable files yield "" instead of failing the batch."""
    import numpy as np

    sizes = []
    for p in paths:
        try:
            sizes.append(os.path.getsize(p))
        except OSError:
            sizes.append(-1)

    results: list[str | None] = [None] * len(paths)
    device_ok = backend in ("tpu", "device", "auto")

    def host_hash(i: int) -> None:
        try:
            results[i] = file_checksum(paths[i])
        except OSError:
            results[i] = ""

    buckets: dict[int, list[int]] = {}
    for i, size in enumerate(sizes):
        if size < 0:
            results[i] = ""
        elif device_ok and 0 < size <= DEVICE_MAX_BYTES:
            buckets.setdefault(_bucket(size), []).append(i)
        else:
            host_hash(i)

    for max_chunks, idxs in buckets.items():
        if len(idxs) < _MIN_DEVICE_BATCH and backend == "auto":
            for i in idxs:
                host_hash(i)
            continue
        rows, row_idxs = [], []
        msgs = np.zeros((len(idxs), max_chunks * 1024), np.uint8)
        lens = np.zeros((len(idxs),), np.int32)
        for i in idxs:
            try:
                with open(paths[i], "rb") as f:
                    data = f.read(max_chunks * 1024 + 1)
            except OSError:
                results[i] = ""
                continue
            if len(data) > max_chunks * 1024:  # grew since the size scan
                host_hash(i)
                continue
            j = len(rows)
            rows.append(i)
            msgs[j, : len(data)] = np.frombuffer(data, np.uint8)
            lens[j] = len(data)
            row_idxs.append(i)
        if not rows:
            continue
        # one batch-shape policy for every device hash call site
        from ...ops.cas import DEVICE_BATCH, pack_canonical_batch

        for off in range(0, len(rows), DEVICE_BATCH):
            part = row_idxs[off : off + DEVICE_BATCH]
            n = len(part)
            batch, blens = pack_canonical_batch(
                [
                    msgs[off + j, : lens[off + j]].tobytes()
                    for j in range(n)
                ],
                max_chunks,
            )
            words = blake3_jax.hash_batch(batch, blens, max_chunks=max_chunks)
            for j, h in enumerate(blake3_jax.words_to_hex(words, 64)[:n]):
                results[part[j]] = h

    return [r if r is not None else "" for r in results]

