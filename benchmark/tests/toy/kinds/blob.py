"""The toy kind of `test_kinds.py`, with all four functions a kind
module may have (benchmark/README.md). A blob is `BLOB`, a record count
and length-prefixed records of seeded bytes: a format the harness does
not know, so only `write` can put it on disk and its cas_id is of the
bytes found there."""

from __future__ import annotations

import os
import struct

import numpy as np

MAGIC = b"BLOB"
HEADER = len(MAGIC) + 4


def reference_bytes(entry: dict) -> bytes:
    rng = np.random.default_rng(entry["content"])
    lo, hi = entry["blob"]["record_bytes"]
    records = [rng.bytes(int(rng.integers(lo, hi + 1)))
               for _ in range(entry["blob"]["records"])]
    return MAGIC + struct.pack("<I", len(records)) + b"".join(
        struct.pack("<I", len(r)) + r for r in records)


def write(path: str, entry: dict) -> None:
    with open(path, "wb") as f:
        f.write(reference_bytes(entry))


def embed_pad(entries: list[dict]) -> int:
    """The batch a pass over these blobs would hand the embedder, a row
    a record, padded as `ops/embed_jax.py` pads: the stills of the
    location never reach it."""
    rows = sum(e["blob"]["records"] for e in entries)
    return 1 << max(0, (rows - 1).bit_length())


def programs(entries: list[dict], location: str, n_dev: int) -> list[tuple]:
    from spacedrive_tpu.models import embedder
    from spacedrive_tpu.ops import embed_jax

    if not entries:
        return []
    pad = embed_pad(entries)
    return [(0, f"blob_embed_pad{pad}", lambda: embed_jax.embed_batch(np.zeros(
        (pad, embedder.IMAGE_SIZE, embedder.IMAGE_SIZE, 3), np.float32)))]


def _size_off(entry: dict, row_size: int, header: int = HEADER) -> int:
    return abs(row_size - (len(reference_bytes(entry)) - HEADER + header))


def compare(c, state: dict) -> set[str]:
    """The row's size against the reference's, header included."""
    sizes = {bytes(pub_id): int.from_bytes(blob or b"", "little")
             for pub_id, blob in state["db"].execute(
                 "SELECT pub_id, size_in_bytes_bytes FROM file_path")}
    bad = set()
    for e in state["entries"]:
        row = state["rows"].get(e["rel"])
        off = HEADER if row is None else _size_off(
            e, sizes[bytes(row["pub_id"])])
        c.worst("blob_size_off", off, 0)
        if off:
            bad.add(e["rel"])
    return bad


def control(config: dict, entries: list[dict], location: str,
            seed: int) -> dict:
    """The guarantee broken: the size without the header."""
    return {"blob_size_off": [max(
        _size_off(e, os.path.getsize(os.path.join(location, e["rel"])),
                  header=0) for e in entries), 0]}
