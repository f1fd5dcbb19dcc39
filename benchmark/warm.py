"""Which device programs a cell's passes can dispatch, derived from the
location's manifest and the program's own bucket tables and autotuner
limits, and the warm-up that runs each of them once before the window.

Nothing is listed by hand: hash programs are (chunk bucket × pad rung)
pairs from `ops/cas.py`, resize programs (canvas bucket × batch pad)
pairs from `ops/thumbnail_jax.py`, embed programs batch pads from
`ops/embed_jax.py`; how large a part, chunk or batch can get comes from
`parallel/autotune.py`'s static sizes times its widest window scale.
"""

from __future__ import annotations

import ctypes
import os
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

#: host RSS one wide-tile Pallas hash program needs while Mosaic compiles
#: it (3.9 GB measured, PERF.md §7); glibc keeps the freed heap until
#: malloc_trim, so N compiles in flight cost N times this
COMPILE_PEAK_BYTES = 4 << 30


def _pow2_pads(n: int, cap: int) -> list[int]:
    top = 1 << max(0, (min(n, cap) - 1).bit_length())
    return [1 << k for k in range(top.bit_length())]


def hash_programs(sizes: list[int], n_dev: int) -> list[tuple[int, int]]:
    """(rows, chunks) of every hash program files of these sizes can
    reach: a part of k messages pads to the smallest ladder rung ≥ k, and
    a bucket with n files can form parts of 1..min(n, dispatch cap)."""
    from spacedrive_tpu.ops import cas

    per_bucket: Counter = Counter()
    for size in sizes:
        if size > cas.MINIMUM_FILE_SIZE:
            per_bucket[cas.LARGE_CHUNKS] += 1
        else:
            chunks = max(1, -(-cas.message_len(size) // 1024))
            per_bucket[next(b for b in cas.SMALL_BUCKETS if chunks <= b)] += 1
    ladder = cas.batch_ladder(n_dev)
    out = []
    for chunks, n in sorted(per_bucket.items()):
        largest = min(n, cas.device_batch(n_dev))
        below = 0
        for rung in ladder:
            if below < largest:
                out.append((rung, chunks))
            below = rung
    return out


def decoded_size(path: str) -> tuple[int, int]:
    """(h, w) the thumbnailer's decode hands to the resize, read from
    the file's header alone: JPEGs decode in draft mode at the smallest
    DCT scale that still covers the target."""
    from PIL import Image

    from spacedrive_tpu.object.media.thumbnail import process
    from spacedrive_tpu.ops import thumbnail_jax as tj

    with Image.open(path) as img:
        if img.format == "JPEG":
            img.draft("RGB", tj.scale_dimensions(*img.size))
        w, h = img.size
    if max(h, w) > process.MAX_DIM:
        step = -(-max(h, w) // process.MAX_DIM)
        h, w = -(-h // step), -(-w // step)
    return h, w


def media_programs(image_paths: list[str], n_dev: int) -> dict:
    """{"resize": [((bh, bw), pad), ...], "embed": [pad, ...]}"""
    from spacedrive_tpu.ops import thumbnail_jax as tj
    from spacedrive_tpu.parallel import autotune

    scale = int(autotune.SCALE_MAX)
    per_bucket: Counter = Counter()
    for path in image_paths:
        per_bucket[tj.bucket_for(*decoded_size(path))] += 1
    thumb_cap = autotune.THUMB_DEVICE_BATCH * n_dev * scale
    embed_cap = autotune.EMBED_DEVICE_BATCH * n_dev * scale
    return {
        "resize": [(b, pad) for b, n in sorted(per_bucket.items())
                   for pad in _pow2_pads(n, thumb_cap)],
        "embed": _pow2_pads(len(image_paths), embed_cap) if image_paths else [],
    }


def release_freed_heap() -> None:
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass  # not glibc: nothing to trim


def compile_threads(memory_limit: int, rss: int) -> int:
    """As many compiles in flight as fit in 3/4 of the host's memory on
    top of what the runtime already holds."""
    by_memory = (memory_limit * 3 // 4 - rss) // COMPILE_PEAK_BYTES
    return int(max(1, min((os.cpu_count() or 2) - 2, by_memory)))


def run_programs(hashes, media: dict, n_dev: int, threads: int,
                 own=()) -> list:
    """Dispatch every program once, widest first, in `threads` threads
    (XLA and Mosaic compile outside the GIL; with a warm compile cache
    each call only loads and runs). `own` are the (weight, name, fn) a
    configuration's kinds of file bring: `fn()` runs one program to its
    end. → [(name, seconds), ...]"""
    import jax
    import numpy as np

    from spacedrive_tpu.models import embedder
    from spacedrive_tpu.ops import blake3_jax, embed_jax
    from spacedrive_tpu.ops import thumbnail_jax as tj

    devices = jax.devices() if n_dev > 1 else None

    def timed(name, fn):
        t0 = time.perf_counter()
        fn()
        release_freed_heap()
        return name, round(time.perf_counter() - t0, 2)

    def hash_one(rows, chunks):
        jax.block_until_ready(blake3_jax.hash_batch(
            np.zeros((rows, chunks * 1024), np.uint8),
            np.ones((rows,), np.int32), max_chunks=chunks, devices=devices))

    def resize_one(bucket, pad):
        bh, bw = bucket
        tj.resize_batch([np.zeros((bh, bw, 4), np.uint8)] * pad,
                        [(min(bh, tj.OUT_CANVAS) // 2,
                          min(bw, tj.OUT_CANVAS) // 2)] * pad)

    def embed_one(pad):
        embed_jax.embed_batch(np.zeros(
            (pad, embedder.IMAGE_SIZE, embedder.IMAGE_SIZE, 3), np.float32))

    jobs = [(rows * chunks, f"hash_{rows}x{chunks}",
             lambda r=rows, c=chunks: hash_one(r, c)) for rows, chunks in hashes]
    jobs += [(0, f"resize_{b[0]}x{b[1]}_pad{pad}",
              lambda b=b, pad=pad: resize_one(b, pad))
             for b, pad in media["resize"]]
    jobs += [(0, f"embed_pad{pad}", lambda pad=pad: embed_one(pad))
             for pad in media["embed"]]
    jobs += list(own)
    jobs.sort(key=lambda j: -j[0])
    with ThreadPoolExecutor(threads, thread_name_prefix="bench-warm") as pool:
        futures = [pool.submit(timed, name, fn) for _w, name, fn in jobs]
        return [f.result() for f in futures]
