"""chip_smoke — the quickest proof that the index pass still runs on the chip.

One process, one command, no network:

    python3 chip_smoke.py [--seed N] [--work-dir DIR] [--keep]

It refuses to run unless JAX's first device is a TPU. Then, from the
seed, it builds a location a user would call real (≥20,000 files in a
nested tree, ≥8,192 of them over 100 KiB, exact duplicates, 12-megapixel
photos and small images), indexes it through the entry points `sdx
index` uses (Node → library → location → scan chain → thumbnailer),
serves a few reads over HTTP from the same node, and checks every result
against a plain reference: native-C and pure-Python BLAKE3 for the
cas_ids, PIL for thumbnails, NumPy for embeddings. It asserts that the
pass USED the chip — Mosaic-compiled Pallas kernel, degradation ladder
at level 0, zero fallback counters, every job COMPLETED — and, on a
multi-chip host, that every chip hashed and resized. A second pass over
a fresh data dir, with SD_PROCS=2 workers spawned by the process that
owns the chip, must compile nothing.

Stdout carries two lines, both JSON objects: first the summary (counts,
compile counts, per-phase seconds, ending `"claim": null`), then, last,
the verdict `{"ok": ..., "device": {"platform", "kind", "count"}}` with
exactly those keys. The exit code is 0 only if every check passed. This
is a smoke, not a benchmark: the seconds it prints are wall-clock phases
of one run and claim nothing.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import shutil
import struct
import sys
import threading
import time
import urllib.parse
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))

#: corpus scale — the floors ISSUE 21 fixed for a location "users would
#: call real"; a run below them reports ok=false
SCALE = {
    "raw_large": 8704,      # large-only dirs: ≥ 2×4096−1 contiguous rows, so a
                            # whole window is hot-bucket even at 4 chips
    "mixed_large": 512,     # large files scattered among small ones
    "small": 10752,         # ≤100 KiB files over all eight chunk buckets
    "duplicates": 600,      # exact copies of earlier files
    "photos_jpeg": 128,     # 4032×3024 JPEG, EXIF orientation on some
    "photos_png": 32,       # 4032×3024 PNG: no DCT draft → the 4096 canvas
    "small_images": 160,    # ≤1024 px, three size classes
}
FLOORS = {"files": 20000, "large": 8192, "photos": 128, "small_images": 128}

PHOTO_W, PHOTO_H = 4032, 3024
REF_SAMPLE = 48  # pure-Python BLAKE3 sample (≥32, every bucket + sampled path)
PIXEL_SAMPLE = 12
EMBED_SAMPLE = 24

# the cas_id message layout, restated here from the reference's spec
# (core/src/object/cas.rs) so the sample check does not lean on
# spacedrive_tpu.ops.cas for what it is checking
_MIN_SAMPLED = 100 * 1024
_HEAD = 8 * 1024
_SAMPLE = 10 * 1024
_N_SAMPLES = 4
# small-file buckets by message chunk count, same reason
_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 101)
_BUCKET_SHARE = (0.30, 0.18, 0.14, 0.12, 0.10, 0.07, 0.05, 0.04)


def log(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def layout(size: int) -> list[tuple[int, int]]:
    """(offset, length) ranges of a file that its cas_id hashes."""
    if size <= _MIN_SAMPLED:
        return [(0, size)]
    jump = (size - 2 * _HEAD) // _N_SAMPLES
    return (
        [(0, _HEAD)]
        + [(_HEAD + k * jump, _SAMPLE) for k in range(_N_SAMPLES)]
        + [(size - _HEAD, _HEAD)]
    )


def reference_message(path: str) -> bytes:
    size = os.path.getsize(path)
    parts = [struct.pack("<Q", size)]
    with open(path, "rb") as f:
        for off, ln in layout(size):
            f.seek(off)
            parts.append(f.read(ln))
    return b"".join(parts)


# --- stamp -------------------------------------------------------------------


def require_checkout() -> None:
    """Exit, before JAX takes the chip, unless the program this script
    drives sits next to it: alone, there is nothing to prove."""
    if not os.path.isfile(os.path.join(HERE, "spacedrive_tpu", "__init__.py")):
        print(f"chip_smoke: no spacedrive_tpu package next to {__file__}; "
              "run it from the root of a checkout", file=sys.stderr)
        raise SystemExit(2)


def require_tpu() -> dict:
    """The device stamp, or exit: no accelerator, no result."""
    import jax
    import jaxlib

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r} "
              f"({len(devs)} device(s)); refusing to run", file=sys.stderr)
        raise SystemExit(2)
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - the stamp is informational
        libtpu = "unknown"
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "cpu_count": os.cpu_count(),
    }


# --- native build from what git would commit -----------------------------------


def rebuild_native() -> None:
    """Drop any prebuilt shared objects (the tool copies the disk, git
    would not) and failure sentinels, then demand a fresh build: the
    pure-Python BLAKE3 would turn the parity check into the longest
    phase and hide a missing compiler."""
    native_dir = os.path.join(HERE, "spacedrive_tpu", "native")
    for name in os.listdir(native_dir):
        if name.endswith(".so") or name.endswith(".build_failed"):
            os.remove(os.path.join(native_dir, name))
    from spacedrive_tpu import native

    if not native.available():
        raise SystemExit("chip_smoke: native BLAKE3 did not build "
                         "(no C compiler?)")


# --- compile accounting --------------------------------------------------------


class CompileCounter:
    """Counts XLA compile requests and persistent-cache hits per phase
    through jax.monitoring (a request that is not a hit compiled)."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.phase = "setup"
        self._lock = threading.Lock()
        self.counts: dict[str, dict[str, float]] = {}
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _slot(self) -> dict[str, float]:
        return self.counts.setdefault(
            self.phase, {"requests": 0, "cache_hits": 0, "seconds": 0.0})

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self.REQUEST:
            with self._lock:
                slot = self._slot()
                slot["requests"] += 1
                slot["seconds"] += seconds

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            with self._lock:
                self._slot()["cache_hits"] += 1

    def report(self) -> dict:
        with self._lock:
            return {
                phase: {
                    "requests": int(c["requests"]),
                    "cache_hits": int(c["cache_hits"]),
                    "compiled": int(c["requests"] - c["cache_hits"]),
                    "seconds": round(c["seconds"], 1),
                }
                for phase, c in self.counts.items()
            }


# --- host memory -----------------------------------------------------------------

#: host RSS one wide-tile Pallas hash program needs while Mosaic compiles
#: it (3.9 GB measured, AOT against the v5e descriptor, PR 21). glibc
#: keeps the freed heap, so N in flight cost N× this until malloc_trim.
COMPILE_PEAK_BYTES = 4 << 30


def host_memory_limit() -> int:
    """Bytes this process may use: the smallest of the cgroup limits
    that exist and the machine's MemTotal."""
    limits = []
    for path in ("/sys/fs/cgroup/memory.max",
                 "/sys/fs/cgroup/memory/memory.limit_in_bytes"):
        try:
            with open(path) as f:
                raw = f.read().strip()
            if raw.isdigit():
                limits.append(int(raw))
        except OSError:
            pass
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemTotal"):
                limits.append(int(line.split()[1]) * 1024)
    return min(limits)


def _rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


class HostMemory:
    """Samples this process's RSS: the peak per phase goes in the JSON,
    and a run that nears the machine's limit ends itself — the kernel's
    OOM kill takes the chip down with the process."""

    def __init__(self, limit: int) -> None:
        self.limit = limit
        self.phase = "setup"
        self.peaks: dict[str, int] = {}
        threading.Thread(target=self._run, name="smoke-rss",
                         daemon=True).start()

    def _run(self) -> None:
        while True:
            rss = _rss_bytes()
            self.peaks[self.phase] = max(self.peaks.get(self.phase, 0), rss)
            if rss > 0.85 * self.limit:
                log(f"host RSS {rss >> 20} MiB is over 85% of the "
                    f"{self.limit >> 20} MiB limit in phase "
                    f"{self.phase!r}; ending the run before the OOM killer")
                os._exit(3)
            time.sleep(0.5)

    def report(self) -> dict:
        return {k: v >> 20 for k, v in self.peaks.items()}


def release_freed_heap() -> None:
    """Hand glibc's free lists back to the OS after the compile burst."""
    import ctypes

    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass  # not glibc: nothing to trim


def warm_hash_ladder(n_dev: int, threads: int) -> tuple[ThreadPoolExecutor, list]:
    """Compile every (chunk bucket × ladder rung) hash program the pass
    can dispatch, in parallel threads (XLA/Mosaic compile outside the
    GIL). Each wide-tile Pallas program takes minutes alone; one after
    another, a cold mixed pass could not finish inside the driver's
    limit. Returns the executor and futures of (rows, chunks, seconds)."""
    import jax
    import numpy as np

    from spacedrive_tpu.ops import blake3_jax, cas

    devices = jax.devices() if n_dev > 1 else None

    def one(rows: int, chunks: int):
        t0 = time.perf_counter()
        out = blake3_jax.hash_batch(
            np.zeros((rows, chunks * 1024), np.uint8),
            np.ones((rows,), np.int32), max_chunks=chunks, devices=devices,
        )
        jax.block_until_ready(out)
        release_freed_heap()  # or the next compile stacks on this one's
        return rows, chunks, round(time.perf_counter() - t0, 1)

    shapes = [
        (rows, chunks)
        for chunks in sorted({*cas.SMALL_BUCKETS, cas.LARGE_CHUNKS})
        for rows in cas.batch_ladder(n_dev)
    ]
    # longest first, so the minutes-long wide-tile compiles start at once
    shapes.sort(key=lambda s: -(s[0] * s[1]))
    pool = ThreadPoolExecutor(threads, thread_name_prefix="smoke-compile")
    return pool, [pool.submit(one, r, c) for r, c in shapes]


# --- corpus --------------------------------------------------------------------


def _write_file(path: str, size: int, seed: tuple[int, int]) -> None:
    """Seeded content in the ranges a cas_id reads, holes elsewhere: the
    bytes on disk stay near the hashed bytes, every cas_id is distinct,
    and the same (size, seed) anywhere else is an exact duplicate."""
    import numpy as np

    rng = np.random.default_rng(seed)
    with open(path, "wb") as f:
        f.truncate(size)
        for off, ln in layout(size):
            f.seek(off)
            f.write(rng.bytes(ln))


def _photo_pixels(seed: tuple[int, int], w: int, h: int, blocky: bool):
    """Compressible, distinct content: a seeded low-res colour field
    blown up to (w, h) — smooth (bicubic) for photos, flat blocks
    (nearest) for scans and screenshots."""
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(seed)
    gw, gh = max(2, w // 252), max(2, h // 252)
    field = rng.integers(0, 256, (gh, gw, 3), dtype=np.uint8)
    return Image.fromarray(field).resize(
        (w, h), Image.NEAREST if blocky else Image.BICUBIC)


def _write_image(path: str, seed: tuple[int, int], w: int, h: int,
                 orientation: int) -> None:
    from PIL import Image

    png = path.endswith(".png")
    img = _photo_pixels(seed, w, h, blocky=png)
    if png:
        img.save(path, "PNG", compress_level=1)
    else:
        exif = Image.Exif()
        exif[0x0112] = orientation
        img.save(path, "JPEG", quality=88, exif=exif)


def build_corpus(root: str, seed: int) -> dict:
    """Write the location; returns what the checks need to know about
    it (counts, image dims + orientation per path)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    files: list[tuple[str, int, tuple[int, int]]] = []  # (rel, size, seed)

    def add(rel_dir: str, name: str, size: int) -> None:
        files.append((os.path.join(rel_dir, name), size, (seed, len(files))))

    def large_size() -> int:
        # log-uniform 100 KiB+1 … 1 MiB: most bytes in big files, yet a
        # filesystem that stores the holes still writes under 4 GB
        return int(math.exp(rng.uniform(math.log(_MIN_SAMPLED + 1),
                                        math.log(1 << 20))))

    def small_size(bucket_i: int) -> int:
        hi = min(_BUCKETS[bucket_i] * 1024 - 8, _MIN_SAMPLED)
        lo = 1 if bucket_i == 0 else _BUCKETS[bucket_i - 1] * 1024 - 8 + 1
        return int(rng.integers(lo, hi + 1))

    # large-only sibling dirs: contiguous in the walk, so whole identify
    # windows are 57-chunk rows at the top ladder rung
    per_dir = 512
    for i in range(SCALE["raw_large"]):
        add(f"media/raw/shoot-{i // per_dir:02d}", f"clip_{i:05d}.bin",
            large_size())
    # the sampled/whole-file boundary, both sides
    for j, size in enumerate((_MIN_SAMPLED - 1, _MIN_SAMPLED,
                              _MIN_SAMPLED + 1, 1, 1016, 1017)):
        add("docs/edge", f"edge_{j}.dat", size)
    # small files over every bucket, nested three deep
    exts = ("txt", "md", "json", "csv", "log", "py", "html", "dat")
    buckets = rng.choice(len(_BUCKETS), SCALE["small"], p=_BUCKET_SHARE)
    for i, b in enumerate(buckets):
        area = ("docs", "projects", "mail", "notes")[i % 4]
        add(f"{area}/{i % 23:02d}/{i % 7}", f"f{i:05d}.{exts[i % 8]}",
            small_size(int(b)))
    # large files among small ones: partial hot-bucket batches
    for i in range(SCALE["mixed_large"]):
        add(f"projects/{i % 23:02d}/{i % 7}", f"asset_{i:04d}.bin",
            large_size())
    # exact duplicates, deeper than every original so the breadth-first
    # walk reaches them last and the existing-object link branch runs
    n_orig = len(files)
    for i, src in enumerate(rng.choice(n_orig, SCALE["duplicates"],
                                       replace=False)):
        _rel, size, src_seed = files[int(src)]
        files.append((os.path.join("zz_backup/old/disk/a/b",
                                   f"{i % 10}", f"copy_{i:04d}.bak"),
                      size, src_seed))

    for rel_dir in {os.path.dirname(rel) for rel, _s, _seed in files}:
        os.makedirs(os.path.join(root, rel_dir), exist_ok=True)
    for rel, size, fseed in files:
        _write_file(os.path.join(root, rel), size, fseed)
    t_files = time.perf_counter() - t0

    # images: (rel, w, h, orientation)
    images: list[tuple[str, int, int, int]] = []
    orientations = (1, 1, 1, 6, 1, 3, 1, 8)
    for i in range(SCALE["photos_jpeg"]):
        images.append((f"photos/2024-{1 + i % 12:02d}/IMG_{i:04d}.jpg",
                       PHOTO_W, PHOTO_H, orientations[i % 8]))
    for i in range(SCALE["photos_png"]):
        images.append((f"photos/scans/scan_{i:03d}.png", PHOTO_W, PHOTO_H, 1))
    classes = (("pictures/icons", 96, 256, "png"),
               ("pictures/web", 300, 512, "jpg"),
               ("pictures/screens", 800, 1024, "png"))
    for i in range(SCALE["small_images"]):
        rel_dir, lo, hi, ext = classes[i % 3]
        w = int(rng.integers(lo, hi + 1))
        h = max(16, int(w * rng.uniform(0.5, 1.0)))
        images.append((f"{rel_dir}/pic_{i:03d}.{ext}", w, h, 1))
    for rel_dir in {os.path.dirname(rel) for rel, *_ in images}:
        os.makedirs(os.path.join(root, rel_dir), exist_ok=True)
    with ThreadPoolExecutor(max(2, (os.cpu_count() or 2) // 2)) as pool:
        list(pool.map(
            lambda it: _write_image(os.path.join(root, it[1][0]),
                                    (seed + 1, it[0]), *it[1][1:]),
            enumerate(images),
        ))

    n_large = sum(1 for _r, size, _s in files if size > _MIN_SAMPLED)
    return {
        "files": len(files) + len(images),
        "plain_files": len(files),
        "large": n_large,
        "duplicates": SCALE["duplicates"],
        "images": {os.path.join(root, rel): (w, h, o)
                   for rel, w, h, o in images},
        "bytes_apparent": sum(size for _r, size, _s in files),
        "bytes_on_disk": sum(
            os.stat(os.path.join(root, rel)).st_blocks * 512
            for rel, _size, _s in files),
        "seconds_files": round(t_files, 1),
        "seconds_total": round(time.perf_counter() - t0, 1),
    }


# --- the pass, exactly as `sdx index` runs it ---------------------------------------


async def index_pass(data_dir: str, corpus: str, after=None) -> dict:
    """Node(use_device=True) → start → cli.index_location (the body of
    `sdx index --backend tpu`) → optional `after(node, summary)` on the
    same started node → shutdown."""
    from spacedrive_tpu import cli
    from spacedrive_tpu.node import Node

    node = Node(data_dir, use_device=True)
    node.config.config.p2p.enabled = False  # no network in the smoke
    await node.start()
    try:
        summary = await cli.index_location(node, corpus, "smoke", "tpu")
        summary["procpool_workers"] = node.procpool.worker_count()
        if after is not None:
            await after(node, summary)
        return summary
    finally:
        await node.shutdown()


# --- checks --------------------------------------------------------------------


class Checks:
    def __init__(self) -> None:
        self.failed: list[str] = []
        self.passed = 0

    def ok(self, cond: bool, what: str) -> bool:
        if cond:
            self.passed += 1
        else:
            self.failed.append(what)
            log(f"CHECK FAILED: {what}")
        return bool(cond)


def _full_path(loc_path: str, row: dict) -> str:
    from spacedrive_tpu.files.isolated_path import full_path_from_db_row

    return full_path_from_db_row(loc_path, row)


def check_cas(chk: Checks, lib, corpus: str, meta: dict, seed: int) -> dict:
    """Every cas_id against native C; a seeded sample over every bucket
    and the sampled path against the pure-Python reference, with the
    message assembled by this file's own reading of the layout."""
    import numpy as np

    from spacedrive_tpu.ops import cas
    from spacedrive_tpu.ops.blake3_ref import blake3_hex

    rows = lib.db.query(
        "SELECT * FROM file_path WHERE is_dir = 0 ORDER BY id")
    chk.ok(len(rows) == meta["files"],
           f"file_path rows {len(rows)} != files written {meta['files']}")
    paths = [_full_path(corpus, r) for r in rows]
    mismatches = 0
    for off in range(0, len(rows), 2048):
        part = paths[off:off + 2048]
        want = cas.cas_ids_native_cpu([reference_message(p) for p in part])
        for r, w in zip(rows[off:off + 2048], want):
            mismatches += r["cas_id"] != w
    chk.ok(mismatches == 0,
           f"{mismatches} cas_ids differ from the native-C reference")

    by_bucket: dict[int, list[int]] = {}
    for i, p in enumerate(paths):
        size = os.path.getsize(p)
        chunks = (57 if size > _MIN_SAMPLED
                  else next(b for b in _BUCKETS if (8 + size + 1023) // 1024 <= b))
        by_bucket.setdefault(chunks, []).append(i)
    chk.ok(sorted(by_bucket) == sorted({*_BUCKETS, 57}),
           f"corpus missed a hash bucket: has {sorted(by_bucket)}")
    rng = np.random.default_rng(seed + 2)
    per = -(-REF_SAMPLE // len(by_bucket))
    sample = [int(i) for idxs in by_bucket.values()
              for i in rng.choice(idxs, min(per, len(idxs)), replace=False)]
    bad = [paths[i] for i in sample
           if rows[i]["cas_id"] != blake3_hex(reference_message(paths[i]))[:16]]
    chk.ok(not bad, f"{len(bad)}/{len(sample)} sampled cas_ids differ from "
                    f"blake3_ref (first: {bad[:1]})")

    distinct = len({r["cas_id"] for r in rows})
    objects = lib.db.count("object")
    chk.ok(distinct == meta["files"] - meta["duplicates"],
           f"distinct cas_ids {distinct} != files − duplicates "
           f"{meta['files'] - meta['duplicates']}")
    chk.ok(objects == distinct,
           f"objects {objects} != distinct cas_ids {distinct} "
           "(duplicate files must link to the existing object)")
    return {"rows": len(rows), "ref_sample": len(sample),
            "buckets": {str(k): len(v) for k, v in sorted(by_bucket.items())},
            "objects": objects}


def check_thumbnails(chk: Checks, node, lib, corpus: str, meta: dict,
                     seed: int) -> dict:
    """One webp per image at scale_dimensions size; a sample of device
    resizes within the suite's CPU↔device tolerance (mean |Δ| < 1.0 of
    255 against PIL's triangle filter on the same decoded pixels —
    tests/test_thumbnailer.py)."""
    import numpy as np
    from PIL import Image

    from spacedrive_tpu.object.media.thumbnail import process
    from spacedrive_tpu.ops import thumbnail_jax as tj

    rows = {
        _full_path(corpus, r): r for r in lib.db.query(
            "SELECT * FROM file_path WHERE is_dir = 0 AND extension IN "
            "('jpg', 'png')")
    }
    chk.ok(set(rows) == set(meta["images"]),
           f"indexed images {len(rows)} != written {len(meta['images'])}")
    store = node.thumbnailer.store
    wrong_size = missing = 0
    buckets: dict[str, int] = {}
    for path, (w, h, orientation) in meta["images"].items():
        thumb = store.path_for(str(lib.id), rows[path]["cas_id"])
        if not os.path.exists(thumb):
            missing += 1
            continue
        tw, th = tj.scale_dimensions(w, h)
        if orientation >= 5:
            tw, th = th, tw
        with Image.open(thumb) as im:
            wrong_size += im.size != (tw, th)
    chk.ok(missing == 0, f"{missing} images have no stored thumbnail")
    chk.ok(wrong_size == 0,
           f"{wrong_size} thumbnails are not at scale_dimensions size")
    chk.ok(node.thumbnailer.generated == len(meta["images"]),
           f"thumbnailer.generated {node.thumbnailer.generated} != images "
           f"{len(meta['images'])}")

    rng = np.random.default_rng(seed + 3)
    sample = rng.choice(sorted(meta["images"]), PIXEL_SAMPLE, replace=False)
    worst = 0.0
    for path in sample:
        d = process.decode(str(path), os.path.splitext(path)[1][1:])
        b = tj.bucket_for(*d.array.shape[:2])
        buckets[f"{b[0]}x{b[1]}"] = buckets.get(f"{b[0]}x{b[1]}", 0) + 1
        device_px = process.resize_decoded([d])[0]
        th, tw = d.target
        cpu_px = np.asarray(
            Image.fromarray(d.array).resize((tw, th), Image.BILINEAR))
        worst = max(worst, float(np.abs(
            device_px.astype(int) - cpu_px.astype(int)).mean()))
    chk.ok(worst < 1.0,
           f"device resize differs from the PIL reference: worst mean |Δ| "
           f"{worst:.3f} ≥ 1.0")
    return {"thumbnails": node.thumbnailer.generated,
            "pixel_sample": len(sample), "worst_mean_abs_diff": round(worst, 4),
            "sample_canvas_buckets": buckets}


def embed_reference(params: dict, planes, bf16: bool = False):
    """models/embedder.forward in plain NumPy: 4×4 patch mean-pool of a
    32² RGB plane, tanh(x·w1+b1)·w2+b2, float64 accumulation. With
    `bf16`, the matmul operands are first rounded to bfloat16 — what a
    TPU's default matmul precision does to float32 operands."""
    import ml_dtypes
    import numpy as np

    def operand(a):
        a = np.asarray(a, np.float32)
        if bf16:
            a = a.astype(ml_dtypes.bfloat16).astype(np.float32)
        return a.astype(np.float64)

    x = np.asarray(planes, np.float32)
    b = x.shape[0]
    x = x.reshape(b, 8, 4, 8, 4, 3).mean(axis=(2, 4)).reshape(b, 192)
    h = np.tanh(operand(x) @ operand(params["w1"]) + params["b1"])
    return operand(h) @ operand(params["w2"]) + params["b2"]


def check_embeddings(chk: Checks, lib, corpus: str, meta: dict,
                     seed: int) -> dict:
    import numpy as np

    from spacedrive_tpu.models import embedder

    n = lib.db.count("object_embedding")
    chk.ok(n == len(meta["images"]),
           f"object_embedding rows {n} != images {len(meta['images'])}")
    rng = np.random.default_rng(seed + 4)
    sample = [str(p) for p in rng.choice(sorted(meta["images"]), EMBED_SAMPLE,
                                         replace=False)]
    got = []
    for path in sample:
        row = lib.db.query_one(
            "SELECT e.vector FROM object_embedding e JOIN file_path fp "
            "ON fp.object_id = e.object_id WHERE fp.name = ? AND "
            "fp.extension = ?",
            tuple(os.path.basename(path).rsplit(".", 1)),
        )
        got.append(embedder.blob_to_vector(row["vector"]) if row else None)
    chk.ok(all(v is not None for v in got), "sampled embeddings are missing "
           "or not finite float32[128]")
    if any(v is None for v in got):
        return {"embeddings": n}
    planes = np.stack([embedder.decode_image(p) for p in sample])
    params = embedder.params()
    exact = embed_reference(params, planes)
    rounded = embed_reference(params, planes, bf16=True)
    # The device may run both matmuls in one bfloat16 pass (TPU default
    # precision for f32 operands). The tolerance is 4× what that
    # operand rounding alone does to THESE inputs: a wrong weight, a
    # missing tanh or a dropped layer is orders of magnitude outside
    # it, bf16 passes are inside.
    tol = 4.0 * float(np.abs(rounded - exact).max()) + 1e-6
    err = float(np.abs(np.stack(got) - exact).max())
    chk.ok(err <= tol, f"embeddings differ from the NumPy reference: max |Δ| "
                       f"{err:.2e} > tolerance {tol:.2e}")
    return {"embeddings": n, "sample": len(sample),
            "max_abs_err": float(f"{err:.3e}"), "tolerance": float(f"{tol:.3e}")}


def _http(method: str, url: str, body: dict | None = None) -> tuple[int, bytes]:
    req = urllib.request.Request(
        url, method=method,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    # no proxy: the server is this process, on loopback
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
    with opener.open(req, timeout=120) as resp:
        return resp.status, resp.read()


async def check_served_reads(chk: Checks, node, lib, corpus: str,
                             meta: dict) -> dict:
    """start_api on the indexed node, then a few reads a client would
    send — fetched off-loop: a blocking urlopen on the server's own
    loop deadlocks."""
    from spacedrive_tpu.object.media.thumbnail.store import get_shard_hex
    from spacedrive_tpu.telemetry import counter_value

    port = await node.start_api()
    base = f"http://127.0.0.1:{port}"
    lib_id = str(lib.id)
    t0 = time.perf_counter()

    status, body = await asyncio.to_thread(
        _http, "POST", f"{base}/rspc/search.paths",
        {"library_id": lib_id,
         "arg": {"filter": {"search": "IMG_000"}, "take": 50}})
    want = sorted(os.path.basename(p)[:-4] for p in meta["images"]
                  if os.path.basename(p).startswith("IMG_000"))
    names = sorted(n_["name"] for n_ in json.loads(body)["result"]["nodes"]) \
        if status == 200 else []
    chk.ok(status == 200 and names == want and len(want) > 0,
           f"search.paths: status {status}, hits {names} != written {want}")

    photo = next(p for p in sorted(meta["images"]) if p.endswith("IMG_0007.jpg"))
    hits_before = counter_value("sd_search_queries_total", path="device")
    status, body = await asyncio.to_thread(
        _http, "GET", f"{base}/search?" + urllib.parse.urlencode(
            {"library_id": lib_id, "q": photo, "take": 5}))
    result = json.loads(body)["result"] if status == 200 else {}
    nodes = result.get("nodes") or []
    first = nodes[0] if nodes else {}
    chk.ok(status == 200 and result.get("resolved") is True
           and len(nodes) == 5 and first.get("name") == "IMG_0007",
           f"GET /search: status {status}, first hit {first.get('name')!r} "
           "(the query photo must rank itself first)")
    score = (result.get("scores") or {}).get(str(first.get("id")), 0.0)
    chk.ok(abs(score - 1.0) < 1e-2,
           f"GET /search: self-similarity {score:.4f} is not ≈ 1")
    chk.ok(counter_value("sd_search_queries_total", path="device")
           == hits_before + 1
           and counter_value("sd_search_queries_total", path="host") == 0,
           "semantic query did not score on the device (host fallback)")

    cas_id = lib.db.query_one(
        "SELECT cas_id FROM file_path WHERE name = 'IMG_0007'")["cas_id"]
    ns = node.thumbnailer.store.namespace(lib_id)
    status, body = await asyncio.to_thread(
        _http, "GET",
        f"{base}/spacedrive/thumbnail/{ns}/{get_shard_hex(cas_id)}/{cas_id}.webp")
    with open(node.thumbnailer.store.path_for(lib_id, cas_id), "rb") as f:
        stored = f.read()
    chk.ok(status == 200 and body == stored and body[8:12] == b"WEBP",
           f"GET thumbnail: status {status}, {len(body)} bytes vs "
           f"{len(stored)} stored")
    return {"requests": 3, "seconds": round(time.perf_counter() - t0, 2)}


def check_used_the_chip(chk: Checks, summary: dict, stamp: dict,
                        n_images: int, before: dict) -> dict:
    """The pass ran where it was asked to: nothing demoted, nothing fell
    back, nothing failed — and on a multi-chip host every chip worked."""
    import jax

    from spacedrive_tpu.ops import blake3_pallas
    from spacedrive_tpu.parallel import mesh
    from spacedrive_tpu.telemetry import counter_value, events, gauge_value
    from spacedrive_tpu.telemetry import metrics as tm

    n_dev = stamp["count"]
    chk.ok(blake3_pallas.pallas_mode() == "tpu",
           f"Pallas mode is {blake3_pallas.pallas_mode()!r}, not 'tpu'")
    chk.ok(summary["device"] == {k: stamp[k] for k in
                                 ("platform", "kind", "count")},
           f"index summary device {summary['device']} != stamp")
    chk.ok(summary["ladder_level"] == mesh.LEVEL_MESH
           and mesh.LADDER.level == mesh.LEVEL_MESH,
           f"degradation ladder ended at level {mesh.LADDER.level}")
    chk.ok(gauge_value("sd_device_demotion_level") == 0,
           "sd_device_demotion_level != 0")
    chk.ok(counter_value("sd_cas_backend_fallback_total") == 0
           and summary["cas_backend_fallbacks"] == 0,
           "sd_cas_backend_fallback_total != 0 (hashes left the device)")
    chk.ok(summary["thumbnail_cpu_fallbacks"] == 0
           and summary["thumbnail_errors"] == 0,
           f"thumbnail CPU fallbacks {summary['thumbnail_cpu_fallbacks']}, "
           f"errors {summary['thumbnail_errors']}")
    bad_events = [
        e for e in events.RESILIENCE_EVENTS.snapshot()
        if e["type"] in ("device_demote", "thumbnail_cpu_fallback")
    ] + [
        e for e in events.ERROR_EVENTS.snapshot()
        if str((e.get("fields") or {}).get("source", "")).endswith(".ladder")
        or (e.get("fields") or {}).get("source") == "cas.auto"
    ]
    chk.ok(not bad_events,
           f"{len(bad_events)} demotion/fallback/ladder events on the rings "
           f"(first: {bad_events[:1]})")
    chk.ok(summary["jobs_failed"] == 0 and summary["jobs"] == {
        "indexer": "COMPLETED", "file_identifier": "COMPLETED",
        "media_processor": "COMPLETED"}, f"job chain: {summary['jobs']}")
    device_stages = (tm.THUMB_STAGE_SECONDS.stats(stage="device")["count"]
                     - before["thumb_device_stages"])
    want_stages = -(-n_images // (32 * n_dev))
    chk.ok(device_stages >= want_stages,
           f"thumbnail device stages {device_stages} < {want_stages}")
    chk.ok(counter_value("sd_embed_files_total", result="embedded")
           - before["embedded"] == n_images,
           "sd_embed_files_total{result=embedded} != images")

    out: dict = {"thumb_device_stages": int(device_stages)}
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    out["peak_bytes_in_use"] = peaks
    chk.ok(all(p > 0 for p in peaks),
           f"a device never held a buffer: peak_bytes_in_use {peaks}")
    if n_dev > 1:
        for op in ("blake3", "thumbnail"):
            rows = (tm.SHARD_BATCH_ROWS.stats(op=op)["count"]
                    - before[f"shard_rows_{op}"])
            chk.ok(rows > 0, f"sd_shard_batch_rows{{op={op}}} never observed")
            occ = tm.DEVICE_DISPATCH_OCCUPANCY.recent(op=op)
            occ = occ[len(occ) % n_dev:]
            per_dev = [round(sum(occ[i::n_dev]), 2) for i in range(n_dev)]
            chk.ok(len(occ) > 0 and all(v > 0 for v in per_dev),
                   f"sd_device_dispatch_occupancy{{op={op}}} per device "
                   f"{per_dev}: a chip got no real rows")
            out[f"occupancy_sum_{op}"] = per_dev
            out[f"sharded_dispatches_{op}"] = int(rows)
    return out


def telemetry_baseline() -> dict:
    """Counters the ladder warm-up also touches, read before the pass."""
    from spacedrive_tpu.telemetry import counter_value
    from spacedrive_tpu.telemetry import metrics as tm

    return {
        "thumb_device_stages": tm.THUMB_STAGE_SECONDS.stats(
            stage="device")["count"],
        "embedded": counter_value("sd_embed_files_total", result="embedded"),
        "shard_rows_blake3": tm.SHARD_BATCH_ROWS.stats(op="blake3")["count"],
        "shard_rows_thumbnail": tm.SHARD_BATCH_ROWS.stats(
            op="thumbnail")["count"],
    }


# --- main ----------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--work-dir", default=os.path.join(HERE, ".chip_smoke_work"),
                    help="where the corpus and the two node data dirs go "
                         "(removed afterwards)")
    ap.add_argument("--keep", action="store_true",
                    help="leave the work dir in place")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    require_checkout()
    stamp = require_tpu()
    log(f"stamp: {json.dumps(stamp)}")
    sys.path.insert(0, HERE)
    rebuild_native()

    from spacedrive_tpu.ops import configure_compilation_cache

    cache_dir = configure_compilation_cache()
    compiles = CompileCounter()
    memory = HostMemory(host_memory_limit())
    chk = Checks()
    phases: dict[str, float] = {}
    n_dev = stamp["count"]

    shutil.rmtree(args.work_dir, ignore_errors=True)
    corpus = os.path.join(args.work_dir, "corpus")
    os.makedirs(corpus)
    try:
        # set-up: the ladder compiles in threads while this one writes
        # the corpus (the two overlap; both are reported)
        t0 = time.perf_counter()
        compiles.phase = memory.phase = "ladder_warmup"
        # as many compiles in flight as fit in 3/4 of the host's memory
        # on top of what the TPU runtime already holds (≈13 GiB per
        # chip's worth of pinned buffers on the v5e machines)
        threads = max(1, min(
            (os.cpu_count() or 2) - 2,
            (memory.limit * 3 // 4 - _rss_bytes()) // COMPILE_PEAK_BYTES))
        pool, futures = warm_hash_ladder(n_dev, threads)
        meta = build_corpus(corpus, args.seed)
        phases["corpus_s"] = meta["seconds_total"]
        log(f"corpus: {meta['files']} files, {meta['large']} over 100 KiB, "
            f"{len(meta['images'])} images in {meta['seconds_total']} s")
        programs = sorted((f.result() for f in futures), key=lambda p: -p[2])
        pool.shutdown()
        release_freed_heap()
        phases["setup_wall_s"] = round(time.perf_counter() - t0, 1)
        log(f"hash ladder: {len(programs)} programs ready after "
            f"{phases['setup_wall_s']} s on {threads} threads "
            f"(slowest {programs[:3]}); host RSS now {_rss_bytes() >> 20} MiB")
        chk.ok(compiles.report().get("ladder_warmup", {}).get("requests", 0)
               >= len(programs),
               "compile counter saw fewer requests than programs compiled")
        n_images = len(meta["images"])
        chk.ok(meta["files"] >= FLOORS["files"]
               and meta["large"] >= FLOORS["large"]
               and SCALE["photos_jpeg"] >= FLOORS["photos"]
               and SCALE["small_images"] >= FLOORS["small_images"],
               f"corpus below the contract's floors: {meta['files']} files, "
               f"{meta['large']} large")

        # pass 1: cold data dir, then the served reads and every check
        # against the references, on the same started node
        results: dict = {}

        async def after_cold(node, summary) -> None:
            lib = next(iter(node.libraries.libraries.values()))
            compiles.phase = memory.phase = "checks"
            t1 = time.perf_counter()
            results["used_chip"] = check_used_the_chip(
                chk, summary, stamp, n_images, baseline)
            results["serve"] = await check_served_reads(
                chk, node, lib, corpus, meta)
            results["cas"] = await asyncio.to_thread(
                check_cas, chk, lib, corpus, meta, args.seed)
            results["thumbs"] = await asyncio.to_thread(
                check_thumbnails, chk, node, lib, corpus, meta, args.seed)
            results["embed"] = await asyncio.to_thread(
                check_embeddings, chk, lib, corpus, meta, args.seed)
            phases["checks_s"] = round(time.perf_counter() - t1, 1)

        # SD_FAULTS arms the fault plane exactly as `sdx` does — after
        # the warm-up, so an injected device failure shows up as what
        # it is: a pass that left the chip, and a red smoke
        from spacedrive_tpu.utils import faults

        faults.install_from_env()
        baseline = telemetry_baseline()
        compiles.phase = memory.phase = "pass_cold"
        cold = asyncio.run(index_pass(
            os.path.join(args.work_dir, "node-cold"), corpus, after_cold))
        phases["pass_cold_s"] = cold["seconds"]
        phases["pass_cold_job_s"] = cold["job_seconds"]
        log(f"cold pass: {cold['files']} files, {cold['thumbnails']} thumbs "
            f"in {cold['seconds']} s; jobs {cold['jobs']} {cold['job_seconds']}")

        # pass 2: what a second `sdx index` process would see with a
        # warm compile cache — fresh data dir, fresh autotuner state
        # (a new process inherits neither), every program already
        # compiled. SD_PROCS=2: the process that owns the chip spawns
        # CPU-pinned workers.
        from spacedrive_tpu.parallel import autotune

        autotune.reset()
        os.environ["SD_PROCS"] = "2"
        compiles.phase = memory.phase = "pass_warm"
        warm = asyncio.run(index_pass(
            os.path.join(args.work_dir, "node-warm"), corpus))
        os.environ.pop("SD_PROCS")
        phases["pass_warm_s"] = warm["seconds"]
        phases["pass_warm_job_s"] = warm["job_seconds"]
        log(f"warm pass: {warm['files']} files in {warm['seconds']} s "
            f"{warm['job_seconds']}")
        compile_report = compiles.report()
        warm_compiles = compile_report.get("pass_warm", {}).get("requests", 0)
        chk.ok(warm_compiles == 0,
               f"the warm pass requested {warm_compiles} compiles, not 0")
        chk.ok(warm["procpool_workers"] == 2,
               f"warm pass had {warm['procpool_workers']} pool workers, not 2")
        for key in ("files", "objects", "jobs", "jobs_failed", "ladder_level",
                    "cas_backend_fallbacks", "thumbnail_cpu_fallbacks",
                    "thumbnail_errors"):
            chk.ok(warm[key] == cold[key],
                   f"warm pass {key} {warm[key]!r} != cold {cold[key]!r}")
        chk.ok(warm["thumbnails"] == cold["thumbnails"],
               f"warm pass thumbnails {warm['thumbnails']} != cold")
    finally:
        if not args.keep:
            shutil.rmtree(args.work_dir, ignore_errors=True)

    ok = not chk.failed
    device = {k: stamp[k] for k in ("platform", "kind", "count")}
    print(json.dumps({
        "ok": ok,
        "device": device,
        "stamp": stamp,
        "kind": "smoke, not a benchmark: one run's wall-clock phases",
        "seed": args.seed,
        "counts": {
            "files": meta["files"], "large": meta["large"],
            "duplicates": meta["duplicates"], "images": n_images,
            "bytes_apparent": meta["bytes_apparent"],
            "bytes_on_disk": meta["bytes_on_disk"],
            **{k: results.get(k) for k in ("cas", "thumbs", "embed", "serve")},
        },
        "used_chip": results.get("used_chip"),
        "compile_cache_dir": cache_dir,
        "compiles": compile_report,
        "compile_threads": threads,
        "host_rss_peak_mib": memory.report(),
        "host_memory_limit_mib": memory.limit >> 20,
        "hash_programs": [list(p) for p in programs],
        "phases": {**phases,
                   "total_s": round(time.perf_counter() - t_start, 1)},
        "checks_passed": chk.passed,
        "failed": chk.failed,
        "claim": None,
    }))
    # the verdict, alone on the last line, with exactly these keys
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
