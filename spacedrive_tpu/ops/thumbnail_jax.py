"""Batched thumbnail resize on TPU.

Parity targets (behavior, not implementation):
- ref:core/src/object/media/thumbnail/process.rs:394-461 — decode →
  `scale_dimensions` to TARGET_PX=262144 (≈512²) → Triangle-filter
  resize → EXIF-orientation correction → webp quality 30.
- ref:crates/images/src/lib.rs:89 — `scale_dimensions` keeps aspect and
  makes w*h ≈ target_px.
- ref:crates/ffmpeg/src/lib.rs:20-33 — video thumbs bound the max
  dimension to 256 instead.

TPU-first design. The reference resizes one image at a time on a CPU
pool. Here, decoded images are padded into a small set of canvas
*buckets* (squares + landscape halves; a portrait transposes in where
it does not fit as it stands) and a whole batch is resized in
ONE device call per bucket via `jax.image.scale_and_translate`, vmapped
with *per-image* scale factors as traced arguments — so a single
compiled program handles arbitrary (h, w) inputs inside a bucket. XLA
lowers separable scale_and_translate to two weight matmuls per image,
which ride the MXU; `antialias=True` + `method="triangle"` is exactly
the reference's Triangle filter for downscale. Crop to the per-image
target dims, orientation flips, and webp encode stay on host (cheap,
variable-shape).

What crosses the link is held to what the pixels need: the colour
planes go as `[B, bh, bw, 3]` canvases, an alpha plane goes beside them
(`[B, bh, bw, 1]`, the same jitted function) only for the images that
have one, every thumbnail comes back in a landscape output canvas
(`OUT_CANVAS_HW`, or `OUT_CANVAS_WIDE_HW` for a target over 4:1), the
host side of a call's canvases is a view of one kept buffer, the arena
(`_staging_canvas`), that `pack` writes a frame and the filter's margin
into, on the link the planes are folded into the row (`_resize_rows`
says why), and one call takes at most `CALL_CANVAS_BYTES` of canvases
(`call_rows`).

The host never resamples a frame with a side up to `MAX_SIDE`: above
the square rungs a canvas is one of `WIDE_BUCKETS` wide and a quarter,
a half, three quarters or the whole of that high (`bucket_for`), so a
24 MP photo, a 48 MP one and a 16,000-pixel panorama each go to the
filter with every pixel the decoder handed on.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
from typing import Sequence

import numpy as np

TARGET_PX = 262144  # ref:core/src/object/media/thumbnail/mod.rs:45
WEBP_QUALITY = 30  # ref:thumbnail/mod.rs:49
VIDEO_MAX_DIM = 256  # ref:thumbnail/process.rs:470

# Square input buckets (images are padded up to the next one). Whether
# upstream caps a decodable dimension at 4096 (crates/images/src/
# consts.rs:33, cited from memory) cannot be checked here; what is known
# is that its thumbnail is a Triangle filter over the whole decoded
# picture (BASELINE.md:28).
BUCKETS = (256, 512, 1024, 2048, 4096)
# Canvas widths above the squares (ISSUE 38): a frame with a side over
# 4096 takes the smallest canvas by area among these widths at a
# quarter, a half, three quarters or the whole of the width high. A
# 5712 × 4284 photo fills 86 % of (4608, 6144), an 8064 × 6048 one 97 %
# of (6144, 8192), a 16382 × 3628 panorama 89 % of (4096, 16384); a
# square 8192 rung would hold the first at 36 %.
WIDE_BUCKETS = (6144, 8192, 12288, 16384)
# Longest side the device path takes whole.
MAX_SIDE = WIDE_BUCKETS[-1]
# Longest side of a thumbnail in the first output canvas: covers aspect
# ratios up to 4:1 at TARGET_PX (tw = sqrt(262144·4) = 1024).
OUT_CANVAS = 1024
# The output canvases, landscape: a portrait whose target is higher
# transposes in, so th ≤ tw and th·tw ≈ TARGET_PX give th ≤ 512. The
# second takes the targets over 4:1 up to MAX_ASPECT (tw = sqrt(262144·16)
# = 2048, th ≤ 256: a panorama); more extreme aspects resize on the
# host, counted (`sd_thumbnail_host_resize_total`).
OUT_CANVAS_HW = (OUT_CANVAS // 2, OUT_CANVAS)
OUT_CANVAS_WIDE_HW = (OUT_CANVAS // 4, 2 * OUT_CANVAS)
MAX_ASPECT = (2 * OUT_CANVAS) ** 2 / TARGET_PX  # 16.0
# Canvas bytes one device call takes (a chunk's bucket goes in as many
# calls as that asks for, `call_rows`). 1.5 GiB is the widest call
# formed before there was a bound, 32 canvases of (4096, 4096) × 3 or
# 128 of (2048, 2048) × 3, which so still go as one; XLA fuses the
# float32 convert into the first pass (1.71 GB of peak HBM for that
# call, PERF.md §4), and were it to materialise the float32 canvases
# they would be 6 GiB beside the 1.5, inside the chip's 16 GB.
CALL_CANVAS_BYTES = 3 << 29


def scale_dimensions(w: int, h: int, target_px: int = TARGET_PX) -> tuple[int, int]:
    """Aspect-preserving dims with w*h ≈ target_px; never upscales.

    Parity: ref:crates/images/src/lib.rs:89 (`scale_dimensions`).
    """
    if w * h <= target_px:
        return w, h
    ratio = math.sqrt(target_px / (w * h))
    return max(1, round(w * ratio)), max(1, round(h * ratio))


def video_dimensions(w: int, h: int, max_dim: int = VIDEO_MAX_DIM) -> tuple[int, int]:
    """Bound the max dimension (video thumbs, ref:sd_ffmpeg size=256)."""
    if max(w, h) <= max_dim:
        return w, h
    ratio = max_dim / max(w, h)
    return max(1, round(w * ratio)), max(1, round(h * ratio))


def call_rows(bh: int, bw: int, planes: int) -> int:
    """Canvases of (bh, bw, planes) one device call takes: the largest
    power of two within `CALL_CANVAS_BYTES`, and always one."""
    fit = max(1, CALL_CANVAS_BYTES // (bh * bw * planes))
    return 1 << (fit.bit_length() - 1)


def out_canvas_for(th: int, tw: int) -> tuple[int, int] | None:
    """The output canvas a (th, tw) target comes back in, whichever way
    up it goes in; None beyond `MAX_ASPECT` (or for a target larger than
    `scale_dimensions` makes one)."""
    lo, hi = sorted((th, tw))
    for oh, ow in (OUT_CANVAS_HW, OUT_CANVAS_WIDE_HW):
        if lo <= oh and hi <= ow:
            return oh, ow
    return None


def bucket_for(h: int, w: int) -> tuple[int, int] | None:
    """Smallest canvas bucket holding (h, w) in its landscape
    orientation; None if over the cap.

    Buckets are (b, b) squares plus the (b/2, b) landscape half — most
    photos are 4:3/3:2/16:9, so the half canvas cuts the padded
    host→device transfer nearly 2× while keeping the compiled-shape
    count at 2 per ladder rung (the reason canvases exist at all:
    SURVEY §7 hard part 3, shape bucketing vs recompilation). A portrait
    that does not fit it as it stands transposes in on the host
    (resize_batch), so both orientations share one device call. A frame
    with a side over 4096 takes one of the `WIDE_BUCKETS` canvases."""
    m = max(h, w)
    b = next((x for x in BUCKETS if m <= x), None)
    if b is None:
        # above the squares: every quarter step of every width, the
        # smallest by area (ties to the narrower)
        fits = [(q * x // 4, x) for x in WIDE_BUCKETS if m <= x
                for q in (1, 2, 3, 4) if min(h, w) <= q * x // 4]
        return min(fits, key=lambda c: (c[0] * c[1], c[1]), default=None)
    half = b // 2
    # only the big rungs: for small canvases the halved payload saves
    # less than the compile + executable load each extra jitted shape
    # costs per process
    if b >= 1024 and min(h, w) <= half:
        return (half, b)
    return (b, b)


def _resize_rows(out_hw: tuple[int, int], planes: int):
    """The body shared by the single-device and sharded bucket programs
    (identical math ⇒ identical pixels either way). Canvases cross the
    link with their planes folded into the row, [B, BH, BW·planes], and
    so do the results: a row-major `uint8` array with a 3- or 4-wide
    minor dimension is re-tiled on the host, element by element, on its
    way to the device (2.4 M `Transpose` calls a 32-image put, each an
    event of a traced run); a plain row goes as it is, and the device
    unfolds it. The planes are resized independently."""
    import jax
    import jax.numpy as jnp

    def one(img, scale):
        out = jax.image.scale_and_translate(
            img.astype(jnp.float32),
            shape=(*out_hw, planes),
            spatial_dims=(0, 1),
            scale=scale,
            translation=jnp.zeros((2,), jnp.float32),
            method="triangle",
            antialias=True,
        )
        return jnp.clip(jnp.round(out), 0, 255).astype(jnp.uint8)

    def rows(canvases, scales):
        b, bh, row = canvases.shape
        out = jax.vmap(one)(
            canvases.reshape(b, bh, row // planes, planes), scales)
        return out.reshape(b, out_hw[0], out_hw[1] * planes)

    return rows


@functools.cache
def _resize_fn():
    """Lazily built jitted bucket-resize (jax imported on first use)."""
    import jax

    @functools.partial(jax.jit, static_argnames=("out_hw", "planes"))
    def resize_bucket(canvases, scales, out_hw: tuple[int, int], planes: int):
        # [B, BH, BW·planes] uint8 canvases (square or landscape-half
        # buckets; 3 colour planes or 1 alpha plane) + per-image [B, 2]
        # (sy, sx) scales → [B, OH, OW·planes] uint8, resized into the
        # top-left corner. One compiled program per (bucket, pad,
        # planes); the per-image scale is a traced operand, so every
        # (h, w) in the bucket reuses it.
        return _resize_rows(out_hw, planes)(canvases, scales)

    return resize_bucket


_sharded_resize_fns: dict[tuple, object] = {}


def _resize_fn_sharded(devices):
    """dp-sharded bucket resize: the batch dim splits over a flat mesh,
    every device running the same vmapped per-image program on its
    local rows under shard_map — no collectives, so pixels stay
    bit-identical to the single-device call. One compiled program per
    (device set, bucket, pad, planes) like the single-device cache."""
    key = tuple(d.id for d in devices)
    fn = _sharded_resize_fns.get(key)
    if fn is None:
        import jax

        from jax.sharding import Mesh, PartitionSpec as P

        import numpy as _np

        mesh = Mesh(_np.array(list(devices)), ("dp",))

        @functools.partial(jax.jit, static_argnames=("out_hw", "planes"))
        def resize_bucket_sharded(
            canvases, scales, out_hw: tuple[int, int], planes: int
        ):
            return jax.shard_map(
                _resize_rows(out_hw, planes), mesh=mesh,
                in_specs=(P("dp"), P("dp")),
                out_specs=P("dp"), check_vma=False,
            )(canvases, scales)

        fn = (mesh, resize_bucket_sharded)
        _sharded_resize_fns[key] = fn
    return fn


# The kept host side of every bucket call: one flat buffer (the arena),
# lent to one call at a time as a view of its first bytes, whatever the
# call's bucket, pad and planes. A canvas mapped anew pays a page fault
# per 4 KiB `pack` fills (1.4-1.5 s of a 1.5 GiB call on the chip's
# host). No call takes more than `CALL_CANVAS_BYTES` of canvases, so the
# arena grows to the largest call seen and never past that; None while
# it is lent, and before the first call. `sd_thumbnail_staging_total`
# says whether a call found it.
_arena: np.ndarray | None = None
_arena_lock = threading.Lock()


@contextlib.contextmanager
def _staging_canvas(bpad: int, bh: int, bw: int, planes: int):
    """Lend a [bpad, bh, bw, planes] staging canvas for one device call:
    a view of the arena where it is there and large enough (counted
    `kept`), else a canvas of the call's own (`mapped`: first use, a
    larger call, the arena lent to another call, or a call over
    `CALL_CANVAS_BYTES`). The canvas is taken back only when the call
    has returned its output: on the CPU backend `device_put` may alias
    the host array, and a call that failed may still be reading it. Of
    the arena and a canvas taken back the larger is kept, up to the
    bound. Its bytes are whatever the last call of any bucket left
    there, or nothing yet: `_pack_one` says what a call has to write."""
    from ..telemetry import metrics as _tm

    global _arena
    shape = (bpad, bh, bw, planes)
    size = math.prod(shape)
    with _arena_lock:
        kept = _arena is not None and _arena.size >= size
        if kept:
            buf, _arena = _arena, None
    if not kept:
        buf = np.empty(size, np.uint8)
    _tm.THUMB_STAGING.inc(result="kept" if kept else "mapped")
    yield buf[:size].reshape(shape)
    with _arena_lock:
        if buf.size <= CALL_CANVAS_BYTES and (
                _arena is None or buf.size > _arena.size):
            _arena = buf


def _pack_one(canvas: np.ndarray, img: np.ndarray,
              scale: tuple[float, float]) -> int:
    """Write `img` ([h, w, C]) into the top-left corner of its [bh, bw, C]
    canvas with the margin the filter reads; → bytes written.

    The margin: `scale_and_translate` with the triangle kernel and
    `antialias=True` gives input row i a weight of exactly 0 for output
    row o unless |(o + 0.5)/s − 0.5 − i| < max(1/s, 1), and output rows
    past th = s·h are cropped on the host. So no kept pixel reads below
    row h − 1 + ⌈max(1/s, 1)⌉, and likewise to the right: that many
    rows and columns (one more, for float32's rounding of the bound)
    are the image's edge replicated, so that the window clamps at the
    image's boundary as the reference resampler does, and the corner
    between them. Whatever the rest of the canvas holds (the bytes a
    previous call of any bucket, pad or plane count left in the arena)
    is a finite uint8 times a weight of 0, in the sum and in the
    weights' own normalising sum alike, so nothing else is written.

    A frame whose rows are contiguous goes in as one copy. Anything
    else (a portrait transposed in, the colour planes of an RGBA still)
    goes plane by plane: numpy copies a [w, h, 3] view against the
    grain pixel by pixel, a [w, h] plane in blocks, 2-4 times faster."""
    h, w, planes = img.shape
    my, mx = (min(room, math.ceil(max(1.0 / s, 1.0)) + 1)
              for room, s in zip((canvas.shape[0] - h, canvas.shape[1] - w),
                                 scale))
    if img.strides[1:] == (planes, 1):
        canvas[:h, :w] = img
    else:
        for c in range(planes):
            canvas[:h, :w, c] = img[..., c]
    canvas[h:h + my, :w] = canvas[h - 1:h, :w]
    canvas[:h + my, w:w + mx] = canvas[:h + my, w - 1:w]
    return (h + my) * (w + mx) * planes


def _resize_bucket(images, targets, bh: int, bw: int, devs,
                   out_hw: tuple[int, int] = OUT_CANVAS_HW) -> np.ndarray:
    """Pack one bucket's canvases and run its device call: `images` are
    [h, w, C] uint8 arrays of one C that fit (bh, bw) as they are handed
    in, `targets` their (th, tw), each inside the output canvas `out_hw`.
    Returns the [bpad, OH, OW, C] uint8 result (validated — a device
    returning the wrong shape is an error the caller can demote on,
    never a silent corruption)."""
    import jax

    from ..telemetry import metrics as _tm
    from ..telemetry import span as _span
    from ..utils import faults as _faults

    n_dev = len(devs) if devs else 1
    n_planes = images[0].shape[2]
    # Pad the batch dim to the next power of two so compile count is
    # bounded at (buckets × log2 max-batch) programs, not one per
    # arbitrary group size; a sharded call also rounds up to the
    # device count so rows divide evenly over the mesh.
    bpad = 1 << max(0, (len(images) - 1).bit_length())
    if n_dev > 1:
        bpad = max(bpad, n_dev)
        bpad += (-bpad) % n_dev
    oh, ow = out_hw
    bucket = f"{bh}x{bw}"
    with _staging_canvas(bpad, bh, bw, n_planes) as canv:
        with _span("pack") as part:
            scales = np.ones((bpad, 2), np.float32)
            written = 0
            # canvases past the group are never written nor returned
            for j, (img, (th, tw)) in enumerate(zip(images, targets)):
                scale = (th / img.shape[0], tw / img.shape[1])
                scales[j] = scale
                written += _pack_one(canv[j], img, scale)
        _tm.THUMB_DEVICE_SECONDS.inc(part.duration, part="pack")
        _tm.THUMB_PACK_BYTES.inc(written)
        spec = _faults.hit("device.thumbnail")
        if spec is not None:
            if spec.mode == "raise":
                raise _faults.InjectedFault(
                    "injected device failure (thumbnail)")
            if spec.mode == "xla":
                raise _faults.device_error("device.thumbnail")
        if n_dev > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from .cas import shard_occupancy

            mesh, fn = _resize_fn_sharded(devs)
            _tm.SHARD_BATCH_ROWS.observe(bpad // n_dev, op="thumbnail")
            for frac in shard_occupancy(len(images), bpad, n_dev):
                _tm.DEVICE_DISPATCH_OCCUPANCY.observe(frac, op="thumbnail")
            where = NamedSharding(mesh, P("dp"))
        else:
            fn = _resize_fn()
            # a single surviving device: committed inputs pin the jit
            # there, not on a default device that may be the dead one
            where = devs[0] if devs else None
        # the three transfers depend on one another, so blocking between
        # them hides nothing and gives each its own span
        with _span("put") as part:
            on_device = jax.block_until_ready((
                jax.device_put(canv.reshape(bpad, bh, -1), where),
                jax.device_put(scales, where)))
        _tm.THUMB_DEVICE_SECONDS.inc(part.duration, part="put")
        _tm.THUMB_DEVICE_BYTES.inc(canv.nbytes + scales.nbytes, dir="h2d")
        with _span("run") as part:
            result = jax.block_until_ready(
                fn(*on_device, out_hw=(oh, ow), planes=n_planes))
        _tm.THUMB_DEVICE_SECONDS.inc(part.duration, part="run")
        _tm.THUMB_DEVICE_CALLS.inc(bucket=bucket, out=f"{oh}x{ow}")
        _tm.THUMB_CANVAS_BYTES.inc(canv.nbytes, bucket=bucket)
        with _span("get") as part:
            out = np.asarray(result)
        _tm.THUMB_DEVICE_SECONDS.inc(part.duration, part="get")
        _tm.THUMB_DEVICE_BYTES.inc(out.nbytes, dir="d2h")
    if spec is not None and spec.mode == "wrong_shape":
        out = out[:, : oh // 2]
    if out.shape != (bpad, oh, ow * n_planes):
        raise ValueError(
            f"device resize returned shape {out.shape}, "
            f"expected {(bpad, oh, ow * n_planes)}"
        )
    return out.reshape(bpad, oh, ow, n_planes)


def _resize_on_ladder(images, targets, bh: int, bw: int,
                      out_hw: tuple[int, int]) -> np.ndarray:
    """`_resize_bucket` on the degradation ladder (parallel.mesh.LADDER):
    a failed bucket call demotes — full mesh → surviving subset → single
    default device (the per-image math is identical at every rung, so
    pixels never change) — and the bucket re-runs at the demoted rung
    instead of failing the chunk."""
    from ..parallel import mesh as _mesh

    # bounded: one attempt per rung plus one half-open probe — a tiny
    # reset_timeout must not oscillate probe/demote forever
    for attempt in range(4):
        devs, level = _mesh.ladder_devices()
        if (
            level < _mesh.LEVEL_HOST
            and len(devs) > 1 and len(images) >= len(devs)
        ):
            use = devs
        elif level == _mesh.LEVEL_SUBSET and devs:
            # unsharded at the subset rung: still pin to a surviving
            # chip, never the (possibly dead) default
            use = devs[:1]
        else:
            use = None
        try:
            out = _resize_bucket(images, targets, bh, bw, use, out_hw)
        except Exception as exc:  # noqa: BLE001 - demote & retry
            # always settle the ladder bookkeeping (a probe left
            # unreported would block re-arming), THEN decide whether
            # anything is left to demote to
            _mesh.LADDER.record_failure(level, devs)
            if level >= _mesh.LEVEL_HOST or attempt == 3:
                raise
            from ..telemetry import events as _events

            _events.record_error("thumbnail.ladder", exc)
            continue
        if use is not None:
            _mesh.LADDER.record_success(level)
        else:
            # ran on the single default device — says nothing about the
            # rung's chips; release a held probe
            _mesh.LADDER.probe_inconclusive(level)
        return out
    raise AssertionError("unreachable: the last attempt returns or raises")


def resize_batch(
    images: Sequence[np.ndarray],
    targets: Sequence[tuple[int, int]],
    devices: Sequence | None = None,
) -> list[np.ndarray]:
    """Resize a batch of uint8 images, HxWx3 RGB or HxWx4 RGBA, to
    per-image (th, tw); a result has its input's channels.

    Groups by input bucket and output canvas, writes each image and
    the filter's margin into its call's canvas, a view of the kept
    arena (`_staging_canvas`, `_pack_one`), runs the group's device
    calls (one, or as many as `call_rows` makes of it: the per-image
    math does not depend on its neighbours, so the bytes are the same
    either way) for the colour planes and again,
    through the same program at one plane, for the alpha of those
    images that have it; crops on host. A portrait goes in as it stands
    where its bucket and its output canvas take it that way (a clip's
    1920 × 1080 frame, bound to 256 × 144), transposed where they do not
    (a photo: its target is over 512 high); the two ways run the
    separable passes in the other order, so a byte may differ by 1 where
    a float sum lands on .5.
    Returns resized uint8 arrays in input order. Images too large for
    any bucket or with th/tw beyond the output canvases must be filtered
    by the caller beforehand.

    With >1 local device (or an explicit `devices` list) the batch dim
    of each bucket call dp-shards over the chip mesh — one dispatch,
    every chip resizing its slice of the canvases.

    Auto dispatches ride the degradation ladder (`_resize_on_ladder`);
    explicit `devices` stay strict and re-raise."""
    from ..telemetry import metrics as _tm
    from ..telemetry import span as _span

    results: list[np.ndarray | None] = [None] * len(images)
    by_bucket: dict[tuple, list[int]] = {}
    # a portrait that its bucket or the output canvas does not take as it
    # stands transposes in (a view here; `_pack_one` makes the copy) and
    # is un-transposed after the crop
    flip: list[bool] = [False] * len(images)
    placed: list[np.ndarray] = list(images)  # as each goes into its canvas
    canvas_targets = list(targets)
    for i, img in enumerate(images):
        if img.ndim != 3 or img.shape[2] not in (3, 4):
            raise ValueError(f"image {i}: shape {img.shape} is not HxWx3|4")
        h, w = img.shape[:2]
        b = bucket_for(h, w)
        if b is None:
            raise ValueError(f"image {i} ({h}x{w}) exceeds max bucket")
        th, tw = targets[i]
        oh, ow = out_hw = out_canvas_for(th, tw) or OUT_CANVAS_HW
        if h > w and (h > b[0] or th > oh or tw > ow):
            flip[i] = True
            placed[i] = np.transpose(img, (1, 0, 2))
            th, tw = canvas_targets[i] = (tw, th)
        if th > oh or tw > ow:
            raise ValueError(
                f"image {i}: target {targets[i]} exceeds the output canvas")
        by_bucket.setdefault((b, out_hw), []).append(i)

    def dispatch(members, channels, bh, bw, out_hw):
        """→ the members' [OH, OW, C] results, in as many device calls
        as `call_rows` makes of them."""
        rows = call_rows(bh, bw, channels.stop - channels.start)
        out: list[np.ndarray] = []
        for lo in range(0, len(members), rows):
            part = members[lo:lo + rows]
            group = [placed[i][..., channels] for i in part]
            want = [canvas_targets[i] for i in part]
            if devices is not None:
                got = _resize_bucket(group, want, bh, bw, list(devices),
                                     out_hw)
            else:
                got = _resize_on_ladder(group, want, bh, bw, out_hw)
            out.extend(got[:len(part)])
        return out

    for ((bh, bw), out_hw), idxs in by_bucket.items():
        with_alpha = [i for i in idxs if placed[i].shape[2] == 4]
        colour = dispatch(idxs, slice(0, 3), bh, bw, out_hw)
        alpha = dispatch(with_alpha, slice(3, 4), bh, bw, out_hw)
        with _span("crop") as part:
            alpha_row = {i: k for k, i in enumerate(with_alpha)}
            for j, i in enumerate(idxs):
                th, tw = canvas_targets[i]
                out = colour[j][:th, :tw]
                if i in alpha_row:
                    out = np.concatenate(
                        [out, alpha[alpha_row[i]][:th, :tw]], axis=-1)
                results[i] = np.transpose(out, (1, 0, 2)) if flip[i] else out
        _tm.THUMB_DEVICE_SECONDS.inc(part.duration, part="crop")
        if with_alpha:
            _tm.THUMB_RESIZE_IMAGES.inc(len(with_alpha), alpha="1")
        if len(with_alpha) < len(idxs):
            _tm.THUMB_RESIZE_IMAGES.inc(len(idxs) - len(with_alpha), alpha="0")
    return results  # type: ignore[return-value]


def apply_orientation(arr: np.ndarray, orientation: int) -> np.ndarray:
    """EXIF orientation 1-8 → corrected array (host, zero-copy views
    where possible). Parity: ref:crates/media-metadata/src/image/
    orientation.rs applied post-resize (process.rs:421-428)."""
    if orientation == 2:
        return arr[:, ::-1]
    if orientation == 3:
        return arr[::-1, ::-1]
    if orientation == 4:
        return arr[::-1]
    if orientation == 5:
        return np.transpose(arr, (1, 0, 2))
    if orientation == 6:
        return np.transpose(arr[::-1], (1, 0, 2))
    if orientation == 7:
        return np.transpose(arr[::-1, ::-1], (1, 0, 2))
    if orientation == 8:
        return np.transpose(arr[:, ::-1], (1, 0, 2))
    return arr
