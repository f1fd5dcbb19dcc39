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
*buckets* (squares + landscape halves; portraits transpose in — bounded
XLA compile shapes) and a whole batch is resized in
ONE device call per bucket via `jax.image.scale_and_translate`, vmapped
with *per-image* scale factors as traced arguments — so a single
compiled program handles arbitrary (h, w) inputs inside a bucket. XLA
lowers separable scale_and_translate to two weight matmuls per image,
which ride the MXU; `antialias=True` + `method="triangle"` is exactly
the reference's Triangle filter for downscale. Crop to the per-image
target dims, orientation flips, and webp encode stay on host (cheap,
variable-shape).
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import numpy as np

TARGET_PX = 262144  # ref:core/src/object/media/thumbnail/mod.rs:45
WEBP_QUALITY = 30  # ref:thumbnail/mod.rs:49
VIDEO_MAX_DIM = 256  # ref:thumbnail/process.rs:470

# Square input buckets (images are padded up to the next one). 4096 is
# the reference's max decodable dimension (ref:crates/images/src/consts.rs:33).
BUCKETS = (256, 512, 1024, 2048, 4096)
# Output canvas: covers aspect ratios up to 4:1 at TARGET_PX
# (tw = sqrt(262144·4) = 1024); more extreme aspects fall back to CPU.
OUT_CANVAS = 1024
MAX_ASPECT = (OUT_CANVAS * OUT_CANVAS) / TARGET_PX  # 4.0


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


def bucket_for(h: int, w: int) -> tuple[int, int] | None:
    """Smallest canvas bucket holding (h, w) in its landscape
    orientation; None if over the cap.

    Buckets are (b, b) squares plus the (b/2, b) landscape half — most
    photos are 4:3/3:2/16:9, so the half canvas cuts the padded
    host→device transfer nearly 2× while keeping the compiled-shape
    count at 2 per ladder rung (the reason canvases exist at all:
    SURVEY §7 hard part 3, shape bucketing vs recompilation). Portrait
    images transpose into the landscape canvas on the host
    (resize_batch), so both orientations share one device call."""
    m = max(h, w)
    b = next((x for x in BUCKETS if m <= x), None)
    if b is None:
        return None
    half = b // 2
    # only the big rungs: for small canvases the halved payload saves
    # less than the compile + executable load each extra jitted shape
    # costs per process
    if b >= 1024 and min(h, w) <= half:
        return (half, b)
    return (b, b)


def _one_resize(out_size: int):
    """Per-image resize body shared by the single-device and sharded
    bucket programs (identical math ⇒ identical pixels either way)."""
    import jax
    import jax.numpy as jnp

    def one(img, scale):
        out = jax.image.scale_and_translate(
            img.astype(jnp.float32),
            shape=(out_size, out_size, 4),
            spatial_dims=(0, 1),
            scale=scale,
            translation=jnp.zeros((2,), jnp.float32),
            method="triangle",
            antialias=True,
        )
        return jnp.clip(jnp.round(out), 0, 255).astype(jnp.uint8)

    return one


@functools.cache
def _resize_fn():
    """Lazily built jitted bucket-resize (jax imported on first use)."""
    import jax

    @functools.partial(jax.jit, static_argnames=("out_size",))
    def resize_bucket(canvases, scales, out_size: int):
        # [B, BH, BW, 4] uint8 RGBA canvases (square or landscape-half
        # buckets) + per-image [B, 2] (sy, sx) scales → [B, OUT, OUT, 4]
        # uint8, resized into the top-left
        # corner. One compiled program per (bucket, out) pair; the
        # per-image scale is a traced operand, so every (h, w) in the
        # bucket reuses it.
        return jax.vmap(_one_resize(out_size))(canvases, scales)

    return resize_bucket


_sharded_resize_fns: dict[tuple, object] = {}


def _resize_fn_sharded(devices):
    """dp-sharded bucket resize: the batch dim splits over a flat mesh,
    every device running the same vmapped per-image program on its
    local rows under shard_map — no collectives, so pixels stay
    bit-identical to the single-device call. One compiled program per
    (device set, bucket, out) like the single-device cache."""
    key = tuple(d.id for d in devices)
    fn = _sharded_resize_fns.get(key)
    if fn is None:
        import jax

        from jax.sharding import Mesh, PartitionSpec as P

        import numpy as _np

        mesh = Mesh(_np.array(list(devices)), ("dp",))

        @functools.partial(jax.jit, static_argnames=("out_size",))
        def resize_bucket_sharded(canvases, scales, out_size: int):
            def body(c, s):
                return jax.vmap(_one_resize(out_size))(c, s)

            return jax.shard_map(
                body, mesh=mesh, in_specs=(P("dp"), P("dp")),
                out_specs=P("dp"), check_vma=False,
            )(canvases, scales)

        fn = (mesh, resize_bucket_sharded)
        _sharded_resize_fns[key] = fn
    return fn


def _resize_bucket(
    images, targets, flip, idxs, bh: int, bw: int, out_size: int, devs
) -> np.ndarray:
    """Pack one bucket's canvases and run its device call; returns the
    [bpad, out, out, 4] uint8 result (validated — a device returning
    the wrong shape is an error the caller can demote on, never a
    silent corruption)."""
    from ..utils import faults as _faults

    n_dev = len(devs) if devs else 1
    # Pad the batch dim to the next power of two so compile count is
    # bounded at (buckets × log2 max-batch) programs, not one per
    # arbitrary group size; a sharded call also rounds up to the
    # device count so rows divide evenly over the mesh.
    bpad = 1 << max(0, (len(idxs) - 1).bit_length())
    if n_dev > 1:
        bpad = max(bpad, n_dev)
        bpad += (-bpad) % n_dev
    canv = np.zeros((bpad, bh, bw, 4), np.uint8)
    scales = np.ones((bpad, 2), np.float32)
    for j, i in enumerate(idxs):
        img = images[i]
        th, tw = targets[i]
        if flip[i]:
            img = np.transpose(img, (1, 0, 2))
            th, tw = tw, th
        h, w = img.shape[:2]
        # Edge-replicate into the padding so the antialias window
        # clamps at the image boundary instead of pulling in zeros
        # (the reference resampler clamps at edges too).
        canv[j, :h, :w] = img
        canv[j, h:, :w] = img[h - 1 : h, :]
        canv[j, :h, w:] = img[:, w - 1 : w]
        canv[j, h:, w:] = img[h - 1, w - 1]
        scales[j] = (th / h, tw / w)
    spec = _faults.hit("device.thumbnail")
    if spec is not None:
        if spec.mode == "raise":
            raise _faults.InjectedFault("injected device failure (thumbnail)")
        if spec.mode == "xla":
            raise _faults.device_error("device.thumbnail")
    if n_dev > 1:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..telemetry import metrics as _tm
        from .cas import shard_occupancy

        mesh, fn = _resize_fn_sharded(devs)
        _tm.SHARD_BATCH_ROWS.observe(bpad // n_dev, op="thumbnail")
        for frac in shard_occupancy(len(idxs), bpad, n_dev):
            _tm.DEVICE_DISPATCH_OCCUPANCY.observe(frac, op="thumbnail")
        sh = NamedSharding(mesh, P("dp"))
        out = np.asarray(fn(
            jax.device_put(canv, sh),
            jax.device_put(scales, sh),
            out_size=out_size,
        ))
    elif devs:
        # single surviving device: committed inputs pin the jit there,
        # not on a default device that may be the dead one
        import jax

        out = np.asarray(_resize_fn()(
            jax.device_put(canv, devs[0]), jax.device_put(scales, devs[0]),
            out_size=out_size,
        ))
    else:
        out = np.asarray(_resize_fn()(canv, scales, out_size=out_size))
    if spec is not None and spec.mode == "wrong_shape":
        out = out[:, : out_size // 2]
    if out.shape != (bpad, out_size, out_size, 4):
        raise ValueError(
            f"device resize returned shape {out.shape}, "
            f"expected {(bpad, out_size, out_size, 4)}"
        )
    return out


def resize_batch(
    images: Sequence[np.ndarray],
    targets: Sequence[tuple[int, int]],
    out_size: int = OUT_CANVAS,
    devices: Sequence | None = None,
) -> list[np.ndarray]:
    """Resize a batch of HxWx4 uint8 RGBA images to per-image (th, tw).

    Groups by input bucket, pads to the bucket canvas, runs one device
    call per bucket, crops on host. Returns resized uint8 arrays in
    input order. Images too large for any bucket or with th/tw beyond
    the output canvas must be filtered by the caller beforehand.

    With >1 local device (or an explicit `devices` list) the batch dim
    of each bucket call dp-shards over the chip mesh — one dispatch,
    every chip resizing its slice of the canvases.

    Auto dispatches ride the degradation ladder (parallel.mesh.LADDER):
    a failed bucket call demotes — full mesh → surviving subset →
    single default device (the per-image math is identical at every
    rung, so pixels never change) — and the bucket re-runs at the
    demoted rung instead of failing the chunk. Explicit `devices` stay
    strict and re-raise."""
    results: list[np.ndarray | None] = [None] * len(images)
    by_bucket: dict[tuple[int, int], list[int]] = {}
    flip: list[bool] = [False] * len(images)
    for i, img in enumerate(images):
        h, w = img.shape[:2]
        b = bucket_for(h, w)
        if b is None:
            raise ValueError(f"image {i} ({h}x{w}) exceeds max bucket")
        # portrait images ride the landscape half-canvas transposed
        # (cheap uint8 host transpose; un-transposed after the crop)
        flip[i] = b[0] < b[1] and h > w
        by_bucket.setdefault(b, []).append(i)

    for (bh, bw), idxs in by_bucket.items():
        if devices is not None:
            out = _resize_bucket(
                images, targets, flip, idxs, bh, bw, out_size, list(devices)
            )
        else:
            from ..parallel import mesh as _mesh

            # bounded: one attempt per rung plus one half-open probe —
            # a tiny reset_timeout must not oscillate probe/demote forever
            for attempt in range(4):
                devs, level = _mesh.ladder_devices()
                if (
                    level < _mesh.LEVEL_HOST
                    and len(devs) > 1 and len(idxs) >= len(devs)
                ):
                    use = devs
                elif level == _mesh.LEVEL_SUBSET and devs:
                    # unsharded at the subset rung: still pin to a
                    # surviving chip, never the (possibly dead) default
                    use = devs[:1]
                else:
                    use = None
                try:
                    out = _resize_bucket(
                        images, targets, flip, idxs, bh, bw, out_size, use
                    )
                except Exception as exc:  # noqa: BLE001 - demote & retry
                    # always settle the ladder bookkeeping (a probe left
                    # unreported would block re-arming), THEN decide
                    # whether anything is left to demote to
                    _mesh.LADDER.record_failure(level, devs)
                    if level >= _mesh.LEVEL_HOST or attempt == 3:
                        raise
                    from ..telemetry import events as _events

                    _events.record_error("thumbnail.ladder", exc)
                    continue
                if use is not None:
                    _mesh.LADDER.record_success(level)
                else:
                    # ran on the single default device — says nothing
                    # about the rung's chips; release a held probe
                    _mesh.LADDER.probe_inconclusive(level)
                break
        for j, i in enumerate(idxs):
            th, tw = targets[i]
            if flip[i]:
                results[i] = np.transpose(out[j, :tw, :th], (1, 0, 2))
            else:
                results[i] = out[j, :th, :tw]
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
