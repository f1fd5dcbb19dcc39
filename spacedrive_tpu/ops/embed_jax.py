"""Batched image embedding on TPU — the semantic-search device leg.

Same dispatch discipline as the thumbnail resize (ops/thumbnail_jax.py,
PR 4): ONE compiled program per (device set, batch-pad) pair, the batch
dim padded to a power of two so compile count stays bounded, dp-sharded
over the chip mesh via shard_map when more than one device can hold a
real row, and demoted down the DeviceLadder on failure. The per-image
math body lives in models/embedder.forward and is closed over by the
jitted single-device, sharded, and host programs alike — identical
math ⇒ identical vectors at every rung, which is what lets a
replicated index trust a locally recomputed vector.
"""

from __future__ import annotations

import functools
from typing import Sequence

import numpy as np

from ..models import embedder as _embedder


@functools.cache
def _embed_fn():
    """Lazily built jitted embed pass (jax imported on first use)."""
    import jax

    @jax.jit
    def embed(params, images):
        # [B, S, S, 3] f32 → [B, EMBED_DIM] f32
        return _embedder.forward(params, images)

    return embed


_sharded_embed_fns: dict[tuple, object] = {}


def _embed_fn_sharded(devices):
    """dp-sharded embed: the batch dim splits over a flat mesh, every
    device running the same forward on its local rows under shard_map —
    no collectives (the forward is per-row), so vectors stay
    bit-identical to the single-device call."""
    key = tuple(d.id for d in devices)
    fn = _sharded_embed_fns.get(key)
    if fn is None:
        import jax

        from jax.sharding import Mesh, PartitionSpec as P

        import numpy as _np

        mesh = Mesh(_np.array(list(devices)), ("dp",))

        @jax.jit
        def embed_sharded(params, images):
            def body(imgs):
                return _embedder.forward(params, imgs)

            return jax.shard_map(
                body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                check_vma=False,
            )(images)

        fn = (mesh, embed_sharded)
        _sharded_embed_fns[key] = fn
    return fn


def _embed_chunk(images: np.ndarray, devs) -> np.ndarray:
    """Pad one chunk and run its device call; returns the
    [bpad, EMBED_DIM] f32 result (validated — a device returning the
    wrong shape is an error the caller can demote on, never a silent
    corruption)."""
    from ..utils import faults as _faults

    params = _embedder.params()
    n = images.shape[0]
    n_dev = len(devs) if devs else 1
    # power-of-two batch pad bounds compile count at log2(max-batch)
    # programs; a sharded call also rounds up to the device count so
    # rows divide evenly over the mesh
    bpad = 1 << max(0, (n - 1).bit_length())
    if n_dev > 1:
        bpad = max(bpad, n_dev)
        bpad += (-bpad) % n_dev
    if bpad != n:
        pad = np.zeros((bpad - n, *images.shape[1:]), images.dtype)
        batch = np.concatenate([images, pad], axis=0)
    else:
        batch = images
    spec = _faults.hit("embed.forward")
    if spec is not None:
        if spec.mode == "raise":
            raise _faults.InjectedFault("injected device failure (embed)")
        if spec.mode == "xla":
            raise _faults.device_error("embed.forward")
    if n_dev > 1:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..telemetry import metrics as _tm
        from .cas import shard_occupancy

        mesh, fn = _embed_fn_sharded(devs)
        _tm.SHARD_BATCH_ROWS.observe(bpad // n_dev, op="embed")
        for frac in shard_occupancy(n, bpad, n_dev):
            _tm.DEVICE_DISPATCH_OCCUPANCY.observe(frac, op="embed")
        out = np.asarray(fn(
            jax.device_put(params, NamedSharding(mesh, P())),
            jax.device_put(batch, NamedSharding(mesh, P("dp"))),
        ))
    elif devs:
        # single surviving device: committed inputs pin the jit there,
        # not on a default device that may be the dead one
        import jax

        out = np.asarray(_embed_fn()(
            jax.device_put(params, devs[0]), jax.device_put(batch, devs[0]),
        ))
    else:
        out = np.asarray(_embed_fn()(params, batch))
    if spec is not None and spec.mode == "wrong_shape":
        out = out[:, : _embedder.EMBED_DIM // 2]
    if out.shape != (bpad, _embedder.EMBED_DIM):
        raise ValueError(
            f"device embed returned shape {out.shape}, "
            f"expected {(bpad, _embedder.EMBED_DIM)}"
        )
    return out


def embed_batch(
    images: np.ndarray, devices: Sequence | None = None
) -> np.ndarray:
    """Embed a [N, S, S, 3] f32 batch → [N, EMBED_DIM] f32.

    With >1 local device (and at least one real row per chip) the batch
    dim dp-shards over the mesh; auto dispatches ride the degradation
    ladder (parallel.mesh.LADDER) — full mesh → surviving subset →
    single default device — with bit-identical vectors at every rung.
    Explicit `devices` stay strict and re-raise."""
    if images.ndim != 4 or images.shape[1:] != (
        _embedder.IMAGE_SIZE, _embedder.IMAGE_SIZE, 3
    ):
        raise ValueError(f"embed input shape {images.shape} is not "
                         f"[N, {_embedder.IMAGE_SIZE}, "
                         f"{_embedder.IMAGE_SIZE}, 3]")
    n = images.shape[0]
    if n == 0:
        return np.zeros((0, _embedder.EMBED_DIM), np.float32)
    if devices is not None:
        return _embed_chunk(images, list(devices))[:n]
    from ..parallel import mesh as _mesh

    # bounded: one attempt per rung plus one half-open probe — a tiny
    # reset_timeout must not oscillate probe/demote forever
    for attempt in range(4):
        devs, level = _mesh.ladder_devices()
        if level < _mesh.LEVEL_HOST and len(devs) > 1 and n >= len(devs):
            use = devs
        elif level == _mesh.LEVEL_SUBSET and devs:
            # unsharded at the subset rung: still pin to a surviving
            # chip, never the (possibly dead) default
            use = devs[:1]
        else:
            use = None
        try:
            out = _embed_chunk(images, use)
        except Exception as exc:  # noqa: BLE001 - demote & retry
            # always settle the ladder bookkeeping (a probe left
            # unreported would block re-arming), THEN decide whether
            # anything is left to demote to
            _mesh.LADDER.record_failure(level, devs)
            if level >= _mesh.LEVEL_HOST or attempt == 3:
                raise
            from ..telemetry import events as _events

            _events.record_error("embed.ladder", exc)
            continue
        if use is not None:
            _mesh.LADDER.record_success(level)
        else:
            # ran on the single default device — says nothing about
            # the rung's chips; release a held probe
            _mesh.LADDER.probe_inconclusive(level)
        return out[:n]
    raise RuntimeError("unreachable: embed ladder loop exhausted")
