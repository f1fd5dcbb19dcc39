"""Deterministic JAX-native image embedder — the semantic-search trunk.

The reference's third device workload is an ONNX image model driven by
an actor (ref:crates/ai/src/image_labeler/actor.rs); its output here is
not labels but a fixed-width f32 vector per image, persisted in
`object_embedding` and replicated through the CRDT plane. Quality is
explicitly not the bar (PAPER.md reproduces the *engine*, not the
model) — determinism, shape discipline, and throughput are:

- **determinism**: weights derive from a fixed seed via a pinned
  bit-generator, so every node materializes the *same* projection and
  a replicated vector equals the locally computed one bit-for-bit.
  A provisioned checkpoint (`embedder.npz`, same artifact format as
  the labeler's) overrides the derived weights when present.
- **shape discipline**: one input shape (IMAGE_SIZE² RGB f32), one
  output shape (EMBED_DIM f32) — the dispatch layer (ops/embed_jax)
  never sees a ragged tensor.
- **the math body lives here** so the jitted single-device, shard_map
  and host programs in ops/embed_jax all close over the identical
  forward function (PR 4's tri-path parity discipline).
"""

from __future__ import annotations

import os
from typing import Any

import numpy as np

from . import checkpoint

#: fixed model vocabulary — wire format (vector width in the DB and on
#: the sync plane), not a load knob
EMBED_DIM = 128
IMAGE_SIZE = 32
PATCH = 4  # mean-pool patch edge → (IMAGE_SIZE/PATCH)² · 3 features
HIDDEN = 128
MODEL_NAME = "patchpool-v1"

ENV_VAR = "SD_EMBED"

ARTIFACT_NAME = "embedder.npz"


def enabled() -> bool:
    """SD_EMBED=0 turns the whole subsystem into a true no-op: no
    pipeline stage, no DB writes, no sync ops, no index."""
    return os.environ.get(ENV_VAR, "1") != "0"


def _derived_params() -> dict[str, np.ndarray]:
    """Seed-derived projection weights. PCG64 with a fixed seed is a
    pinned stream (numpy guarantees stream stability per bit
    generator), so every process on every node derives byte-identical
    weights — the property the replicated index leans on."""
    rng = np.random.Generator(np.random.PCG64(0))
    feat = (IMAGE_SIZE // PATCH) ** 2 * 3
    return {
        "w1": rng.standard_normal((feat, HIDDEN)).astype(np.float32)
        * np.float32(1.0 / np.sqrt(feat)),
        "b1": np.zeros((HIDDEN,), np.float32),
        "w2": rng.standard_normal((HIDDEN, EMBED_DIM)).astype(np.float32)
        * np.float32(1.0 / np.sqrt(HIDDEN)),
        "b2": np.zeros((EMBED_DIM,), np.float32),
    }


_params: dict[str, np.ndarray] | None = None


def params(models_dir: str | os.PathLike | None = None) -> dict[str, np.ndarray]:
    """The embedder weights: a provisioned `embedder.npz` checkpoint if
    one is installed, else the seed-derived projection. Cached for the
    process lifetime (first resolution wins, like the labeler's
    artifact)."""
    global _params
    if _params is not None:
        return _params
    if models_dir is not None:
        path = os.path.join(os.fspath(models_dir), ARTIFACT_NAME)
        if os.path.exists(path):
            try:
                tree, meta = checkpoint.load(path)
                if meta.get("kind") == "embedder" and all(
                    k in tree for k in ("w1", "b1", "w2", "b2")
                ):
                    _params = {
                        k: np.asarray(tree[k], np.float32)
                        for k in ("w1", "b1", "w2", "b2")
                    }
                    return _params
            except (OSError, ValueError):
                pass  # corrupt artifact → derived weights still work
    _params = _derived_params()
    return _params


def reset_params_cache() -> None:
    global _params
    _params = None


def save_artifact(models_dir: str | os.PathLike,
                  tree: dict[str, np.ndarray] | None = None) -> str:
    """Install an embedder checkpoint using the labeler artifact format
    (classes empty — this trunk emits vectors, not a vocabulary)."""
    path = os.path.join(os.fspath(models_dir), ARTIFACT_NAME)
    checkpoint.save(
        path,
        tree if tree is not None else _derived_params(),
        classes=[],
        image_size=IMAGE_SIZE,
        widths=(HIDDEN, EMBED_DIM),
        depths=(1, 1),
        extra={"kind": "embedder", "model": MODEL_NAME},
    )
    return path


def forward(p: dict[str, Any], images):
    """The per-batch forward body — [B, S, S, 3] f32 in [0,1] →
    [B, EMBED_DIM] f32. jnp-only; ops/embed_jax closes over this exact
    function for the jitted, sharded, and host programs so the three
    paths are bit-identical by construction. Patch mean-pool (a fixed
    8×8 grid) then a 2-layer tanh projection: per-row math only, no
    cross-batch reductions, so dp-sharding the batch dim cannot change
    a single bit."""
    import jax.numpy as jnp

    x = images.astype(jnp.float32)
    b = x.shape[0]
    g = IMAGE_SIZE // PATCH
    x = x.reshape(b, g, PATCH, g, PATCH, 3).mean(axis=(2, 4))
    x = x.reshape(b, g * g * 3)
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return (h @ p["w2"] + p["b2"]).astype(jnp.float32)


def plane_from_frame(frame: Any, scale: int = 1) -> np.ndarray:
    """A decoded frame → the embedder's `uint8` [S, S, 3] plane: RGB
    (an alpha band dropped as `convert("RGB")` drops it), one PIL
    default resize, the stored pixels with no EXIF orientation. The one
    definition of the plane: `decode_image` hands it the opened PIL
    image, the thumbnailer's tap (object/media/job.py) the RGB/RGBA
    frame it decoded for the thumbnail (a PIL image, or libheif's
    `uint8` array), and because both asked `images.draft_jpeg` for the
    frame, the plane is a function of the file's bytes alone. Counts
    the plane in `sd_embed_decode_total` by the DCT `scale` its frame
    was decoded at, in the process that made it."""
    from PIL import Image

    from ..telemetry import metrics as _tm

    img = frame if isinstance(frame, Image.Image) else Image.fromarray(frame)
    if img.mode != "RGB":
        img = img.convert("RGB")
    plane = np.asarray(img.resize((IMAGE_SIZE, IMAGE_SIZE)))
    _tm.EMBED_DECODE.inc(scale="8" if scale >= 8 else "4" if scale >= 4
                         else "2" if scale >= 2 else "1")
    return plane


def input_plane(plane: np.ndarray) -> np.ndarray:
    """`plane_from_frame`'s `uint8` plane → the forward's f32 in [0, 1]."""
    return plane.astype(np.float32) / 255.0


def decode_image(path: str) -> np.ndarray | None:
    """Decode one image file to the embedder's input plane: a JPEG at
    the DCT scale `images.draft_jpeg` asks for (the thumbnailer's own
    request, so a 12 MP photo decodes at 1/4), everything else PIL
    opens as it is, then `plane_from_frame`. HEIF, SVG and PDF do not
    come from PIL and ride `format_image`. Module-level so the media
    job's embed leg, the mesh embed stage and query-by-image run the
    EXACT same code path, and the media job's planes from the
    thumbnailer's frames are the same bits; None = undecodable or over
    `MAXIMUM_FILE_SIZE`."""
    from PIL import Image

    from ..object.media import images

    try:
        ext = os.path.splitext(path)[1].lstrip(".").lower()
        if ext in (images.HEIF_EXTENSIONS | images.SVG_EXTENSIONS
                   | images.PDF_EXTENSIONS):
            plane = plane_from_frame(images.format_image(path))
        else:
            if os.path.getsize(path) > images.MAXIMUM_FILE_SIZE:
                return None
            with Image.open(path) as img:
                plane = plane_from_frame(img, images.draft_jpeg(img))
    except Exception:  # noqa: BLE001 - undecodable → caller skips
        return None
    return input_plane(plane)


def vector_to_blob(vec: np.ndarray) -> bytes:
    """f32 LE wire/DB encoding of one embedding vector."""
    return np.asarray(vec, dtype="<f4").tobytes()


def blob_to_vector(blob: bytes, dim: int = EMBED_DIM) -> np.ndarray | None:
    """Strictly validated blob → vector decode (None = corrupt/foreign
    width — a poisoned sync op must never wedge index maintenance)."""
    if not isinstance(blob, (bytes, bytearray, memoryview)):
        return None
    if len(blob) != dim * 4:
        return None
    arr = np.frombuffer(bytes(blob), dtype="<f4")
    if arr.shape != (dim,) or not np.all(np.isfinite(arr)):
        return None
    return arr.astype(np.float32)
