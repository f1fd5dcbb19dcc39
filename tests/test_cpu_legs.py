"""The CPU legs of a pass, each against a plain computation of its own:
link prep (pub_id check + deterministic object id), the pHash gray decode
of the mesh stage and of the duplicates job, the mesh shard's host hash
against the pure-Python BLAKE3 reference, and the thumbnailer's software
path (stored bytes, errors counted)."""

import os
import struct
import types
import unicodedata
import uuid

import numpy as np
import pytest
from PIL import Image

from spacedrive_tpu.ops import blake3_ref

# --- link prep ------------------------------------------------------------

#: the object-id namespace, restated: a prep that minted ids under any
#: other namespace would split one content into two objects across nodes
_OBJECT_NS = uuid.UUID("5d0b5e1f-c45e-4a8a-9b7e-8f3a2d6c0001")
_PUB = "0123456789abcdef0123456789abcdef"
_CAFE = "café"  # precomposed é


@pytest.mark.parametrize("res, linkable", [
    ({"pub_id": _PUB, "cas_id": "aa" * 8, "ext": "jpg"}, True),
    ({"pub_id": _PUB.upper(), "cas_id": "bb" * 8, "ext": "JPG"}, True),
    ({"pub_id": _PUB, "cas_id": unicodedata.normalize("NFC", _CAFE),
      "ext": "tar.gz"}, True),
    ({"pub_id": _PUB, "cas_id": unicodedata.normalize("NFD", _CAFE),
      "ext": ""}, True),
    ({"pub_id": _PUB, "cas_id": "", "ext": "txt"}, False),
    ({"pub_id": _PUB, "cas_id": None, "ext": "txt"}, False),
    ({"pub_id": _PUB, "cas_id": 1234, "ext": "txt"}, False),
    ({"pub_id": "not-hex", "cas_id": "cc" * 8, "ext": "png"}, False),
    ({"pub_id": None, "cas_id": "dd" * 8, "ext": "png"}, False),
    ({"cas_id": "ee" * 8, "ext": "png"}, False),
], ids=["plain", "upper_hex_pub", "nfc_cas", "nfd_cas", "empty_cas",
        "none_cas", "int_cas", "bad_hex_pub", "none_pub", "no_pub"])
def test_link_prep_matches_direct_computation(res, linkable):
    from spacedrive_tpu.object.file_identifier.link import _prep_results

    lib = uuid.UUID("11111111-2222-3333-4444-555555555555")
    usable = _prep_results(lib, [res])
    if not linkable:
        assert usable == []
        return
    cas = res["cas_id"]
    want = uuid.uuid5(_OBJECT_NS, f"{lib}:{cas}").bytes
    assert usable == [(res, bytes.fromhex(res["pub_id"]), cas, want)]


def test_link_prep_keys_the_object_on_the_exact_cas_string():
    """No normalisation, of form or of case: two spellings are two
    contents."""
    from spacedrive_tpu.object.file_identifier.link import _prep_results

    lib = uuid.uuid4()
    spellings = [unicodedata.normalize("NFC", _CAFE),
                 unicodedata.normalize("NFD", _CAFE), "AB" * 8, "ab" * 8]
    got = _prep_results(lib, [{"pub_id": _PUB, "cas_id": c}
                              for c in spellings])
    assert [u[2] for u in got] == spellings
    assert len({u[3] for u in got}) == len(spellings)


# --- pHash gray decode ------------------------------------------------------


def _field(seed: int, w: int, h: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    grid = rng.integers(0, 256, (4, 6, 3), dtype=np.uint8)
    return np.asarray(Image.fromarray(grid).resize((w, h), Image.BICUBIC))


def _write(kind: str, path: str) -> None:
    rgb = _field(sum(map(ord, kind)), 300, 200)
    if kind == "jpeg":
        Image.fromarray(rgb).save(path, "JPEG", quality=90)
    elif kind == "png_rgba":
        alpha = np.linspace(0, 255, 300, dtype=np.uint8)[None, :]
        rgba = np.dstack([rgb, np.broadcast_to(alpha, (200, 300))])
        Image.fromarray(rgba, "RGBA").save(path, "PNG")
    elif kind == "png_gray":
        Image.fromarray(rgb).convert("L").save(path, "PNG")
    elif kind == "jpeg_gray":
        Image.fromarray(rgb).convert("L").save(path, "JPEG", quality=90)
    elif kind == "png_palette":
        Image.fromarray(rgb).convert("P").save(path, "PNG")
    elif kind == "jpeg_exif_turned":
        exif = Image.Exif()
        exif[0x0112] = 6  # rotate 90° on display
        Image.fromarray(rgb).save(path, "JPEG", quality=90, exif=exif)
    else:
        with open(path, "wb") as f:
            f.write(b"\x89PNG not really an image")


def _gray_reference(path: str, draft: bool) -> np.ndarray:
    """The pHash plane computed directly: JPEG at its smallest DCT scale
    over 32 × 32 (original only), RGB channels to luma, bilinear to
    32 × 32, in [0, 1]. The EXIF orientation tag is not applied."""
    with Image.open(path) as img:
        if draft and img.format == "JPEG":
            img.draft("RGB", (32, 32))
        rgb = np.asarray(img.convert("RGBA"))[..., :3]
    small = Image.fromarray(rgb).convert("L").resize((32, 32),
                                                     Image.BILINEAR)
    return np.asarray(small, np.float32) / 255.0


_GRAY_CASES = ["jpeg", "png_rgba", "png_gray", "jpeg_gray", "png_palette",
               "jpeg_exif_turned", "undecodable_with_thumb",
               "undecodable_no_thumb"]


def _gray_case(tmp_path, case: str):
    """→ (original path, thumbnail path or None, expected plane or None)."""
    kind = case.split("_with")[0].split("_no")[0]
    orig = str(tmp_path / f"orig.{'jpg' if 'jpeg' in kind else 'png'}")
    _write(kind, orig)
    thumb = None
    if case == "undecodable_with_thumb":
        thumb = str(tmp_path / "thumb.webp")
        Image.fromarray(_field(3, 96, 64)).save(thumb, "WEBP", quality=30)
    if kind != "undecodable":
        return orig, thumb, _gray_reference(orig, draft=True)
    if thumb is not None:
        return orig, thumb, _gray_reference(thumb, draft=False)
    return orig, thumb, None


@pytest.mark.parametrize("case", _GRAY_CASES)
def test_stage_phash_gray_matches_reference(tmp_path, case):
    from spacedrive_tpu.location.indexer.stages import _gray_plane

    orig, thumb, want = _gray_case(tmp_path, case)
    got = _gray_plane(orig, thumb)
    if want is None:
        assert got is None
        return
    assert got.dtype == np.float32 and got.shape == (32, 32)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("case", _GRAY_CASES)
def test_duplicates_decode_gray_matches_stage_leg(tmp_path, case):
    """The duplicates job's decode reads the same plane the mesh pHash
    stage reads, from a `file_path` row and the node's thumbnail store."""
    from spacedrive_tpu.location.indexer.stages import _gray_plane
    from spacedrive_tpu.object.duplicates import DuplicateDetectorJob

    orig, thumb, want = _gray_case(tmp_path, case)
    name, ext = os.path.basename(orig).split(".")
    cas = "0123456789abcdef"

    class _Db:
        def find_one(self, table, id):
            assert table == "location" and id == 1
            return {"id": 1, "path": str(tmp_path)}

    class _Store:
        def path_for(self, library_id, cas_id):
            assert cas_id == cas
            return thumb or str(tmp_path / "absent.webp")

    node = types.SimpleNamespace(
        thumbnailer=types.SimpleNamespace(store=_Store()))
    ctx = types.SimpleNamespace(library=types.SimpleNamespace(
        db=_Db(), id="lib", node=node))
    row = {"location_id": 1, "materialized_path": "/", "name": name,
           "extension": ext, "is_dir": 0, "cas_id": cas}
    got = DuplicateDetectorJob({})._decode_gray(ctx, row)
    if want is None:
        assert got is None and _gray_plane(orig, thumb) is None
        return
    assert np.array_equal(got, want)
    assert np.array_equal(got, _gray_plane(orig, thumb))


# --- the mesh shard's host hash -----------------------------------------------


def _cas_reference(content: bytes) -> str:
    """cas_id computed directly from the upstream sampling layout
    (core/src/object/cas.rs): size as u64 LE, then the whole file up to
    100 KiB, else an 8 KiB header, four 10 KiB samples spaced evenly
    after it and an 8 KiB footer; BLAKE3, first 16 hex digits."""
    size = len(content)
    msg = struct.pack("<Q", size)
    if size <= 100 * 1024:
        msg += content
    else:
        jump = (size - 2 * 8192) // 4
        msg += content[:8192]
        for k in range(4):
            at = 8192 + k * jump
            msg += content[at:at + 10240]
        msg += content[size - 8192:]
    return blake3_ref.blake3_hex(msg)[:16]


@pytest.mark.parametrize("size", [0, 1, 100 * 1024 - 1, 100 * 1024,
                                  25 * 1000 * 1000],
                         ids=["empty", "one_byte", "threshold_minus_1",
                              "threshold", "25MB"])
async def test_mesh_shard_hash_matches_blake3_reference(tmp_path, size):
    from spacedrive_tpu.jobs.manager import JobBuilder
    from spacedrive_tpu.location.indexer.job import IndexerJob
    from spacedrive_tpu.location.indexer.mesh import (
        _execute_shard_sync,
        build_shard_entries,
    )
    from spacedrive_tpu.location.locations import LocationCreateArgs
    from spacedrive_tpu.node import Node

    corpus = tmp_path / "corpus"
    corpus.mkdir()
    content = np.random.default_rng(size % 997).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    (corpus / "f.bin").write_bytes(content)
    node = Node(str(tmp_path / "node"), use_device=False, with_labeler=False)
    node.config.config.p2p.enabled = False
    await node.start()
    try:
        lib = await node.create_library("legs")
        loc = LocationCreateArgs(path=str(corpus)).create(lib)
        await JobBuilder(IndexerJob({"location_id": loc["id"]})).spawn(
            node.jobs, lib)
        await node.jobs.wait_idle()
        entries = build_shard_entries(lib, loc)
        assert [e["name"] for e in entries] == ["f"]
        (result,) = _execute_shard_sync(lib, loc, entries, "cpu")
        # an empty file carries the vouched-empty sentinel, not a hash
        assert result["cas_id"] == ("" if size == 0
                                    else _cas_reference(content))
        row = lib.db.find_one("file_path", name="f")
        assert row["cas_id"] == (result["cas_id"] or None) or size == 0
    finally:
        await node.shutdown()


# --- the thumbnailer's software path ----------------------------------------


@pytest.mark.parametrize("kind", ["jpeg", "png_rgba", "undecodable"])
async def test_software_thumbnailer_stores_cpu_bytes_and_counts_errors(
        tmp_path, kind):
    from spacedrive_tpu.object.media.thumbnail import Thumbnailer, process

    ext = "jpg" if kind == "jpeg" else "png"
    path = str(tmp_path / f"img.{ext}")
    _write(kind, path)
    th = Thumbnailer(tmp_path / "data", use_device=False)
    try:
        bid = th.new_indexed_thumbnails_batch(
            "libS", [("5eed000000000001", path, ext)])
        await th.wait_batch(bid)
        stored = th.store.path_for("libS", "5eed000000000001")
        if kind == "undecodable":
            assert (th.errors, th.generated) == (1, 0)
            assert not os.path.exists(stored)
            with pytest.raises((process.ThumbError, OSError)):
                process.generate_one_cpu(path, ext)
            return
        assert (th.errors, th.generated) == (0, 1)
        with open(stored, "rb") as f:
            assert f.read() == process.generate_one_cpu(path, ext)
    finally:
        await th.shutdown()
