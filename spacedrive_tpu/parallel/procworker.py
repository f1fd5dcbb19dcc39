"""Slim worker-process runtime for the multi-process execution plane.

One of these runs inside every ``parallel/procpool.py`` worker process,
launched with a ``python -c`` one-liner calling :func:`main` (a fresh
interpreter — no forked locks, no inherited JAX runtime, no re-imported
``__main__``). Requests and responses are length-prefixed msgpack
frames over the worker's own stdio pipe; the worker re-points fd 1 at
stderr immediately so a stray ``print`` anywhere below can never
corrupt the framing. The contract that keeps the plane safe:

- **import-light**: no Node, no event loop, no jax. The module-level
  imports here are stdlib; each stage lazily imports exactly the
  CPU-side modules it needs (``ops/cas.py`` is importable without jax
  for exactly this reason). ``JAX_PLATFORMS`` is pinned to ``cpu`` in
  the worker env as a belt-and-braces guard — a worker must never
  contend for the owner's accelerator;
- **shared-nothing**: stage payloads arrive as msgpack blobs (plain
  dicts/lists/str/bytes/ints — sdlint SD022 enforces the same purity
  at the submit call sites) and results leave the same way. No DB
  connection, no sockets, no library objects ever cross the boundary;
  SQLite commits stay on the owning process;
- **single-writer telemetry**: workers feed their OWN registry (the
  same families — both sides import ``telemetry.metrics``) and ship an
  additive delta blob with each result; the owner merges it
  (``registry.merge_delta``), so metrics, spans, and rings keep
  exactly one writer per process. A batch that dies with its worker
  never shipped its delta, so a retried batch counts exactly once.

Stages mirror the in-process implementations bit-for-bit (same
functions where possible), so ``SD_PROCS=0`` vs pool output is
identical — the golden contract tests/test_procpool.py holds.

Wire frames (owner → worker): ``[job_id, stage, payload_blob,
stall_s]``; (worker → owner): ``[job_id, ok, body_blob, delta_blob]``.
"""

from __future__ import annotations

import os
import struct
import sys
import time
from typing import Any

#: frame header: little-endian u32 byte length
_HDR = struct.Struct("<I")
#: a single frame is bounded — a runaway payload fails loudly instead
#: of OOMing the worker (64 MiB covers any sane batch quantum)
MAX_FRAME = 64 << 20


def read_frame(fp: Any) -> bytes | None:
    """One length-prefixed frame; None on clean EOF."""
    hdr = fp.read(_HDR.size)
    if not hdr:
        return None
    if len(hdr) < _HDR.size:
        raise EOFError("torn frame header")
    (n,) = _HDR.unpack(hdr)
    if n > MAX_FRAME:
        raise ValueError(f"frame of {n} bytes exceeds {MAX_FRAME}")
    body = fp.read(n)
    if len(body) < n:
        raise EOFError("torn frame body")
    return body


def write_frame(fp: Any, blob: bytes) -> None:
    fp.write(_HDR.pack(len(blob)))
    fp.write(blob)
    fp.flush()


# --- stages ----------------------------------------------------------------
#
# Every stage is a pure function payload(dict) -> result(dict), both
# msgpack-plain. Heavy imports happen inside the stage (first call per
# worker pays them once; the pool is persistent).


def _stage_echo(payload: dict) -> dict:
    """Round-trip probe (tests + pool warmup)."""
    return payload


def _stage_hash_entries(payload: dict) -> dict:
    """The shard plane's CPU half: stat → sampled read → chunk-cache
    digests → host BLAKE3 cas_ids for journal-keyed entries
    (location/indexer/mesh.py:_execute_shard_sync's read/hash leg).

    payload: {"loc_path": str, "entries": [{"pub_id", "mat", "name",
    "ext"}, ...]}
    result:  {"results": [{"pub_id", "cas_id" | None, "identity" |
    None, "chunks" | None, "error" | None}, ...]}
    """
    from ..files.isolated_path import full_path_from_db_row
    from ..location.indexer.journal import stat_identity
    from ..ops import cas
    from ..telemetry import metrics as _tm

    loc_path = payload["loc_path"]
    out: list[dict] = []
    messages: list[bytes] = []
    msg_idx: list[int] = []
    for e in payload["entries"]:
        row = {"materialized_path": e["mat"], "name": e["name"],
               "extension": e["ext"], "is_dir": False}
        full = full_path_from_db_row(loc_path, row)
        ident = stat_identity(full)
        rec: dict[str, Any] = {
            "pub_id": e["pub_id"],
            "identity": (
                [ident.inode, ident.dev, ident.mtime_ns, ident.size]
                if ident is not None else None
            ),
            "cas_id": None, "chunks": None, "error": None,
        }
        out.append(rec)
        if ident is None:
            continue  # vanished/unreadable: the next walk removes it
        if ident.size == 0:
            rec["cas_id"] = ""  # vouched-empty sentinel
            continue
        try:
            msg = cas.read_message(full, ident.size)
        except OSError:
            rec["identity"] = None  # no vouch for an unreadable file
            rec["error"] = "unreadable"
            continue
        rec["chunks"] = cas.build_chunk_cache(msg).to_payload()
        messages.append(msg)
        msg_idx.append(len(out) - 1)
    if messages:
        for i, cas_hex in zip(msg_idx, cas.cas_ids(messages, "cpu")):
            out[i]["cas_id"] = cas_hex
        # bytes merge additively across workers; the hash-stage WALL is
        # observed once by the owner (mesh._pool_hash) — concurrent
        # workers' per-batch times would sum to CPU-seconds and skew
        # autotune.observed_files_per_s low on pool-accelerated nodes
        _tm.INDEX_BYTES_HASHED.inc(sum(len(m) for m in messages))
    return {"results": out}


def _stage_journal_match(payload: dict) -> dict:
    """consult_many's CPU half: payload decode + strict validation +
    identity compare per pre-fetched journal row (the SQL stays on the
    owner). Mirrors IndexJournal verdict semantics exactly; the owner
    does all verdict counting.

    payload: {"items": [[[mat, name, ext], identity-or-None], ...],
              "rows": [row-dict-or-None aligned with items]}
    result:  {"verdicts": [[verdict, entry-or-None, corrupt], ...]}
    """
    from ..location.indexer import journal as _journal

    verdicts: list[list] = []
    for (key, ident_raw), row in zip(payload["items"], payload["rows"]):
        ident = (
            _journal.Identity(*(int(x) for x in ident_raw))
            if ident_raw is not None else None
        )
        verdict, entry = _journal.judge_row(row, ident)
        if entry is None:
            # no row (miss), or a corrupt one: the owner drops it (the
            # DB write stays there)
            verdicts.append([verdict, None, row is not None])
            continue
        plain = {
            "identity": (
                [entry.identity.inode, entry.identity.dev,
                 entry.identity.mtime_ns, entry.identity.size]
                if entry.identity is not None else None
            ),
            "stale": entry.stale,
            "cas_id": entry.cas_id,
            "thumb": entry.thumb,
            "media": entry.media_digest,
            "phash": entry.phash,
            "embed": entry.embed,
            # already strictly validated by entry_of_row — the owner
            # reconstructs without re-validating
            "chunks": entry.chunks.to_payload()
            if entry.chunks is not None else None,
        }
        verdicts.append([verdict, plain, False])
    return {"verdicts": verdicts}


def _stage_link_prep(payload: dict) -> dict:
    """apply_cas_results' pure prep: per-result pub_id validation and
    the deterministic (library, cas) object pub_id (uuid5). Row reads
    and the sync-write commit stay on the owning process.

    payload: {"library_id": str, "results": [{"pub_id", "cas_id",
    "ext"}, ...]}
    result:  {"usable": [[idx, fp_pub, cas, obj_pub], ...]}
    """
    from ..object.file_identifier.link import object_pub_for

    lib_id = payload["library_id"]
    usable: list[list] = []
    for i, res in enumerate(payload["results"]):
        cas = res.get("cas_id")
        if not cas or not isinstance(cas, str):
            continue  # empty/unreadable files carry no cas to link
        try:
            fp_pub = bytes.fromhex(str(res["pub_id"]))
        except (KeyError, ValueError):
            continue
        usable.append([i, fp_pub, cas, object_pub_for(lib_id, cas)])
    return {"usable": usable}


def _stage_thumb_cpu(payload: dict) -> dict:
    """The thumbnail software pipeline for one image: decode → CPU
    resize → orientation/overlay → webp encode, bit-identical to the
    actor's host fallback path (process.generate_one_cpu).

    A deterministic image failure (undecodable/oversized/vanished)
    returns ``{"webp": None, "error": ...}`` rather than raising: the
    actor then counts the error directly instead of paying a second
    full inline decode that is guaranteed to fail the same way — only
    pool-infrastructure failures surface as job errors.

    payload: {"path": str, "ext": str}
    result:  {"webp": bytes | None, "error": str | None}
    """
    from ..object.media.thumbnail.process import ThumbError, generate_one_cpu
    from ..telemetry import metrics as _tm

    t0 = time.perf_counter()
    try:
        webp = generate_one_cpu(payload["path"], payload["ext"])
    except (ThumbError, OSError) as exc:
        return {"webp": None, "error": f"{type(exc).__name__}: {exc}"}
    _tm.THUMB_STAGE_SECONDS.observe(
        time.perf_counter() - t0, stage="encode")
    return {"webp": webp, "error": None}


def _stage_phash_gray(payload: dict) -> dict:
    """The duplicate detector's decode leg: original-first JPEG draft
    decode (thumbnail fallback) to the 32×32 grayscale pHash plane
    (object/duplicates.py:_decode_gray, minus the DB lookups).

    payload: {"path": str | None, "thumb_path": str | None}
    result:  {"gray": bytes | None}  (float32 DCT_SIZE² plane)
    """
    import numpy as np

    from ..ops import phash_jax

    def _decode(path: str, draft: bool):
        from PIL import Image

        with Image.open(path) as img:
            if draft and img.format == "JPEG":
                img.draft("RGB", (phash_jax.DCT_SIZE, phash_jax.DCT_SIZE))
            return phash_jax.to_gray32(np.asarray(img.convert("RGBA")))

    for path, draft in ((payload.get("path"), True),
                        (payload.get("thumb_path"), False)):
        if not path or not os.path.exists(path):
            continue
        try:
            return {"gray": _decode(path, draft).astype(np.float32).tobytes()}
        except Exception:  # noqa: BLE001 - undecodable → next source
            continue
    return {"gray": None}


def _stage_embed_decode(payload: dict) -> dict:
    """The embedding stage's decode leg: image file → the embedder's
    fixed input plane (models/embedder.decode_image — the EXACT code
    path the inline fallback runs, DCT scale chosen from the file's
    format and size alone, so pooled and single-process decodes are
    bit-identical). Undecodable files return None slots; the owner
    skips them without paying a second guaranteed-to-fail decode.

    payload: {"paths": [str, ...]}
    result:  {"planes": [bytes | None, ...]}  (f32 S·S·3 planes)
    """
    from ..models.embedder import decode_image

    planes: list[bytes | None] = []
    for path in payload["paths"]:
        img = decode_image(path)
        planes.append(None if img is None else img.tobytes())
    return {"planes": planes}


STAGES = {
    "echo": _stage_echo,
    "identify.hash_entries": _stage_hash_entries,
    "journal.match": _stage_journal_match,
    "link.prep": _stage_link_prep,
    "thumb.cpu": _stage_thumb_cpu,
    "phash.gray": _stage_phash_gray,
    "embed.decode": _stage_embed_decode,
}


# --- the worker main loop --------------------------------------------------


def main() -> None:
    """Serve stage requests over stdio until EOF (the owner closing our
    stdin is the clean shutdown signal)."""
    # claim the framing pipe privately, then point fd 1 at stderr so
    # library prints can never interleave with frames
    out = os.fdopen(os.dup(1), "wb", buffering=0)
    os.dup2(2, 1)
    inp = os.fdopen(os.dup(0), "rb", buffering=0)
    # guards, not configuration: a worker must never grab an
    # accelerator or re-arm the owner's fault plan in its own process
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("SD_FAULTS", None)

    import msgpack

    from ..telemetry import metrics as _tm  # mint families for deltas
    from ..telemetry.registry import REGISTRY

    del _tm
    while True:
        frame = read_frame(inp)
        if frame is None:
            return
        job_id, stage, blob, stall_s = msgpack.unpackb(frame, raw=False)
        before = REGISTRY.delta_capture()
        try:
            if stall_s:
                # armed by the owner when the procpool.worker `stall`
                # fault fires — the batch is delayed inside the worker
                time.sleep(stall_s)
            fn = STAGES.get(stage)
            if fn is None:
                raise KeyError(f"unknown procpool stage {stage!r}")
            payload = msgpack.unpackb(blob, raw=False)
            body = msgpack.packb(fn(payload), use_bin_type=True)
            ok = True
        except BaseException as exc:  # noqa: BLE001 - errors are data
            body = msgpack.packb(
                {"error": f"{type(exc).__name__}: {exc}"},
                use_bin_type=True,
            )
            ok = False
        delta = REGISTRY.delta_diff(before, REGISTRY.delta_capture())
        write_frame(out, msgpack.packb(
            [job_id, ok, body, msgpack.packb(delta, use_bin_type=True)],
            use_bin_type=True,
        ))


if __name__ == "__main__":
    main()
