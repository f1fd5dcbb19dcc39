"""Batched BLAKE3 on TPU via JAX/XLA.

Bit-exact with `blake3_ref` (golden-tested). Design, TPU-first:

- A batch of B messages, each padded to ``C * 1024`` bytes, hashes as
  ``N = B*C`` *independent* chunk lanes (BLAKE3 chunks chain from the IV
  with only a chunk counter, so every chunk of every file is parallel).
  One ``lax.scan`` of 16 steps walks the 64-byte blocks of all chunks at
  once; each step is one vectorized compression over ``[N]`` lanes —
  pure 32-bit VPU arithmetic, no data-dependent control flow.
- The chunk→root tree reduction runs level-by-level: level ``d`` pairs
  adjacent CVs with ONE batched parent compression over ``[B, C/2^d]``
  lanes. Odd leftovers per file are the binary digits of the chunk
  count; they are gathered per level and merged up the right spine at
  the end (masked, with per-file ROOT-flag selection). Total graph size
  stays ~O(log C) compressions, so XLA compiles fast for any bucket.
- Ragged lengths are handled with per-lane masks (block_len / flags /
  active selects); fixed ``C`` per compiled bucket keeps shapes static.

The reference hashes at most 56 KiB + 8 bytes per file for content
addressing (ref:core/src/object/cas.rs:10-21), i.e. C=57 is the hot
bucket; whole small files (≤100 KiB ⇒ C≤101) and full-file validation
(ref:core/src/object/validation/hash.rs) use larger buckets.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import blake3_pallas
from .blake3_ref import CHUNK_END, CHUNK_START, IV, MSG_PERMUTATION, PARENT, ROOT

_U = jnp.uint32

CHUNK_LEN = 1024
BLOCK_LEN = 64


def _rotr(x: jax.Array, r: int) -> jax.Array:
    return (x >> _U(r)) | (x << _U(32 - r))


def _g(v: list[jax.Array], a: int, b: int, c: int, d: int, mx: jax.Array, my: jax.Array) -> None:
    v[a] = v[a] + v[b] + mx
    v[d] = _rotr(v[d] ^ v[a], 16)
    v[c] = v[c] + v[d]
    v[b] = _rotr(v[b] ^ v[c], 12)
    v[a] = v[a] + v[b] + my
    v[d] = _rotr(v[d] ^ v[a], 8)
    v[c] = v[c] + v[d]
    v[b] = _rotr(v[b] ^ v[c], 7)


import numpy as _np

_PERM = _np.array(MSG_PERMUTATION, _np.int32)  # host constant, safe under tracing


def _compress8(
    h: list[jax.Array],
    m: list[jax.Array],
    t_lo: jax.Array,
    block_len: jax.Array,
    flags: jax.Array,
) -> list[jax.Array]:
    """Vectorized compression; returns the 8 chaining-value words.

    Every argument is a (list of) uint32 array(s) with a common batch
    shape; 64-bit counters are split, t_hi pinned to 0 (4 TiB cap).
    The 7 rounds run as a `lax.scan` with the message schedule permuted
    by one gather per round — identical math to unrolling, but ~35×
    fewer HLO ops, which keeps XLA compile time sane for every bucket.
    """
    zeros = jnp.zeros_like(h[0])
    v = tuple(h) + (
        _U(IV[0]) + zeros, _U(IV[1]) + zeros, _U(IV[2]) + zeros, _U(IV[3]) + zeros,
        t_lo + zeros, zeros, block_len + zeros, flags + zeros,
    )
    m_arr = jnp.stack(m, axis=0)  # [16, ...]

    def round_body(carry, _):
        v, m = carry
        v = list(v)
        _g(v, 0, 4, 8, 12, m[0], m[1])
        _g(v, 1, 5, 9, 13, m[2], m[3])
        _g(v, 2, 6, 10, 14, m[4], m[5])
        _g(v, 3, 7, 11, 15, m[6], m[7])
        _g(v, 0, 5, 10, 15, m[8], m[9])
        _g(v, 1, 6, 11, 12, m[10], m[11])
        _g(v, 2, 7, 8, 13, m[12], m[13])
        _g(v, 3, 4, 9, 14, m[14], m[15])
        return (tuple(v), m[_PERM]), None

    (v, _), _ = jax.lax.scan(round_body, (v, m_arr), None, length=7)
    return [v[i] ^ v[i + 8] for i in range(8)]


def _parent_cvs(left: jax.Array, right: jax.Array, flags: jax.Array) -> jax.Array:
    """Batched parent-node compression. left/right: [..., 8] uint32."""
    h = [_U(IV[i]) + jnp.zeros_like(flags) for i in range(8)]
    m = [left[..., i] for i in range(8)] + [right[..., i] for i in range(8)]
    out = _compress8(h, m, jnp.zeros_like(flags), _U(BLOCK_LEN) + jnp.zeros_like(flags), flags)
    return jnp.stack(out, axis=-1)


def _as_words(msgs: jax.Array, max_chunks: int) -> jax.Array:
    """uint32[B, C*256] message words (natural LE order) from either a
    uint8[B, C*1024] byte array (device bitcast — the words ARE the
    little-endian byte stream) or an already-viewed uint32 array (the
    host path: `np.view(np.uint32)` is a zero-copy reinterpret, so
    numpy callers skip the device pass entirely)."""
    if msgs.dtype == jnp.uint32:
        return msgs
    b_dim = msgs.shape[0]
    return jax.lax.bitcast_convert_type(
        msgs.reshape(b_dim, max_chunks, 16, 16, 4), _U
    ).reshape(b_dim, max_chunks * 256)


def _chunk_cvs(
    words: jax.Array, lengths: jax.Array, max_chunks: int, mode: str | None
) -> tuple[jax.Array, jax.Array]:
    """All chunk chaining values.

    words: uint32[B, max_chunks*256] natural-order LE message words
    (see `_as_words`); lengths: int32[B]; mode: the chunk-stage backend
    (`blake3_pallas.pallas_mode()`: "tpu", "interpret", or None = XLA).
    Returns (cvs: uint32[B, C, 8], n_chunks: int32[B]). Single-chunk
    files get their ROOT flag here.
    """
    b_dim, wpad = words.shape
    c_dim = max_chunks
    assert wpad == c_dim * 256

    lengths = lengths.astype(jnp.int32)
    n_chunks = jnp.maximum(1, (lengths + CHUNK_LEN - 1) // CHUNK_LEN)  # [B]

    n = b_dim * c_dim
    chunk_idx = jnp.repeat(jnp.arange(c_dim, dtype=jnp.int32)[None, :], b_dim, axis=0).reshape(n)
    len_n = jnp.repeat(lengths[:, None], c_dim, axis=1).reshape(n)
    nch_n = jnp.repeat(n_chunks[:, None], c_dim, axis=1).reshape(n)

    chunk_len = jnp.clip(len_n - chunk_idx * CHUNK_LEN, 0, CHUNK_LEN)  # [N]
    is_root_chunk = nch_n == 1  # single-chunk messages root at the chunk level
    t_lo = chunk_idx.astype(_U)

    if mode is not None:
        # Pallas kernel for the hot stage (ops/blake3_pallas.py): it
        # reads the natural [N, 256] layout (contiguous HBM — the
        # word-major transpose happens per-tile in VMEM) and derives
        # block_len/flags/active from the compact per-lane vectors, so
        # beyond the message words only [N]-sized arrays cross HBM
        h_fin8 = blake3_pallas.chunk_cvs(
            words.reshape(n, 256),
            chunk_len.astype(_U)[None, :],
            is_root_chunk.astype(_U)[None, :],
            t_lo[None, :],
            interpret=(mode == "interpret"),
        )  # [8, N]
        cvs = h_fin8.T.reshape(b_dim, c_dim, 8)
        return cvs, n_chunks

    # XLA body: word-major [blk, word, N] layout so each scan step
    # reads 16 contiguous [N] rows
    wm = words.reshape(b_dim, c_dim, 16, 16).transpose(2, 3, 0, 1).reshape(16, 16, n)

    n_blocks = jnp.maximum(1, (chunk_len + BLOCK_LEN - 1) // BLOCK_LEN)
    blk = jnp.arange(16, dtype=jnp.int32)[:, None]  # [16, 1]
    block_len = jnp.clip(chunk_len[None, :] - blk * BLOCK_LEN, 0, BLOCK_LEN)  # [16, N]
    active = blk < n_blocks[None, :]
    is_first = blk == 0
    is_last = blk == (n_blocks[None, :] - 1)
    flags = (
        jnp.where(is_first, _U(CHUNK_START), _U(0))
        | jnp.where(is_last, _U(CHUNK_END), _U(0))
        | jnp.where(is_last & is_root_chunk[None, :], _U(ROOT), _U(0))
    )

    h0 = [_U(IV[i]) + jnp.zeros((n,), _U) for i in range(8)]

    def step(h, xs):
        m_words, bl, fl, act = xs
        m = [m_words[k] for k in range(16)]
        out = _compress8(h, m, t_lo, bl.astype(_U), fl)
        h_new = [jnp.where(act, out[i], h[i]) for i in range(8)]
        return h_new, None

    h_fin, _ = jax.lax.scan(step, h0, (wm, block_len.astype(_U), flags, active))
    cvs = jnp.stack(h_fin, axis=-1).reshape(b_dim, c_dim, 8)
    return cvs, n_chunks


def _tree_reduce(cvs: jax.Array, n_chunks: jax.Array) -> jax.Array:
    """Reduce [B, C, 8] chunk CVs to [B, 8] root words.

    Level d pairs adjacent nodes; a file's leftover at level d exists
    iff bit d of its chunk count is set (binary-counter identity with
    the spec's incremental stack). The right spine then merges saved
    nodes lowest-level-first; the highest merge carries ROOT.
    """
    b_dim, c_dim, _ = cvs.shape
    if c_dim == 1:
        return cvs[:, 0, :]

    n_d = n_chunks  # nodes remaining at the current level, per file
    saved = []  # (bit_set[B], cv[B, 8]) per level, lowest first
    cur = cvs
    d = 0
    while cur.shape[1] > 1:
        width = cur.shape[1]
        bit = (n_d & 1) == 1
        idx = jnp.clip(n_d - 1, 0, width - 1)
        leftover = jnp.take_along_axis(cur, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0, :]
        saved.append((bit, leftover))

        pairs = width // 2
        left = cur[:, 0:2 * pairs:2, :]
        right = cur[:, 1:2 * pairs + 1:2, :]
        # The j==0 pair is the file's root iff exactly 2 nodes remain
        # here and no leftovers were saved below (n == 2 << d).
        is_root_pair = n_chunks == (2 << d)
        cols = jnp.arange(pairs, dtype=jnp.int32)
        flags = jnp.where(
            (cols[None, :] == 0) & is_root_pair[:, None], _U(PARENT | ROOT), _U(PARENT)
        )
        cur = _parent_cvs(left, right, flags)
        n_d = n_d >> 1
        d += 1
    # Top level: a single node remains.
    saved.append(((n_d & 1) == 1, cur[:, 0, :]))

    out = jnp.zeros((b_dim, 8), _U)
    started = jnp.zeros((b_dim,), bool)
    for d, (bit, cv) in enumerate(saved):
        # ROOT iff no higher bits remain above level d.
        is_top = (n_chunks >> (d + 1)) == 0
        flags = jnp.where(is_top, _U(PARENT | ROOT), _U(PARENT))
        merged = _parent_cvs(cv, out, flags)
        out = jnp.where(
            (bit & ~started)[:, None], cv,
            jnp.where((bit & started)[:, None], merged, out),
        )
        started = started | bit
    return out


def _traced_hash_body(mode: str | None, msgs, lengths, max_chunks: int):
    """Chunk stage + tree reduce — the ONE hash body both the
    single-device and the shard_map per-device programs trace. (A
    second copy here is how the two paths would silently stop being
    bit-identical.)"""
    cvs, n_chunks = _chunk_cvs(
        _as_words(msgs, max_chunks), lengths, max_chunks, mode
    )
    return _tree_reduce(cvs, n_chunks)


def _make_mode_impl(mode: str | None):
    @functools.partial(jax.jit, static_argnames=("max_chunks",))
    def impl(msgs, lengths, max_chunks):
        return _traced_hash_body(mode, msgs, lengths, max_chunks)

    return impl


# one jitted program family per chunk-stage backend
_hash_batch_impl_modes = {
    mode: _make_mode_impl(mode) for mode in (None, "tpu", "interpret")
}


# --- multi-device dp dispatch ----------------------------------------------
#
# One dispatch feeds every chip: the batch dim is split over a flat
# `dp` mesh, each device runs the SAME chunk-stage (Pallas on TPU, XLA
# elsewhere) + tree reduce on its local rows under `shard_map` — the
# hash of a row never needs another row, so there are no collectives
# and per-device math is bit-identical to the single-device path.
# Compiled programs cache per (pallas mode, device set); shapes stay on
# the per-device warm ladder because cas.pack_canonical_batch pads the
# global batch to ladder-rung × device-count.

_sharded_impls: dict[tuple, Any] = {}


def _dp_mesh(devices):
    import numpy as np

    from jax.sharding import Mesh

    return Mesh(np.array(list(devices)), ("dp",))


def _sharded_impl(mode: str | None, devices):
    key = (mode, tuple(d.id for d in devices))
    impl = _sharded_impls.get(key)
    if impl is None:
        from jax.sharding import PartitionSpec as P

        mesh = _dp_mesh(devices)
        # donation frees the (large) message buffer for reuse the
        # moment the transfer is consumed; CPU backends don't implement
        # it and would only warn
        donate = (0,) if devices[0].platform != "cpu" else ()

        @functools.partial(
            jax.jit, static_argnames=("max_chunks",), donate_argnums=donate
        )
        def impl(msgs, lengths, max_chunks):
            def body(m, l):
                return _traced_hash_body(mode, m, l, max_chunks)

            # rows never meet (no collective), so the varying-axes
            # check buys nothing — and it rejects both bodies: the XLA
            # scan's carry starts from unvarying constants, and the
            # Pallas out_shape carries no vma
            return jax.shard_map(
                body, mesh=mesh, in_specs=(P("dp"), P("dp")),
                out_specs=P("dp"), check_vma=False,
            )(msgs, lengths)

        _sharded_impls[key] = impl
    return impl


def shard_put(arr, devices):
    """Place a batch on the flat `dp` mesh over `devices` (dim 0
    split, trailing dims replicated). A no-op when the array already
    has that sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(arr, NamedSharding(_dp_mesh(devices), P("dp")))


def _hash_batch_sharded(msgs, lengths, max_chunks: int, devices) -> jax.Array:
    from ..telemetry import metrics as _tm

    _tm.SHARD_BATCH_ROWS.observe(msgs.shape[0] // len(devices), op="blake3")
    return _sharded_impl(blake3_pallas.pallas_mode(), devices)(
        shard_put(msgs, devices), shard_put(lengths, devices),
        max_chunks=max_chunks,
    )


def hash_batch(msgs, lengths, max_chunks: int | None = None,
               devices=None) -> jax.Array:
    """Hash B messages. msgs: uint8[B, C*1024] (zero-padded) or its
    uint32[B, C*256] LE-word view; lengths: int32[B] actual byte
    counts. Returns uint32[B, 8] — the first 32 digest bytes as LE
    words (all the framework ever needs: cas_id is 8 bytes, validator
    checksum 32). Numpy byte arrays are reinterpreted as uint32 on the
    HOST (a zero-copy view — same transfer bytes, and the device skips
    the byte-pack pass entirely). The chunk stage runs as a Pallas
    kernel on real TPUs (ops/blake3_pallas.py), XLA otherwise
    (`blake3_pallas.pallas_mode`); a kernel that fails to compile or
    run is the caller's error — no second implementation stands in.

    `devices`: ≥2 devices shard the batch dim over a flat `dp` mesh
    (one dispatch feeds every chip; B must divide evenly — callers pad
    through cas.pack_canonical_batch). None/1 device keeps the classic
    single-device dispatch byte-for-byte."""
    import numpy as np

    from ..utils import faults as _faults

    spec = _faults.hit("device.blake3")
    if spec is not None:
        if spec.mode == "raise":
            raise _faults.InjectedFault("injected device failure (blake3)")
        if spec.mode == "xla":
            raise _faults.device_error("device.blake3")
        # "wrong_shape" falls through and truncates the result below —
        # exercising the caller-side digest-shape validation (cas)
    if not hasattr(msgs, "dtype"):  # lists / bytes-likes
        msgs = np.asarray(msgs, np.uint8)
    if isinstance(msgs, np.ndarray) and msgs.dtype == np.uint8:
        msgs = np.ascontiguousarray(msgs).view(np.uint32)
    if msgs.dtype not in (jnp.uint8, jnp.uint32):
        msgs = jnp.asarray(msgs, jnp.uint8)
    if max_chunks is None:
        words_per_chunk = 256 if msgs.dtype == jnp.uint32 else CHUNK_LEN
        max_chunks = msgs.shape[1] // words_per_chunk
    lengths = jnp.asarray(lengths, jnp.int32)
    if devices is not None and len(devices) > 1:
        devices = list(devices)
        if msgs.shape[0] % len(devices):
            raise ValueError(
                f"batch of {msgs.shape[0]} rows does not divide over "
                f"{len(devices)} devices — pad through pack_canonical_batch"
            )
        out = _hash_batch_sharded(msgs, lengths, max_chunks, devices)
    else:
        if devices is not None and len(devices) == 1:
            # pin the single-device dispatch to THIS device (the
            # ladder's surviving chip) — committed inputs make jit
            # execute there, instead of on a default device that may
            # be the dead one
            msgs = jax.device_put(msgs, devices[0])
            lengths = jax.device_put(lengths, devices[0])
        out = _hash_batch_impl_modes[blake3_pallas.pallas_mode()](
            msgs, lengths, max_chunks=max_chunks
        )
    if spec is not None and spec.mode == "wrong_shape":
        out = out[:, :4]
    return out


def words_to_digests(words, out_len: int = 32) -> list[bytes]:
    """Host-side: [B, 8] uint32 LE words -> digest bytes."""
    import numpy as np

    arr = np.asarray(words).astype("<u4")
    raw = arr.tobytes()
    stride = 32
    return [raw[i * stride:i * stride + out_len] for i in range(arr.shape[0])]


def words_to_hex(words, hex_chars: int = 64) -> list[str]:
    nbytes = (hex_chars + 1) // 2
    return [d.hex()[:hex_chars] for d in words_to_digests(words, nbytes)]
