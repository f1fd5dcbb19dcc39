"""BLAKE3 chunk compression as a Pallas TPU kernel.

The chunk stage is ~94% of the hash FLOPs (16 blocks × 7 rounds of the
compression permutation per 1 KiB chunk; the tree merge above it is
O(log C)). This kernel runs that stage as one Pallas program over lane
tiles, reading the message words in their NATURAL layout `[N, 256]`
(chunk-major — exactly the bytes as they sit in HBM after a free host
uint32 view) and transposing each `[L, 256]` tile to `[256, L]` inside
VMEM so the VPU's 8×128 registers vectorize across chunk lanes. Message
schedules are host-precomputed (perm^r applied to static indices — no
in-kernel gathers).

An earlier design fed the kernel `[16, 16, N]` word-major data, which
forced XLA to materialize an HBM transpose + byte-pack of the whole
batch around the kernel. The transpose now happens INSIDE the kernel
(VMEM, per-tile) and the byte→word bitcast on the HOST (numpy view —
zero copy). Device timings for either design: not measured on the
current rig (PERF.md).

On real TPUs BOTH loops — the 16-block walk and the 7 rounds — are
fully unrolled: a `fori_loop` carrying the `[8, L]` state costs a
Mosaic layout round-trip per block.
Interpret mode (tests) keeps the block walk ROLLED instead — the
unrolled body is a ~5k-op graph whose CPU compile takes minutes
(see _build_kernel).

Bit-exactness contract is identical to ops/blake3_jax.py (golden-tested
against the reference vectors); `ops/blake3_jax.hash_batch` calls this
kernel when the backend is a real TPU (`SD_BLAKE3_PALLAS=0` chooses the
XLA body instead, `=1` forces interpret mode elsewhere). A kernel that
fails to compile or run raises to the caller — nothing stands in for
it. Guide: /opt/skills/guides/pallas_guide.md.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from .blake3_ref import BLOCK_LEN, CHUNK_END, CHUNK_START, IV, MSG_PERMUTATION, ROOT

LANES = 2048  # big-batch lane tile: [2048, 256] words ≈ 2 MiB VMEM (scoped limit 16 MiB)
LANES_SMALL = 512  # small batches / interpret mode: avoid the pad-to-tile floor
_ROUNDS = 7


@functools.lru_cache(maxsize=1)
def _schedules() -> tuple[tuple[int, ...], ...]:
    """schedule[r][k] = original word index feeding slot k in round r
    (the permutation applied r times), so rounds unroll with static
    indices instead of in-kernel gathers."""
    perm = list(range(16))
    out = []
    for _ in range(_ROUNDS):
        out.append(tuple(perm))
        perm = [perm[i] for i in MSG_PERMUTATION]
    return tuple(out)


def _build_kernel(unroll: bool = True):
    """The chunk kernel. `unroll=True` (real TPU) inlines the 16-block
    walk — a fori_loop carrying the [8, L] state costs a Mosaic layout
    round-trip per block. Interpret mode
    gets `unroll=False`: the unrolled body is a ~5k-op graph whose CPU
    compile takes MINUTES (the parity test ran hours), while the rolled
    loop compiles the body once; the block math is shared, so parity
    coverage is identical."""
    import jax
    import jax.numpy as jnp

    U = jnp.uint32
    schedules = _schedules()
    iv = [np.uint32(IV[i]) for i in range(8)]

    def rotr(x, r):
        return (x >> np.uint32(r)) | (x << np.uint32(32 - r))

    def kernel(words_ref, chunk_len_ref, is_root_ref, t_ref, out_ref):
        lanes = out_ref.shape[1]
        zeros = jnp.zeros((lanes,), U)
        # one in-VMEM transpose per tile: [L, 256] natural (contiguous
        # HBM reads) -> [256, L] so each message word is a lane vector.
        # Replaces an XLA HBM transpose of the whole batch (see module
        # docstring); int32 idioms throughout — Mosaic has no unsigned
        # vector max (arith.maxui).
        wt = jnp.transpose(words_ref[...], (1, 0))
        # per-block block_len/flags/active derive from the compact
        # per-lane chunk_len IN-KERNEL: shipping them as [16, N] arrays
        # is 3 × 16 × N words of HBM traffic + an XLA prologue
        chunk_len = chunk_len_ref[0, :].astype(jnp.int32)
        n_blocks = jnp.maximum(1, (chunk_len + BLOCK_LEN - 1) // BLOCK_LEN)
        is_root = is_root_ref[0, :] != np.uint32(0)
        t_lo = t_ref[0, :]

        def block_step(b, h):
            """One 64-byte block over all lanes; `b` may be traced."""
            m = [wt[b * 16 + j] for j in range(16)]
            blen = jnp.clip(chunk_len - b * BLOCK_LEN, 0, BLOCK_LEN).astype(U)
            last = n_blocks == (b + 1)
            flags = jnp.where(last, U(CHUNK_END), U(0))
            flags = jnp.where(last & is_root, flags | U(ROOT), flags)
            flags = jnp.where(b == 0, flags | U(CHUNK_START), flags)
            act = n_blocks > b
            v = list(h) + [
                iv[0] + zeros, iv[1] + zeros, iv[2] + zeros, iv[3] + zeros,
                t_lo, zeros, blen, flags,
            ]

            def g(a, bb, c, d, mx, my):
                v[a] = v[a] + v[bb] + mx
                v[d] = rotr(v[d] ^ v[a], 16)
                v[c] = v[c] + v[d]
                v[bb] = rotr(v[bb] ^ v[c], 12)
                v[a] = v[a] + v[bb] + my
                v[d] = rotr(v[d] ^ v[a], 8)
                v[c] = v[c] + v[d]
                v[bb] = rotr(v[bb] ^ v[c], 7)

            for r in range(_ROUNDS):
                s = schedules[r]
                g(0, 4, 8, 12, m[s[0]], m[s[1]])
                g(1, 5, 9, 13, m[s[2]], m[s[3]])
                g(2, 6, 10, 14, m[s[4]], m[s[5]])
                g(3, 7, 11, 15, m[s[6]], m[s[7]])
                g(0, 5, 10, 15, m[s[8]], m[s[9]])
                g(1, 6, 11, 12, m[s[10]], m[s[11]])
                g(2, 7, 8, 13, m[s[12]], m[s[13]])
                g(3, 4, 9, 14, m[s[14]], m[s[15]])

            out = [v[i] ^ v[i + 8] for i in range(8)]
            return tuple(jnp.where(act, out[i], h[i]) for i in range(8))

        h = tuple(iv[i] + zeros for i in range(8))
        if unroll:
            for b in range(16):
                h = block_step(b, h)
        else:
            h = jax.lax.fori_loop(0, 16, block_step, h)

        for i in range(8):
            out_ref[i, :] = h[i]

    return kernel


@functools.lru_cache(maxsize=4)
def _chunk_cvs_call(interpret: bool, lanes: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = _build_kernel(unroll=not interpret)
    mem = {} if interpret else {"memory_space": pltpu.VMEM}

    @functools.partial(jax.jit, static_argnames=())
    def run(words, chunk_len, is_root, t_lo):
        """words [N, 256] natural chunk-major; chunk_len/is_root/t_lo
        [1, N] (N a multiple of `lanes`) -> cvs [8, N] uint32."""
        n = words.shape[0]
        grid = (n // lanes,)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((8, n), jnp.uint32),
            grid=grid,
            in_specs=[
                pl.BlockSpec((lanes, 256), lambda i: (i, 0), **mem),
                pl.BlockSpec((1, lanes), lambda i: (0, i), **mem),
                pl.BlockSpec((1, lanes), lambda i: (0, i), **mem),
                pl.BlockSpec((1, lanes), lambda i: (0, i), **mem),
            ],
            out_specs=pl.BlockSpec((8, lanes), lambda i: (0, i), **mem),
            interpret=interpret,
        )(words, chunk_len, is_root, t_lo)

    return run


def pallas_mode() -> str | None:
    """'tpu' (real kernel), 'interpret', or None (disabled).

    Default: real kernel on TPU backends only. SD_BLAKE3_PALLAS=1
    forces interpret mode elsewhere (tests); =0 disables entirely.
    Only reached from a device dispatch, so a backend that cannot
    initialise raises here instead of reading as "not a TPU".
    """
    env = os.environ.get("SD_BLAKE3_PALLAS")
    if env == "0":
        return None
    import jax

    if jax.devices()[0].platform == "tpu":
        return "tpu"
    return "interpret" if env == "1" else None


def chunk_cvs(words, chunk_len, is_root, t_lo, *, interpret: bool):
    """Pad the lane dim to the chosen tile and run the kernel; returns
    [8, N]. `words` is [N, 256] natural layout; the other inputs are
    compact per-lane vectors [1, N] (block_len/flags/active derive
    in-kernel). Big batches use the wide tile (fewer grid steps); small
    batches and interpret mode use the small one so the pad-to-tile
    floor stays cheap."""
    import jax.numpy as jnp

    n = words.shape[0]
    lanes = LANES_SMALL if (interpret or n < 4 * LANES) else LANES
    pad = (-n) % lanes
    if pad:
        # pad lanes hash as zero-length chunks; their CVs are sliced off
        words = jnp.pad(words, ((0, pad), (0, 0)))
        chunk_len = jnp.pad(chunk_len, ((0, 0), (0, pad)))
        is_root = jnp.pad(is_root, ((0, 0), (0, pad)))
        t_lo = jnp.pad(t_lo, ((0, 0), (0, pad)))
    out = _chunk_cvs_call(interpret, lanes)(words, chunk_len, is_root, t_lo)
    return out[:, :n]
