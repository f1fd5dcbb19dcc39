"""A/B: in-VMEM transpose strategies for the BLAKE3 chunk kernel.

An earlier device profile (PROFILE.md §3, in git history at 2fc3a99;
not re-measured on the current rig) put most of the batch-4096 dispatch
in the `[L, 256] -> [256, L]` in-VMEM transpose. Round-4's A/B (staging the transpose per
16-word block) was a wash — Mosaic emits the same relayout volume. This
experiment tries the remaining idea from the round-4 verdict: route the
permutation through the MXU instead of the VPU relayout path.

A transpose IS a matmul against an identity: T(A) = dot(A, I) with the
contraction on dim 0. uint32 words don't fit f32 exactly, so each word
splits into two 16-bit halves (exact in f32), each half transposes on
the MXU, and the halves recombine with one shift+or. Identity matrices
are per-tile constants ([L, L] f32; L=512 keeps that at 1 MiB VMEM).

Variants, all bit-exact against the production kernel:
  baseline    — jnp.transpose inside the kernel (today's shipping path)
  mxu         — 16-bit split + two dot_generals + recombine
  mxu-fused   — same, but the f32 halves feed the first round's m[]
                directly where possible (no early combine)  [dropped if
                it can't be made bit-exact cheaply]

Timing: chained-marginal device cost (a
single dispatch times launch latency, the marginal chained dispatch is
device-bound), distinct inputs each link, plus digest equality checks.

Usage (real TPU shell): python experiments/transpose_ab.py
"""

from __future__ import annotations

import functools
import sys
import time

import numpy as np

sys.path.insert(0, ".")

from spacedrive_tpu.ops.blake3_pallas import (  # noqa: E402
    LANES, _build_kernel, _schedules,
)
from spacedrive_tpu.ops.blake3_ref import (  # noqa: E402
    BLOCK_LEN, CHUNK_END, CHUNK_START, IV, ROOT,
)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def build_variant(transpose_mode: str, lanes: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    U = jnp.uint32
    schedules = _schedules()
    iv = [np.uint32(IV[i]) for i in range(8)]

    def rotr(x, r):
        return (x >> np.uint32(r)) | (x << np.uint32(32 - r))

    def kernel(words_ref, chunk_len_ref, is_root_ref, t_ref, out_ref):
        nlanes = out_ref.shape[1]
        zeros = jnp.zeros((nlanes,), U)
        a = words_ref[...]
        if transpose_mode == "baseline":
            wt = jnp.transpose(a, (1, 0))
        elif transpose_mode == "mxu":
            # 16-bit split -> two MXU transposes vs identity -> combine.
            # Sums have exactly one nonzero term, so f32 is exact.
            ident = jax.lax.broadcasted_iota(jnp.int32, (nlanes, nlanes), 0) \
                == jax.lax.broadcasted_iota(jnp.int32, (nlanes, nlanes), 1)
            ident_f = ident.astype(jnp.float32)
            ai = a.astype(jnp.int32)
            lo = (ai & jnp.int32(0xFFFF)).astype(jnp.float32)
            hi = jax.lax.shift_right_logical(
                ai, jnp.int32(16)).astype(jnp.float32)
            dims = (((0,), (0,)), ((), ()))
            # HIGHEST = true f32 (3-pass bf16 decomposition): the TPU
            # default single-pass bf16 truncates 16-bit values to 8
            # mantissa bits and corrupts the words
            lo_t = jax.lax.dot_general(
                lo, ident_f, dims, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            hi_t = jax.lax.dot_general(
                hi, ident_f, dims, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            wt = (hi_t.astype(jnp.int32).astype(U) << U(16)) \
                | lo_t.astype(jnp.int32).astype(U)
        else:
            raise ValueError(transpose_mode)

        chunk_len = chunk_len_ref[0, :].astype(jnp.int32)
        n_blocks = jnp.maximum(1, (chunk_len + BLOCK_LEN - 1) // BLOCK_LEN)
        is_root = is_root_ref[0, :] != np.uint32(0)
        t_lo = t_ref[0, :]

        def block_step(b, h):
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

            def g(aa, bb, c, d, mx, my):
                v[aa] = v[aa] + v[bb] + mx
                v[d] = rotr(v[d] ^ v[aa], 16)
                v[c] = v[c] + v[d]
                v[bb] = rotr(v[bb] ^ v[c], 12)
                v[aa] = v[aa] + v[bb] + my
                v[d] = rotr(v[d] ^ v[aa], 8)
                v[c] = v[c] + v[d]
                v[bb] = rotr(v[bb] ^ v[c], 7)

            for r in range(7):
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
        for b in range(16):
            h = block_step(b, h)
        for i in range(8):
            out_ref[i, :] = h[i]

    @jax.jit
    def run(words, chunk_len, is_root, t_lo):
        n = words.shape[0]
        grid = (n // lanes,)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((8, n), jnp.uint32),
            grid=grid,
            in_specs=[
                pl.BlockSpec((lanes, 256), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, lanes), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, lanes), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, lanes), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((8, lanes), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
        )(words, chunk_len, is_root, t_lo)

    return run


def marginal_ms(fn, args_list, chain_k=24, repeats=7):
    import jax.numpy as jnp

    def chain(k, off):
        acc = None
        for i in range(k):
            w = fn(*args_list[(off + i) % len(args_list)])
            s = jnp.sum(w, dtype=jnp.float32)
            acc = s if acc is None else acc + s
        np.asarray(acc)

    chain(chain_k, 0)
    samples = []
    for rep in range(repeats):
        t0 = time.perf_counter()
        chain(1, rep)
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        chain(chain_k, rep)
        tk = time.perf_counter() - t0
        samples.append((tk - t1) / (chain_k - 1) * 1e3)
    samples.sort()
    return samples[len(samples) // 2], samples[0], samples[-1]


def main():
    import jax

    n = 4096
    lanes_cfgs = [512, 2048]
    rng = np.random.default_rng(0)
    log(f"devices: {jax.devices()}")

    # distinct inputs per chain link (defeat result caching)
    base = rng.integers(0, 2**32, size=(n, 256), dtype=np.uint32)
    chunk_len = np.full((1, n), 1024, np.uint32)
    is_root = np.zeros((1, n), np.uint32)
    t_lo = np.arange(n, dtype=np.uint32).reshape(1, n)
    inputs = []
    for i in range(6):
        w = base.copy()
        w[:, 0] = i + 1
        inputs.append((jax.device_put(w), jax.device_put(chunk_len),
                       jax.device_put(is_root), jax.device_put(t_lo)))
    jax.block_until_ready(inputs[-1][0])

    results = {}
    ref_out = None
    for lanes in lanes_cfgs:
        for mode in ("baseline", "mxu"):
            tag = f"{mode}@L{lanes}"
            try:
                fn = build_variant(mode, lanes)
                out = np.asarray(fn(*inputs[0]))
                if ref_out is None:
                    ref_out = out
                else:
                    assert np.array_equal(out, ref_out), f"{tag} MISMATCH"
                med, lo, hi = marginal_ms(fn, inputs)
                bps = n * 1024 / (med / 1e3) / 1e9
                results[tag] = (med, lo, hi, bps)
                log(f"{tag}: {med:.3f} ms [{lo:.3f}-{hi:.3f}]  "
                    f"{bps:.1f} GB/s  bit-exact ok")
            except Exception as e:  # noqa: BLE001 - report per-variant
                log(f"{tag}: FAILED {type(e).__name__}: {str(e)[:300]}")
                results[tag] = None
    print(results)


if __name__ == "__main__":
    main()
