"""BLAKE3 over many messages at once, in plain NumPy, written from the
specification (O'Connor, Aumasson, Neves, Wilcox-O'Hearn, "BLAKE3: one
function, fast everywhere", 2020, section 2 and the reference
implementation's constants). It imports nothing of the program under
test. Lanes are chunks (first stage) and parent nodes (second stage);
one Python-level operation works on every lane.
"""

from __future__ import annotations

import numpy as np

IV = np.array([0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
               0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19], np.uint32)
PERMUTATION = (2, 6, 3, 10, 7, 0, 4, 13, 1, 11, 12, 5, 9, 14, 15, 8)
CHUNK_START, CHUNK_END, PARENT, ROOT = 1, 2, 4, 8
CHUNK = 1024
BLOCK = 64
#: chunks hashed per slab, so the padded byte array stays near 64 MiB
SLAB_CHUNKS = 1 << 16


def _rotr(x: np.ndarray, n: int) -> np.ndarray:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _g(a, b, c, d, mx, my):
    a = a + b + mx
    d = _rotr(d ^ a, 16)
    c = c + d
    b = _rotr(b ^ c, 12)
    a = a + b + my
    d = _rotr(d ^ a, 8)
    c = c + d
    b = _rotr(b ^ c, 7)
    return a, b, c, d


def compress(cv: np.ndarray, m: np.ndarray, counter: np.ndarray,
             block_len: np.ndarray, flags: np.ndarray) -> np.ndarray:
    """cv uint32[8, N], m uint32[16, N], the rest uint32/uint64[N] →
    the first eight output words, uint32[8, N]."""
    n = cv.shape[1]
    counter = counter.astype(np.uint64)
    a = cv[0:4].copy()
    b = cv[4:8].copy()
    c = np.repeat(IV[0:4, None], n, axis=1)
    d = np.stack([
        (counter & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        (counter >> np.uint64(32)).astype(np.uint32),
        block_len.astype(np.uint32),
        flags.astype(np.uint32),
    ])
    for rnd in range(7):
        a, b, c, d = _g(a, b, c, d, m[0:8:2], m[1:8:2])
        b, c, d = np.roll(b, -1, 0), np.roll(c, -2, 0), np.roll(d, -3, 0)
        a, b, c, d = _g(a, b, c, d, m[8:16:2], m[9:16:2])
        b, c, d = np.roll(b, 1, 0), np.roll(c, 2, 0), np.roll(d, 3, 0)
        if rnd < 6:
            m = m[PERMUTATION, :]
    return np.concatenate([a ^ c, b ^ d])


def _chunk_cvs(data: np.ndarray, lens: np.ndarray, counters: np.ndarray,
               single: np.ndarray) -> np.ndarray:
    """data uint8[N, 1024] zero-padded, lens[N] bytes in each chunk,
    counters[N] the chunk's index in its message, single[N] whether the
    chunk is its message's only one (then its last block is the root).
    → uint32[8, N]"""
    n = data.shape[0]
    words = data.view("<u4").reshape(n, 16, 16)
    n_blocks = np.maximum(1, -(-lens // BLOCK))
    cv = np.repeat(IV[:, None], n, axis=1)
    for b in range(16):
        active = b < n_blocks
        if not active.any():
            break
        idx = np.nonzero(active)[0]
        last = (n_blocks[idx] - 1) == b
        flags = (np.where(b == 0, CHUNK_START, 0)
                 + np.where(last, CHUNK_END, 0)
                 + np.where(last & single[idx], ROOT, 0))
        block_len = np.clip(lens[idx] - BLOCK * b, 0, BLOCK)
        m = np.ascontiguousarray(words[idx, b, :].T)
        cv[:, idx] = compress(cv[:, idx], m, counters[idx], block_len, flags)
    return cv


def hash_many(messages: list[bytes], out_len: int = 32) -> list[bytes]:
    """The first `out_len` (≤ 32) digest bytes of each message."""
    if not messages:
        return []
    n_chunks = np.array([max(1, -(-len(m) // CHUNK)) for m in messages])
    total = int(n_chunks.sum())
    owner = np.repeat(np.arange(len(messages)), n_chunks)
    first = np.concatenate([[0], np.cumsum(n_chunks)[:-1]])
    index = np.arange(total) - first[owner]
    msg_len = np.array([len(m) for m in messages])
    lens = np.clip(msg_len[owner] - CHUNK * index, 0, CHUNK)
    single = (n_chunks == 1)[owner]

    cvs = np.empty((8, total), np.uint32)
    for lo in range(0, total, SLAB_CHUNKS):
        hi = min(total, lo + SLAB_CHUNKS)
        data = np.zeros((hi - lo, CHUNK), np.uint8)
        for j in range(int(owner[lo]), int(owner[hi - 1]) + 1):
            # the part of message j that falls into this slab
            c0 = max(lo, int(first[j]))
            c1 = min(hi, int(first[j] + n_chunks[j]))
            part = messages[j][(c0 - int(first[j])) * CHUNK:
                               (c1 - int(first[j])) * CHUNK]
            flat = data[c0 - lo:c1 - lo].reshape(-1)
            flat[:len(part)] = np.frombuffer(part, np.uint8)
        cvs[:, lo:hi] = _chunk_cvs(data, lens[lo:hi], index[lo:hi],
                                   single[lo:hi])

    # parents, level by level: neighbours pair up, an odd last node
    # moves up as it is; with complete left subtrees that is the tree
    # the specification defines
    width = int(n_chunks.max())
    nodes = np.zeros((len(messages), width, 8), np.uint32)
    nodes[owner, index] = cvs.T
    count = n_chunks.copy()
    zeros = np.zeros(0, np.uint32)
    while (count > 1).any():
        pairs = count // 2
        mi, pi = np.nonzero(np.arange(width // 2 + 1)[None, :] < pairs[:, None])
        left = nodes[mi, 2 * pi]
        right = nodes[mi, 2 * pi + 1]
        flags = np.where(count[mi] == 2, PARENT | ROOT, PARENT)
        zeros = np.zeros(len(mi), np.uint32)
        out = compress(
            np.repeat(IV[:, None], len(mi), axis=1),
            np.ascontiguousarray(np.concatenate([left, right], axis=1).T),
            zeros, zeros + BLOCK, flags,
        )
        odd = np.nonzero(count % 2 == 1)[0]
        odd = odd[count[odd] > 1]
        carried = nodes[odd, count[odd] - 1].copy()
        nodes[mi, pi] = out.T
        nodes[odd, count[odd] // 2] = carried
        count = np.where(count > 1, (count + 1) // 2, count)
    return [nodes[i, 0].astype("<u4").tobytes()[:out_len]
            for i in range(len(messages))]


def hash_hex(message: bytes, hex_chars: int = 64) -> str:
    return hash_many([message])[0].hex()[:hex_chars]
