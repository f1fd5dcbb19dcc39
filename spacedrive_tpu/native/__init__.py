"""Native (C) runtime components, loaded via ctypes.

The reference's runtime is native Rust/C (blake3 crate, libwebp, ffmpeg,
…); this package holds the new framework's native equivalents, compiled
on first use with the system toolchain and cached next to the sources.
Every consumer has a pure-Python fallback, so the framework degrades
gracefully on hosts without a C compiler.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_LOAD_FAILED = False


def _build(src: str, out: str, extra_args: tuple[str, ...] = ()) -> bool:
    """Compile one source into a shared object, caching failure in a
    sentinel file so fresh processes don't retry a known-bad build."""
    sentinel = out + ".build_failed"
    try:
        src_mtime = os.path.getmtime(src)
        if os.path.exists(sentinel) and \
                os.path.getmtime(sentinel) >= src_mtime:
            return False
    except OSError:
        return False
    for cc in ("cc", "gcc", "g++", "clang"):
        try:
            r = subprocess.run(
                [cc, "-O3", "-fPIC", "-shared", "-pthread", src, "-o", out]
                + list(extra_args),
                capture_output=True, timeout=120,
            )
            if r.returncode == 0:
                if os.path.exists(sentinel):
                    os.remove(sentinel)
                return True
        except (OSError, subprocess.TimeoutExpired):
            continue
    try:
        with open(sentinel, "w") as f:
            f.write("build failed; delete this file to retry\n")
    except OSError:
        pass
    return False


def load() -> ctypes.CDLL | None:
    """The native library, building it if needed; None if unavailable."""
    global _LIB, _LOAD_FAILED
    if _LIB is not None or _LOAD_FAILED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _LOAD_FAILED:
            return _LIB
        so = os.path.join(_DIR, "_sdnative.so")
        src = os.path.join(_DIR, "blake3.c")
        try:
            if not os.path.exists(so) or os.path.getmtime(so) < os.path.getmtime(src):
                if not _build(src, so):
                    _LOAD_FAILED = True
                    return None
            lib = ctypes.CDLL(so)
            lib.b3_hash.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint32,
            ]
            lib.b3_hash_many.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int32, ctypes.c_void_p, ctypes.c_int32,
            ]
            lib.b3_state_size.restype = ctypes.c_uint32
            lib.b3_init.argtypes = [ctypes.c_void_p]
            lib.b3_update.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64]
            lib.b3_finalize.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint32]
            _LIB = lib
        except OSError:
            _LOAD_FAILED = True
    return _LIB


def available() -> bool:
    return load() is not None


def blake3_digest(data: bytes, out_len: int = 32) -> bytes | None:
    """One-shot native BLAKE3; None if the native lib is unavailable."""
    lib = load()
    if lib is None:
        return None
    out = (ctypes.c_uint8 * 64)()
    lib.b3_hash(data, len(data), out, min(out_len, 64))
    return bytes(out[:out_len])


class StreamingHasher:
    """Incremental native BLAKE3 — bounded memory over unbounded input
    (the validator's full-file hash, ref:core/src/object/validation/hash.rs:9-25)."""

    def __init__(self):
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._state = ctypes.create_string_buffer(lib.b3_state_size())
        lib.b3_init(self._state)

    def update(self, data: bytes | memoryview) -> "StreamingHasher":
        data = bytes(data) if isinstance(data, memoryview) else data
        self._lib.b3_update(self._state, data, len(data))
        return self

    def digest(self, out_len: int = 32) -> bytes:
        out = (ctypes.c_uint8 * 64)()
        self._lib.b3_finalize(self._state, out, min(out_len, 64))
        return bytes(out[:out_len])


def blake3_many(messages: list[bytes], nthreads: int | None = None) -> list[bytes] | None:
    """32-byte digests for a batch of messages using the threaded C path.

    This is the multi-core CPU baseline the TPU path is benchmarked
    against (the reference hashes on all cores via tokio `join_all`,
    ref:core/src/object/file_identifier/mod.rs:105-147).
    """
    lib = load()
    if lib is None:
        return None
    if nthreads is None:
        nthreads = os.cpu_count() or 1
    n = len(messages)
    lens = np.fromiter((len(m) for m in messages), np.uint32, n)
    offsets = np.zeros(n, np.uint64)
    np.cumsum(lens[:-1], out=offsets[1:])
    base = np.frombuffer(b"".join(messages), np.uint8)
    out = np.empty(n * 32, np.uint8)
    lib.b3_hash_many(
        base.ctypes.data, offsets.ctypes.data, lens.ctypes.data,
        n, out.ctypes.data, nthreads,
    )
    raw = out.tobytes()
    return [raw[i * 32:(i + 1) * 32] for i in range(n)]


# --- video decode frontend (FFmpeg FFI, ref:crates/ffmpeg) ----------------

_VIDEO_LIB: ctypes.CDLL | None = None
_VIDEO_FAILED = False
_AV_LIBS = ("-lavformat", "-lavcodec", "-lavutil", "-lswscale", "-lm")


def load_video() -> ctypes.CDLL | None:
    """The native FFmpeg frontend (movie_decoder.c), building on first
    use; None when libav headers/libraries are absent (callers fall
    back to cv2)."""
    global _VIDEO_LIB, _VIDEO_FAILED
    if _VIDEO_LIB is not None or _VIDEO_FAILED:
        return _VIDEO_LIB
    with _LOCK:
        if _VIDEO_LIB is not None or _VIDEO_FAILED:
            return _VIDEO_LIB
        so = os.path.join(_DIR, "_sdvideo.so")
        src = os.path.join(_DIR, "movie_decoder.c")
        try:
            if not os.path.exists(so) or \
                    os.path.getmtime(so) < os.path.getmtime(src):
                if not _build(src, so, _AV_LIBS):
                    _VIDEO_FAILED = True
                    return None
            lib = ctypes.CDLL(so)
            lib.sd_video_frame.restype = ctypes.c_int
            lib.sd_video_frame.argtypes = [
                ctypes.c_char_p, ctypes.c_double,
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
                ctypes.c_char_p, ctypes.c_int,
            ]
            lib.sd_video_meta.restype = ctypes.c_int
            lib.sd_video_meta.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_double),
                ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int64),
                ctypes.c_char_p, ctypes.c_int,
            ]
            lib.sd_video_free.argtypes = [ctypes.c_void_p]
            _VIDEO_LIB = lib
        except OSError:
            _VIDEO_FAILED = True
    return _VIDEO_LIB


def video_available() -> bool:
    return load_video() is not None


def video_frame(path: str, seek_fraction: float = 0.1):
    """(HxWxC uint8, rotation_degrees, is_cover) or None. C is what the
    frontend converted to: 3, RGB, for footage; 4, RGBA, where the
    decoded frame's pixel format has alpha (a PNG cover).

    Preferred-stream selection with embedded-cover preference, ~10%
    seek, display-matrix rotation (ref:movie_decoder.rs:32-629, cover
    check :352)."""
    lib = load_video()
    if lib is None:
        return None
    buf = ctypes.c_void_p()
    w = ctypes.c_int()
    h = ctypes.c_int()
    channels = ctypes.c_int()
    rot = ctypes.c_int()
    cover = ctypes.c_int()
    err = ctypes.create_string_buffer(256)
    rc = lib.sd_video_frame(
        os.fsencode(path), seek_fraction, ctypes.byref(buf),
        ctypes.byref(w), ctypes.byref(h), ctypes.byref(channels),
        ctypes.byref(rot), ctypes.byref(cover), err, len(err),
    )
    if rc != 0:
        raise ValueError(
            f"video decode failed: {err.value.decode(errors='replace')}"
        )
    try:
        n = w.value * h.value * channels.value
        arr = np.frombuffer(
            ctypes.string_at(buf.value, n), np.uint8
        ).reshape(h.value, w.value, channels.value).copy()
    finally:
        lib.sd_video_free(buf)
    return arr, rot.value, bool(cover.value)


def video_meta(path: str):
    """{duration_seconds, fps, width, height, frame_count, codec} or
    None when the native frontend is unavailable; raises on bad files."""
    lib = load_video()
    if lib is None:
        return None
    dur = ctypes.c_double()
    fps = ctypes.c_double()
    w = ctypes.c_int()
    h = ctypes.c_int()
    frames = ctypes.c_int64()
    codec = ctypes.create_string_buffer(64)
    rc = lib.sd_video_meta(
        os.fsencode(path), ctypes.byref(dur), ctypes.byref(fps),
        ctypes.byref(w), ctypes.byref(h), ctypes.byref(frames),
        codec, len(codec),
    )
    if rc != 0:
        raise ValueError(f"video probe failed: {path}")
    return {
        "duration_seconds": dur.value, "fps": fps.value,
        "width": w.value, "height": h.value,
        "frame_count": int(frames.value),
        "codec": codec.value.decode(errors="replace"),
    }
