"""Multi-device dp-dispatch parity — the tier-1 smoke for the sharded
indexing hot paths (ISSUE 4 acceptance: forced-8-device cas_id and
thumbnail outputs bit-identical to single-device and CPU reference).

conftest.py forces an 8-device virtual CPU platform before jax loads,
so every test here exercises the REAL shard_map programs with no TPU.
"""

import numpy as np
import pytest

from spacedrive_tpu.ops import cas
from spacedrive_tpu.ops.blake3_ref import StreamingBlake3

RNG = np.random.default_rng(1234)


def _devices():
    import jax

    devs = jax.devices()
    assert len(devs) == 8, "conftest must force 8 virtual devices"
    return devs


def _ragged_messages():
    # spans buckets 1/2/4/8, includes empties and non-multiples of 1024
    sizes = [0, 1, 5, 1000, 1024, 2048, 3000, 4000, 7000, 8000, 100, 6500]
    return [
        cas.message_from_bytes(
            RNG.integers(0, 256, s, dtype=np.uint8).tobytes()
        )
        for s in sizes
    ]


def test_sharded_cas_bit_identical_to_single_device_and_cpu():
    devs = _devices()
    msgs = _ragged_messages()
    want = [StreamingBlake3().update(m).hexdigest()[:16] for m in msgs]
    sharded = cas.cas_ids_begin(msgs, devices=devs)()
    single = cas.cas_ids_begin(msgs, devices=devs[:1])()
    assert sharded == want
    assert single == want


def test_sharded_cas_odd_device_counts_and_pad_rows():
    # 3 and 5 devices force ladder rungs (96/480) no power of two hits;
    # ragged pad rows must still slice off cleanly
    devs = _devices()
    msgs = _ragged_messages()[:7]
    want = [StreamingBlake3().update(m).hexdigest()[:16] for m in msgs]
    for k in (3, 5):
        assert cas.cas_ids_begin(msgs, devices=devs[:k])() == want


def test_hash_batch_rejects_undividable_shard():
    import jax

    from spacedrive_tpu.ops import blake3_jax

    arr = np.zeros((3, 1024), np.uint8)
    lens = np.ones((3,), np.int32)
    with pytest.raises(ValueError, match="does not divide"):
        blake3_jax.hash_batch(arr, lens, max_chunks=1,
                              devices=jax.devices()[:2])


def test_batch_ladder_and_device_batch_scale():
    assert cas.batch_ladder(1) == cas.BATCH_LADDER
    assert cas.batch_ladder(8) == (256, 2048, 8192)
    assert cas.device_batch(8) == 8 * cas.DEVICE_BATCH
    # per-device rows always land on the warm single-device ladder
    for n_dev in (2, 3, 8):
        for rung in cas.batch_ladder(n_dev):
            assert rung // n_dev in cas.BATCH_LADDER


def test_pack_canonical_batch_matches_zero_fill_reference():
    """The np.empty + explicit-tail-zero pack must produce the exact
    bytes the old full-zero-fill pack produced (micro-benchmark-style
    parity: same ladder, same pad rows, same lengths)."""
    msgs = _ragged_messages()

    def reference(messages, max_chunks, n_devices=1):
        n_pad = next(
            s for s in cas.batch_ladder(n_devices) if s >= len(messages)
        )
        arr = np.zeros((n_pad, max_chunks * 1024), np.uint8)
        lens = np.ones((n_pad,), np.int32)
        for j, msg in enumerate(messages):
            arr[j, : len(msg)] = np.frombuffer(msg, np.uint8)
            lens[j] = len(msg)
        return arr, lens

    for n_dev in (1, 3, 8):
        got_arr, got_lens = cas.pack_canonical_batch(msgs, 8, n_devices=n_dev)
        ref_arr, ref_lens = reference(msgs, 8, n_devices=n_dev)
        assert got_arr.shape == ref_arr.shape
        assert np.array_equal(got_arr, ref_arr)
        assert np.array_equal(got_lens, ref_lens)


def test_sharded_resize_same_pixels_as_single_device():
    import jax

    from spacedrive_tpu.ops import thumbnail_jax as tj

    devs = _devices()
    shapes = [(200, 150), (100, 240), (256, 256), (50, 60),
              (180, 90), (90, 180), (30, 30), (250, 200), (128, 77)]
    imgs = [RNG.integers(0, 256, (h, w, 4), dtype=np.uint8)
            for h, w in shapes]
    targets = []
    for img in imgs:
        h, w = img.shape[:2]
        tw, th = tj.scale_dimensions(w, h)
        targets.append((th, tw))
    sharded = tj.resize_batch(imgs, targets, devices=devs)
    single = tj.resize_batch(imgs, targets, devices=devs[:1])
    for a, b in zip(sharded, single):
        assert a.shape == b.shape
        assert np.array_equal(a, b)


def test_sharded_dispatch_telemetry():
    from spacedrive_tpu import telemetry

    devs = _devices()
    before = len(telemetry.histogram_recent(
        "sd_device_shard_batch_rows", op="blake3"))
    msgs = _ragged_messages()
    cas.cas_ids_begin(msgs, devices=devs)()
    rows = telemetry.histogram_recent("sd_device_shard_batch_rows",
                                      op="blake3")
    assert len(rows) > before
    # every per-device shard sits on the warm ladder
    assert all(r in cas.BATCH_LADDER for r in rows[before:])
    occ = telemetry.histogram_recent("sd_device_dispatch_occupancy",
                                     op="blake3")
    assert occ and all(0.0 <= v <= 1.0 for v in occ)


def test_auto_policy_keeps_small_batches_single_device(monkeypatch):
    """Without explicit devices, a tiny batch must NOT shard (padding
    32-row rungs across 8 chips to hash 5 files is a net loss); a batch
    filling half the smallest sharded rung must."""
    # cas imports blake3_jax lazily (workers must import cas jax-free),
    # so the patch lands on the blake3_jax module itself
    from spacedrive_tpu.ops import blake3_jax

    calls = []
    real = blake3_jax.hash_batch

    def spy(arr, lens, max_chunks=None, devices=None, **kw):
        calls.append(len(devices) if devices is not None else 1)
        return real(arr, lens, max_chunks=max_chunks, devices=devices, **kw)

    monkeypatch.setattr(blake3_jax, "hash_batch", spy)
    small = [cas.message_from_bytes(b"x" * 100) for _ in range(5)]
    cas.cas_ids_begin(small)()
    assert calls == [1]
    calls.clear()
    big = [
        cas.message_from_bytes(
            RNG.integers(0, 256, 64, dtype=np.uint8).tobytes()
        )
        for _ in range(8 * cas.BATCH_LADDER[0] // 2)
    ]
    cas.cas_ids_begin(big)()
    assert calls == [8]


def test_pallas_chunk_kernel_under_dp_shard_map(monkeypatch):
    """The Pallas chunk kernel (interpret mode here) INSIDE the dp
    shard_map equals the pure-Python reference — the combination a
    multi-chip host runs, which used to fail at trace time and fall
    back without a word."""
    from spacedrive_tpu.ops import blake3_jax, blake3_pallas
    from spacedrive_tpu.ops.blake3_ref import blake3_hex

    monkeypatch.setenv("SD_BLAKE3_PALLAS", "1")
    assert blake3_pallas.pallas_mode() == "interpret"
    lens = [0, 5, 1024, 1025, 2048, 1500]  # 6 rows → 3 per device
    msgs = np.zeros((len(lens), 2 * 1024), np.uint8)
    data = RNG.integers(0, 256, 2048, dtype=np.uint8)
    for i, n in enumerate(lens):
        msgs[i, :n] = data[:n]
    words = blake3_jax.hash_batch(
        msgs, np.array(lens, np.int32), max_chunks=2, devices=_devices()[:2]
    )
    got = blake3_jax.words_to_hex(words, 64)
    assert got == [blake3_hex(data[:n].tobytes()) for n in lens]


def test_pallas_failure_is_the_callers_error(monkeypatch):
    """A kernel that cannot run raises to the caller; no second device
    implementation stands in for it."""
    from spacedrive_tpu.ops import blake3_jax, blake3_pallas

    def boom(*a, **k):
        raise RuntimeError("mosaic says no")

    monkeypatch.setenv("SD_BLAKE3_PALLAS", "1")
    monkeypatch.setattr(blake3_pallas, "chunk_cvs", boom)
    # a shape no other test compiles, so the jit cache cannot serve it
    msgs = np.zeros((3, 4 * 1024), np.uint8)
    with pytest.raises(RuntimeError, match="mosaic says no"):
        blake3_jax.hash_batch(msgs, np.full((3,), 4000, np.int32),
                              max_chunks=4)
