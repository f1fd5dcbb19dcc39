"""The plain references against known answers and a second witness."""

import numpy as np
import pytest

from benchmark.reference import blake3_np, cas_layout, media

# official BLAKE3 test vectors: input byte i is i % 251
VECTORS = {
    0: "af1349b9f5f9a1a6a0404dea36dcc9499bcb25c9adc112b7cc9a93cae41f3262",
    1: "2d3adedff11b61f14c886e35afa036736dcd87a74d27b5c1510225d0f592e213",
    1024: "42214739f095a406f3fc83deb889744ac00df831c10daa55189b5d121c855af7",
    1025: "d00278ae47eb27b34faecf67b4fe263f82d5412916c1ffd97c8cb7fb814b8444",
}


@pytest.mark.parametrize("n", sorted(VECTORS))
def test_blake3_official_vectors(n):
    data = bytes(i % 251 for i in range(n))
    assert blake3_np.hash_hex(data) == VECTORS[n]


def test_blake3_agrees_with_the_programs_reference_on_every_shape():
    from spacedrive_tpu.ops.blake3_ref import blake3_hex

    rng = np.random.default_rng(3)
    lens = [0, 1, 63, 64, 65, 1023, 1024, 1025, 2048, 2049, 3 * 1024, 5000,
            7 * 1024, 8 * 1024 + 1, 31 * 1024, 57352, 100 * 1024 + 8]
    lens += [int(x) for x in rng.integers(0, 110000, 12)]
    msgs = [rng.bytes(n) for n in lens]
    got = blake3_np.hash_many(msgs)
    assert [g.hex() for g in got] == [blake3_hex(m) for m in msgs]


def test_layout_is_the_upstream_message(tmp_path):
    small, large = tmp_path / "s", tmp_path / "l"
    small.write_bytes(b"x" * 102400)
    large.write_bytes(bytes(range(256)) * 401)  # 102,656 bytes: sampled
    assert cas_layout.ranges(102400) == [(0, 102400)]
    assert len(cas_layout.message(str(small))) == 8 + 102400
    assert len(cas_layout.message(str(large))) == 57352
    assert cas_layout.message_len(102401) == 57352
    r = cas_layout.ranges(1 << 20)
    assert r[0] == (0, 8192) and r[-1] == ((1 << 20) - 8192, 8192)
    assert [ln for _o, ln in r[1:-1]] == [10240] * 4

    from spacedrive_tpu.ops import cas

    ours = blake3_np.hash_many([cas_layout.message(str(p))
                                for p in (small, large)], 8)
    assert [d.hex() for d in ours] == [cas.cas_id_cpu(str(small)),
                                       cas.cas_id_cpu(str(large))]


def test_thumbnail_size_and_orientation():
    assert media.scale_dimensions(4032, 3024, 262144) == (591, 443)
    assert media.thumbnail_size(4032, 3024, 6, 262144) == (443, 591)
    assert media.thumbnail_size(300, 200, 1, 262144) == (300, 200)
    a = np.arange(2 * 3 * 3).reshape(2, 3, 3)
    assert media.orient(a, 3)[0, 0].tolist() == a[1, 2].tolist()
    assert media.orient(a, 6).shape == (3, 2, 3)


def test_embedding_reference_matches_the_programs_forward():
    import jax

    from spacedrive_tpu.models import embedder

    planes = np.random.default_rng(1).random((5, 32, 32, 3)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(embedder.forward(embedder.params(), planes))
    want = media.embed_forward(planes)
    assert np.abs(got - want).max() < 1e-5
    # the control is far outside what float32 rounding does
    assert np.abs(media.embed_forward(planes, control=True) - want).max() > 1e-2
