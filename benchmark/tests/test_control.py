"""Every control comes out as not correct, at a size a test run holds."""

import pytest

from benchmark import control, harness
from benchmark.tests.conftest import tiny_configs


@pytest.mark.parametrize("name,seed", [("homedir", 5), ("photolib", 2147483999),
                                       ("photolib", 7)])
def test_controls_fail(tmp_path, name, seed):
    config = tiny_configs()[name]
    generator = harness.Bench().generator(config)
    r = control.readings(config, generator, seed, str(tmp_path))
    fails = control.not_correct(r)
    assert fails["thumbnail_pixel_gap"], r
    assert fails["embedding_gap"], r
    if r["cas_mismatch"] is not None:
        assert fails["cas_mismatch"], r
    # the codec alone stays inside the limit, or sound runs could not
    assert r["thumbnail_codec_alone"] < r["thumbnail_pixel_gap"][1]
