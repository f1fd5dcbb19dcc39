"""`photolib.raw` through the harness at a tiny size on the CPU, with a
fixture of its own: conftest.py's `tiny_root` cuts the frames to a few
hundred KB, here they keep their 25-40 MB (they are holes), so the
sampled ranges lie where the cell's do. A sound run is correct, a run
whose sampled read is moved by a byte is not, and the cas_id control
fails."""

import json
import os

import pytest

from benchmark import control, harness
from benchmark.tests.conftest import ROOT, cpu_stamp

SEED = 2147483999


def tiny_config() -> dict:
    """The configuration at a size a test run can hold: the frames keep
    their sizes (they are holes), the counts and the exports shrink."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "photolib_raw.json")) as f:
        config = json.load(f)
    config["frames"] = 24
    config["exports"] = 2
    config["export"].update(width=640, height=427)
    return config


@pytest.fixture()
def raw_root(tmp_path):
    """A checkout's worth of benchmark files that holds this one
    configuration, tiny, and its cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"] = [c for c in doc["configs"] if c["name"] == "photolib_raw"]
    doc["workloads"] = [w for w in doc["workloads"]
                        if w["name"] == "photolib.raw"]
    doc["configs"][0]["file"] = "tiny_photolib_raw.json"
    with open(tmp_path / "tiny_photolib_raw.json", "w") as f:
        json.dump(tiny_config(), f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(doc, f)
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    return str(tmp_path)


def run(raw_root, tmp_path):
    return harness.run_cell("photolib.raw", SEED, 1.0, False, root=raw_root,
                            require=cpu_stamp, work=str(tmp_path / "work"))


def failing(result) -> set:
    return {k for k, (v, lim) in result["compared"].items() if v > lim}


def test_sound_run_is_correct(raw_root, tmp_path):
    r = run(raw_root, tmp_path)
    assert r["correct"] is True, failing(r)
    assert r["failed"] == 0 and r["attempted"] >= 26
    assert set(r["metrics"]) == {"pass_rate", "setup_s"}
    assert all(v == 0 for k, (v, _lim) in r["compared"].items()
               if k not in ("thumbnail_pixel_gap", "embedding_gap"))


def test_moved_sample_is_not_correct(raw_root, tmp_path, monkeypatch):
    """The program reads its second sample one byte late."""
    from spacedrive_tpu.ops import cas

    real = cas.sample_ranges

    def moved(size):
        ranges = real(size)
        if len(ranges) > 1:
            ranges[2] = (ranges[2][0] + 1, ranges[2][1])
        return ranges

    monkeypatch.setattr(cas, "sample_ranges", moved)
    r = run(raw_root, tmp_path)
    assert r["correct"] is False
    assert "cas_mismatch" in failing(r) and r["failed"] > 0


def test_cas_control_fails(raw_root, tmp_path):
    bench = harness.Bench(raw_root)
    config = bench.cell("photolib.raw")["config"]
    r = control.readings(config, bench.generator(config), SEED, str(tmp_path))
    assert r["cas_mismatch"] == [24, 0]
    assert control.not_correct(r)["cas_mismatch"]
