"""The benchmark's own tests run on the CPU: they check the harness, not
the chip. `python3 -m pytest benchmark/tests -q` from the root."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the Pallas chunk kernel in interpret mode, as on the chip but slow
os.environ.setdefault("SD_BLAKE3_PALLAS", "1")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


def tiny_configs() -> dict:
    """The two configurations at a size a test run can hold."""
    out = {}
    for name in ("homedir", "photolib"):
        with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
            out[name] = json.load(f)
    home, photo = out["homedir"], out["photolib"]
    home["files"] = 120
    home["file_size"]["max_bytes"] = 3000
    home["image_share"] = 0.04
    home["images"] = [
        {"name": "camera", "share": 0.5, "format": "jpg", "width": [640, 640],
         "height": [480, 480], "exif_orientations": [3, 6, 1, 8]},
        {"name": "icons", "share": 0.5, "format": "png", "width": [96, 128],
         "height": [0, 1]},
    ]
    photo["photos"] = 8
    photo["photos_per_screenshot"] = 3
    photo["photo"].update(width=640, height=480, exif_orientations=[3, 6, 1, 8])
    photo["screenshot"].update(width=234, height=506)
    return out


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout's worth of benchmark files with tiny configurations:
    BENCHMARK.json, `benchmark/` linked in, the configs beside them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    configs = tiny_configs()
    for entry in doc["configs"]:
        entry["file"] = f"tiny_{entry['name']}.json"
        with open(tmp_path / entry["file"], "w") as f:
            json.dump(configs[entry["name"]], f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(doc, f)
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    return str(tmp_path)


def cpu_stamp(_chips: int) -> dict:
    """Stands in for the harness's look for a chip."""
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}
