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


def _tiny_home_tree(config: dict) -> None:
    config["files"] = 120
    config["file_size"]["max_bytes"] = 3000
    config["image_share"] = 0.04
    config["images"] = [
        {"name": "camera", "share": 0.5, "format": "jpg", "width": [640, 640],
         "height": [480, 480], "exif_orientations": [3, 6, 1, 8]},
        {"name": "icons", "share": 0.5, "format": "png", "width": [96, 128],
         "height": [0, 1]},
    ]


def _tiny_camera_roll(config: dict) -> None:
    config["photos"] = 8
    config["photos_per_screenshot"] = 3
    config["photo"].update(width=640, height=480, exif_orientations=[3, 6, 1, 8])
    config["screenshot"].update(width=234, height=506)


def _tiny_raw_shoot(config: dict) -> None:
    config["frames"] = 8
    config["frame"].update(min_bytes=200_000, max_bytes=400_000)
    config["exports"] = 2
    config["export"].update(width=640, height=427)


#: generator → what cuts its configuration to a size a test run can hold
TINY = {"home_tree": _tiny_home_tree, "camera_roll": _tiny_camera_roll,
        "raw_shoot": _tiny_raw_shoot}


def tiny_configs() -> dict:
    """Every configuration of BENCHMARK.json by name, cut by its
    generator's entry in TINY; one whose generator has none stays as its
    file has it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = json.load(f)["configs"]
    out = {}
    for entry in entries:
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        TINY.get(config["generator"], lambda _config: None)(config)
        out[entry["name"]] = config
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
