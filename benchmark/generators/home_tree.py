"""`home_tree`: a user's home directory, from the parameters of a
configuration file (benchmark/configs/homedir.json names the sources).

File sizes are lognormal, directories have Poisson depths and files
land in them by a Zipf weight, a share of files are exact copies of
earlier ones placed deepest (a breadth-first walk reaches them last, so
the existing-object link branch runs), and a small share are images.
The shape is the configuration's; a run's seed gives the bytes and the
order (see `plan`).
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.generators.common import seed_words


def _directories(rng, n_dirs: int, p: dict) -> list[str]:
    names = ("docs", "projects", "mail", "notes", "music", "work", "src",
             "archive", "shared", "tmp")
    dirs: list[tuple[str, int]] = []  # (path, depth)
    by_depth: dict[int, list[int]] = {}
    for i in range(n_dirs):
        depth = int(min(p["max_depth"], 1 + rng.poisson(p["depth_poisson_mean"] - 1)))
        while depth > 1 and not by_depth.get(depth - 1):
            depth -= 1
        if depth == 1:
            path = f"{names[i % len(names)]}_{i:04d}"
        else:
            parent = by_depth[depth - 1][int(rng.integers(len(by_depth[depth - 1])))]
            path = f"{dirs[parent][0]}/d{i:04d}"
        by_depth.setdefault(depth, []).append(len(dirs))
        dirs.append((path, depth))
    return [d for d, _depth in dirs]


def plain_size(rng, p: dict) -> int:
    size = math.exp(rng.normal(math.log(p["median_bytes"]), p["sigma"]))
    return int(min(p["max_bytes"], max(p["min_bytes"], size)))


def plan(config: dict, seed: int, scale: float = 1.0) -> list[dict]:
    """The manifest for (config, seed). The location's shape — the tree,
    the set of sizes, which sizes are copied, the images' classes and
    dimensions — is drawn from the configuration's `shape_seed` and is
    the same for every seed; `seed` gives every file its bytes and deals
    the sizes out to the files in another order, so that a seed changes
    the answers and not the work. `scale` shrinks the count for the
    warm-up location and the tests, never for a timed pass."""
    shape = np.random.default_rng(
        seed_words(config.get("shape_seed", 0), 0x73686170))
    rng = np.random.default_rng(seed_words(seed, 0x686F6D65))
    n_files = max(8, int(config["files"] * scale))
    n_images = max(1, round(n_files * config["image_share"]))
    n_dups = int(n_files * config["duplicate_share"])
    n_plain = n_files - n_images - n_dups
    dp = config["directories"]
    dirs = _directories(shape, max(2, n_files // dp["files_per_directory_mean"]), dp)
    weight = 1.0 / np.arange(1, len(dirs) + 1) ** dp["zipf_exponent"]
    weight = shape.permutation(weight / weight.sum())
    exts = list(config["extensions"])
    ext_p = np.array([config["extensions"][e] for e in exts], float)
    ext_p /= ext_p.sum()
    home = shape.choice(len(dirs), n_plain, p=weight)
    ext_i = shape.choice(len(exts), n_plain, p=ext_p)
    sizes = [plain_size(shape, config["file_size"]) for _ in range(n_plain)]
    # both sides of the sampled/whole boundary are always present
    sizes[:6] = (102399, 102400, 102401, 1, 1016, 1017)
    copied = shape.choice(n_plain, n_dups, replace=False)  # slots of `sizes`

    dealt = rng.permutation(n_plain)  # file i gets sizes[dealt[i]]
    holder = np.argsort(dealt)        # sizes[k] went to file holder[k]
    manifest: list[dict] = []
    for i in range(n_plain):
        manifest.append({
            "rel": f"{dirs[int(home[i])]}/f{i:06d}.{exts[int(ext_i[i])]}",
            "size": sizes[int(dealt[i])],
            "content": seed_words(seed, i),
        })
    for i, slot in enumerate(copied):
        orig = manifest[int(holder[int(slot)])]
        manifest.append({
            "rel": f"zz_backup/old/disk/a/b/c/d/e/f/g/h/{i % 10}/copy_{i:05d}.bak",
            "size": orig["size"], "content": orig["content"],
        })
    classes = config["images"]
    class_p = np.array([c["share"] for c in classes], float)
    pick = shape.choice(len(classes), n_images, p=class_p / class_p.sum())
    pick[0] = 0  # the first class (the largest photos) is always present
    seen = [0] * len(classes)
    for i, ci in enumerate(pick):
        c = classes[int(ci)]
        nth, seen[int(ci)] = seen[int(ci)], seen[int(ci)] + 1
        w = int(shape.integers(c["width"][0], c["width"][1] + 1))
        h = (c["height"][0] if c["height"][0] == c["height"][1]
             else max(16, int(w * shape.uniform(0.5, 1.0))))
        orientations = c.get("exif_orientations", [1])
        manifest.append({
            "rel": f"pictures/{c['name']}/img_{i:05d}.{c['format']}",
            "size": 0, "content": seed_words(seed, 1 << 30 | i),
            "image": {"w": w, "h": h, "format": c["format"],
                      "orientation": orientations[nth % len(orientations)],
                      "blocky": c["format"] == "png"},
        })
    return manifest


def new_entry(config: dict, rng, manifest: list[dict], serial: int,
              seed: int) -> dict:
    """One more plain file beside an existing one (the traffic's adds)."""
    beside = manifest[int(rng.integers(len(manifest)))]["rel"]
    return {
        "rel": f"{beside.rsplit('/', 1)[0]}/new_{serial:06d}.dat",
        "size": plain_size(rng, config["file_size"]),
        "content": seed_words(seed, 1 << 29 | serial),
    }
