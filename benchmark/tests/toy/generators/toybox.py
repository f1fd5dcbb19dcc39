"""`toybox`: the location of `test_kinds.py`. Plain files, JPEGs, blobs
(`kinds/blob.py` writes them) and files of the kind `bare`, which brings
no function and so stands for "as a plain file"."""

from __future__ import annotations

from benchmark.generators.common import seed_words


def plan(config: dict, seed: int, scale: float = 1.0) -> list[dict]:
    def count(key: str) -> int:
        return max(1, int(config[key] * scale))

    image = config["image"]
    manifest: list[dict] = [{
        "rel": f"docs/note_{i:03d}.txt", "size": 900 + 37 * i,
        "content": seed_words(seed, i),
    } for i in range(count("plain"))]
    manifest += [{
        "rel": f"pictures/img_{i:03d}.{image['format']}", "size": 0,
        "content": seed_words(seed, 1 << 30 | i),
        "image": {"w": image["width"], "h": image["height"],
                  "format": image["format"],
                  "orientation": image["orientation"], "blocky": False},
    } for i in range(count("images"))]
    manifest += [{
        "rel": f"blobs/b_{i:03d}.blob", "size": 0, "kind": "blob",
        "content": seed_words(seed, 1 << 29 | i), "blob": config["blob"],
    } for i in range(count("blobs"))]
    manifest += [{
        "rel": f"bare/r_{i:03d}.bin", "size": 150_000 + i, "kind": "bare",
        "content": seed_words(seed, 1 << 28 | i),
    } for i in range(count("bares"))]
    return manifest


def new_entry(config: dict, rng, manifest: list[dict], serial: int,
              seed: int) -> dict:
    return {"rel": f"docs/new_{serial:06d}.txt",
            "size": int(rng.integers(1, 4000)),
            "content": seed_words(seed, 1 << 27 | serial)}
