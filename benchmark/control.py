"""python3 benchmark/control.py --workload <name> --seeds 1,2,3

The controls of `correct`: the plain reference put in the program's
place and computed one notch below what the configuration states, read
by the same comparison at the cell's own size. Each has to come out as
not correct. The benchmark's runs do not run this; `tests/test_control.py`
keeps it at a size a test run can hold. It needs no chip: every control
is host arithmetic on the location a seed gives.

  thumbnail  the reference without the EXIF orientation the
             configuration guarantees, through webp at the stated quality
  embedding  the forward pass with float8 (e4m3) matmul operands where
             the configuration states bfloat16
  cas_id     BLAKE3 over the file's bytes without the 8-byte size prefix
             the upstream layout states (an exact comparison: limit 0)

A kind of file a configuration lists brings the control of its own
guarantee: `control(config, entries, location, seed) -> {name: [value,
limit]}`, its reference a notch below, printed beside these three.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, harness  # noqa: E402
from benchmark.generators.common import (  # noqa: E402
    entries_of, is_plain, write_manifest)
from benchmark.reference import blake3_np, cas_layout, media  # noqa: E402


def readings(config: dict, generator, seed: int, work: str,
             kinds: dict | None = None) -> dict:
    """Control readings for one seed, beside the limits they must pass.
    `kinds` are the configuration's kind modules by name."""
    kinds = kinds or {}
    location = os.path.join(work, "control-location")
    shutil.rmtree(location, ignore_errors=True)
    os.makedirs(location)
    try:
        manifest = generator.plan(config, seed)
        images = [e for e in manifest if e.get("image")]
        write_manifest(location, [e for e in manifest if not is_plain(e)],
                       kinds)
        sample = check.media_sample(images, seed)
        upstream = config["upstream"]["thumbnail"]
        refs = check.media_references(location, sample, upstream["target_px"])
        no_exif = check.media_references(location, sample,
                                         upstream["target_px"], "thumbnail")
        fp8 = check.media_references(location, sample, upstream["target_px"],
                                     "embedding")
        # a turned image (orientation 5-8) has another shape and fails the
        # exact size check; the reading is of the images that keep theirs
        thumb = max(media.thumbnail_gap(
            media.encode_webp(no_exif[r]["pixels"], upstream["webp_quality"]),
            refs[r]["pixels"]) for r in refs
            if no_exif[r]["pixels"].shape == refs[r]["pixels"].shape)
        # what the codec alone costs: the reference through webp
        codec = max(media.thumbnail_gap(
            media.encode_webp(refs[r]["pixels"], upstream["webp_quality"]),
            refs[r]["pixels"]) for r in refs)
        embed = max(media.embed_gap(fp8[r]["vector"], refs[r]["vector"])
                    for r in refs)
        plain = [e for e in manifest if is_plain(e)][:256]
        want = check.reference_cas(location, plain)
        no_prefix = blake3_np.hash_many(
            [check.plain_message(e)[8:] for e in plain], cas_layout.CAS_HEX // 2)
        cas = sum(d.hex() != want[e["rel"]] for e, d in zip(plain, no_prefix))
        out = {
            "seed": seed, "sample": len(sample),
            "thumbnail_pixel_gap": [thumb, check.THUMB_GAP_LIMIT],
            "thumbnail_codec_alone": codec,
            "embedding_gap": [embed, check.EMBED_GAP_LIMIT],
            "cas_mismatch": [cas, 0] if plain else None,
        }
        for kind, mod in kinds.items():
            if not hasattr(mod, "control"):
                continue
            own = mod.control(config, entries_of(manifest, kind), location,
                              seed)
            if set(own) & set(out):
                raise SystemExit(f"benchmark: the kind {kind!r} gives a "
                                 f"control under {sorted(set(own) & set(out))}"
                                 ", which control.py holds")
            out.update(own)
        return out
    finally:
        shutil.rmtree(location, ignore_errors=True)


def not_correct(reading: dict) -> dict:
    """Which numbers the control fails."""
    return {k: v[0] > v[1] for k, v in reading.items()
            if isinstance(v, list)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = harness.Bench(ROOT)
    spec = bench.cell(args.workload)
    generator = bench.generator(spec["config"])
    kinds = bench.kinds(spec["config"])
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(spec["config"], generator, seed, harness.WORK, kinds)
        print(json.dumps({**r, "workload": args.workload,
                          "fails": not_correct(r)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
