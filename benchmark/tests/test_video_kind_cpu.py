"""`photolib.video` on the CPU: the generator's plan (the same shape for
every seed, the shot rule at full size and at the tiny one), what
`kinds/video.py` writes, the programs it names, a tiny whole run through
the harness that is correct, a run whose clips' thumbnails are taken
from frame 0 that is not, and every control incorrect. The fixture is
this file's own, as `test_raw_shoot_cpu.py`'s is: clips of 320 x 180 (a
portrait one is 180 wide, no multiple of 16, as 1080 is)."""

import json
import os

import numpy as np
import pytest

from benchmark import control, harness
from benchmark.generators import clip_roll
from benchmark.generators.common import entries_of, write_manifest
from benchmark.reference import video as ref
from benchmark.tests.conftest import ROOT, cpu_stamp

SEED = 2147483999
BIG = 3000000019  # more than 32 signed bits hold


def full_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "photolib_video.json")) as f:
        return json.load(f)


def tiny_config() -> dict:
    """The configuration at a size a test run can hold: 8 clips of 320 x
    180 and a 640 x 480 still; rate, durations, cuts and the share of
    portrait clips stay."""
    config = full_config()
    config["clips"] = 8
    config["clip"].update(width=320, height=180)
    config["photo"].update(width=640, height=480)
    return config


@pytest.fixture()
def video_root(tmp_path):
    """A checkout's worth of benchmark files that holds this one
    configuration, tiny, and its cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"] = [c for c in doc["configs"]
                      if c["name"] == "photolib_video"]
    doc["workloads"] = [w for w in doc["workloads"]
                        if w["name"] == "photolib.video"]
    doc["configs"][0]["file"] = "tiny_photolib_video.json"
    with open(tmp_path / "tiny_photolib_video.json", "w") as f:
        json.dump(tiny_config(), f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(doc, f)
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    return str(tmp_path)


def run(video_root, tmp_path):
    return harness.run_cell("photolib.video", SEED, 1.0, False,
                            root=video_root, require=cpu_stamp,
                            work=str(tmp_path / "work"))


def failing(result) -> set:
    return {k for k, (v, lim) in result["compared"].items() if v > lim}


def written(tmp_path, config=None, seed=SEED):
    config = config or tiny_config()
    bench = harness.Bench(ROOT)
    kinds = bench.kinds(config)
    location = str(tmp_path / "location")
    os.makedirs(location)
    manifest = clip_roll.plan(config, seed)
    write_manifest(location, manifest, kinds)
    return config, location, manifest, kinds


# --- the generator's plan ---------------------------------------------------


@pytest.mark.parametrize("config", [full_config(), tiny_config()],
                         ids=["full", "tiny"])
def test_plan_has_the_same_shape_for_every_seed(config):
    a, b = clip_roll.plan(config, 1), clip_roll.plan(config, BIG)
    assert clip_roll.plan(config, BIG) == b
    assert [e["rel"] for e in a] == [e["rel"] for e in b]
    va, vb = ([e["video"] for e in entries_of(m, "video")] for m in (a, b))
    assert sorted(v["frames"] for v in va) == sorted(v["frames"] for v in vb)
    assert [v["frames"] for v in va] != [v["frames"] for v in vb]
    assert [(v["w"], v["h"]) for v in va] == [(v["w"], v["h"]) for v in vb]
    assert [e.get("image") for e in a] == [e.get("image") for e in b]
    assert all(x["content"] != y["content"] for x, y in zip(a, b))


def test_full_plan_is_the_deployment():
    config = full_config()
    manifest = clip_roll.plan(config, BIG)
    clips = entries_of(manifest, "video")
    stills = [e for e in manifest if e.get("image")]
    assert (len(manifest), len(clips), len(stills)) == (108, 96, 12)
    assert sum((e["video"]["w"], e["video"]["h"]) == (1080, 1920)
               for e in clips) == 24
    assert all((e["video"]["w"], e["video"]["h"]) in ((1920, 1080),
                                                     (1080, 1920))
               and e["video"]["fps"] == 30 for e in clips)
    frames = [e["video"]["frames"] for e in clips]
    assert min(frames) >= 150 and max(frames) <= 600
    assert 170 <= float(np.median(frames)) <= 200  # a median of 6 s
    assert all(e["image"]["w"] == 4032 and e["image"]["h"] == 3024
               for e in stills)


@pytest.mark.parametrize("config", [full_config(), tiny_config()],
                         ids=["full", "tiny"])
@pytest.mark.parametrize("seed", [1, SEED, BIG])
def test_the_shot_rule_holds(config, seed):
    """Frame 0, every frame a decoder may take for the mark and the
    middle frame: three pictures, whichever decoder ran."""
    for e in entries_of(clip_roll.plan(config, seed), "video"):
        v = e["video"]
        assert clip_roll.shots_apart(v), e
        mark = ref.mark_frame(v["frames"])
        key = mark - mark % v["key_interval"]
        shots = [clip_roll.shot_of(v, f) for f in (0, key, mark,
                                                   v["frames"] // 2)]
        assert shots[1] == shots[2] and len(set(shots)) == 3, e


def test_a_cut_before_the_mark_breaks_the_rule():
    v = entries_of(clip_roll.plan(tiny_config(), 3), "video")[0]["video"]
    mark = ref.mark_frame(v["frames"])
    assert not clip_roll.shots_apart({**v, "cuts": [5, mark, v["cuts"][2]]})
    assert not clip_roll.shots_apart({**v, "cuts": [mark, *v["cuts"][1:]]})


# --- what is written --------------------------------------------------------


def test_written_clips_are_what_the_plan_says(tmp_path):
    import cv2

    _config, location, manifest, _kinds = written(tmp_path)
    for e in entries_of(manifest, "video"):
        v, path = e["video"], os.path.join(location, e["rel"])
        assert e["size"] == os.path.getsize(path) > 0
        cap = cv2.VideoCapture(path)
        try:
            assert int(cap.get(cv2.CAP_PROP_FRAME_COUNT)) == v["frames"]
            assert int(cap.get(cv2.CAP_PROP_FRAME_WIDTH)) == v["w"]
            assert int(cap.get(cv2.CAP_PROP_FRAME_HEIGHT)) == v["h"]
            assert cap.get(cv2.CAP_PROP_FPS) == pytest.approx(v["fps"])
        finally:
            cap.release()
        # three pictures on disk too: two shots are far apart, two
        # frames of one shot (the regular key frame and the mark) near
        mark = ref.mark_frame(v["frames"])
        key = mark - mark % v["key_interval"]
        at = {f: ref.frame_at(path, f).astype(np.int16)
              for f in (0, key, mark, v["frames"] // 2)}
        gap = lambda a, b: float(np.abs(at[a] - at[b]).mean())  # noqa: E731
        assert gap(key, mark) < 3
        assert gap(0, mark) > 20 and gap(v["frames"] // 2, mark) > 20, e


def test_same_seed_same_bytes(tmp_path):
    config = tiny_config()
    config["clips"] = 2
    bench = harness.Bench(ROOT)
    for d in ("a", "b"):
        os.makedirs(tmp_path / d)
        write_manifest(str(tmp_path / d), clip_roll.plan(config, 5),
                       bench.kinds(config))
    for e in clip_roll.plan(config, 5):
        with open(tmp_path / "a" / e["rel"], "rb") as f, \
                open(tmp_path / "b" / e["rel"], "rb") as g:
            assert f.read() == g.read()


def test_a_codec_the_writer_cannot_open_ends_set_up(tmp_path):
    config = tiny_config()
    config["clip"]["codec"] = "zzzz"
    bench = harness.Bench(ROOT)
    os.makedirs(tmp_path / "location")
    with pytest.raises(SystemExit, match="zzzz"):
        write_manifest(str(tmp_path / "location"),
                       clip_roll.plan(config, 5)[:1], bench.kinds(config))


# --- programs ---------------------------------------------------------------


def test_programs_are_named_from_the_programs_own_tables(tmp_path):
    from spacedrive_tpu.ops import thumbnail_jax as tj

    config, location, manifest, kinds = written(tmp_path)
    clips = entries_of(manifest, "video")
    own = kinds["video"].programs(clips, location, 1)
    bh, bw = tj.bucket_for(180, 320)
    # 8 clips, landscape and portrait in one canvas, RGBA as decoded
    assert [name for _w, name, _fn in own] == [
        f"video_resize_{bh}x{bw}x4_pad{pad}" for pad in (1, 2, 4, 8)]
    assert [w for w, _name, _fn in own] == sorted(w for w, _n, _f in own)
    for _w, _name, fn in own[:2]:
        fn()  # runs one to its end
    # the full-size frame reaches the canvas no other cell dispatches
    assert tj.bucket_for(1080, 1920) == tj.bucket_for(1920, 1080) \
        == (2048, 2048)


def test_the_probe_takes_a_sound_decoder(tmp_path):
    from spacedrive_tpu import native

    if not native.video_available():
        pytest.skip("libav is absent: the program decodes with cv2")
    _config, location, manifest, kinds = written(tmp_path)
    portrait = next(e for e in entries_of(manifest, "video")
                    if e["video"]["w"] % 16)
    assert kinds["video"].decoder_is_sound(
        os.path.join(location, portrait["rel"]), ROOT)
    assert not kinds["video"].decoder_is_sound(
        os.path.join(location, "no-such-clip.mp4"), ROOT)


# --- the whole run and the controls -----------------------------------------


def test_sound_run_is_correct(video_root, tmp_path):
    r = run(video_root, tmp_path)
    assert r["correct"] is True, failing(r)
    assert r["failed"] == 0 and r["attempted"] >= 9
    assert set(r["metrics"]) == {"pass_rate", "setup_s"}
    own = {k for k in r["compared"] if k.startswith("video_")}
    assert own == {
        "video_thumbnail_missing", "video_thumbnail_wrong_size",
        "video_strip_missing", "video_frame_gap", "video_kind_wrong",
        "video_media_data_missing", "video_facts_wrong", "video_embedded"}
    assert all(r["compared"][k][0] == 0 for k in own - {"video_frame_gap"})
    assert 0 < r["compared"]["video_frame_gap"][0] < 12


def test_first_frame_is_not_correct(video_root, tmp_path, monkeypatch):
    """The program takes every clip's thumbnail from frame 0: no seek."""
    from spacedrive_tpu.object.media.thumbnail import process

    monkeypatch.setattr(process, "VIDEO_SEEK_FRACTION", 0.0)
    r = run(video_root, tmp_path)
    assert r["correct"] is False
    assert failing(r) == {"video_frame_gap"} and r["failed"] == 0


def test_no_strips_and_no_probe_are_not_correct(video_root, tmp_path,
                                                monkeypatch):
    """The strips left off, and the media job's probe finding nothing."""
    from spacedrive_tpu.object.media import media_data
    from spacedrive_tpu.object.media.thumbnail import process

    monkeypatch.setattr(process, "apply_film_strip", lambda arr: arr)
    monkeypatch.setattr(media_data.VideoMetadata, "from_path",
                        classmethod(lambda cls, path: None))
    r = run(video_root, tmp_path)
    assert r["correct"] is False
    assert failing(r) == {"video_strip_missing", "video_media_data_missing"}
    assert r["failed"] > 0


def test_wrong_facts_are_not_correct(video_root, tmp_path, monkeypatch):
    """A probe that reports half the frames."""
    from spacedrive_tpu.object.media import media_data

    real = media_data.VideoMetadata.from_path.__func__

    def halved(cls, path):
        meta = real(cls, path)
        meta.frame_count //= 2
        return meta

    monkeypatch.setattr(media_data.VideoMetadata, "from_path",
                        classmethod(halved))
    r = run(video_root, tmp_path)
    assert r["correct"] is False and failing(r) == {"video_facts_wrong"}


@pytest.mark.parametrize("seed", [5, SEED, BIG])
def test_controls_fail(tmp_path, seed):
    bench = harness.Bench(ROOT)
    config = tiny_config()
    r = control.readings(config, bench.generator(config), seed, str(tmp_path),
                         bench.kinds(config))
    fails = control.not_correct(r)
    # the stills' own controls are `test_control.py`'s: the one still of
    # the tiny location has EXIF orientation 1, so leaving it out is sound
    for name in ("video_frame_gap_frame0", "video_frame_gap_middle",
                 "video_strip_missing", "video_thumbnail_wrong_size"):
        assert fails[name], r
    # the codec alone stays inside the limit, or sound runs could not
    assert r["video_frame_gap_codec_alone"] < r["video_frame_gap_frame0"][1]
