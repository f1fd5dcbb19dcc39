"""`photolib.raw` at a small size (ISSUE 27): a RAW shoot added as a
location, 24 sparse `.dng` frames of 25-40 MB and 2 exported JPEGs,
indexed as `sdx index --backend tpu` does (`Node` + `cli.index_location`)
and held against `benchmark/reference/`, which imports nothing of the
program. At this size the parts stay unsharded on the 8-device mesh and
the 57-chunk bucket pads to its 32-row rung."""

import asyncio
import json
import os

import pytest

from benchmark import check, harness
from benchmark.generators import common, raw_shoot
from benchmark.reference import blake3_np, cas_layout
from spacedrive_tpu import telemetry
from spacedrive_tpu.ops import cas

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (7, 2147483999, 3000000019)  # the last: over 32 signed bits
FRAMES, EXPORTS = 24, 2


def full_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "photolib_raw.json")) as f:
        return json.load(f)


def tiny_config() -> dict:
    config = full_config()
    config["frames"] = FRAMES
    config["exports"] = EXPORTS
    config["export"].update(width=640, height=427)
    return config


def frames_of(manifest):
    return [e for e in manifest if not e.get("image")]


# --- the generator ----------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_plan_gives_one_shape_and_other_bytes_for_every_seed(seed):
    config = tiny_config()
    m, other = raw_shoot.plan(config, seed), raw_shoot.plan(config, seed + 1)
    assert m == raw_shoot.plan(config, seed)
    assert len(frames_of(m)) == FRAMES and len(m) == FRAMES + EXPORTS
    assert [e["rel"] for e in m] == [e["rel"] for e in other]
    assert sorted(e["size"] for e in m) == sorted(e["size"] for e in other)
    assert [e["size"] for e in m] != [e["size"] for e in other]
    assert all(a["content"] != b["content"] for a, b in zip(m, other))
    contents = [tuple(e["content"]) for e in m]
    assert len(set(contents)) == len(m)  # no two files share content
    assert m[0]["rel"] == "DCIM/100CANON/IMG_0000.dng"
    assert m[-1]["rel"] == f"exports/IMG_{EXPORTS - 1:04d}.jpg"
    assert m[-1]["image"] == {"w": 640, "h": 427, "format": "jpg",
                              "orientation": 1, "blocky": False}


@pytest.mark.parametrize("seed", SEEDS)
def test_every_frame_takes_the_sampled_layout(seed):
    config = tiny_config()
    lo, hi = config["frame"]["min_bytes"], config["frame"]["max_bytes"]
    for e in frames_of(raw_shoot.plan(config, seed)):
        assert cas_layout.MINIMUM_FILE_SIZE < lo <= e["size"] <= hi
        assert cas_layout.message_len(e["size"]) == 57352


@pytest.mark.parametrize("scale,frames,exports", [
    (1.0, 2048, 32), (0.04, 81, 1), (0.001, 2, 1)])
def test_scale_shrinks_the_counts(scale, frames, exports):
    m = raw_shoot.plan(full_config(), 5, scale=scale)
    assert len(frames_of(m)) == frames and len(m) == frames + exports
    if frames > 1000:  # a new DCF folder every 1,000 frames
        assert m[1000]["rel"] == "DCIM/101CANON/IMG_1000.dng"


def test_no_traffic_adds_files_to_a_shoot():
    with pytest.raises(NotImplementedError):
        raw_shoot.new_entry(tiny_config(), None, [], 1, 7)


# --- the sampled read against the reference layout --------------------------


def _odd_jump_size(start: int) -> int:
    size = start
    while ((size - 2 * cas_layout.HEADER_OR_FOOTER_SIZE)
           // cas_layout.SAMPLE_COUNT) % 2 == 0:
        size += 1
    return size


@pytest.mark.parametrize("size", [
    102400, 102401, _odd_jump_size(25_000_000), _odd_jump_size(32_500_001),
    _odd_jump_size(39_999_990)],
    ids=["100KiB", "100KiB+1", "25MB", "32.5MB", "40MB"])
def test_read_message_is_the_reference_layout_on_a_sparse_file(tmp_path, size):
    entry = {"rel": "f.dng", "size": size, "content": [11, 0, size & 0xFFFF]}
    path = str(tmp_path / entry["rel"])
    common.write_plain(path, size, entry["content"])
    assert os.path.getsize(path) == size
    got = cas.read_message(path, size)
    assert got == cas_layout.message(path) == check.plain_message(entry)
    assert len(got) == cas_layout.message_len(size)
    assert cas.sample_ranges(size) == cas_layout.ranges(size)


# --- one index pass of the tiny location ------------------------------------


async def _index(data_dir: str, location: str) -> dict:
    from spacedrive_tpu import cli
    from spacedrive_tpu.node import Node

    node = Node(data_dir, use_device=True)
    node.config.config.p2p.enabled = False
    await node.start()
    try:
        return await cli.index_location(node, location, "raw", "tpu")
    finally:
        await node.shutdown()


def _indexed(root, seed: int = SEEDS[1]) -> dict:
    """Write the tiny location, index it once, read the library back
    with sqlite3 alone."""
    from spacedrive_tpu.parallel import autotune

    location, data_dir = str(root / "location"), str(root / "node")
    os.makedirs(location)
    manifest = raw_shoot.plan(tiny_config(), seed)
    common.write_manifest(location, manifest)
    autotune.reset()
    # a fresh registry: `sd_span_seconds` holds 64 series, and a path first
    # seen after those a worker's earlier test files left folds into
    # `__overflow__` (PERF.md §7), which the cases below would read as absent
    telemetry.reset()
    before = harness.flat_counters()
    summary = asyncio.run(_index(data_dir, location))
    counters = {k: v - before.get(k, 0.0)
                for k, v in harness.flat_counters().items()}
    db = check.library_db(data_dir)
    try:
        rows = {check._rel(r): dict(r) for r in db.execute(
            "SELECT materialized_path, name, extension, cas_id, object_id "
            "FROM file_path WHERE is_dir = 0")}
        kinds = dict(db.execute("SELECT id, kind FROM object").fetchall())
        embedded = {r[0] for r in db.execute(
            "SELECT object_id FROM object_embedding")}
    finally:
        db.close()
    thumbs = {n for _d, _dirs, names in os.walk(
        os.path.join(data_dir, "thumbnails")) for n in names
        if n.endswith(".webp")}
    return {"location": location, "manifest": manifest, "summary": summary,
            "counters": counters, "rows": rows, "kinds": kinds,
            "embedded": embedded, "thumbs": thumbs,
            "want": check.reference_cas(location, manifest)}


@pytest.fixture(scope="module")
def indexed(tmp_path_factory):
    return _indexed(tmp_path_factory.mktemp("raw_shoot"))


def test_every_file_has_a_row_and_no_other(indexed):
    assert set(indexed["rows"]) == {e["rel"] for e in indexed["manifest"]}


def test_every_cas_id_is_blake3_over_the_reference_layout(indexed):
    got = {rel: r["cas_id"] for rel, r in indexed["rows"].items()}
    assert got == indexed["want"]
    # the reference, restated here for one frame: from the file's bytes
    frame = frames_of(indexed["manifest"])[0]
    message = cas_layout.message(os.path.join(indexed["location"], frame["rel"]))
    assert len(message) == 57352
    assert blake3_np.hash_many([message], 8)[0].hex() == got[frame["rel"]]


def test_every_file_has_an_object_and_no_two_frames_share_one(indexed):
    objects = [r["object_id"] for r in indexed["rows"].values()]
    assert None not in objects
    assert len(set(objects)) == len(objects) == FRAMES + EXPORTS


def test_a_frame_is_an_image_object(indexed):
    from spacedrive_tpu.files.kind import ObjectKind

    for rel, r in indexed["rows"].items():
        assert indexed["kinds"][r["object_id"]] == int(ObjectKind.Image), rel


def test_the_exports_have_a_webp_and_an_embedding_row(indexed):
    for e in indexed["manifest"][FRAMES:]:
        r = indexed["rows"][e["rel"]]
        assert r["cas_id"] + ".webp" in indexed["thumbs"]
        assert r["object_id"] in indexed["embedded"]


def test_no_frame_has_a_thumbnail_or_an_embedding(indexed):
    for e in frames_of(indexed["manifest"]):
        r = indexed["rows"][e["rel"]]
        assert r["cas_id"] + ".webp" not in indexed["thumbs"]
        assert r["object_id"] not in indexed["embedded"]
    assert len(indexed["thumbs"]) == len(indexed["embedded"]) == EXPORTS


def test_the_jobs_complete_on_the_device_path_without_a_thumbnail_error(indexed):
    s = indexed["summary"]
    assert s["jobs"] == {"indexer": "COMPLETED", "file_identifier": "COMPLETED",
                         "media_processor": "COMPLETED"}
    assert s["jobs_failed"] == 0 and s["files"] == FRAMES + EXPORTS
    assert s["thumbnail_errors"] == 0
    assert s["cas_backend_fallbacks"] == 0 and s["ladder_level"] == 0
    assert s["thumbnail_cpu_fallbacks"] == 0
    assert s["thumbnails"] == EXPORTS


def test_a_moved_sample_changes_every_frames_cas_id(tmp_path, monkeypatch):
    """One sample range read a byte late, where the program computes it."""
    real = cas.sample_ranges

    def moved(size):
        ranges = real(size)
        if len(ranges) > 1:
            ranges[2] = (ranges[2][0] + 1, ranges[2][1])
        return ranges

    monkeypatch.setattr(cas, "sample_ranges", moved)
    run = _indexed(tmp_path)
    frames = [e["rel"] for e in frames_of(run["manifest"])]
    assert all(run["rows"][rel]["cas_id"] != run["want"][rel] for rel in frames)
    assert all(len(run["rows"][rel]["cas_id"]) == 16 for rel in frames)


# --- the new counters add up ------------------------------------------------


def _dispatches(counters: dict, family: str) -> dict[tuple[int, int], float]:
    head = family + "{"
    out = {}
    for key, v in counters.items():
        if key.startswith(head) and v:
            labels = dict(kv.split("=") for kv in key[len(head):-1].split(","))
            out[int(labels["chunks"]), int(labels["rung"])] = v
    return out


def test_messages_are_counted_by_layout(indexed):
    sizes = [e["size"] for e in indexed["manifest"]]
    c = indexed["counters"]
    sampled = sum(s > cas_layout.MINIMUM_FILE_SIZE for s in sizes)
    assert sampled >= FRAMES
    assert c["sd_identifier_messages_total{layout=sampled}"] == sampled
    assert c["sd_identifier_messages_total{layout=whole}"] == len(sizes) - sampled


def test_filled_rows_are_the_hashed_files(indexed):
    rows = _dispatches(indexed["counters"], "sd_cas_dispatch_rows_total")
    assert sum(rows.values()) == FRAMES + EXPORTS
    sampled = indexed["counters"]["sd_identifier_messages_total{layout=sampled}"]
    assert rows[57, 32] == sampled  # one part, the smallest rung, unsharded


def test_dispatched_bytes_are_the_padded_arrays(indexed):
    c = indexed["counters"]
    rows = _dispatches(c, "sd_cas_dispatch_rows_total")
    nbytes = _dispatches(c, "sd_cas_dispatch_bytes_total")
    assert set(nbytes) == set(rows)
    # every (bucket, rung) here was dispatched once
    assert nbytes == {(chunks, rung): rung * chunks * 1024
                      for chunks, rung in rows}
    staged = c["sd_feeder_h2d_bytes_total"]
    assert staged == sum(cas_layout.message_len(e["size"])
                         for e in indexed["manifest"])
    assert staged < sum(nbytes.values())


def test_chunk_cache_is_observed_once_a_window(indexed):
    c = indexed["counters"]
    windows = c["sd_identifier_stage_seconds{stage=read}.count"]
    assert windows >= 1
    assert c["sd_identifier_stage_seconds{stage=chunk_cache}.count"] == windows
    assert 0 < c["sd_identifier_stage_seconds{stage=chunk_cache}.sum"] \
        < c["sd_span_seconds{stage=feeder.fetch.identify.rows}.sum"]


def test_journal_record_is_a_span_under_identify_db(indexed):
    c = indexed["counters"]
    key = "sd_span_seconds{stage=identify.db.journal.record}"
    assert c[key + ".count"] == c["sd_span_seconds{stage=identify.db}.count"] >= 1
    assert 0 < c[key + ".sum"] <= c["sd_span_seconds{stage=identify.db}.sum"]
    # the journal's commit files under it
    assert c["sd_span_seconds{stage=identify.db.journal.record.db.txn}.count"] >= 1


# --- a frame is an image nothing decodes ------------------------------------


def test_dng_resolves_to_the_image_kind():
    from spacedrive_tpu.files.kind import ObjectKind
    from spacedrive_tpu.object.file_identifier.link import kind_for_row

    from spacedrive_tpu.object.media.thumbnail import process

    assert kind_for_row({"extension": "dng"}) == ObjectKind.Image
    assert not process.can_generate("dng") and not process.can_generate("DNG")


@pytest.mark.parametrize("name", [
    "IMAGE_EXTENSIONS", "VIDEO_EXTENSIONS", "DOC_EXTENSIONS",
    "THUMBNAILABLE_EXTENSIONS", "EXIF_EXTENSIONS", "MEDIA_DATA_EXTENSIONS"])
def test_dng_is_in_no_thumbnailable_set(name):
    from spacedrive_tpu.object.media import job
    from spacedrive_tpu.object.media.thumbnail import process

    extensions = getattr(process, name, None) or getattr(job, name)
    assert extensions and "dng" not in extensions


# --- the six readers on a hand-made ctx -------------------------------------

#: two passes of the full cell as the counters would read them: two
#: windows of 1,024 frames at the top rung and the 32 exports a pass
CELL = {
    "sd_identifier_messages_total{layout=sampled}": 2 * 2048.0,
    "sd_identifier_messages_total{layout=whole}": 2 * 32.0,
    "sd_cas_dispatch_rows_total{chunks=57,rung=1024}": 2 * 2048.0,
    "sd_cas_dispatch_rows_total{chunks=101,rung=32}": 2 * 32.0,
    "sd_cas_dispatch_bytes_total{chunks=57,rung=1024}": 4 * 1024.0 * 57 * 1024,
    "sd_cas_dispatch_bytes_total{chunks=101,rung=32}": 2 * 32.0 * 101 * 1024,
    "sd_feeder_h2d_bytes_total": 2 * (2048 * 57352.0 + 32 * 90008),
    "sd_identifier_stage_seconds{stage=dispatch}.sum": 0.25,
    "sd_identifier_stage_seconds{stage=chunk_cache}.sum": 0.416,
    "sd_span_seconds{stage=identify.db.journal.record}.sum": 0.832,
    "sd_span_seconds{stage=identify.db.journal.record}.count": 6.0,
}
DISPATCHED = 4 * 1024 * 57 * 1024 + 2 * 32 * 101 * 1024
WANT = {
    "sampled_message_share": 100.0 * 2048 / 2080,
    "hash_top_rung_share": 100.0 * 2048 / 2080,
    "hash_pad_share": 100.0 * (1 - CELL["sd_feeder_h2d_bytes_total"] / DISPATCHED),
    "hash_link_gbps": DISPATCHED / 0.25 / 1e9,
    "chunk_cache_us_per_file": 100.0,
    "journal_record_us_per_file": 200.0,
}
#: what a program without this PR's counters has of the same families
PARENT = {
    "sd_feeder_h2d_bytes_total": CELL["sd_feeder_h2d_bytes_total"],
    "sd_identifier_stage_seconds{stage=dispatch}.sum": 0.25,
    "sd_identifier_stage_seconds{stage=read}.sum": 1.5,
    "sd_span_seconds{stage=identify.db}.sum": 2.0,
}


def _ctx(counters: dict) -> dict:
    return {"counters": counters, "hashed": {"files": 4160, "bytes": 0},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_its_value(name):
    read = harness.Bench(ROOT).reader(name)
    assert read(_ctx(CELL)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_none_without_its_counter(name):
    read = harness.Bench(ROOT).reader(name)
    assert read(_ctx(PARENT)) is None
    assert read(_ctx({})) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_is_declared_for_the_two_cells_that_hash(name):
    """As PR 27 listed them; a later cell that hashes appends itself."""
    declared = {m["name"]: m for m in harness.Bench(ROOT).doc["per_layer"]}
    assert declared[name]["workloads"][:2] == ["photolib.raw", "homedir.cold"]
    assert declared[name]["moves"] == "pass_rate"


def test_a_sharded_top_rung_counts_as_the_top_rung():
    read = harness.Bench(ROOT).reader("hash_top_rung_share")
    counters = {"sd_cas_dispatch_rows_total{chunks=57,rung=4096}": 3000.0,
                "sd_cas_dispatch_rows_total{chunks=57,rung=1024}": 100.0,
                "sd_cas_dispatch_rows_total{chunks=57,rung=32}": 100.0}
    ctx = _ctx(counters)
    ctx["device"]["count"] = 4  # 1,024 there is 256 rows a device
    assert read(ctx) == pytest.approx(100.0 * 3000 / 3200)
