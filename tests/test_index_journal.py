"""Incremental indexing: index journal + dirty-range rehash.

Covers the PR-7 acceptance surface:
- dirty-range rehash is bit-identical to a full rehash (golden), and
  steady-state work is proportional to the changed bytes;
- a warm pass over an unchanged location re-reads ZERO bytes (journal
  hits), while a mutated file is re-hashed to the correct cas_id (the
  pre-journal pipeline kept the stale cas forever);
- torn/corrupt journal state degrades to a cold pass — never a wrong
  or stale cas_id;
- a `thumbnail.persist` injected crash leaves the journal consistent on
  cold-resume (no vouch for an unstored thumb);
- duplicates/orphan-remover consult the journal (phash reuse, orphan
  pruning);
- the watcher's targeted invalidations (stale / rename / delete).
"""

import asyncio
import os

import numpy as np
import pytest

from spacedrive_tpu.jobs import JobManager
from spacedrive_tpu.location.indexer import journal as journal_mod
from spacedrive_tpu.location.indexer.journal import (
    Identity,
    IndexJournal,
    key_of,
    prune_orphans,
)
from spacedrive_tpu.location.locations import LocationCreateArgs, scan_location
from spacedrive_tpu.node import Libraries
from spacedrive_tpu.ops import cas
from spacedrive_tpu.ops.cas import cas_id_cpu
from spacedrive_tpu.tasks import TaskSystem
from spacedrive_tpu.telemetry import counter_value


# --- dirty-range rehash (ops/cas.py) ---------------------------------------


def test_dirty_range_bit_identical_golden():
    """Mutations in and out of sampled ranges, repeated passes, small
    and large files: the dirty-range cas_id always equals the full
    rehash."""
    import random

    rng = random.Random(5)
    for size in (300_000, 150_000, 40_000, 2_000):
        data = bytearray(os.urandom(size))
        msg = cas.message_from_bytes(bytes(data), size)
        cache = cas.build_chunk_cache(msg)
        for _ in range(3):
            off = rng.randrange(0, size)
            data[off] = (data[off] + 1) % 256
            msg = cas.message_from_bytes(bytes(data), size)
            got, cache, _dirty, _hashed = cas.dirty_range_rehash(msg, cache)
            assert got == cas.cas_id_from_bytes_cpu(bytes(data))


def test_dirty_range_work_proportional_to_change():
    """Steady state (CV tree cached): one mutated byte rehashes exactly
    one 1 KiB chunk of the 57,352-byte large-file message."""
    data = bytearray(os.urandom(300_000))
    msg = cas.message_from_bytes(bytes(data), len(data))
    cas_id, cache = cas.host_rehash_with_cache(msg)
    assert cas_id == cas.cas_id_from_bytes_cpu(bytes(data))
    data[100] ^= 1  # inside the 8 KiB header sample
    msg = cas.message_from_bytes(bytes(data), len(data))
    got, cache, dirty, hashed = cas.dirty_range_rehash(msg, cache)
    assert got == cas.cas_id_from_bytes_cpu(bytes(data))
    assert dirty == 1 and hashed == 1024

    # a mutation OUTSIDE every sampled range: zero dirty chunks, cas
    # unchanged (content-invisible to the sampling layout)
    data2 = bytearray(data)
    data2[20_000] ^= 1
    assert not any(
        o <= 20_000 < o + ln for o, ln in cas.sample_ranges(len(data2))
    )
    msg2 = cas.message_from_bytes(bytes(data2), len(data2))
    got2, _c, dirty2, hashed2 = cas.dirty_range_rehash(msg2, cache)
    assert got2 == got and dirty2 == 0 and hashed2 == 0


def test_dirty_range_refuses_message_length_change():
    # small file: message = header + whole file, so growing the file
    # changes the message length → dirty-range must refuse
    data = os.urandom(40_000)
    msg = cas.message_from_bytes(data, len(data))
    _, cache = cas.host_rehash_with_cache(msg)
    grown = data + b"x"
    with pytest.raises(ValueError):
        cas.dirty_range_rehash(
            cas.message_from_bytes(grown, len(grown)), cache
        )


def test_dirty_range_handles_large_file_size_change():
    # large files keep the FIXED 57,352-byte message across size
    # changes (the size header + freshly read samples are part of the
    # message), so dirty-range stays bit-identical even then
    data = os.urandom(200_000)
    msg = cas.message_from_bytes(data, len(data))
    _, cache = cas.host_rehash_with_cache(msg)
    grown = data + os.urandom(1000)
    got, _c, dirty, _h = cas.dirty_range_rehash(
        cas.message_from_bytes(grown, len(grown)), cache
    )
    assert got == cas.cas_id_from_bytes_cpu(grown)
    assert dirty >= 1  # at minimum the size-header chunk changed


def test_chunk_cache_payload_validation():
    """from_payload rejects every malformed shape (torn journal blobs
    must degrade to a cold pass, not a wrong cas)."""
    msg = cas.message_from_bytes(os.urandom(150_000), 150_000)
    _, cache = cas.host_rehash_with_cache(msg)
    good = cache.to_payload()
    assert cas.ChunkCache.from_payload(good) is not None
    bad = [
        None, [], "x", {},
        {**good, "len": -1},
        {**good, "dig": good["dig"][:-1]},               # truncated
        {**good, "dig": [b"short"] * len(good["dig"])},  # wrong width
        {**good, "cvs": [[b"x" * 31] * 2]},              # torn CV
        {**good, "cvs": []},
    ]
    for payload in bad:
        assert cas.ChunkCache.from_payload(payload) is None


# --- scan-chain harness ----------------------------------------------------


def _build_tree(loc):
    rng = np.random.default_rng(9)
    (loc / "docs").mkdir(parents=True)
    (loc / "docs" / "a.txt").write_bytes(b"hello journal")
    (loc / "big.bin").write_bytes(
        rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    )
    (loc / "small.bin").write_bytes(
        rng.integers(0, 256, 9_000, dtype=np.uint8).tobytes()
    )
    (loc / "empty.txt").write_bytes(b"")
    from PIL import Image

    Image.new("RGB", (32, 24), (10, 200, 10)).save(loc / "green.png")


async def _scan(library, location, mgr, n_prev_jobs=0):
    job_id = await scan_location(library, location, mgr, backend="cpu")
    await mgr.wait(job_id)
    for _ in range(80):
        await mgr.wait_idle()
        rows = library.db.query("SELECT status FROM job")
        if len(rows) >= n_prev_jobs + 3 and all(
            r["status"] in (2, 6) for r in rows
        ):
            break
    return len(library.db.query("SELECT status FROM job"))


def _mk_library(tmp_path, node=None, name="jlib"):
    libs = Libraries(tmp_path / "data", node=node)
    return libs.create(name)


class _Node:
    image_labeler = None

    def __init__(self, data_dir):
        from spacedrive_tpu.object.media.thumbnail import Thumbnailer

        self.thumbnailer = Thumbnailer(data_dir, use_device=False)


@pytest.mark.asyncio
async def test_warm_pass_reads_nothing_and_rehashes_only_changes(
    tmp_path, monkeypatch
):
    loc_path = tmp_path / "stuff"
    _build_tree(loc_path)
    node = _Node(tmp_path / "data")
    library = _mk_library(tmp_path, node)
    mgr = JobManager(TaskSystem(2))
    location = LocationCreateArgs(path=str(loc_path)).create(library)

    from spacedrive_tpu.object.file_identifier import job as fi_job

    reads: list[str] = []
    real_read = fi_job._open_and_read

    def counting_read(path, size, want_identity):
        reads.append(os.fspath(path))
        return real_read(path, size, want_identity)

    # the identifier's one door to a file's bytes (open, identity from
    # the descriptor where the journal knows nothing, sampled preads)
    monkeypatch.setattr(fi_job, "_open_and_read", counting_read)

    n_jobs = await _scan(library, location, mgr)
    await node.thumbnailer.wait_library_batch(library.id)
    cold_reads = len(reads)
    assert cold_reads >= 3  # every non-empty file was read once
    assert library.db.count("index_journal") >= 5

    # ---- warm pass, nothing changed: ZERO message reads ----
    reads.clear()
    h0 = counter_value("sd_index_journal_ops_total", result="hit")
    n_jobs = await _scan(library, location, mgr, n_jobs)
    assert reads == []
    assert counter_value("sd_index_journal_ops_total", result="hit") > h0

    # ---- mutate the large file in place: only IT is re-read, its new
    # cas is bit-identical to a full rehash, and the object re-links ----
    big = loc_path / "big.bin"
    old_row = library.db.find_one("file_path", name="big", extension="bin")
    with open(big, "r+b") as f:
        f.seek(100)
        f.write(b"MUTATED")
    os.utime(big)  # ensure a visible mtime tick even on coarse clocks
    reads.clear()
    n_jobs = await _scan(library, location, mgr, n_jobs)
    assert [os.path.basename(p) for p in reads] == ["big.bin"]
    row = library.db.find_one("file_path", name="big", extension="bin")
    assert row["cas_id"] == cas_id_cpu(big)
    assert row["cas_id"] != old_row["cas_id"]  # stale-cas bug is fixed
    assert row["object_id"] is not None
    assert row["object_id"] != old_row["object_id"]

    # ---- third pass after another in-place mutation: the dirty-range
    # path hashes only the affected chunks, never the device ----
    with open(big, "r+b") as f:
        f.seek(50)
        f.write(b"AGAIN")
    os.utime(big)
    b0 = counter_value("sd_index_bytes_hashed_total")
    await _scan(library, location, mgr, n_jobs)
    hashed = counter_value("sd_index_bytes_hashed_total") - b0
    assert 0 < hashed < cas.LARGE_MSG_LEN  # strictly less than a full message
    row = library.db.find_one("file_path", name="big", extension="bin")
    assert row["cas_id"] == cas_id_cpu(big)

    await node.thumbnailer.shutdown()
    await mgr.system.shutdown()
    library.close()


@pytest.mark.asyncio
async def test_hidden_flag_change_keeps_cas(tmp_path):
    """A metadata-only change (hidden flag via rename is a different
    path — here: walker update with unchanged identity) must NOT clear
    the cas: the journal hit proves the content is untouched."""
    loc_path = tmp_path / "stuff"
    loc_path.mkdir()
    (loc_path / "keep.bin").write_bytes(os.urandom(5000))
    library = _mk_library(tmp_path)
    mgr = JobManager(TaskSystem(2))
    location = LocationCreateArgs(path=str(loc_path)).create(library)
    n = await _scan(library, location, mgr)
    row = library.db.find_one("file_path", name="keep", extension="bin")
    assert row["cas_id"] is not None

    # force the row into to_update WITHOUT touching the file: flip the
    # DB's hidden flag so the walker sees a difference
    library.db.update("file_path", {"id": row["id"]}, hidden=1)
    await _scan(library, location, mgr, n)
    after = library.db.find_one("file_path", name="keep", extension="bin")
    assert after["cas_id"] == row["cas_id"]  # journal hit → cas kept
    assert after["hidden"] == 0
    await mgr.system.shutdown()
    library.close()


@pytest.mark.asyncio
async def test_corrupt_journal_degrades_to_cold_pass(tmp_path):
    """Torn/corrupt journal rows (garbage payload) read as `bypassed`,
    are dropped, and the pass produces correct cas_ids the cold way."""
    loc_path = tmp_path / "stuff"
    _build_tree(loc_path)
    library = _mk_library(tmp_path)
    mgr = JobManager(TaskSystem(2))
    location = LocationCreateArgs(path=str(loc_path)).create(library)
    n = await _scan(library, location, mgr)
    assert library.db.count("index_journal") >= 4

    # tear every payload + identity blob (simulated torn/corrupt file)
    library.db.execute(
        "UPDATE index_journal SET payload = X'DEADBEEF', inode = X'00'"
    )
    b0 = counter_value("sd_index_journal_ops_total", result="bypassed")
    await _scan(library, location, mgr, n)
    assert counter_value("sd_index_journal_ops_total", result="bypassed") > b0
    for name, ext, p in (
        ("big", "bin", loc_path / "big.bin"),
        ("small", "bin", loc_path / "small.bin"),
        ("a", "txt", loc_path / "docs" / "a.txt"),
    ):
        row = library.db.find_one("file_path", name=name, extension=ext)
        assert row["cas_id"] == cas_id_cpu(p)  # never wrong, never stale
    # corrupt rows were dropped and re-recorded fresh (usable again)
    rows = library.db.query("SELECT payload FROM index_journal")
    assert all(r["payload"] != b"\xde\xad\xbe\xef" for r in rows)
    await mgr.system.shutdown()
    library.close()


@pytest.mark.asyncio
async def test_thumbnail_persist_crash_keeps_journal_consistent(tmp_path):
    """PR-6 fault point: a crash between chunk store and the journal
    write (the InjectedCrash models process death, so the media job's
    rendezvous — and its vouches — die with it). Invariant: the index
    journal NEVER claims a thumb the store doesn't hold, at the crash
    point and after the cold resume, and a fresh pass converges to
    all-stored + all-vouched."""
    from spacedrive_tpu.object.media.thumbnail import Thumbnailer
    from spacedrive_tpu.utils import faults

    loc_path = tmp_path / "stuff"
    loc_path.mkdir()
    from PIL import Image

    rng = np.random.default_rng(3)
    for i in range(6):
        Image.fromarray(
            rng.integers(0, 255, (40, 52, 3), dtype=np.uint8), "RGB"
        ).save(loc_path / f"p{i}.png")

    # phase 1: index + identify with NO thumbnailer — journal holds cas
    # vouches, zero thumb vouches
    class _Bare:
        thumbnailer = None
        image_labeler = None

    node = _Bare()
    library = _mk_library(tmp_path, node)
    mgr = JobManager(TaskSystem(2))
    location = LocationCreateArgs(path=str(loc_path)).create(library)
    n = await _scan(library, location, mgr)
    rows = library.db.query(
        "SELECT * FROM file_path WHERE is_dir = 0 AND cas_id IS NOT NULL"
    )
    assert len(rows) == 6
    journal = IndexJournal(library.db)
    lib_id = str(library.id)

    def vouched_thumbs() -> set[str]:
        out = set()
        for r in rows:
            _v, entry = journal.lookup(
                location["id"], key_of(r), None, count_invalidated=False
            )
            if entry is not None and entry.thumb:
                out.add(r["cas_id"])
        return out

    # phase 2: the "process" crashes between chunk store and journal
    # write while thumbnailing
    t1 = Thumbnailer(tmp_path / "data", use_device=False)
    t1._chunk_rows = 2
    loc_dir = str(loc_path)
    entries = [
        (r["cas_id"], os.path.join(loc_dir, f"{r['name']}.png"), "png")
        for r in rows
    ]
    with faults.active(
        faults.FaultPlan.parse("thumbnail.persist:crash:times=1")
    ):
        t1.new_indexed_thumbnails_batch(lib_id, entries)
        with pytest.raises(faults.InjectedCrash):
            await t1._worker  # process death mid-batch
    stored = {c for c, _p, _e in entries if t1.store.exists(lib_id, c)}
    assert 0 < len(stored) < len(entries)  # a partial prefix landed
    # the journal vouches NOTHING it cannot prove: vouches ⊆ stored
    assert vouched_thumbs() <= stored

    # phase 3: cold resume — fresh actor + fresh media pass; the job
    # vouches only store-verified thumbs, and everything converges
    node.thumbnailer = Thumbnailer(tmp_path / "data", use_device=False)
    await _scan(library, location, mgr, n)
    await node.thumbnailer.wait_library_batch(lib_id)
    await _scan(library, location, mgr, n + 3)  # vouch pass post-drain
    all_cas = {r["cas_id"] for r in rows}
    assert {c for c in all_cas if node.thumbnailer.store.exists(lib_id, c)} \
        == all_cas
    assert vouched_thumbs() == all_cas
    await node.thumbnailer.shutdown()
    await mgr.system.shutdown()
    library.close()


@pytest.mark.asyncio
async def test_warm_media_pass_skips_thumb_and_exif(tmp_path, monkeypatch):
    loc_path = tmp_path / "stuff"
    _build_tree(loc_path)
    node = _Node(tmp_path / "data")
    library = _mk_library(tmp_path, node)
    mgr = JobManager(TaskSystem(2))
    location = LocationCreateArgs(path=str(loc_path)).create(library)
    n = await _scan(library, location, mgr)
    await node.thumbnailer.wait_library_batch(library.id)

    from spacedrive_tpu.object.media import job as media_job

    extracts = []
    real = media_job.ImageMetadata.from_path

    def counting(path):
        extracts.append(path)
        return real(path)

    monkeypatch.setattr(media_job.ImageMetadata, "from_path",
                        staticmethod(counting))
    dispatched_before = node.thumbnailer.generated + node.thumbnailer.skipped
    await _scan(library, location, mgr, n)
    # warm pass: EXIF not re-extracted, thumbnail not re-dispatched
    assert extracts == []
    assert node.thumbnailer.generated + node.thumbnailer.skipped \
        == dispatched_before
    await node.thumbnailer.shutdown()
    await mgr.system.shutdown()
    library.close()


# --- journal unit surface --------------------------------------------------


def _memory_journal(tmp_path):
    lib = _mk_library(tmp_path)
    return lib, IndexJournal(lib.db)


def test_journal_lookup_verdicts_and_stale(tmp_path):
    lib, journal = _memory_journal(tmp_path)
    loc_id = lib.db.insert(
        "location", pub_id=os.urandom(16), name="l", path="/tmp/x"
    )
    key = ("/", "f", "bin")
    ident = Identity(1, 2, 3, 4)
    assert journal.lookup(loc_id, key, ident)[0] == "miss"
    journal.record_cas(loc_id, key, ident, "cafe" * 4)
    verdict, entry = journal.lookup(loc_id, key, ident)
    assert verdict == "hit" and entry.cas_id == "cafe" * 4
    # identity drift → invalidated (entry still returned)
    verdict, entry = journal.lookup(loc_id, key, Identity(1, 2, 99, 4))
    assert verdict == "invalidated" and entry is not None
    # watcher invalidation → stale even with a matching identity
    assert journal.mark_stale(loc_id, key) == 1
    verdict, _ = journal.lookup(loc_id, key, ident)
    assert verdict == "invalidated"
    # a fresh record clears the stale bit
    journal.record_cas(loc_id, key, ident, "beef" * 4)
    assert journal.lookup(loc_id, key, ident)[0] == "hit"
    lib.close()


#: what one entry (or none) reads as, asked under Identity(1, 2, 3, 4)
JUDGED = {"miss": "miss", "hit": "hit", "invalidated": "invalidated",
          "stale": "invalidated", "corrupt": "bypassed",
          "disabled": "bypassed"}


def _one_entry(tmp_path, case, monkeypatch):
    """A journal that holds one entry in the state `case` names (none
    for `miss`) → (library, journal, location id, key, identity asked)."""
    lib, journal = _memory_journal(tmp_path)
    loc_id = lib.db.insert(
        "location", pub_id=os.urandom(16), name="l", path="/tmp/x"
    )
    key, ident = ("/", "f", "bin"), Identity(1, 2, 3, 4)
    msg = cas.message_from_bytes(b"x" * 5000)
    if case != "miss":
        journal.record_cas(loc_id, key, ident, "cafe" * 4,
                           cas.build_chunk_cache(msg))
    if case == "invalidated":
        ident = Identity(1, 2, 99, 4)
    elif case == "stale":
        assert journal.mark_stale(loc_id, key) == 1
    elif case == "corrupt":
        lib.db.execute("UPDATE index_journal SET payload = X'00ff'")
    elif case == "disabled":
        monkeypatch.setenv("SD_INDEX_JOURNAL", "0")
    return lib, journal, loc_id, key, ident


@pytest.mark.parametrize("count_invalidated", [False, True],
                         ids=["reconsult", "first_consult"])
@pytest.mark.parametrize("case", sorted(JUDGED))
def test_a_windows_judge_is_lookup_less_its_read(tmp_path, monkeypatch, case,
                                                 count_invalidated):
    """The identifier reads a window's journal rows once (`fetch_rows`)
    and judges each file in memory (`judge`): verdict, entry, what is
    counted on `sd_index_journal_ops_total` and per location, and the
    corrupt row's drop are `lookup`'s, case by case."""
    from spacedrive_tpu import telemetry

    seen = {}
    for how in ("lookup", "window"):
        lib, journal, loc_id, key, asked = _one_entry(
            tmp_path / how, case, monkeypatch)
        telemetry.reset()
        if how == "lookup":
            verdict, entry = journal.lookup(
                loc_id, key, asked, count_invalidated=count_invalidated)
        else:
            rows = journal.fetch_rows(loc_id, [("/", "other", "bin"), key])
            assert (rows is None) == (case == "disabled")
            verdict, entry = journal.judge(
                loc_id, key, rows, asked, count_invalidated=count_invalidated)
            assert journal.judge(loc_id, ("/", "other", "bin"), rows, asked,
                                 count=False)[0] == (
                "bypassed" if case == "disabled" else "miss")
        seen[how] = (
            verdict, entry,
            {r: counter_value("sd_index_journal_ops_total", result=r)
             for r in ("hit", "miss", "invalidated", "bypassed")},
            list(journal_mod._LOC_RUNTIME.values()),
            lib.db.count("index_journal"),
        )
        lib.close()
        monkeypatch.delenv("SD_INDEX_JOURNAL", raising=False)
    assert seen["window"] == seen["lookup"]
    verdict, entry, ops, _per_location, left = seen["window"]
    assert verdict == JUDGED[case]
    assert (entry is not None) == (verdict in ("hit", "invalidated"))
    counted = count_invalidated or verdict != "invalidated"
    assert ops == {**dict.fromkeys(ops, 0.0), verdict: 1.0 if counted else 0.0}
    # nothing recorded, or the corrupt row dropped; switched off, the
    # journal is not read and its row stays
    assert left == (0 if case in ("miss", "corrupt") else 1)
    telemetry.reset()


def test_fetch_rows_asks_once_per_chunk_of_keys(tmp_path, monkeypatch):
    from spacedrive_tpu.db.database import blob_u64

    lib, journal = _memory_journal(tmp_path)
    loc_id = lib.db.insert(
        "location", pub_id=os.urandom(16), name="l", path="/tmp/x"
    )
    other = lib.db.insert(
        "location", pub_id=os.urandom(16), name="m", path="/tmp/y"
    )
    keys = [(f"/d{i % 9}/", f"f{i}", "bin" if i % 2 else "") for i in range(700)]
    journal.record_many(loc_id, [
        (k, Identity(i, 1, 1, 1), f"{i:016x}", None, None)
        for i, k in enumerate(keys) if i % 3 == 0])
    journal.record_cas(other, keys[1], Identity(9, 9, 9, 9), "ee" * 8)
    asked = []
    real = lib.db.query
    monkeypatch.setattr(lib.db, "query", lambda sql, params=(): (
        asked.append(len(params)), real(sql, params))[1])
    rows = journal.fetch_rows(loc_id, keys)
    # 300 + 300 + 100 keys of three parts, and the location
    assert asked == [901, 901, 301]
    assert sorted(rows) == sorted(k for i, k in enumerate(keys) if i % 3 == 0)
    assert all(blob_u64(rows[k]["inode"]) == keys.index(k) for k in rows)
    assert journal.fetch_rows(loc_id, []) == {} and len(asked) == 3
    lib.close()


@pytest.mark.asyncio
async def test_a_cold_pass_records_the_identity_the_walker_will_stat(
    tmp_path, monkeypatch
):
    """A cold pass takes each file's identity from the descriptor it
    reads it through. Field for field it is what a `stat` of the path
    gives, so the next pass's walker, which stats paths, finds every
    file a hit and the identifier opens nothing."""
    from spacedrive_tpu.object.file_identifier import job as fi_job

    loc_path = tmp_path / "stuff"
    _build_tree(loc_path)
    node = _Node(tmp_path / "data")
    library = _mk_library(tmp_path, node)
    mgr = JobManager(TaskSystem(2))
    location = LocationCreateArgs(path=str(loc_path)).create(library)
    d0 = counter_value("sd_identifier_identity_total", source="descriptor")
    p0 = counter_value("sd_identifier_identity_total", source="path")
    n_jobs = await _scan(library, location, mgr)
    await node.thumbnailer.wait_library_batch(library.id)
    rows = library.db.query("SELECT * FROM index_journal")
    assert len(rows) == 5  # a.txt, big.bin, small.bin, empty.txt, green.png
    for row in rows:
        name = row["name"] + ("." + row["extension"] if row["extension"] else "")
        st = os.stat(str(loc_path) + row["materialized_path"] + name)
        got = journal_mod.entry_of_row(row).identity
        assert (got.inode, got.dev, got.mtime_ns, got.size) == (
            st.st_ino, st.st_dev, st.st_mtime_ns, st.st_size), name
    # four by descriptor, the empty file by its path
    assert counter_value("sd_identifier_identity_total",
                         source="descriptor") - d0 == 4
    assert counter_value("sd_identifier_identity_total",
                         source="path") - p0 == 1

    def refuse(*a, **kw):
        raise AssertionError("the warm pass opened a file")

    monkeypatch.setattr(fi_job, "_open_and_read", refuse)
    h0 = counter_value("sd_index_journal_ops_total", result="hit")
    m0 = counter_value("sd_index_journal_ops_total", result="miss")
    i0 = counter_value("sd_index_journal_ops_total", result="invalidated")
    await _scan(library, location, mgr, n_jobs)
    assert counter_value("sd_index_journal_ops_total", result="hit") - h0 >= 5
    assert counter_value("sd_index_journal_ops_total", result="miss") == m0
    assert counter_value("sd_index_journal_ops_total",
                         result="invalidated") == i0
    await node.thumbnailer.shutdown()
    await mgr.system.shutdown()
    library.close()


def test_journal_rename_moves_vouches_and_delete_subtree(tmp_path):
    lib, journal = _memory_journal(tmp_path)
    loc_id = lib.db.insert(
        "location", pub_id=os.urandom(16), name="l", path="/tmp/x"
    )
    ident = Identity(5, 6, 7, 8)
    journal.record_cas(loc_id, ("/d/", "f", "bin"), ident, "aa" * 8)
    journal.vouch_thumb(loc_id, ("/d/", "f", "bin"), "aa" * 8)
    # file rename keeps the cas AND thumb vouches (content unchanged)
    journal.rename_path(loc_id, ("/d/", "f", "bin"), ("/d/", "g", "bin"))
    verdict, entry = journal.lookup(loc_id, ("/d/", "g", "bin"), ident)
    assert verdict == "hit" and entry.thumb and entry.cas_id == "aa" * 8
    # directory rename moves the subtree
    journal.rename_path(
        loc_id, ("/", "d", ""), ("/", "e", ""), "/d/", "/e/"
    )
    assert journal.lookup(loc_id, ("/e/", "g", "bin"), ident)[0] == "hit"
    # directory delete removes the subtree
    journal.delete_path(loc_id, ("/", "e", ""), "/e/")
    assert journal.lookup(loc_id, ("/e/", "g", "bin"), ident)[0] == "miss"
    lib.close()


def test_journal_amend_refuses_stale_and_foreign_cas(tmp_path):
    lib, journal = _memory_journal(tmp_path)
    loc_id = lib.db.insert(
        "location", pub_id=os.urandom(16), name="l", path="/tmp/x"
    )
    key = ("/", "f", "bin")
    ident = Identity(1, 1, 1, 1)
    journal.record_cas(loc_id, key, ident, "11" * 8)
    # amend against the WRONG cas: refused
    journal.vouch_thumb(loc_id, key, "22" * 8)
    assert not journal.lookup(loc_id, key, ident)[1].thumb
    # amend after staleness: refused (a stale vouch must not resurrect)
    journal.mark_stale(loc_id, key)
    journal.vouch_thumb(loc_id, key, "11" * 8)
    _, entry = journal.lookup(loc_id, key, ident)
    assert not entry.thumb
    lib.close()


def test_record_many_carries_vouches_for_unchanged_cas(tmp_path):
    """An mtime-only touch re-records the SAME cas: thumb/media/phash
    vouches must carry forward (no re-thumbnail / EXIF re-probe), while
    a content change (different cas) must void them."""
    lib, journal = _memory_journal(tmp_path)
    loc_id = lib.db.insert(
        "location", pub_id=os.urandom(16), name="l", path="/tmp/x"
    )
    key = ("/", "f", "jpg")
    ident = Identity(1, 1, 100, 4)
    journal.record_cas(loc_id, key, ident, "aa" * 8)
    journal.vouch_thumb(loc_id, key, "aa" * 8)
    journal.vouch_media(loc_id, key, "aa" * 8, "digest1")
    journal.record_phash(loc_id, key, "aa" * 8, b"\x01" * 8)
    _, entry = journal.lookup(loc_id, key, ident)

    touched = Identity(1, 1, 200, 4)  # mtime moved, content didn't
    journal.record_many(loc_id, [(key, touched, "aa" * 8, None, entry)])
    verdict, e2 = journal.lookup(loc_id, key, touched)
    assert verdict == "hit"
    assert e2.thumb and e2.media_digest == "digest1" and e2.phash == b"\x01" * 8

    changed = Identity(1, 1, 300, 4)
    journal.record_many(loc_id, [(key, changed, "bb" * 8, None, e2)])
    _, e3 = journal.lookup(loc_id, key, changed)
    assert not e3.thumb and e3.media_digest is None and e3.phash is None
    lib.close()


def test_journal_disabled_bypasses(tmp_path, monkeypatch):
    monkeypatch.setenv("SD_INDEX_JOURNAL", "0")
    lib, journal = _memory_journal(tmp_path)
    loc_id = lib.db.insert(
        "location", pub_id=os.urandom(16), name="l", path="/tmp/x"
    )
    key = ("/", "f", "bin")
    ident = Identity(1, 1, 1, 1)
    journal.record_cas(loc_id, key, ident, "11" * 8)  # no-op
    assert journal.lookup(loc_id, key, ident)[0] == "bypassed"
    assert lib.db.count("index_journal") == 0
    lib.close()


def test_prune_orphans_drops_rows_without_file_path(tmp_path):
    lib, journal = _memory_journal(tmp_path)
    loc_id = lib.db.insert(
        "location", pub_id=os.urandom(16), name="l", path="/tmp/x"
    )
    lib.db.insert(
        "file_path", pub_id=os.urandom(16), location_id=loc_id,
        materialized_path="/", name="alive", extension="bin", is_dir=0,
    )
    ident = Identity(1, 1, 1, 1)
    journal.record_cas(loc_id, ("/", "alive", "bin"), ident, "aa" * 8)
    journal.record_cas(loc_id, ("/", "ghost", "bin"), ident, "bb" * 8)
    from spacedrive_tpu.object.orphan_remover import process_clean_up

    process_clean_up(lib.db)  # consults the journal: prunes the ghost
    keys = {
        (r["name"]) for r in lib.db.query("SELECT name FROM index_journal")
    }
    assert keys == {"alive"}
    assert prune_orphans(lib.db) == 0  # idempotent
    lib.close()


@pytest.mark.asyncio
async def test_duplicates_reuse_journal_phash(tmp_path, monkeypatch):
    """The duplicate detector consults the journal: a vouched pHash for
    the same cas skips the original's decode entirely."""
    from PIL import Image

    from spacedrive_tpu.jobs.manager import JobBuilder
    from spacedrive_tpu.object.duplicates import DuplicateDetectorJob

    loc_path = tmp_path / "stuff"
    loc_path.mkdir()
    rng = np.random.default_rng(4)
    Image.fromarray(
        rng.integers(0, 255, (48, 64, 3), dtype=np.uint8), "RGB"
    ).save(loc_path / "img.png")

    node = _Node(tmp_path / "data")
    library = _mk_library(tmp_path, node)
    library.node = node
    mgr = JobManager(TaskSystem(2))
    location = LocationCreateArgs(path=str(loc_path)).create(library)
    await _scan(library, location, mgr)

    async def run_dupes():
        job = DuplicateDetectorJob({})
        await JobBuilder(job).spawn(mgr, library)
        await mgr.wait_idle()
        for _ in range(50):
            await mgr.wait_idle()
            if job.run_metadata.get("hashed") is not None:
                break
        return job

    job = await run_dupes()
    assert job.run_metadata["hashed"] == 1

    # clear the object's phash (orphan-remove + re-link scenario); the
    # journal still vouches it, so the re-run must NOT decode
    library.db.execute("UPDATE object SET phash = NULL")
    import spacedrive_tpu.object.duplicates as dup_mod

    def boom(self, ctx, row):
        raise AssertionError("journal-vouched file was re-decoded")

    monkeypatch.setattr(
        dup_mod.DuplicateDetectorJob, "_decode_gray", boom
    )
    job2 = await run_dupes()
    assert job2.run_metadata.get("reused") == 1
    row = library.db.query("SELECT phash FROM object WHERE phash IS NOT NULL")
    assert len(row) == 1
    await node.thumbnailer.shutdown()
    await mgr.system.shutdown()
    library.close()


# --- watcher-driven targeted invalidation ----------------------------------


@pytest.mark.asyncio
async def test_watcher_events_invalidate_journal(tmp_path):
    from spacedrive_tpu.location.manager import LocationManager, _Watched
    from spacedrive_tpu.location.watcher import EventKind, WatchEvent

    loc_path = tmp_path / "stuff"
    loc_path.mkdir()
    (loc_path / "w.bin").write_bytes(os.urandom(2000))
    library = _mk_library(tmp_path)
    mgr = JobManager(TaskSystem(2))
    location = LocationCreateArgs(path=str(loc_path)).create(library)
    await _scan(library, location, mgr)
    journal = IndexJournal(library.db)
    ident = journal_mod.stat_identity(loc_path / "w.bin")
    assert journal.lookup(
        location["id"], ("/", "w", "bin"), ident,
        count_invalidated=False,
    )[0] == "hit"

    class _FakeNode:
        jobs = mgr

    manager = LocationManager(_FakeNode())
    entry = _Watched(library=library, location=location, watcher=None)

    # MODIFY → targeted stale (entry survives, vouch stops)
    await manager._on_event(
        entry, WatchEvent(EventKind.MODIFY, str(loc_path / "w.bin"))
    )
    verdict, jentry = journal.lookup(
        location["id"], ("/", "w", "bin"), ident, count_invalidated=False
    )
    assert verdict == "invalidated" and jentry is not None
    if entry.flush_handle is not None:
        entry.flush_handle.cancel()

    # re-vouch, then RENAME → the vouch MOVES (no re-hash needed)
    journal.record_cas(location["id"], ("/", "w", "bin"), ident, "ab" * 8)
    os.replace(loc_path / "w.bin", loc_path / "w2.bin")
    ident2 = journal_mod.stat_identity(loc_path / "w2.bin")
    await manager._on_event(
        entry,
        WatchEvent(
            EventKind.RENAME, str(loc_path / "w2.bin"),
            old_path=str(loc_path / "w.bin"),
        ),
    )
    assert journal.lookup(
        location["id"], ("/", "w2", "bin"), ident2,
        count_invalidated=False,
    )[0] == "hit"

    # REMOVE → journal row deleted
    os.remove(loc_path / "w2.bin")
    await manager._on_event(
        entry, WatchEvent(EventKind.REMOVE, str(loc_path / "w2.bin"))
    )
    assert journal.lookup(
        location["id"], ("/", "w2", "bin"), ident2,
        count_invalidated=False,
    )[0] == "miss"
    await mgr.system.shutdown()
    library.close()


@pytest.mark.asyncio
async def test_rename_storm_widens_debounce_instead_of_per_event_rescans(
    tmp_path, monkeypatch
):
    """ISSUE-8 satellite (PR 7 follow-up): a synthetic rename storm —
    every event's journal entry still vouching — must WIDEN the settle
    window (coalescing the burst) instead of firing per-event rescans;
    a burst of real content changes keeps the snappy base window."""
    import spacedrive_tpu.location.manager as manager_mod
    from spacedrive_tpu.location.manager import LocationManager, _Watched
    from spacedrive_tpu.location.watcher import EventKind, WatchEvent

    loc_path = tmp_path / "storm"
    loc_path.mkdir()
    n = 12
    for i in range(n):
        (loc_path / f"f{i}.bin").write_bytes(os.urandom(1500))
    library = _mk_library(tmp_path)
    mgr = JobManager(TaskSystem(2))
    location = LocationCreateArgs(path=str(loc_path)).create(library)
    await _scan(library, location, mgr)
    journal = IndexJournal(library.db)
    loc_id = location["id"]
    for i in range(n):
        assert journal.lookup(
            loc_id, ("/", f"f{i}", "bin"),
            journal_mod.stat_identity(loc_path / f"f{i}.bin"),
            count_invalidated=False,
        )[0] == "hit"

    class _FakeNode:
        jobs = mgr

    rescans: list[str] = []

    async def fake_light_scan(lib, loc, sub, jobs):
        rescans.append(sub)

    monkeypatch.setattr(manager_mod, "light_scan_location", fake_light_scan)
    manager = LocationManager(_FakeNode())
    manager.debounce = 0.05
    manager.debounce_max = 0.4
    entry = _Watched(library=library, location=location, watcher=None)

    # one real content change opens the burst (schedules a flush at the
    # base window)…
    with open(loc_path / "f0.bin", "r+b") as f:
        f.write(b"X")
    await manager._on_event(
        entry, WatchEvent(EventKind.MODIFY, str(loc_path / "f0.bin"))
    )
    assert entry.last_debounce == pytest.approx(manager.debounce)

    # …then the rename storm lands: every event is journal-vouched, so
    # the PENDING rescan gets pushed out with a widened window
    for i in range(1, n):
        os.replace(loc_path / f"f{i}.bin", loc_path / f"g{i}.bin")
        await manager._on_event(
            entry,
            WatchEvent(
                EventKind.RENAME, str(loc_path / f"g{i}.bin"),
                old_path=str(loc_path / f"f{i}.bin"),
            ),
        )
    assert entry.burst_vouched >= n - 1
    assert entry.last_debounce > manager.debounce
    assert entry.last_debounce <= manager.debounce_max
    # the storm triggered ZERO rescans while it ran
    assert rescans == []

    # after the widened window settles, exactly ONE flush fires, with
    # one shallow rescan for the single real change
    await asyncio.sleep(entry.last_debounce + 0.2)
    for _ in range(50):
        if rescans and not manager._flush_tasks:
            break
        await asyncio.sleep(0.05)
    assert len(rescans) == 1
    # the renames were applied precisely (vouches moved, rows renamed)
    assert library.db.find_one("file_path", name="g3") is not None
    assert journal.lookup(
        loc_id, ("/", "g3", "bin"),
        journal_mod.stat_identity(loc_path / "g3.bin"),
        count_invalidated=False,
    )[0] == "hit"
    # burst accounting reset by the flush
    assert entry.burst_total == 0 and entry.burst_vouched == 0
    await mgr.system.shutdown()
    library.close()


@pytest.mark.asyncio
async def test_touch_storm_widens_content_storm_does_not(tmp_path, monkeypatch):
    """MODIFY bursts: size-stable (touch/attrib) events are vouched —
    the dirty-range path re-vouches them in ~ms — so the window widens;
    size-changing content writes are NOT vouched and the window stays at
    the base."""
    import spacedrive_tpu.location.manager as manager_mod
    from spacedrive_tpu.location.manager import LocationManager, _Watched
    from spacedrive_tpu.location.watcher import EventKind, WatchEvent

    loc_path = tmp_path / "touchy"
    loc_path.mkdir()
    n = 8
    for i in range(n):
        (loc_path / f"t{i}.bin").write_bytes(os.urandom(1200))
    library = _mk_library(tmp_path)
    mgr = JobManager(TaskSystem(2))
    location = LocationCreateArgs(path=str(loc_path)).create(library)
    await _scan(library, location, mgr)

    class _FakeNode:
        jobs = mgr

    async def fake_light_scan(lib, loc, sub, jobs):
        pass

    monkeypatch.setattr(manager_mod, "light_scan_location", fake_light_scan)
    manager = LocationManager(_FakeNode())
    manager.debounce = 0.05
    manager.debounce_max = 0.4
    entry = _Watched(library=library, location=location, watcher=None)

    # touch storm: mtime bumps, size unchanged → vouched burst widens
    for i in range(n):
        os.utime(loc_path / f"t{i}.bin")
        await manager._on_event(
            entry, WatchEvent(EventKind.MODIFY, str(loc_path / f"t{i}.bin"))
        )
    assert entry.burst_vouched == n
    assert entry.last_debounce > manager.debounce
    if entry.flush_handle is not None:
        entry.flush_handle.cancel()
        entry.flush_handle = None
    entry.burst_total = entry.burst_vouched = 0

    # content storm: every write GROWS the file (size change = real
    # work pending) → nothing vouches, base window holds
    for i in range(n):
        with open(loc_path / f"t{i}.bin", "ab") as f:
            f.write(os.urandom(64))
        await manager._on_event(
            entry, WatchEvent(EventKind.MODIFY, str(loc_path / f"t{i}.bin"))
        )
    assert entry.burst_vouched == 0
    assert entry.last_debounce == pytest.approx(manager.debounce)
    if entry.flush_handle is not None:
        entry.flush_handle.cancel()
    await mgr.system.shutdown()
    library.close()
