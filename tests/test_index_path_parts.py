"""The index path's three leaf spans name their parts (PR 36): a walk
call splits six ways on `sd_indexer_walk_seconds{part}`, the identifier's
row loop times its stat, journal consult and rehash beside its reads, a
transaction its `COMMIT` apart from its body, the database counts its
reads, and every span's record lands in one ring."""

import os
import time

import pytest

from spacedrive_tpu import telemetry
from spacedrive_tpu.files.isolated_path import IsolatedFilePathData
from spacedrive_tpu.location.indexer import walk, walk_single_dir
from spacedrive_tpu.location.indexer.rules import no_hidden
from spacedrive_tpu.telemetry import metrics, spans, trace

PARTS = ("scan", "rules", "remove_query", "journal", "fetch", "diff")


def walk_parts():
    return {p: metrics.INDEXER_WALK_SECONDS.stats(part=p) for p in PARTS}


def span_stats(path):
    return metrics.SPAN_SECONDS.stats(stage=path)


# --- walk → scan, rules, remove_query, journal, fetch, diff ------------------


@pytest.fixture()
def tree(tmp_path):
    """12 directories of 24 files under a root that holds 24 more."""
    root = tmp_path / "tree"
    for d in [root] + [root / f"d{i:02d}" for i in range(12)]:
        d.mkdir()
        for j in range(24):
            (d / f"f{j:02d}.txt").write_bytes(b"x" * j)
    return str(root)


def timed_walk(fn, root, journal_check, **kw):
    """One walk call under a `walk` span as the job holds it, with
    fetchers slow enough to weigh beside the scan. → result, wall."""
    def fetch(isos):
        time.sleep(0.02)
        return []

    def remove(parent, isos):
        time.sleep(0.001)
        return []

    t0 = time.perf_counter()
    with telemetry.span("walk"):
        result = fn(root, [no_hidden()],
                    lambda p, d: IsolatedFilePathData.new(1, root, p, d),
                    fetch, remove, journal_check=journal_check, **kw)
    return result, time.perf_counter() - t0


@pytest.mark.parametrize("fn,entries", [(walk, 13 * 24 + 12),
                                        (walk_single_dir, 24 + 12)],
                         ids=["full", "shallow"])
def test_a_walk_call_observes_each_part_once_and_they_add_up(tree, fn, entries):
    telemetry.reset()
    result, wall = timed_walk(fn, tree, lambda iso, meta: "miss")
    assert not result.errors and len(result.walked) == entries
    got = walk_parts()
    assert {p: s["count"] for p, s in got.items()} == dict.fromkeys(PARTS, 1)
    assert all(s["sum"] >= 0 for s in got.values())
    assert sum(s["sum"] for s in got.values()) == pytest.approx(wall, rel=0.05)
    # four of the six are also spans under the caller's `walk`; `scan`
    # holds the two that are clock pairs
    for part in ("journal", "fetch", "diff"):
        assert span_stats(f"walk.{part}")["sum"] == got[part]["sum"]
    assert span_stats("walk.scan")["sum"] == pytest.approx(
        sum(got[p]["sum"] for p in ("scan", "rules", "remove_query")))
    assert got["rules"]["sum"] > 0
    dirs = 13 if fn is walk else 1
    assert got["remove_query"]["sum"] >= dirs * 0.001
    assert got["fetch"]["sum"] >= 0.02
    assert span_stats("walk")["count"] == 1  # the parent keeps its name


def test_a_second_walk_call_observes_each_part_again(tree):
    telemetry.reset()
    for _ in range(2):
        timed_walk(walk, tree, lambda iso, meta: "miss")
    assert {s["count"] for s in walk_parts().values()} == {2}
    assert span_stats("walk.scan")["count"] == 2


@pytest.mark.parametrize("fn", [walk, walk_single_dir], ids=["full", "shallow"])
def test_without_a_journal_the_part_reads_zero_not_absent(tree, fn):
    telemetry.reset()
    timed_walk(fn, tree, None)
    got = walk_parts()
    assert got["journal"] == {**got["journal"], "count": 1, "sum": 0.0}
    assert span_stats("walk.journal")["count"] == 0  # no span for no work
    assert got["fetch"]["count"] == got["diff"]["count"] == 1


def test_an_empty_directory_still_observes_six_parts(tmp_path):
    telemetry.reset()
    root = tmp_path / "empty"
    root.mkdir()
    result, _wall = timed_walk(walk, str(root), lambda iso, meta: "miss")
    assert result.walked == []
    got = walk_parts()
    assert {s["count"] for s in got.values()} == {1}
    assert got["journal"]["sum"] == got["fetch"]["sum"] == got["diff"]["sum"] == 0


def test_a_journal_consult_is_timed_under_its_own_part(tree):
    telemetry.reset()

    def slow_check(iso, meta):
        time.sleep(0.0002)
        return "hit"

    result, _wall = timed_walk(walk_single_dir, tree, slow_check)
    files = [e for e in result.walked if not e.iso_file_path.is_dir]
    assert {e.journal_verdict for e in files} == {"hit"}
    assert walk_parts()["journal"]["sum"] >= len(files) * 0.0002


# --- identify.rows → stat, journal, rehash beside read and chunk_cache -------

STAGES = ("read", "chunk_cache", "stat", "journal", "rehash")


def stage_stats():
    return {s: metrics.IDENTIFIER_STAGE_SECONDS.stats(stage=s) for s in STAGES}


def walked_and_saved(tmp_path, corpus):
    """A library whose location `corpus` is walked and saved, nothing
    identified: → (library, location row)."""
    import asyncio

    from spacedrive_tpu.jobs import JobManager
    from spacedrive_tpu.jobs.manager import JobBuilder
    from spacedrive_tpu.location.indexer.job import IndexerJob
    from spacedrive_tpu.location.locations import LocationCreateArgs
    from spacedrive_tpu.node import Libraries
    from spacedrive_tpu.tasks import TaskSystem

    library = Libraries(tmp_path / "libs").create("parts")
    location = LocationCreateArgs(path=str(corpus)).create(library)

    async def index():
        mgr = JobManager(TaskSystem(2))
        await JobBuilder(IndexerJob({"location_id": location["id"]})).spawn(
            mgr, library)
        await mgr.wait_idle()

    asyncio.run(index())
    return library, location


@pytest.fixture()
def indexed(tmp_path):
    """Five files, walked and saved: → (library, location row, corpus
    directory)."""
    corpus = tmp_path / "corpus"
    corpus.mkdir()
    (corpus / "vouched.bin").write_bytes(os.urandom(3000))
    (corpus / "fresh.bin").write_bytes(os.urandom(5000))
    (corpus / "sampled.bin").write_bytes(os.urandom(150_000))
    (corpus / "empty.bin").write_bytes(b"")
    (corpus / "gone.bin").write_bytes(os.urandom(2000))
    library, location = walked_and_saved(tmp_path, corpus)
    yield library, location, corpus
    library.db.close()


def fetch_window(library, location, corpus, chunk_size=100):
    from spacedrive_tpu.object.file_identifier.job import FileIdentifierJob

    job = FileIdentifierJob({"location_id": location["id"], "backend": "cpu",
                             "chunk_size": chunk_size})
    job.data.update(location_id=location["id"], location_path=str(corpus),
                    backend="cpu", chunk_size=chunk_size, cursor=0)
    return job._fetch_window(library, 0)


def test_the_row_loop_times_its_five_stages_once_a_window(indexed):
    from spacedrive_tpu.location.indexer import journal as jn

    library, location, corpus = indexed
    # a hit: the journal vouches for one file's cas_id under its identity
    key = ("/", "vouched", "bin")
    jn.IndexJournal(library.db).record_many(location["id"], [
        (key, jn.stat_identity(corpus / "vouched.bin"), "c" * 16, None, None)])
    os.unlink(corpus / "gone.bin")  # a row whose file cannot be read
    telemetry.reset()
    (rows, metas, messages, _msg_rows, _fin, resolved, to_record, jstats,
     _limit) = fetch_window(library, location, corpus)
    by_name = {r["name"]: m for r, m in zip(rows, metas)}
    assert by_name["vouched"]["cas_id"] == "journal" and jstats["hit"] == 1
    assert by_name["fresh"]["cas_id"] == by_name["sampled"]["cas_id"] == "pending"
    assert by_name["empty"]["cas_id"] is None and by_name["gone"] is None
    assert len(messages) == 2 and list(resolved.values()) == ["c" * 16]
    assert len(to_record) == 3  # fresh, sampled, empty
    got = stage_stats()
    assert {s["count"] for s in got.values()} == {1}
    # two stats of a path (the vouched file, the empty one), two fstats
    # (fresh, sampled); the file that is gone fails in `open`
    assert got["stat"]["sum"] > 0
    # the window's read, then in memory: one hit with bytes_saved, three misses
    assert got["journal"]["sum"] > 0
    assert got["read"]["sum"] > 0 and got["chunk_cache"]["sum"] > 0
    assert got["rehash"]["sum"] == 0.0  # no entry holds a chunk cache yet
    rows_span = span_stats("identify.rows")
    assert rows_span["count"] == 1
    assert sum(s["sum"] for s in got.values()) <= rows_span["sum"]


def test_a_changed_file_is_rehashed_under_its_own_stage(indexed):
    from spacedrive_tpu.location.indexer import journal as jn
    from spacedrive_tpu.ops import cas

    library, location, corpus = indexed
    path = corpus / "sampled.bin"
    msg = cas.read_message(str(path), path.stat().st_size)
    jn.IndexJournal(library.db).record_many(location["id"], [
        (("/", "sampled", "bin"), jn.stat_identity(path),
         cas.cas_ids([msg], "cpu")[0], cas.build_chunk_cache(msg), None)])
    with open(path, "r+b") as f:  # one byte inside the first sampled range
        f.seek(10)
        f.write(b"\xff" if msg[18] != 0xFF else b"\x00")
    os.utime(path, ns=(1, 1))
    telemetry.reset()
    window = fetch_window(library, location, corpus)
    assert window[7]["dirty"] == 1
    changed = next(r["id"] for r in window[0] if r["name"] == "sampled")
    assert window[5][changed] == cas.cas_id_cpu(str(path), path.stat().st_size)
    got = stage_stats()
    assert got["rehash"]["count"] == 1 and got["rehash"]["sum"] > 0
    assert sum(s["sum"] for s in got.values()) <= span_stats(
        "identify.rows")["sum"]
    # the journal knew the file: judged by a stat of its path before the
    # read, as the empty file is; the other three by their descriptors
    assert identity_counts() == {"descriptor": 3, "path": 2}
    assert window[6][changed][1] == jn.stat_identity(path)


# --- the window asks once what it asked once a file (PR 37) ------------------


def identity_counts():
    return {s: metrics.IDENTIFIER_IDENTITY.value(source=s)
            for s in ("descriptor", "path")}


def journal_ops():
    return {r: metrics.INDEX_JOURNAL_OPS.value(result=r)
            for r in ("hit", "miss", "invalidated", "bypassed")}


def open_descriptors():
    return len(os.listdir("/proc/self/fd"))


def seek_and_read(path, size):
    """The reader `cas.read_message` was before it went through a
    descriptor: the reference the descriptor form is held to."""
    import struct

    from spacedrive_tpu.ops import cas

    parts = [struct.pack("<Q", size)]
    with open(path, "rb", buffering=0) as f:
        for off, ln in cas.sample_ranges(size):
            f.seek(off)
            parts.append(f.read(ln))
    return b"".join(parts)


#: a size in every chunk bucket of `cas.SMALL_BUCKETS` (1, 2, 4, 8, 16,
#: 32, 64, 101 chunks of message), both sides of 100 KiB, and sampled
#: sizes whose jump is odd
SIZES = [1, 1016, 1017, 3000, 7000, 15_000, 30_000, 60_000, 102_400,
         102_401, 150_001, 2_000_003]


@pytest.mark.parametrize("size", SIZES)
def test_the_descriptor_reader_returns_read_messages_bytes(tmp_path, size):
    from spacedrive_tpu.ops import cas

    path = tmp_path / "f.bin"
    path.write_bytes(os.urandom(size))
    want = seek_and_read(path, size)
    assert len(want) == cas.message_len(size)
    before = open_descriptors()
    fd = os.open(path, os.O_RDONLY)
    try:
        assert cas.read_message_fd(fd, size) == want
        assert os.lseek(fd, 0, os.SEEK_CUR) == 0  # no seek: the offset stays
    finally:
        os.close(fd)
    assert cas.read_message(path, size) == want
    assert cas.read_message(str(path)) == want  # the size from a stat
    assert open_descriptors() == before


@pytest.mark.parametrize("size,claimed", [(500, 501), (150_000, 300_000),
                                          (0, 1)])
def test_a_short_read_is_an_oserror_and_leaves_no_descriptor(tmp_path, size,
                                                             claimed):
    from spacedrive_tpu.object.file_identifier import job as fi_job
    from spacedrive_tpu.ops import cas

    path = tmp_path / "short.bin"
    path.write_bytes(os.urandom(size))
    before = open_descriptors()
    with pytest.raises(OSError, match="short read"):
        cas.read_message(path, claimed)
    for want_identity in (False, True):
        with pytest.raises(OSError, match="short read"):
            fi_job._open_and_read(str(path), claimed, want_identity)
    with pytest.raises(FileNotFoundError):
        cas.read_message(tmp_path / "none.bin", 10)
    with pytest.raises(FileNotFoundError):
        fi_job._open_and_read(str(tmp_path / "none.bin"), 10, True)
    assert open_descriptors() == before


def test_one_open_gives_the_identity_and_the_bytes(tmp_path):
    from spacedrive_tpu.location.indexer import journal as jn
    from spacedrive_tpu.object.file_identifier import job as fi_job
    from spacedrive_tpu.ops import cas

    path = tmp_path / "f.bin"
    path.write_bytes(os.urandom(150_000))
    before = open_descriptors()
    ident, msg, fstat_s = fi_job._open_and_read(str(path), 150_000, True)
    assert ident == jn.Identity.from_stat(os.stat(path)) == jn.stat_identity(path)
    assert msg == cas.read_message(path, 150_000) and fstat_s > 0
    assert fi_job._open_and_read(str(path), 150_000, False) == (None, msg, 0.0)
    assert open_descriptors() == before


@pytest.fixture(params=[1, 300, 301, 650])
def many(request, tmp_path):
    """n small files in seven directories, walked and saved, the journal
    empty: → (library, location, corpus, n)."""
    n = request.param
    corpus = tmp_path / "corpus"
    for i in range(n):
        d = corpus / f"d{i % 7}"
        d.mkdir(parents=True, exist_ok=True)
        (d / f"f{i:04d}.bin").write_bytes(i.to_bytes(4, "little") * (1 + i % 50))
    library, location = walked_and_saved(tmp_path, corpus)
    yield library, location, corpus, n
    library.db.close()


def test_a_cold_window_asks_the_journal_once_and_no_path_of_its_stat(
        many, monkeypatch):
    """n rows the journal holds nothing for: the page and ⌈n ÷ 300⌉
    journal reads, not one `query_one`; not one `stat` of a path; every
    identity an `fstat`'s, and field for field what a `stat` of the
    path gives, so the next pass's walker finds every file a hit."""
    from spacedrive_tpu.db.database import LibraryDb
    from spacedrive_tpu.location.indexer import journal as jn

    library, location, corpus, n = many
    reads = {"query": [], "query_one": []}
    for name in reads:
        real = getattr(LibraryDb, name)

        def wrapped(self, sql, params=(), _real=real, _name=name):
            reads[_name].append(sql)
            return _real(self, sql, params)

        monkeypatch.setattr(LibraryDb, name, wrapped)
    monkeypatch.setattr(jn, "stat_identity", lambda path: pytest.fail(
        f"a path-based stat of {path} for a file the journal does not know"))
    telemetry.reset()
    (rows, metas, messages, _msg_rows, _fin, resolved, to_record, jstats,
     _limit) = fetch_window(library, location, corpus, chunk_size=1000)
    assert len(rows) == len(messages) == len(to_record) == n and not resolved
    assert reads["query_one"] == []
    assert len(reads["query"]) == 1 + -(-n // 300)
    assert sum("index_journal" in sql for sql in reads["query"]) == -(-n // 300)
    assert identity_counts() == {"descriptor": n, "path": 0}
    assert journal_ops() == {"hit": 0, "miss": n, "invalidated": 0,
                             "bypassed": 0}
    for row in rows:
        key, ident, cas_hex, cache, carry = to_record[row["id"]]
        st = os.stat(corpus / row["materialized_path"].strip("/")
                     / f"{row['name']}.{row['extension']}")
        assert (ident.inode, ident.dev, ident.mtime_ns, ident.size) == (
            st.st_ino, st.st_dev, st.st_mtime_ns, st.st_size)
        assert ident == jn.Identity.from_stat(st)
        assert cas_hex is None and carry is None and cache is not None
    # recorded as link-commit would, the journal vouches for every file
    # under the walker's own stat: the second pass is all hits
    monkeypatch.undo()
    journal = jn.IndexJournal(library.db)
    journal.record_many(location["id"], [
        (key, ident, "c" * 16, cache, None)
        for key, ident, _cas, cache, _carry in to_record.values()])
    telemetry.reset()
    again = fetch_window(library, location, corpus, chunk_size=1000)
    assert again[7]["hit"] == n and again[2] == [] and not again[6]
    assert journal_ops() == {"hit": n, "miss": 0, "invalidated": 0,
                             "bypassed": 0}
    assert identity_counts() == {"descriptor": 0, "path": n}


def test_a_vouched_row_opens_nothing_and_reads_nothing(indexed, monkeypatch):
    from spacedrive_tpu.location.indexer import journal as jn
    from spacedrive_tpu.object.file_identifier import job as fi_job
    from spacedrive_tpu.ops import cas

    library, location, corpus = indexed
    names = ["vouched", "fresh", "sampled", "gone"]
    jn.IndexJournal(library.db).record_many(location["id"], [
        (("/", name, "bin"), jn.stat_identity(corpus / f"{name}.bin"),
         f"{i:016x}", None, None) for i, name in enumerate(names)])

    def refuse(*a, **kw):
        raise AssertionError("a vouched file was opened or read")

    monkeypatch.setattr(fi_job, "_open_and_read", refuse)
    monkeypatch.setattr(cas, "read_message_fd", refuse)
    monkeypatch.setattr(jn, "fd_identity", refuse)
    telemetry.reset()
    window = fetch_window(library, location, corpus)
    assert window[7]["hit"] == 4 and window[2] == []
    assert sorted(window[5].values()) == [f"{i:016x}" for i in range(4)]
    assert journal_ops()["hit"] == 4 and journal_ops()["miss"] == 0
    # four vouched files and the empty one, each by a stat of its path
    assert identity_counts() == {"descriptor": 0, "path": 5}


def test_identities_taken_add_up_to_the_rows_that_took_one(indexed):
    from spacedrive_tpu.location.indexer import journal as jn

    library, location, corpus = indexed
    jn.IndexJournal(library.db).record_many(location["id"], [
        (("/", "vouched", "bin"), jn.stat_identity(corpus / "vouched.bin"),
         "c" * 16, None, None),
        # an entry under another identity: judged by the path's stat,
        # invalidated, read through a descriptor that is not asked again
        (("/", "fresh", "bin"), jn.Identity(1, 2, 3, 4), "d" * 16, None, None)])
    os.unlink(corpus / "gone.bin")  # no identity: `open` fails
    telemetry.reset()
    window = fetch_window(library, location, corpus)
    to_record = window[6]
    assert identity_counts() == {"descriptor": 1, "path": 3}
    # sampled (descriptor); vouched, fresh, empty (path); gone took none
    assert len(to_record) + window[7]["hit"] == 1 + 3
    by_name = {r["name"]: r["id"] for r in window[0]}
    for name in ("sampled", "fresh", "empty"):
        assert to_record[by_name[name]][1] == jn.stat_identity(
            corpus / f"{name}.bin")
    assert journal_ops() == {"hit": 1, "miss": 2, "invalidated": 0,
                             "bypassed": 0}


@pytest.mark.parametrize("journal_on", [True, False], ids=["on", "off"])
def test_a_window_of_every_verdict_counts_what_lookup_counts(
        indexed, monkeypatch, journal_on):
    """hit, invalidated (not counted: the walker counted it), stale with
    a chunk cache (rehashed on the host), a corrupt row (bypassed and
    dropped), no entry (miss); with `SD_INDEX_JOURNAL=0` every row that
    is judged reads `bypassed` and nothing is fetched."""
    from spacedrive_tpu.location.indexer import journal as jn
    from spacedrive_tpu.ops import cas

    library, location, corpus = indexed
    journal = jn.IndexJournal(library.db)
    sampled = cas.read_message(corpus / "sampled.bin", 150_000)
    journal.record_many(location["id"], [
        (("/", "vouched", "bin"), jn.stat_identity(corpus / "vouched.bin"),
         "a" * 16, None, None),
        (("/", "fresh", "bin"), jn.Identity(1, 2, 3, 4), "b" * 16, None, None),
        (("/", "sampled", "bin"), jn.stat_identity(corpus / "sampled.bin"),
         cas.cas_ids([sampled], "cpu")[0], cas.build_chunk_cache(sampled), None),
        (("/", "gone", "bin"), jn.stat_identity(corpus / "gone.bin"),
         "d" * 16, None, None)])
    assert journal.mark_stale(location["id"], ("/", "sampled", "bin")) == 1
    library.db.execute(
        "UPDATE index_journal SET payload = X'00ff' WHERE name = 'gone'")
    # the oracle: per-row `lookup` as the loop asked it before PR 37,
    # on the rows a lookup leaves as they are
    oracle = {name: journal.lookup(
        location["id"], ("/", name, "bin"),
        jn.stat_identity(corpus / f"{name}.bin"), count=False)[0]
        for name in ("vouched", "fresh", "sampled")}
    assert oracle == {"vouched": "hit", "fresh": "invalidated",
                      "sampled": "invalidated"}
    if not journal_on:
        monkeypatch.setenv("SD_INDEX_JOURNAL", "0")
    telemetry.reset()
    window = fetch_window(library, location, corpus)
    by_name = {r["name"]: m and m["cas_id"] for r, m in zip(window[0], window[1])}
    left = {r["name"] for r in library.db.query("SELECT name FROM index_journal")}
    if journal_on:
        assert by_name == {"vouched": "journal", "fresh": "pending",
                           "sampled": "journal", "empty": None,
                           "gone": "pending"}
        assert window[7] == {"hit": 1, "dirty": 1, "dirty_chunks": 0}
        assert journal_ops() == {"hit": 1, "miss": 0, "invalidated": 0,
                                 "bypassed": 1}
        assert left == {"vouched", "fresh", "sampled"}  # the corrupt row went
        # the journal held a row for all four: each judged by its path
        assert identity_counts() == {"descriptor": 0, "path": 5}
    else:
        assert by_name == {"vouched": "pending", "fresh": "pending",
                           "sampled": "pending", "empty": None,
                           "gone": "pending"}
        assert journal_ops() == {"hit": 0, "miss": 0, "invalidated": 0,
                                 "bypassed": 4}
        assert left == {"vouched", "fresh", "sampled", "gone"}
        assert identity_counts() == {"descriptor": 4, "path": 1}


# --- db.txn → body and COMMIT, and the reads ---------------------------------


@pytest.fixture()
def db():
    from spacedrive_tpu.db.database import LibraryDb

    db = LibraryDb(None, memory=True)
    db.execute("CREATE TABLE t (a INTEGER)")
    telemetry.reset()
    yield db
    db.close()


def db_counts():
    return {"txn": metrics.DB_TXN_SECONDS.stats(),
            "commit": metrics.DB_COMMIT_SECONDS.stats(),
            "changes": metrics.DB_CHANGES.value(),
            "reads": metrics.DB_READS.value(),
            "read_s": metrics.DB_READ_SECONDS.value()}


def stages_recorded():
    return [r["stage"] for r in telemetry.recent_spans()]


def test_the_outermost_block_observes_its_commit_once(db):
    with telemetry.span("stage"):
        with db.transaction() as conn:
            for i in range(50):
                conn.execute("INSERT INTO t VALUES (?)", (i,))
    got = db_counts()
    assert got["txn"]["count"] == got["commit"]["count"] == 1
    assert 0 < got["commit"]["sum"] <= got["txn"]["sum"]
    assert got["changes"] == 50
    # the COMMIT is a leaf under the block, the block under its stage
    assert stages_recorded() == ["stage.db.txn.commit", "stage.db.txn", "stage"]
    commit, txn = telemetry.recent_spans()[:2]
    assert commit["parent_id"] == txn["span_id"]
    # the histogram holds the clock pair inside the span
    assert got["commit"]["sum"] <= commit["seconds"] <= txn["seconds"]


def test_a_nested_block_commits_into_the_outer_ones_seconds(db, monkeypatch):
    commit = db._commit
    monkeypatch.setattr(db, "_commit", lambda: (commit(), 1.0)[1])
    with db.transaction() as conn:
        conn.execute("INSERT INTO t VALUES (1)")
        db.execute("INSERT INTO t VALUES (2)")  # commits both rows so far
        assert not conn.in_transaction
        db.executemany("INSERT INTO t VALUES (?)", [(3,), (4,)])
        conn.execute("INSERT INTO t VALUES (5)")
    got = db_counts()
    assert got["txn"]["count"] == got["commit"]["count"] == 1
    assert got["changes"] == 5
    # one span for the outermost COMMIT; the two nested ones are clock
    # pairs, in the outer block's observation and in no span
    assert got["commit"]["sum"] == 3.0
    assert stages_recorded() == ["db.txn.commit", "db.txn"]
    assert db.count("t") == 5


def test_a_block_that_wrote_nothing_observes_nothing(db):
    with db.transaction() as conn:
        conn.execute("SELECT 1").fetchall()
    db.execute("SELECT 1")
    got = db_counts()
    assert got["txn"]["count"] == got["commit"]["count"] == 0
    assert got["changes"] == 0


@pytest.mark.parametrize("nested", [False, True], ids=["outermost", "nested"])
def test_an_exception_rolls_back_and_leaves_no_span_open(db, nested):
    db.execute("INSERT INTO t VALUES (0)")
    telemetry.reset()
    with pytest.raises(ZeroDivisionError):
        with db.transaction() as conn:
            conn.execute("INSERT INTO t VALUES (1)")
            if nested:
                with db.transaction():
                    conn.execute("INSERT INTO t VALUES (2)")
                    1 / 0
            1 / 0
    assert telemetry.current_span() is None
    assert not db._in_txn and not db._conn.in_transaction
    assert stages_recorded() == ["db.txn"]  # ended with the error, no commit
    assert telemetry.recent_spans()[0]["error"] == "ZeroDivisionError"
    got = db_counts()
    assert got["txn"]["count"] == got["commit"]["count"] == 0
    assert db.count("t") == 1
    db.execute("INSERT INTO t VALUES (3)")  # the connection is usable
    assert db.count("t") == 2


def test_a_failed_commit_rolls_back_like_sqlite3s_own_block(db, monkeypatch):
    import sqlite3

    class Conn:
        """The connection, but for a COMMIT that fails once."""

        def __init__(self, conn):
            self._conn, self.rolled_back = conn, 0

        def commit(self):
            raise sqlite3.OperationalError("disk I/O error")

        def rollback(self):
            self.rolled_back += 1
            self._conn.rollback()

        def __getattr__(self, name):
            return getattr(self._conn, name)

    real = db._conn
    monkeypatch.setattr(db, "_conn", Conn(real))
    with pytest.raises(sqlite3.OperationalError):
        db.execute("INSERT INTO t VALUES (1)")
    assert db._conn.rolled_back == 1 and not real.in_transaction
    monkeypatch.setattr(db, "_conn", real)
    assert db.count("t") == 0 and telemetry.current_span() is None


@pytest.mark.parametrize("n", [1, 7, 1000])
def test_changes_count_an_executemany_of_n_rows_as_n(db, n):
    db.executemany("INSERT INTO t VALUES (?)", [(i,) for i in range(n)])
    assert db_counts()["changes"] == n
    db.execute("UPDATE t SET a = a + 1")
    assert db_counts()["changes"] == 2 * n
    assert db.delete("t", a=1) == 1
    assert db_counts()["changes"] == 2 * n + 1


def test_reads_wait_on_the_connection_until_a_transaction_ends(db):
    db.query("SELECT * FROM t")
    db.query_one("SELECT COUNT(*) AS n FROM t")
    db.find("t", a=1), db.find_one("t", a=1), db.count("t")
    assert db_counts()["reads"] == 0 and db._reads == 5
    with db.transaction() as conn:
        conn.execute("INSERT INTO t VALUES (1)")
        assert db.find_one("t", a=1) == {"a": 1}  # a read inside the block
        assert db_counts()["reads"] == 0
    got = db_counts()
    assert got["reads"] == 6 and got["read_s"] > 0 and db._reads == 0
    # a block that wrote nothing, and one that raised, flush all the same
    db.count("t")
    db.execute("SELECT 1")
    assert db_counts()["reads"] == 7
    db.count("t")
    with pytest.raises(ZeroDivisionError):
        with db.transaction():
            1 / 0
    assert db_counts()["reads"] == 8
    assert db_counts()["txn"]["count"] == 1  # reads never count as commits


def test_reads_flush_on_close():
    from spacedrive_tpu.db.database import LibraryDb

    db = LibraryDb(None, memory=True)
    telemetry.reset()
    for _ in range(3):
        db.query_one("SELECT 1 AS one")
    assert db_counts()["reads"] == 0
    db.close()
    assert db_counts()["reads"] == 3
    db.close()  # closing twice counts nothing twice
    assert db_counts()["reads"] == 3


def test_a_read_that_fails_is_a_read(db):
    import sqlite3

    with pytest.raises(sqlite3.OperationalError):
        db.query("SELECT * FROM no_such_table")
    assert db._reads == 1


def test_reads_of_a_pass_equal_the_calls(indexed):
    """`sd_db_reads_total` holds every `query` and `query_one` call, to
    the call: counted here by wrapping the two round an identify window
    and a link-commit's worth of lookups."""
    from spacedrive_tpu.db.database import LibraryDb

    library, location, corpus = indexed
    library.db.execute("UPDATE location SET name = name")  # flush what set-up read
    telemetry.reset()
    calls = {"n": 0}
    real = {name: getattr(LibraryDb, name) for name in ("query", "query_one")}

    def counting(name):
        def wrapped(self, *a, **kw):
            calls["n"] += 1
            return real[name](self, *a, **kw)
        return wrapped

    try:
        for name in real:
            setattr(LibraryDb, name, counting(name))
        fetch_window(library, location, corpus)
        library.db.find("file_path", location_id=location["id"])
        library.db.count("object")
    finally:
        for name, fn in real.items():
            setattr(LibraryDb, name, fn)
    library.db.execute("UPDATE location SET name = name")
    # the page, the window's one journal read, a find, a count
    assert calls["n"] == 1 + 1 + 1 + 1
    assert db_counts()["reads"] == calls["n"]


# --- one ring ----------------------------------------------------------------


def test_recent_spans_are_the_tail_of_the_one_ring():
    telemetry.reset()
    for i in range(5000):
        with telemetry.span("ring.probe") as sp:
            sp.annotate(i=i)
    recent = telemetry.recent_spans()
    assert len(recent) == spans.RECENT_SPANS == 256
    assert [r["fields"]["i"] for r in recent] == list(range(5000 - 256, 5000))
    ring = trace.recent()
    assert len(ring) == trace.TRACE_RING and ring[-256:] == recent
    assert recent[-1] is ring[-1]  # one record, appended once
    assert {"stage", "seconds", "bytes", "error", "trace_id", "span_id",
            "parent_id", "t0"} <= set(recent[-1])
    assert not hasattr(spans, "_recent") and not hasattr(spans, "_recent_lock")
    telemetry.clear_recent()
    assert telemetry.recent_spans() == [] and trace.recent() == []


def test_a_snapshot_and_the_export_read_the_same_records():
    telemetry.reset()
    with telemetry.span("ring.outer", nbytes=5):
        with telemetry.span("inner"):
            pass
    snap = telemetry.snapshot()["spans"]
    assert [s["stage"] for s in snap] == ["ring.outer.inner", "ring.outer"]
    events = [e for e in trace.export()["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in events] == [s["stage"] for s in snap]
    assert events[1]["args"]["bytes"] == 5
    assert events[0]["ts"] == int(snap[0]["t0"] * 1e6)
