"""`span_reduce` on synthetic planes: leaf spans name the idle gaps, two
threads may cover one gap, what nobody covers is unspanned, and nothing
outside `bench.window` counts."""

import pytest

from benchmark import span_reduce as sr

S = 1_000_000_000  # ns


def ev(name, start_s, end_s):
    return (name, int(start_s * S), int((end_s - start_s) * S))


def planes(host_lines, ops, window=(0.0, 10.0)):
    lines = [("main", [ev("bench.window", *window)] + host_lines[0])]
    lines += [(f"t{i}", evs) for i, evs in enumerate(host_lines[1:], 1)]
    return [("/host:CPU", lines),
            ("/device:TPU:0", [("XLA Ops", [ev(f"op{i}", s, e)
                                            for i, (s, e) in enumerate(ops)]),
                               ("Steps", [ev("step", 0.0, 10.0)])])]


def test_a_parent_counts_only_where_no_child_is_open():
    r = sr.reduce_planes(planes(
        [[ev("sd.job", 0.0, 10.0), ev("sd.job.step", 2.0, 5.0),
          ev("sd.job.step.db.txn", 3.0, 4.0)]], ops=[(5.0, 6.0)]))
    assert r["window_s"] == pytest.approx(10.0)
    assert r["idle_s"] == pytest.approx(9.0) and r["gaps"] == 2
    t = r["spans"]
    assert t["job"]["seconds"] == pytest.approx(10.0)
    assert t["job"]["idle_s"] == pytest.approx(2.0 + 4.0)  # 0-2 and 6-10
    assert t["job"]["longest_gap_s"] == pytest.approx(4.0)
    assert t["job.step"]["idle_s"] == pytest.approx(2.0)   # 2-3 and 4-5
    assert t["job.step.db.txn"]["idle_s"] == pytest.approx(1.0)
    assert t["job.step.db.txn"]["calls"] == 1
    assert r["unspanned_s"] == 0.0
    assert sum(v["idle_s"] for v in t.values()) == pytest.approx(r["idle_s"])


def test_two_threads_may_both_cover_a_gap():
    r = sr.reduce_planes(planes(
        [[ev("sd.identify.hash", 1.0, 4.0)],
         [ev("sd.feeder.fetch", 2.0, 6.0),
          ev("sd.feeder.fetch.cas.pack", 3.0, 3.5)]], ops=[(8.0, 9.0)]))
    t = r["spans"]
    assert t["identify.hash"]["idle_s"] == pytest.approx(3.0)
    assert t["feeder.fetch"]["idle_s"] == pytest.approx(3.5)
    assert t["feeder.fetch.cas.pack"]["idle_s"] == pytest.approx(0.5)
    # 0-1, 6-8 and 9-10 have no span; 2-4 is covered twice and counts once
    assert r["unspanned_s"] == pytest.approx(4.0)
    assert r["unspanned_longest_gap_s"] == pytest.approx(3.0)
    by = {(b["after"], b["before"]): b for b in r["unspanned_between"]}
    assert by[("window opens", "identify.hash")]["seconds"] == pytest.approx(1.0)
    assert by[("feeder.fetch", "window closes")]["seconds"] == pytest.approx(3.0)
    assert by[("feeder.fetch", "window closes")]["pieces"] == 2


def test_busy_time_is_nobodys_idle_and_overlapping_same_name_spans_count():
    # one async stage open twice at once on one line (three-deep pipeline)
    r = sr.reduce_planes(planes(
        [[ev("sd.thumbnail.decode", 0.0, 6.0),
          ev("sd.thumbnail.decode", 2.0, 8.0)]], ops=[(1.0, 3.0), (2.5, 4.0)]))
    assert r["idle_s"] == pytest.approx(1.0 + 6.0)
    row = r["spans"]["thumbnail.decode"]
    assert row["calls"] == 2 and row["seconds"] == pytest.approx(12.0)
    assert row["idle_s"] == pytest.approx(1.0 + 4.0)   # 0-1 and 4-8
    assert r["unspanned_s"] == pytest.approx(2.0)      # 8-10
    assert r["longest_gaps"][0]["seconds"] == pytest.approx(6.0)
    assert dict(r["longest_gaps"][0]["by"]) == {
        "thumbnail.decode": pytest.approx(4.0), sr.UNSPANNED: pytest.approx(2.0)}


def test_events_outside_the_window_are_clipped_or_dropped():
    r = sr.reduce_planes(planes(
        [[ev("sd.warm", 0.0, 1.5), ev("sd.walk", 1.0, 3.0),
          ev("sd.late", 7.5, 9.0), ev("sd.after", 8.5, 9.5),
          ev("other.annotation", 3.0, 4.0)]],
        ops=[(0.5, 0.9), (4.0, 5.0), (8.2, 8.4)], window=(2.0, 8.0)))
    assert r["window_s"] == pytest.approx(6.0)
    assert r["idle_s"] == pytest.approx(5.0)
    assert set(r["spans"]) == {"walk", "late"}
    assert r["spans"]["walk"]["seconds"] == pytest.approx(1.0)
    assert r["spans"]["late"]["idle_s"] == pytest.approx(0.5)
    assert r["unspanned_s"] == pytest.approx(3.5)


def test_nothing_to_read_is_none_not_an_error():
    no_spans = planes([[ev("other", 1.0, 2.0)]], ops=[(3.0, 4.0)])
    assert sr.reduce_planes(no_spans) is None          # the parent's program
    host_only = planes([[ev("sd.walk", 1.0, 2.0)]], ops=[])[:1]
    assert sr.reduce_planes(host_only) is None         # no TPU plane
    assert sr.for_run({"trace": None}) is None
    assert sr.for_run({"trace": {"path": "/nonexistent/x.xplane.pb"}}) is None


def test_matching_by_last_components():
    assert sr.ends_with("indexer.save.db.txn", "db.txn")
    assert sr.ends_with("db.txn", "db.txn")
    assert not sr.ends_with("mydb.txn", "db.txn")
    assert not sr.ends_with("walk.x", "walk")
    counters = {"sd_span_seconds{stage=walk}.sum": 1.0,
                "sd_span_seconds{stage=indexer.init.walk}.sum": 2.0,
                "sd_span_seconds{stage=sidewalk}.sum": 4.0,
                "sd_span_seconds{stage=walk}.count": 8.0}
    assert sr.counter(counters, "walk") == 3.0
    assert sr.counter(counters, "walk", "count") == 8.0
    assert sr.counter(counters, "node.start") is None
    table = {"a.db.txn": 1, "db.txn": 2, "a.db": 3}
    assert sr.matching(table, "db.txn") == {"a.db.txn": 1, "db.txn": 2}


def test_the_table_names_every_span_and_the_uncovered_stretch(capsys):
    r = sr.reduce_planes(planes(
        [[ev("sd.walk", 1.0, 3.0), ev("sd.indexer.save", 4.0, 6.0)]],
        ops=[(6.0, 6.5)]))
    text = "\n".join(sr.table_lines(r))
    assert "walk" in text and "indexer.save" in text and sr.UNSPANNED in text
    assert "after walk and before indexer.save" in text
    assert "idle as leaf" in text and "longest gap" in text


def test_for_run_reads_the_moved_trace_once_and_caches(tmp_path, monkeypatch,
                                                       capsys):
    work = tmp_path / "work"
    old = work / "run" / "trace" / "plugins" / "profile" / "2026" / "h.xplane.pb"
    (work / "run").mkdir(parents=True)
    (work / "last.xplane.pb").write_bytes(b"")
    reads = []

    def fake_read(path):
        reads.append(path)
        return planes([[ev("sd.walk", 1.0, 3.0)]], ops=[(5.0, 6.0)])

    monkeypatch.setattr(sr, "read_planes", fake_read)
    ctx = {"trace": {"path": str(old)}}
    first = sr.for_run(ctx)
    assert first["unspanned_s"] == pytest.approx(7.0)
    assert sr.for_run(ctx) is first
    assert reads == [str(work / "last.xplane.pb")]
    assert capsys.readouterr().err.count("spans: window") == 1
