"""The thirteen readers of the index path's parts (PR 36) on a hand-made
`ctx`: the number each computes from its counters, None (never 0, never
an error) where a counter is absent, as on the parent's program, or a
denominator is 0, and how each is declared."""

import os

import pytest

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
INDEX = ["homedir.cold", "homedir.rescan", "photolib.raw"]
ALL = ["homedir.cold", "photolib.cold", "homedir.rescan", "photolib.raw",
       "photolib.video", "photolib.heic"]
FED = ["homedir.cold", "photolib.raw"]
#: name → (unit, better, source, layer, cells)
DECLARED = {
    "walk_listing_us_per_file": ("us/file", "lower", "program_counter", "jobs", INDEX),
    "walk_rules_us_per_file": ("us/file", "lower", "program_counter", "jobs", INDEX),
    "walk_journal_us_per_file": ("us/file", "lower", "program_counter", "jobs", INDEX),
    "walk_fetch_us_per_file": ("us/file", "lower", "program_counter", "jobs", INDEX),
    "fetch_stat_us_per_file": ("us/file", "lower", "program_counter", "feeder", INDEX),
    "fetch_journal_us_per_file": ("us/file", "lower", "program_counter", "feeder", INDEX),
    "fetch_rows_rest_us_per_file": ("us/file", "lower", "program_span", "feeder", INDEX),
    "db_commit_us_per_file": ("us/file", "lower", "program_counter", "jobs", ALL),
    "db_changes_per_file": ("rows/file", "lower", "program_counter", "jobs", ALL),
    "db_reads_per_file": ("reads/file", "lower", "program_counter", "jobs", ALL),
    "db_read_us_per_file": ("us/file", "lower", "program_counter", "jobs", ALL),
    "autotune_decisions_per_pass": ("decisions/pass", "lower", "program_counter", "feeder", FED),
    "identify_window_fill": ("%", "higher", "program_counter", "feeder", FED),
}

PASSES = [{"files": 1000}, {"files": 1000}]
HASHED = 500


def walk_part(part, secs, calls=2):
    return {f"sd_indexer_walk_seconds{{part={part}}}.sum": secs,
            f"sd_indexer_walk_seconds{{part={part}}}.count": float(calls)}


def stage(name, secs, windows=4):
    return {f"sd_identifier_stage_seconds{{stage={name}}}.sum": secs,
            f"sd_identifier_stage_seconds{{stage={name}}}.count": float(windows)}


#: what the parent's program counts of all this: the walk span, the row
#: loop's span and its two timed stages, whole transactions, the
#: controller's gauges (differenced to 0) and the window fill
PARENT = {
    "sd_span_seconds{stage=walk}.sum": 1.2,
    "sd_span_seconds{stage=walk}.count": 2.0,
    "sd_span_seconds{stage=feeder.fetch.identify.rows}.sum": 2.0,
    "sd_span_seconds{stage=feeder.fetch.identify.rows}.count": 4.0,
    **stage("read", 0.5), **stage("chunk_cache", 0.25),
    "sd_db_txn_seconds.sum": 3.0, "sd_db_txn_seconds.count": 12.0,
    "sd_autotune_window_scale{workload=identify}": 0.0,
    "sd_autotune_batch_rung{workload=identify}": 0.0,
    "sd_identifier_batch_fill_ratio.sum": 3.5,
    "sd_identifier_batch_fill_ratio.count": 4.0,
}
CHANGE = {
    **PARENT,
    **walk_part("scan", 0.4), **walk_part("rules", 0.1),
    **walk_part("remove_query", 0.06), **walk_part("journal", 0.3),
    **walk_part("fetch", 0.24), **walk_part("diff", 0.1),
    **stage("stat", 0.125), **stage("journal", 0.375), **stage("rehash", 0.0),
    "sd_span_seconds{stage=walk.scan}.sum": 0.56,
    "sd_span_seconds{stage=indexer.save.db.txn.commit}.sum": 0.9,
    "sd_db_commit_seconds.sum": 1.0, "sd_db_commit_seconds.count": 12.0,
    "sd_db_changes_total": 26_000.0,
    "sd_db_reads_total": 9_000.0,
    "sd_db_read_seconds_total": 0.18,
    "sd_autotune_decisions_total{action=promote,workload=identify}": 3.0,
    "sd_autotune_decisions_total{action=demote,workload=thumbnail}": 1.0,
}
EXPECTED = {
    "walk_listing_us_per_file": 200.0,
    "walk_rules_us_per_file": 50.0,
    "walk_journal_us_per_file": 150.0,
    "walk_fetch_us_per_file": 150.0,   # fetch + remove_query
    "fetch_stat_us_per_file": 250.0,
    "fetch_journal_us_per_file": 750.0,
    # 2.0 − (0.5 + 0.25 + 0.125 + 0.375 + 0) over 500 files hashed
    "fetch_rows_rest_us_per_file": 1500.0,
    "db_commit_us_per_file": 500.0,
    "db_changes_per_file": 13.0,
    "db_reads_per_file": 4.5,
    "db_read_us_per_file": 90.0,
    "autotune_decisions_per_pass": 2.0,
    "identify_window_fill": 87.5,
}
#: the two that read families the parent's program has already
ON_THE_PARENT = {"autotune_decisions_per_pass": 0.0,
                 "identify_window_fill": 87.5}


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


def ctx_with(counters, passes=PASSES, hashed=HASHED):
    return {"passes": passes, "counters": counters,
            "hashed": {"files": hashed}, "trace": None}


def test_thirteen_readers():
    assert len(DECLARED) == len(EXPECTED) == 13


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_computes_its_number(bench, name):
    assert bench.reader(name)(ctx_with(CHANGE)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_on_the_parents_program_only_the_two_old_families_read(bench, name):
    """The driver lays these files over the parent's checkout: a reader
    whose counter the parent lacks gives None there and raises nothing."""
    got = bench.reader(name)(ctx_with(PARENT))
    if name in ON_THE_PARENT:
        assert got == pytest.approx(ON_THE_PARENT[name])
    else:
        assert got is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("counters,passes,hashed", [
    ({}, PASSES, HASHED),
    ({**CHANGE, "sd_identifier_batch_fill_ratio.count": 0.0}, [], 0),
    ({k: 0.0 for k in CHANGE if not k.startswith("sd_autotune_")}, PASSES,
     HASHED),
], ids=["no_counters", "no_pass_no_window", "never_observed"])
def test_nothing_to_read_gives_none_never_zero(bench, name, counters, passes,
                                               hashed):
    assert bench.reader(name)(ctx_with(counters, passes, hashed)) is None


def test_no_file_hashed_gives_none_for_the_row_loops_three(bench):
    for name in ("fetch_stat_us_per_file", "fetch_journal_us_per_file",
                 "fetch_rows_rest_us_per_file"):
        assert bench.reader(name)(ctx_with(CHANGE, hashed=0)) is None


@pytest.mark.parametrize("missing", ["read", "chunk_cache", "stat", "journal",
                                     "rehash"])
def test_the_remainder_needs_every_stage_it_subtracts(bench, missing):
    """A stage the program does not time would sit in the remainder
    unnamed: None, not a remainder that silently holds it."""
    counters = {k: v for k, v in CHANGE.items()
                if not k.startswith(
                    "sd_identifier_stage_seconds{stage=%s}" % missing)}
    read = bench.reader("fetch_rows_rest_us_per_file")
    assert read(ctx_with(counters)) is None
    assert read(ctx_with(CHANGE)) is not None


def test_the_remainder_reads_the_span_by_its_last_components(bench):
    """`identify.rows` under whatever parent: the shard plane runs the
    row loop outside `feeder.fetch`."""
    counters = {k.replace("feeder.fetch.identify.rows", "identify.rows"): v
                for k, v in CHANGE.items()}
    assert bench.reader("fetch_rows_rest_us_per_file")(
        ctx_with(counters)) == pytest.approx(1500.0)


def test_a_stage_timed_at_zero_is_a_reading(bench):
    """`{stage=rehash}` reads 0 s in a cold cell and is observed all the
    same; a rule set that matched in no time would too."""
    counters = {**CHANGE, **walk_part("rules", 0.0), **stage("stat", 0.0)}
    assert bench.reader("walk_rules_us_per_file")(ctx_with(counters)) == 0.0
    assert bench.reader("fetch_stat_us_per_file")(ctx_with(counters)) == 0.0


def test_a_controller_that_decided_nothing_reads_zero(bench):
    """The decisions' counter has no series before its first tick; the
    controller's gauges say it was there."""
    read = bench.reader("autotune_decisions_per_pass")
    gauges = {k: v for k, v in PARENT.items() if k.startswith("sd_autotune_")}
    assert read(ctx_with(gauges)) == 0.0
    assert read(ctx_with({k: v for k, v in CHANGE.items()
                          if not k.startswith("sd_autotune_")})) is None


def test_the_walks_parts_close_on_the_walk_span(bench):
    """listing + rules + journal + fetch + `{part=diff}` is the walk call,
    which is what the job's `walk` span holds."""
    ctx = ctx_with(CHANGE)
    four = sum(bench.reader(n)(ctx) for n in (
        "walk_listing_us_per_file", "walk_rules_us_per_file",
        "walk_journal_us_per_file", "walk_fetch_us_per_file"))
    diff = 1e6 * CHANGE["sd_indexer_walk_seconds{part=diff}.sum"] / 2000
    assert four + diff == pytest.approx(
        bench.reader("walk_scan_us_per_file")(ctx))


@pytest.mark.parametrize("name", sorted(DECLARED))
def test_declared_with_its_cells_and_found_by_name(bench, name):
    unit, better, source, layer, cells = DECLARED[name]
    declared = {m["name"]: m for m in bench.doc["per_layer"]}
    assert declared[name] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "pass_rate", "workloads": cells}
    assert os.path.isfile(bench.find("metrics", name + ".py"))
    known = {w["name"] for w in bench.doc["workloads"]}
    assert set(cells) <= known
    for cell in known:
        listed = name in [m["name"] for m in bench.metrics_for(cell, "per_layer")]
        assert listed == (cell in cells)


def test_the_thirteen_are_appended_and_nothing_before_them_moved(bench):
    names = [m["name"] for m in bench.doc["per_layer"]]
    assert names[-13:] == list(DECLARED)
    assert len(names) == len(set(names)) == 66
    assert names[52] == "heif_exif_ms_per_image"  # PR 34's last, where it was
