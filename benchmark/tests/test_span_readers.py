"""Each per-layer reader this PR adds, on a hand-made `ctx`: the value it
computes, and None (never an error) where the program has no such span
or counter, as the parent commit has not."""

import os

import pytest

from benchmark import harness, span_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PASSES = [{"files": 1000, "summary": {"thumbnailer_generated": 10}},
          {"files": 1000, "summary": {"thumbnailer_generated": 10}}]
COUNTERS = {
    "sd_span_seconds{stage=node.start}.sum": 0.3,
    "sd_span_seconds{stage=node.shutdown}.sum": 0.1,
    "sd_span_seconds{stage=walk}.sum": 1.0,
    "sd_span_seconds{stage=indexer.save}.sum": 2.0,
    "sd_span_seconds{stage=indexer.save.db.txn}.sum": 1.5,
    "sd_db_txn_seconds.sum": 3.0,
    "sd_identifier_stage_seconds{stage=read}.sum": 0.5,
    "sd_identifier_stage_seconds{stage=pack}.sum": 0.25,
    "sd_identifier_stage_seconds{stage=dispatch}.sum": 0.125,
    "sd_thumbnail_work_seconds{stage=decode}.sum": 0.4,
    "sd_thumbnail_work_seconds{stage=encode}.sum": 0.2,
    "sd_thumbnail_stage_seconds{stage=device}.sum": 0.6,
    "sd_embed_stage_seconds{stage=decode}.sum": 6.0,
    "sd_embed_files_total{result=embedded}": 20.0,
}
EXPECTED = {
    "node_start_stop_s": 0.2,
    "walk_scan_us_per_file": 500.0,
    "index_save_us_per_file": 1000.0,
    "db_txn_us_per_file": 1500.0,
    "fetch_read_us_per_file": 1000.0,
    "fetch_pack_us_per_file": 500.0,
    "hash_dispatch_us_per_file": 250.0,
    "decode_ms_per_image": 20.0,
    "encode_ms_per_image": 10.0,
    "resize_host_ms_per_image": 30.0,
    "embed_decode_ms_per_image": 300.0,
    "idle_unspanned_share": 12.5,
}


def ctx_with(counters):
    return {"passes": PASSES, "counters": counters, "hashed": {"files": 500},
            "trace": None,
            "_span_reduce": {"idle_s": 40.0, "unspanned_s": 5.0}}


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_computes_its_value(bench, name):
    assert bench.reader(name)(ctx_with(COUNTERS)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_on_a_program_without_the_spans(bench, name):
    """The parent's counters: the families that were there, none of the
    new ones, and a trace with no `sd.*` event."""
    old = {k: v for k, v in COUNTERS.items()
           if k.startswith(("sd_embed_", "sd_thumbnail_stage_"))}
    ctx = {**ctx_with(old), "_span_reduce": None}
    value = bench.reader(name)(ctx)
    if name in ("resize_host_ms_per_image", "embed_decode_ms_per_image"):
        assert value == pytest.approx(EXPECTED[name])  # read older families
    else:
        assert value is None


#: the cells PR 25 declared each reader in; a later PR lists more, never fewer
CELLS = {
    "node_start_stop_s": {"homedir.cold", "photolib.cold", "homedir.rescan"},
    "walk_scan_us_per_file": {"homedir.cold", "homedir.rescan"},
    "index_save_us_per_file": {"homedir.cold", "homedir.rescan"},
    "db_txn_us_per_file": {"homedir.cold", "photolib.cold", "homedir.rescan"},
    "fetch_read_us_per_file": {"homedir.cold", "homedir.rescan"},
    "fetch_pack_us_per_file": {"homedir.cold"},
    "hash_dispatch_us_per_file": {"homedir.cold", "homedir.rescan"},
    "decode_ms_per_image": {"photolib.cold"},
    "encode_ms_per_image": {"photolib.cold"},
    "resize_host_ms_per_image": {"photolib.cold"},
    "embed_decode_ms_per_image": {"photolib.cold"},
    "idle_unspanned_share": {"homedir.cold", "photolib.cold", "homedir.rescan"},
}


def test_the_twelve_are_declared_with_their_cells(bench):
    """Found by name, not by place: later PRs append after them."""
    declared = {m["name"]: m for m in bench.doc["per_layer"]}
    assert set(CELLS) == set(EXPECTED)
    for name, cells in CELLS.items():
        m = declared[name]
        assert m["moves"] == "pass_rate" and m["better"] == "lower"
        assert cells <= set(m["workloads"]), name
    assert span_reduce.SPAN_PREFIX == "sd."
