"""Churn-soak harness (bench_scale.py) + the million-row maintenance
refactors it forced — the ISSUE 18 scale plane.

The acceptance bars proven here:

- the **mini-soak** (compressed bench_scale lane: small corpus,
  accelerated sampler/history cadence, warmup-scaled trend bars) runs
  end-to-end through the real planes and passes its own verdict: zero
  trend breaches, zero protected sheds, bounded fd/RSS drift, a
  schema-valid BENCH_SCALE.json — and the journal row inventory tracks
  CORPUS SIZE, not pass count;
- **journal prune at 10⁵ rows** runs in bounded batches with event-loop
  yields between them (the heartbeat keeps beating), deletes exactly
  the orphans, and keeps the vouched rows;
- **sync backfill** streams through its rowid cursor in bounded chunks
  (forced small batch → many chunks) with per-chunk coverage probes:
  every row gets its ops exactly once, and a re-run writes zero.

The smoke's RSS/fd bars are generous by design: a seconds-long run
extrapolates absurd per-hour slopes from JAX/aiohttp warmup
allocation. The full ``make bench-scale`` lane owns the real bars.
"""

import asyncio
import json
import os

import pytest

import bench_scale
from spacedrive_tpu.node import Libraries

#: the accelerated-cadence env the smoke lane runs under — sampler and
#: history tick sub-second, trend windows shrink to the run length, and
#: the slope bars scale up to absorb warmup allocation
SMOKE_ENV = {
    "SD_HISTORY_INTERVAL_S": "0.2",
    "SD_RESOURCE_INTERVAL_S": "0.1",
    "SD_RESOURCE_WARMUP_S": "5",
    "SD_RESOURCE_TREND_WINDOW_S": "120",
    "SD_SLO_RSS_MB_PER_H": "200000",
    "SD_SLO_FD_PER_H": "2000",
}


def _mk_library(tmp_path, name="soaklib"):
    libs = Libraries(tmp_path / "data", node=None)
    return libs.create(name)


# --- the mini-soak ---------------------------------------------------------


def test_mini_soak_end_to_end(tmp_path, monkeypatch):
    for k, v in SMOKE_ENV.items():
        monkeypatch.setenv(k, v)
    out = str(tmp_path / "BENCH_SCALE.json")
    doc = asyncio.run(bench_scale.run_soak(
        files=150, seconds=8.0, seed=7, out_path=out,
        work_dir=str(tmp_path / "soak"),
    ))

    assert doc["schema"] == bench_scale.SCHEMA
    assert doc["verdict"]["pass"] is True
    assert doc["slo"]["breaches"] == []
    assert doc["protected_sheds"] == 0
    res = doc["resources"]
    assert abs(res["fd_delta"]) <= bench_scale.FD_DELTA_MAX
    assert res["rss_delta_mb"] <= bench_scale.RSS_DELTA_MAX_MB
    # the trend target: journal rows track corpus size, not pass count
    assert res["journal_rows"] == 150.0
    assert len(doc["throughput"]["passes"]) >= 2
    assert doc["throughput"]["flatness"] >= bench_scale.FLATNESS_MIN
    # every scenario in the default mix actually ran
    assert set(doc["scenarios"]) == {
        "touch", "rename", "reindex", "reads", "orphan"}
    assert all(n > 0 for n in doc["scenarios"].values())

    # the artifact on disk is the same schema-valid document
    with open(out) as f:
        on_disk = json.load(f)
    assert on_disk["schema"] == doc["schema"]
    assert on_disk["verdict"] == doc["verdict"]


def test_corpus_and_deck_are_seed_deterministic(tmp_path):
    a = bench_scale.make_corpus(str(tmp_path / "a"), 64, seed=11)
    b = bench_scale.make_corpus(str(tmp_path / "b"), 64, seed=11)
    c = bench_scale.make_corpus(str(tmp_path / "c"), 64, seed=12)
    rel = lambda root, paths: sorted(
        (os.path.relpath(p, root), os.path.getsize(p)) for p in paths)
    assert rel(str(tmp_path / "a"), a) == rel(str(tmp_path / "b"), b)
    assert rel(str(tmp_path / "a"), a) != rel(str(tmp_path / "c"), c)
    assert bench_scale.parse_mix("touch=4,reads=1") == {
        "touch": 4, "reads": 1}


# --- journal prune at 10⁵ rows ---------------------------------------------


def test_prune_100k_rows_batched_with_loop_yields(tmp_path):
    from spacedrive_tpu.location.indexer.journal import (
        PRUNE_BATCH,
        prune_orphans_step,
    )
    from spacedrive_tpu.object.orphan_remover import process_clean_up_async

    lib = _mk_library(tmp_path)
    loc_id = lib.db.insert(
        "location", pub_id=os.urandom(16), name="l", path="/tmp/x")
    alive = 50
    total = 100_000
    lib.db.insert_many(
        "file_path",
        ("pub_id", "location_id", "materialized_path", "name", "extension",
         "is_dir"),
        [(os.urandom(16), loc_id, "/", f"alive{i}", "bin", 0)
         for i in range(alive)],
    )
    lib.db.insert_many(
        "index_journal",
        ("location_id", "materialized_path", "name", "extension", "cas_id"),
        [(loc_id, "/", f"alive{i}" if i < alive else f"ghost{i}", "bin",
          f"{i:016x}") for i in range(total)],
    )
    assert lib.db.count("index_journal") == total

    # a single step is bounded — never more than one batch of lock hold
    assert prune_orphans_step(lib.db, PRUNE_BATCH) == PRUNE_BATCH

    async def run():
        ticks = 0

        async def heart():
            nonlocal ticks
            while True:
                ticks += 1
                await asyncio.sleep(0)

        beat = asyncio.get_running_loop().create_task(heart())
        try:
            await process_clean_up_async(lib.db)
        finally:
            beat.cancel()
        return ticks

    ticks = asyncio.run(run())
    # ~48 remaining full batches, each followed by a loop yield: the
    # heartbeat task keeps running DURING the prune, not just after
    assert ticks >= (total - alive - PRUNE_BATCH) // PRUNE_BATCH - 2
    kept = {r["name"] for r in lib.db.query("SELECT name FROM index_journal")}
    assert kept == {f"alive{i}" for i in range(alive)}
    lib.close()


# --- sync backfill streams in bounded chunks -------------------------------


def test_backfill_chunked_cursor_covers_every_row_once(tmp_path, monkeypatch):
    from spacedrive_tpu.sync import ingest

    lib = _mk_library(tmp_path)
    rows = 300
    lib.db.insert_many(
        "tag", ("pub_id", "name", "color"),
        [(os.urandom(16), f"t{i}", "#fff") for i in range(rows)],
    )
    # force many chunks so the cursor + per-chunk coverage probe are
    # exercised, not just the single-batch happy path
    monkeypatch.setattr(ingest, "BACKFILL_BATCH", 32)
    written = ingest.backfill_operations(lib.sync)
    assert written >= rows  # ≥: create + per-field update ops per row
    covered = lib.db.query_one(
        "SELECT COUNT(DISTINCT record_id) AS n FROM crdt_operation "
        "WHERE model = 'tag'")
    assert covered["n"] == rows
    # idempotent: the membership probe sees every chunk as covered
    assert ingest.backfill_operations(lib.sync) == 0
    lib.close()
