"""The unified stage-typed execution continuum (ISSUE 19): stage-typed
WORK shards for thumbnails / media / pHash / embeddings, the per-stage
lease law, the per-stage lease targets, and the two-node
chaos proof that a distributed thumbnail+embed pass converges
BIT-IDENTICAL (webp bytes, embedding vectors, journal vouches) to a
single-node pass — including under mid-lease peer death and claim
races (``p2p.steal`` fault point)."""

import asyncio
import os
import uuid

import numpy as np
import pytest
from PIL import Image

from spacedrive_tpu import telemetry
from spacedrive_tpu.telemetry import counter_value, gauge_value
from spacedrive_tpu.utils import faults


# --- corpus + observable-state helpers --------------------------------------


def build_image_corpus(root: str, n: int = 12, seed: int = 5) -> None:
    rng = np.random.default_rng(seed)
    os.makedirs(root, exist_ok=True)
    for i in range(n):
        arr = rng.integers(0, 256, (40 + 8 * (i % 5), 64, 3), np.uint8)
        Image.fromarray(arr).save(os.path.join(root, f"img{i:03d}.png"))


def thumb_map(node, lib, loc_id: int) -> dict[str, bytes | None]:
    """cas_id → stored webp bytes (None = missing): the thumbnail
    stage's observable output, content-keyed so two libraries (solo
    oracle vs mesh coordinator) compare equal."""
    store = node.thumbnailer.store
    out: dict[str, bytes | None] = {}
    for r in lib.db.query(
        "SELECT DISTINCT cas_id FROM file_path WHERE location_id = ? "
        "AND is_dir = 0 AND cas_id IS NOT NULL", (loc_id,)
    ):
        path = store.path_for(str(lib.id), r["cas_id"])
        try:
            with open(path, "rb") as f:
                out[r["cas_id"]] = f.read()
        except OSError:
            out[r["cas_id"]] = None
    return out


def embed_map(lib, loc_id: int) -> dict[str, bytes | None]:
    """cas_id → embedding vector blob (bit-exact f32 bytes)."""
    rows = lib.db.query(
        "SELECT fp.cas_id, oe.vector AS vec FROM file_path fp "
        "JOIN object o ON o.id = fp.object_id "
        "LEFT JOIN object_embedding oe ON oe.object_id = o.id "
        "WHERE fp.location_id = ? AND fp.is_dir = 0 "
        "AND fp.cas_id IS NOT NULL", (loc_id,)
    )
    return {r["cas_id"]: r["vec"] for r in rows}


def vouch_map(lib, loc_id: int) -> dict[tuple, tuple]:
    """journal key → (cas_id, thumb-vouched, embed-vouched)."""
    from spacedrive_tpu.location.indexer.journal import IndexJournal, key_of

    journal = IndexJournal(lib.db)
    out = {}
    for row in lib.db.query(
        "SELECT * FROM index_journal WHERE location_id = ?", (loc_id,)
    ):
        entry = journal._entry_of(row)
        assert entry is not None, "corrupt journal row"
        out[key_of(row)] = (entry.cas_id, bool(entry.thumb),
                            bool(entry.embed))
    return out


async def _index_and_identify(node, lib, loc_id: int) -> None:
    from spacedrive_tpu.jobs.manager import JobBuilder
    from spacedrive_tpu.location.indexer.job import IndexerJob
    from spacedrive_tpu.object.file_identifier.job import FileIdentifierJob

    for job_cls, init in (
        (IndexerJob, {"location_id": loc_id}),
        (FileIdentifierJob, {"location_id": loc_id, "backend": "cpu"}),
    ):
        await JobBuilder(job_cls(init)).spawn(node.jobs, lib)
        await node.jobs.wait_idle()


async def single_node_stage_reference(tmp_path, corpus: str):
    """The oracle: a no-P2P node running the SAME distribute entry
    point (which degrades to pure-local execution — the degradation
    contract is part of what this proves). Returns the three maps."""
    from spacedrive_tpu.location.indexer.mesh import (
        distribute_location_stages,
    )
    from spacedrive_tpu.location.locations import LocationCreateArgs
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.parallel import scheduler

    node = Node(os.path.join(tmp_path, "solo"), use_device=False,
                with_labeler=False)
    node.config.config.p2p.enabled = False
    await node.start()
    try:
        lib = await node.create_library("solo")
        loc = LocationCreateArgs(path=corpus).create(lib)
        await _index_and_identify(node, lib, loc["id"])
        stats = await distribute_location_stages(
            node, lib, loc["id"],
            [scheduler.STAGE_THUMB, scheduler.STAGE_EMBED],
        )
        assert stats["remote_shards"] == 0  # pure-local degradation
        assert stats["stages"].get("thumb", 0) >= 1
        return (
            thumb_map(node, lib, loc["id"]),
            embed_map(lib, loc["id"]),
            vouch_map(lib, loc["id"]),
        )
    finally:
        await node.shutdown()


async def two_node_stage_pass(tmp_path, corpus: str, *,
                              lease_max_s=10.0, shard_files=2,
                              fault_plan=None):
    """Two-node pass: distributed identify first, then the stage-typed
    thumb+embed session (optionally under a fault plan). Returns
    (a, b, lib_a, loc, stats) — caller shuts the nodes down."""
    from spacedrive_tpu.location.indexer.mesh import (
        distribute_location_index,
        distribute_location_stages,
    )
    from spacedrive_tpu.location.locations import LocationCreateArgs
    from spacedrive_tpu.p2p.loopback import make_mesh_pair
    from spacedrive_tpu.parallel import scheduler

    a, b, lib_a, _lib_b, _tasks = await make_mesh_pair(tmp_path)
    loc = LocationCreateArgs(path=corpus).create(lib_a)
    await distribute_location_index(
        a, lib_a, loc["id"], shard_files=shard_files, deadline_s=120.0,
    )
    if fault_plan is not None:
        with faults.active(fault_plan):
            stats = await distribute_location_stages(
                a, lib_a, loc["id"],
                [scheduler.STAGE_THUMB, scheduler.STAGE_EMBED],
                shard_files=shard_files, lease_max_s=lease_max_s,
                deadline_s=120.0,
            )
    else:
        stats = await distribute_location_stages(
            a, lib_a, loc["id"],
            [scheduler.STAGE_THUMB, scheduler.STAGE_EMBED],
            shard_files=shard_files, lease_max_s=lease_max_s,
            deadline_s=120.0,
        )
    return a, b, lib_a, loc, stats


# --- scheduler registry + lease law -----------------------------------------


def test_stage_registry_and_single_stage_lease_parity():
    from spacedrive_tpu.p2p.work import LEASE_MIN_S, LEASE_SLACK
    from spacedrive_tpu.parallel import scheduler

    telemetry.reset()
    assert set(scheduler.STAGES) == {
        "identify.hash", "thumb", "media.extract", "phash", "embed",
    }
    with pytest.raises(KeyError):
        scheduler.spec("no.such.stage")
    # a single-stage grant reproduces the pre-continuum lease law
    # bit-for-bit: min(max(MIN, files/rate*SLACK), lease_max)
    assert scheduler.lease_seconds_for("identify.hash", 16, 2.0, 60.0) \
        == pytest.approx(min(max(LEASE_MIN_S, 16 / 2.0 * LEASE_SLACK), 60.0))
    # no rate anywhere → the static default keeps leases finite
    from spacedrive_tpu.p2p.work import DEFAULT_FILES_PER_S

    got = scheduler.lease_seconds_for("thumb", 128, 0.0, 120.0)
    assert got == pytest.approx(min(max(
        LEASE_MIN_S, 128 / DEFAULT_FILES_PER_S * LEASE_SLACK), 120.0))
    # an observed EWMA becomes the claimer-rate fallback and the gauge
    scheduler.RATES.observe("thumb", 100, 2.0)
    assert scheduler.observed_files_per_s("thumb") == pytest.approx(50.0)
    assert gauge_value("sd_work_stage_rate_files_per_s", stage="thumb") \
        == pytest.approx(50.0)
    telemetry.reset()
    assert scheduler.observed_files_per_s("thumb") == 0.0


def _stage_session(library_id, stages_counts: dict[str, int],
                   files_per_shard=8, lease_max_s=60.0):
    from spacedrive_tpu.p2p.work import WorkSession, WorkShard

    s = WorkSession(id=uuid.uuid4().hex, library_id=library_id,
                    location_pub="00" * 16, lease_max_s=lease_max_s)
    for stage, n in stages_counts.items():
        for i in range(n):
            sid = f"{stage}-{i}"
            s.shards[sid] = WorkShard(
                id=sid, stage=stage,
                entries=[{"pub_id": f"{i:02x}{j:02x}" * 8}
                         for j in range(files_per_shard)],
            )
    return s


def test_multi_stage_lease_sums_per_stage_and_clamps():
    from spacedrive_tpu.p2p.work import LEASE_SLACK, WorkBoard

    telemetry.reset()
    board = WorkBoard()
    session = _stage_session(uuid.uuid4(), {"thumb": 1, "embed": 1},
                             files_per_shard=10, lease_max_s=600.0)
    board.publish(session)
    # per-stage self-report: thumb at 10 files/s, embed at 2 files/s —
    # contributions 10/10*4=4→MIN(5) and 10/2*4=20, summed
    _s, grant, lease_s = board.claim(
        session.id, "p", max_shards=2,
        rates={"thumb": 10.0, "embed": 2.0}, verdict="healthy",
    )
    assert len(grant) == 2
    assert lease_s == pytest.approx(5.0 + 10 / 2.0 * LEASE_SLACK)
    # the session clamp still caps the sum
    board2 = WorkBoard()
    s2 = _stage_session(uuid.uuid4(), {"thumb": 1, "embed": 1},
                        files_per_shard=1000, lease_max_s=7.0)
    board2.publish(s2)
    _s, grant, lease_s = board2.claim(s2.id, "p", max_shards=2,
                                      files_per_s=1.0)
    assert len(grant) == 2 and lease_s == 7.0
    telemetry.reset()


def test_rates_prefer_claimers_fastest_stage():
    """Heterogeneous fleet: a claimer reporting it is fast at embed
    drains embed shards before thumb shards."""
    from spacedrive_tpu.p2p.work import WorkBoard

    telemetry.reset()
    board = WorkBoard()
    session = _stage_session(uuid.uuid4(), {"thumb": 3, "embed": 3})
    board.publish(session)
    _s, grant, _l = board.claim(
        session.id, "gpu-peer", max_shards=3,
        rates={"embed": 500.0, "thumb": 5.0},
    )
    assert [sh.stage for sh in grant] == ["embed", "embed", "embed"]
    # a rate-less claimer keeps publish order (no preference signal)
    _s, grant, _l = board.claim(session.id, "plain-peer", max_shards=3)
    assert [sh.stage for sh in grant] == ["thumb", "thumb", "thumb"]
    telemetry.reset()


def test_sessionless_claim_not_masked_by_newer_leased_session():
    """The strand fix (ISSUE 19 satellite): a newer fully-leased
    session must not hide an older session's AVAILABLE shards from
    sessionless (idle-steal) claims — before the fix a multi-stage
    session finishing one stage first could strand the other stage's
    unclaimed shards behind it."""
    from spacedrive_tpu.p2p.work import WorkBoard

    telemetry.reset()
    lib_id = uuid.uuid4()
    board = WorkBoard()
    older = _stage_session(lib_id, {"embed": 2})
    board.publish(older)
    newer = _stage_session(lib_id, {"thumb": 2})
    board.publish(newer)
    assert newer.created_at >= older.created_at
    # lease EVERYTHING in the newer session
    _s, grant, _l = board.claim(newer.id, "busy", max_shards=99)
    assert len(grant) == 2
    # an idle peer with no session id must fall through to the older
    # session's available shards, not poll the newer one empty-handed
    got, grant, _l = board.claim(None, "idle", library_id=lib_id,
                                 max_shards=2)
    assert got is older, "newer leased session masked older's work"
    assert len(grant) == 2 and all(sh.stage == "embed" for sh in grant)
    # everything in flight everywhere: polls the newest open session
    got, grant, _l = board.claim(None, "late", library_id=lib_id)
    assert got is newer and grant == []
    telemetry.reset()


# --- autotune: per-stage lease targets ----------------------------------------


def test_stage_lease_targets_follow_rates_with_hysteresis():
    from spacedrive_tpu.p2p.work import LEASE_MIN_S, LEASE_SLACK
    from spacedrive_tpu.location.indexer.mesh import shard_files_default
    from spacedrive_tpu.parallel import scheduler
    from spacedrive_tpu.parallel.autotune import Controller, Sample

    telemetry.reset()
    c = Controller(interval=999)
    files = shard_files_default()
    rate = files / 2.0  # → target = 2.0 * LEASE_SLACK (above the floor)
    scheduler.RATES.observe("embed", int(rate * 10), 10.0)
    decisions = [d for d in c.tick(Sample())
                 if d.get("knob") == "stage_lease"]
    assert decisions and decisions[0]["stage"] == "embed"
    want = max(LEASE_MIN_S, 2.0 * LEASE_SLACK)
    assert c.stage_lease["embed"] == pytest.approx(want, rel=0.2)
    assert c.stage_rate("embed") > 0
    assert gauge_value("sd_work_stage_lease_target_seconds",
                       stage="embed") == pytest.approx(
                           c.stage_lease["embed"])
    # inside the hysteresis band: no re-publish
    assert not [d for d in c.tick(Sample())
                if d.get("knob") == "stage_lease"]
    # the continuum state rides the autotune snapshot (→ /mesh)
    snap = c.snapshot()
    assert "embed" in snap["stages"]["lease_targets"]
    assert snap["stages"]["rates"]["embed"]["files_per_s"] > 0
    # telemetry.reset() clears the EWMAs and the derived targets
    telemetry.reset()
    assert scheduler.RATES.rate("embed") == 0.0
    assert not [d for d in c.tick(Sample())
                if d.get("knob") == "stage_lease"]


# --- the two-node distributed thumbnail+embed pass --------------------------


@pytest.mark.asyncio
async def test_two_node_thumb_embed_bit_identical(tmp_path):
    """The continuum acceptance loop: a 2-node stage-typed thumb+embed
    pass converges bit-identical — webp bytes, embedding vectors,
    journal vouches — to the single-node pass, and the peer really
    executed stage shards through the WORK plane."""
    corpus = os.path.join(tmp_path, "corpus")
    build_image_corpus(corpus)
    telemetry.reset()
    ref_thumbs, ref_embeds, ref_vouches = await single_node_stage_reference(
        tmp_path, corpus
    )
    assert all(v is not None for v in ref_thumbs.values())
    assert all(v is not None for v in ref_embeds.values())

    telemetry.reset()
    a, b, lib_a, loc, stats = await two_node_stage_pass(tmp_path, corpus)
    try:
        assert stats["stages"]["thumb"] >= 2
        assert stats["stages"]["embed"] >= 2
        assert stats["remote_shards"] > 0, stats
        assert b.p2p.work.worker.executed_shards > 0
        got_remote = sum(
            counter_value("sd_work_shards_total", result="completed_remote",
                          stage=st)
            for st in ("thumb", "embed")
        )
        assert got_remote > 0
        # the worker self-reports per-stage rates once it executed them
        rates = b.p2p.work.worker.rates_report()
        assert rates.get("thumb", 0) > 0 or rates.get("embed", 0) > 0

        assert thumb_map(a, lib_a, loc["id"]) == ref_thumbs
        assert embed_map(lib_a, loc["id"]) == ref_embeds
        assert vouch_map(lib_a, loc["id"]) == ref_vouches
    finally:
        await a.shutdown()
        await b.shutdown()
    telemetry.reset()


@pytest.mark.asyncio
async def test_stage_peer_death_mid_lease_converges(tmp_path):
    """Chaos: the stealing peer vanishes after its first stage lease.
    The lease expires, the coordinator re-pools and re-executes the
    abandoned stage shards, and the result is STILL bit-identical."""
    corpus = os.path.join(tmp_path, "corpus")
    build_image_corpus(corpus, n=10, seed=23)
    telemetry.reset()
    ref_thumbs, ref_embeds, ref_vouches = await single_node_stage_reference(
        tmp_path, corpus
    )

    telemetry.reset()
    plan = faults.FaultPlan.parse("p2p.steal:vanish:arg=lease,times=1")
    a, b, lib_a, loc, stats = await two_node_stage_pass(
        tmp_path, corpus, lease_max_s=0.5, fault_plan=plan,
    )
    try:
        assert plan.activations().get("p2p.steal", 0) >= 1
        expired = sum(
            counter_value("sd_work_shards_total", result="expired",
                          stage=st)
            for st in ("thumb", "embed")
        )
        assert expired >= 1
        assert stats["local_shards"] + stats["remote_shards"] == \
            stats["shards"]
        assert thumb_map(a, lib_a, loc["id"]) == ref_thumbs
        assert embed_map(lib_a, loc["id"]) == ref_embeds
        assert vouch_map(lib_a, loc["id"]) == ref_vouches
    finally:
        await a.shutdown()
        await b.shutdown()
    telemetry.reset()


@pytest.mark.asyncio
async def test_stage_claim_race_double_execution_converges(tmp_path):
    """Chaos: every stage claim double-leases an in-flight shard —
    thumb and embed shards get executed twice on different nodes. The
    deterministic encoders (same webp bytes, seed-deterministic embed
    forward) make both executions ship identical results, so the
    duplicate completion is absorbed bit-identically."""
    corpus = os.path.join(tmp_path, "corpus")
    build_image_corpus(corpus, n=10, seed=29)
    telemetry.reset()
    ref_thumbs, ref_embeds, ref_vouches = await single_node_stage_reference(
        tmp_path, corpus
    )

    telemetry.reset()
    plan = faults.FaultPlan.parse("p2p.steal:race:arg=claim,times=")
    a, b, lib_a, loc, _stats = await two_node_stage_pass(
        tmp_path, corpus, fault_plan=plan,
    )
    try:
        assert plan.activations().get("p2p.steal", 0) >= 1
        assert thumb_map(a, lib_a, loc["id"]) == ref_thumbs
        assert embed_map(lib_a, loc["id"]) == ref_embeds
        assert vouch_map(lib_a, loc["id"]) == ref_vouches
    finally:
        await a.shutdown()
        await b.shutdown()
    telemetry.reset()
