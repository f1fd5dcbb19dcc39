"""Critical-path attribution (telemetry/attrib.py) — the ISSUE 12
tentpole's provability bar.

Three layers:

- unit: the sweep partitions a synthetic span forest exactly (buckets
  always sum to the window; priority and nesting resolve overlap;
  uncovered wall time is the gap bucket);
- single node, REAL pass: on a clean identify pass the report's
  buckets sum to ≥ 90% of the measured wall time, and under a
  deterministic ``feeder.fetch`` stall (PR 6 fault plane) the link
  bucket — and only the link bucket — absorbs the injected time;
- two REAL nodes on the loopback duplex: a mesh-distributed identify
  pass assembles into ONE trace containing executor-side spans from
  the peer, and an injected ``p2p.trace_pull`` vanish degrades the
  assembly to a partial report instead of blocking it.
"""

import asyncio
import os
import time

import pytest

from spacedrive_tpu import telemetry
from spacedrive_tpu.telemetry import attrib
from spacedrive_tpu.telemetry import trace as sdtrace
from spacedrive_tpu.utils import faults

from test_mesh_indexing import build_corpus


def _span(stage, t0, dur, span_id, parent=None, trace_id="t", **extra):
    return {"stage": stage, "t0": t0, "seconds": dur, "span_id": span_id,
            "parent_id": parent, "trace_id": trace_id, **extra}


# --- unit: the sweep -------------------------------------------------------


def test_bucket_vocabulary():
    assert attrib.bucket_of("identify.hash") == attrib.DEVICE
    assert attrib.bucket_of("mesh.shard_hash") == attrib.DEVICE
    assert attrib.bucket_of("thumbnail.device") == attrib.DEVICE
    assert attrib.bucket_of("identify.db") == attrib.HOST_CPU
    assert attrib.bucket_of("walk") == attrib.HOST_CPU
    assert attrib.bucket_of("thumbnail.decode") == attrib.HOST_CPU
    assert attrib.bucket_of("sync.ingest") == attrib.HOST_CPU
    assert attrib.bucket_of("feeder.fetch") == attrib.LINK
    assert attrib.bucket_of("feeder.wait") == attrib.LINK
    assert attrib.bucket_of("p2p.sync_serve") == attrib.LINK
    assert attrib.bucket_of("relay.push") == attrib.LINK
    assert attrib.bucket_of("task.dispatch") == attrib.QUEUE_WAIT
    # unknown stages are orchestration — the gap
    assert attrib.bucket_of("job.something_new") == attrib.GAP


def test_report_partitions_window_exactly():
    telemetry.reset()
    spans = [
        _span("task.dispatch", 0.0, 1.0, "a"),
        _span("walk", 1.0, 2.0, "b", parent="a"),
        _span("identify.hash", 3.0, 3.0, "c", parent="a"),
        # concurrent prefetch overlapping walk + hash: never on the
        # critical path while a device/host stage runs
        _span("feeder.fetch", 2.5, 3.0, "d", parent="a"),
        _span("identify.db", 7.0, 1.0, "e", parent="a"),
    ]
    doc = attrib.report("t", spans)
    b = doc["buckets"]
    assert abs(doc["wall_seconds"] - 8.0) < 1e-6
    assert abs(sum(b.values()) - doc["wall_seconds"]) < 1e-4
    assert abs(b["queue_wait"] - 1.0) < 1e-6
    assert abs(b["host_cpu"] - 3.0) < 1e-6   # walk 2.0 + db 1.0
    assert abs(b["device"] - 3.0) < 1e-6     # hash outranks the fetch
    assert abs(b["link"] - 0.0) < 1e-6       # fetch fully shadowed
    assert abs(b["gap"] - 1.0) < 1e-6        # 6.0..7.0 uncovered
    assert doc["bucket_fractions"]["device"] == pytest.approx(3 / 8, abs=1e-3)


def test_report_blames_uncovered_stall_as_link_when_waiting():
    telemetry.reset()
    # the feeder.wait shape: consumer blocked, nothing else running
    spans = [
        _span("identify.hash", 0.0, 0.5, "a"),
        _span("feeder.wait", 0.5, 4.0, "w"),
        _span("identify.hash", 4.5, 0.5, "b"),
    ]
    doc = attrib.report("t", spans)
    assert doc["buckets"]["link"] == pytest.approx(4.0, abs=1e-6)
    assert doc["buckets"]["device"] == pytest.approx(1.0, abs=1e-6)
    top = doc["top_segments"][0]
    assert top["stage"] == "feeder.wait" and top["bucket"] == "link"


def test_report_handles_malformed_and_cyclic_records():
    telemetry.reset()
    spans = [
        {"stage": "walk"},                         # no timing: dropped
        _span("walk", 0.0, 1.0, "a", parent="b"),  # cycle a<->b
        _span("identify.db", 0.5, 1.0, "b", parent="a"),
    ]
    doc = attrib.report("t", spans)
    assert doc["spans"] == 2
    assert abs(sum(doc["buckets"].values()) - doc["wall_seconds"]) < 1e-4


def test_pass_markers_resolve_last_pass():
    telemetry.reset()
    attrib.mark_pass("indexer", "trace-1", "started")
    attrib.mark_pass("indexer", "trace-1", "settled", status="COMPLETED")
    attrib.mark_pass("file_identifier", "trace-2", "started")
    # trace-2 never settled: prefer the settled trace-1? no — the most
    # recent SETTLED pass wins, started-only is the fallback
    assert attrib.last_pass_trace() == "trace-1"
    attrib.mark_pass("file_identifier", "trace-2", "settled",
                     status="COMPLETED")
    assert attrib.last_pass_trace() == "trace-2"
    telemetry.reset()
    assert attrib.last_pass_trace() is None


def test_reset_clears_report_cache():
    telemetry.reset()
    doc = attrib.report("t", [_span("walk", 0.0, 1.0, "a")])
    attrib._cache_store("t", doc)
    assert attrib.cached_report("t") is not None
    telemetry.reset()
    assert attrib.cached_report("t") is None


# --- single real node: the provability bar ---------------------------------


async def _identify_pass(tmp_path, corpus, name="attrib-node"):
    """Index + identify under ONE fresh trace; returns (node, lib,
    trace_id, wall_seconds of the identify pass)."""
    from spacedrive_tpu.jobs.manager import JobBuilder
    from spacedrive_tpu.location.indexer.job import IndexerJob
    from spacedrive_tpu.location.locations import LocationCreateArgs
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.object.file_identifier.job import FileIdentifierJob

    node = Node(os.path.join(tmp_path, name), use_device=False,
                with_labeler=False)
    node.config.config.p2p.enabled = False
    await node.start()
    lib = await node.create_library("attrib")
    loc = LocationCreateArgs(path=corpus).create(lib)
    await JobBuilder(IndexerJob({"location_id": loc["id"]})).spawn(
        node.jobs, lib)
    await node.jobs.wait_idle()
    ctx = sdtrace.new_context()
    t0 = time.perf_counter()
    with sdtrace.use(ctx):
        await JobBuilder(FileIdentifierJob(
            {"location_id": loc["id"], "backend": "cpu"}
        )).spawn(node.jobs, lib)
    await node.jobs.wait_idle()
    wall = time.perf_counter() - t0
    return node, lib, ctx.trace_id, wall


def test_clean_pass_buckets_cover_wall_time(tmp_path):
    telemetry.reset()
    corpus = os.path.join(tmp_path, "corpus")
    build_corpus(corpus, n=60)

    async def run():
        node, _lib, trace_id, wall = await _identify_pass(tmp_path, corpus)
        try:
            doc = attrib.report(trace_id)
        finally:
            await node.shutdown()
        return doc, wall

    doc, wall = asyncio.run(run())
    assert doc["spans"] > 0
    total = sum(doc["buckets"].values())
    # the partition is exact over the span window; ≥90% of the measured
    # wall means the spans actually COVER the pass
    assert total == pytest.approx(doc["wall_seconds"], abs=1e-4)
    assert total >= 0.9 * wall, (doc, wall)
    # every bucket is a non-negative share of the window
    assert all(v >= 0 for v in doc["buckets"].values())
    assert sum(doc["bucket_fractions"].values()) == pytest.approx(
        1.0, abs=0.01)


def test_injected_feeder_stall_blames_the_link_bucket(tmp_path):
    """The acceptance bar: a deterministic feeder.fetch stall (PR 6
    fault plane) must land in the link/feeder bucket — not device, not
    host CPU."""
    telemetry.reset()
    corpus = os.path.join(tmp_path, "corpus")
    build_corpus(corpus, n=60)

    async def run():
        with faults.active(faults.FaultPlan.parse(
            "feeder.fetch:stall:delay_s=0.4"
        )):
            node, _lib, trace_id, wall = await _identify_pass(
                tmp_path, corpus, name="stalled")
            try:
                doc = attrib.report(trace_id)
            finally:
                await node.shutdown()
        return doc, wall

    doc, wall = asyncio.run(run())
    b = doc["buckets"]
    # the stall sleeps ≥0.4 s per window before the read while the
    # consumer parks in feeder.wait — the link bucket must dominate
    assert b["link"] >= 0.3, doc
    assert b["link"] > b["device"], doc
    assert b["link"] > b["host_cpu"], doc
    assert sum(b.values()) >= 0.9 * wall


# --- two real nodes: distributed assembly ----------------------------------


def test_cross_node_trace_assembly(tmp_path):
    """A mesh-distributed identify pass is ONE trace: the coordinator's
    assembled report contains executor-side spans pulled from the peer
    under the same trace_id."""
    from spacedrive_tpu.location.indexer.mesh import distribute_location_index
    from spacedrive_tpu.location.locations import LocationCreateArgs
    from spacedrive_tpu.p2p.loopback import make_mesh_pair

    telemetry.reset()
    corpus = os.path.join(tmp_path, "corpus")
    build_corpus(corpus, n=40)

    async def run():
        a, b, lib_a, _lib_b, _tasks = await make_mesh_pair(tmp_path)
        try:
            loc = LocationCreateArgs(path=corpus).create(lib_a)
            ctx = sdtrace.new_context()
            with sdtrace.use(ctx):
                stats = await distribute_location_index(
                    a, lib_a, loc["id"], shard_files=8,
                    lease_max_s=10.0, deadline_s=120.0,
                )
            doc = await attrib.assemble(a, ctx.trace_id, refresh=True)
            return stats, doc
        finally:
            await a.shutdown()
            await b.shutdown()

    stats, doc = asyncio.run(run())
    assert stats["remote_shards"] > 0, "peer stole nothing — no mesh pass"
    assert doc["partial"] is False
    assert doc["remote_spans"] > 0, doc
    # the peer's execution shows up under its short-hash node label
    assert [n for n in doc["nodes"] if n != "local"], doc["nodes"]
    assert doc["wall_seconds"] > 0
    assert sum(doc["buckets"].values()) == pytest.approx(
        doc["wall_seconds"], abs=1e-4)  # per-bucket 6-dp rounding


def test_cross_node_assembly_degrades_on_peer_vanish(tmp_path):
    """p2p.trace_pull vanish: the peer closes the stream instead of
    serving its spans — assembly must return a PARTIAL report with the
    failure recorded, quickly, never block or raise."""
    from spacedrive_tpu.location.indexer.mesh import distribute_location_index
    from spacedrive_tpu.location.locations import LocationCreateArgs
    from spacedrive_tpu.p2p.loopback import make_mesh_pair

    telemetry.reset()
    corpus = os.path.join(tmp_path, "corpus")
    build_corpus(corpus, n=24)

    async def run():
        a, b, lib_a, _lib_b, _tasks = await make_mesh_pair(tmp_path)
        try:
            loc = LocationCreateArgs(path=corpus).create(lib_a)
            ctx = sdtrace.new_context()
            with sdtrace.use(ctx):
                await distribute_location_index(
                    a, lib_a, loc["id"], shard_files=8,
                    lease_max_s=10.0, deadline_s=120.0,
                )
            # times=inf: a vanished peer stays vanished across the
            # resilience policy's retry ladder (times defaults to 1,
            # which models a blip the retry absorbs — not this test)
            from spacedrive_tpu.p2p import operations as _ops

            prev_timeout = _ops.TELEMETRY_TIMEOUT
            _ops.TELEMETRY_TIMEOUT = 1.5  # keep the dead-peer wait short
            try:
                with faults.active(faults.FaultPlan.parse(
                    "p2p.trace_pull:vanish:times=inf"
                )):
                    t0 = time.monotonic()
                    doc = await attrib.assemble(a, ctx.trace_id,
                                                refresh=True)
                    elapsed = time.monotonic() - t0
            finally:
                _ops.TELEMETRY_TIMEOUT = prev_timeout
            return doc, elapsed
        finally:
            await a.shutdown()
            await b.shutdown()

    doc, elapsed = asyncio.run(run())
    assert doc["partial"] is True
    assert doc["pull_failures"], doc
    assert doc["remote_spans"] == 0
    # local spans still produce a full local report
    assert doc["spans"] > 0
    assert elapsed < 60.0, "partial assembly must not block"
    assert telemetry.counter_value("sd_attrib_pull_failures_total") >= 1


def test_assemble_caches_only_settled_complete_reports():
    """Review fix: a mid-pass or partial assembly must NOT freeze in
    the report cache — only a settled pass's complete report is
    immutable."""
    telemetry.reset()

    class Bare:  # no p2p: remote pulls skipped, never partial
        p2p = None

    async def run():
        # running pass: started, never settled → recompute every read
        attrib.mark_pass("file_identifier", "t-live", "started")
        sdtrace.record_span(_span("walk", 0.0, 1.0, "a",
                                  trace_id="t-live"))
        doc = await attrib.assemble(Bare, "t-live")
        assert doc["spans"] == 1
        assert attrib.cached_report("t-live") is None
        # the pass settles: now the report is immutable and cacheable
        attrib.mark_pass("file_identifier", "t-live", "settled",
                         status="COMPLETED")
        doc = await attrib.assemble(Bare, "t-live")
        assert attrib.cached_report("t-live") is not None
        # a chained job re-opening the same trace re-opens the pass
        attrib.mark_pass("media_processor", "t-live", "started")
        assert attrib._pass_settled("t-live") is False
        return doc

    asyncio.run(run())
    telemetry.reset()


def test_rspc_exec_feeds_interactive_request_seconds(tmp_path):
    """Review fix: the interactive_p99 SLO input must cover the rspc
    surface (the normal client path), not only raw HTTP routes."""
    from spacedrive_tpu.node import Node

    telemetry.reset()

    async def run():
        node = Node(os.path.join(tmp_path, "rspc-node"), use_device=False,
                    with_labeler=False)
        node.config.config.p2p.enabled = False
        if node.serve is None:
            pytest.skip("serve gate disabled in this environment")
        try:
            await node.router.exec(node, "library.list")
        finally:
            await node.shutdown()

    asyncio.run(run())
    from spacedrive_tpu.telemetry import histogram_recent

    samples = histogram_recent("sd_serve_request_seconds",
                               klass="interactive")
    assert samples, "rspc exec recorded no request latency"
    telemetry.reset()
