"""Telemetry subsystem: registry semantics, spans, Prometheus text,
and the dispatch-path instrumentation populated by a real dry-run
identify+thumbnail pass."""

import asyncio
import os
import re

import pytest

from spacedrive_tpu import telemetry
from spacedrive_tpu.telemetry import metrics as tm
from spacedrive_tpu.telemetry.registry import (
    MAX_SERIES_PER_FAMILY,
    OVERFLOW_LABEL,
    MetricsRegistry,
)


# --- registry semantics ---------------------------------------------------


def test_counter_monotonic_and_render():
    r = MetricsRegistry()
    c = r.counter("t_requests_total", "requests", labels=("route",))
    c.inc(route="/a")
    c.inc(2, route="/a")
    c.inc(route="/b")
    assert c.value(route="/a") == 3
    with pytest.raises(ValueError):
        c.inc(-1, route="/a")
    text = r.render()
    assert "# TYPE t_requests_total counter" in text
    assert 't_requests_total{route="/a"} 3' in text
    assert 't_requests_total{route="/b"} 1' in text


def test_unlabeled_counter_renders_zero_before_first_event():
    # absence means "not wired"; zero means "wired, idle" — the four
    # acceptance metrics must be scrapeable before traffic arrives
    r = MetricsRegistry()
    r.counter("t_idle_total", "idle")
    assert "t_idle_total 0" in r.render()


def test_gauge_set_inc_dec():
    r = MetricsRegistry()
    g = r.gauge("t_depth", "queue depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value() == 3
    assert "t_depth 3" in r.render()


def test_histogram_bucketing_and_exposition():
    r = MetricsRegistry()
    h = r.histogram("t_lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    text = r.render()
    # cumulative bucket counts, +Inf, sum and count
    assert 't_lat_seconds_bucket{le="0.01"} 2' in text
    assert 't_lat_seconds_bucket{le="0.1"} 3' in text
    assert 't_lat_seconds_bucket{le="1"} 4' in text
    assert 't_lat_seconds_bucket{le="+Inf"} 5' in text
    assert "t_lat_seconds_count 5" in text
    assert h.stats()["count"] == 5
    assert h.recent() == [0.005, 0.005, 0.05, 0.5, 5.0]


def test_label_cardinality_cap_folds_into_overflow():
    r = MetricsRegistry()
    c = r.counter("t_hot_total", "hot path", labels=("key",))
    for i in range(MAX_SERIES_PER_FAMILY + 50):
        c.inc(key=f"k{i}")
    fam = r.get("t_hot_total")
    # the family cannot grow past the cap (+ nothing lost: overflow
    # absorbs the excess)
    assert len(fam._series) <= MAX_SERIES_PER_FAMILY + 1
    assert c.value(key=OVERFLOW_LABEL) == 50


def test_reads_do_not_mint_series():
    """Regression (sdlint SD007's hazard on the read side): probing an
    unseen label set via value()/recent()/stats() must return a default
    WITHOUT creating a permanent series — a dashboard or snapshot helper
    polling a typo'd label must not eat the family's cardinality cap."""
    r = MetricsRegistry()
    c = r.counter("t_ro_total", "reads", labels=("key",))
    g = r.gauge("t_ro_depth", "reads", labels=("key",))
    h = r.histogram("t_ro_seconds", "reads", labels=("key",))
    c.inc(key="real")
    assert c.value(key="typo") == 0.0
    assert g.value(key="typo") == 0.0
    assert h.recent(key="typo") == []
    assert h.stats(key="typo") == {"sum": 0.0, "count": 0}
    for fam_name in ("t_ro_total", "t_ro_depth", "t_ro_seconds"):
        fam = r.get(fam_name)
        assert all("typo" not in k for k in fam._series), fam._series
    assert c.value(key="real") == 1.0  # real series still reads back


def test_unknown_label_names_raise():
    r = MetricsRegistry()
    c = r.counter("t_l_total", "labeled", labels=("a",))
    with pytest.raises(ValueError):
        c.inc(b=1)


def test_type_conflict_raises_and_registration_is_idempotent():
    r = MetricsRegistry()
    c1 = r.counter("t_same_total", "x")
    assert r.counter("t_same_total") is c1
    with pytest.raises(ValueError):
        r.gauge("t_same_total")


def test_reset_zeroes_but_keeps_default_series():
    r = MetricsRegistry()
    c = r.counter("t_r_total", "x")
    c.inc(5)
    r.reset()
    assert c.value() == 0
    assert "t_r_total 0" in r.render()


def test_registry_is_thread_safe_under_contention():
    import threading

    r = MetricsRegistry()
    c = r.counter("t_mt_total", "contended")

    def spin():
        for _ in range(5000):
            c.inc()

    threads = [threading.Thread(target=spin) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 8 * 5000


def test_label_escaping_in_exposition():
    r = MetricsRegistry()
    c = r.counter("t_esc_total", "x", labels=("p",))
    c.inc(p='we"ird\\path\n')
    assert 't_esc_total{p="we\\"ird\\\\path\\n"} 1' in r.render()


def _parse_prom(text: str) -> dict[str, float]:
    """{'name{labels}': value} for every sample line in the exposition."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        out[key] = float(val)
    return out


def test_render_histogram_inf_count_sum_consistency():
    """Prometheus-contract check over the RENDERED text: for every
    histogram series the +Inf bucket equals _count, buckets are
    monotonically non-decreasing, and _sum parses back to the observed
    total — the scrape surface can't drift from the internal state."""
    r = MetricsRegistry()
    h = r.histogram("t_c_seconds", "x", labels=("stage",),
                    buckets=(0.01, 0.1, 1.0))
    obs = {"a": [0.005, 0.5, 50.0], "b": [0.05]}
    for stage, vals in obs.items():
        for v in vals:
            h.observe(v, stage=stage)
    samples = _parse_prom(r.render())
    for stage, vals in obs.items():
        inf = samples[f't_c_seconds_bucket{{stage="{stage}",le="+Inf"}}']
        count = samples[f't_c_seconds_count{{stage="{stage}"}}']
        total = samples[f't_c_seconds_sum{{stage="{stage}"}}']
        assert inf == count == len(vals)
        assert total == pytest.approx(sum(vals))
        cum = [
            samples[f't_c_seconds_bucket{{stage="{stage}",le="{le}"}}']
            for le in ("0.01", "0.1", "1", "+Inf")
        ]
        assert cum == sorted(cum), f"non-monotonic buckets for {stage}"


def test_render_consistency_across_every_registered_family():
    """The same invariant over the LIVE process registry after real
    traffic: every histogram family's rendered +Inf == _count."""
    telemetry.REGISTRY.render()  # must not raise
    for fam_name, fam in telemetry.REGISTRY._families.items():
        if fam.kind != "histogram":
            continue
        for key, s in fam._series.items():
            assert sum(s.bucket_counts) == s.count, (fam_name, key)


def test_telemetry_reset_clears_spans_trace_and_event_rings():
    from spacedrive_tpu.telemetry import events, trace

    with telemetry.span("reset_probe"):
        pass
    events.ring("reset_probe_ring").emit("tick")
    assert telemetry.recent_spans() and trace.recent()
    telemetry.reset()
    assert telemetry.recent_spans() == []
    assert trace.recent() == []
    assert events.ring("reset_probe_ring").snapshot() == []


def test_telemetry_reset_clears_attrib_slo_and_history_tails(tmp_path):
    """reset() must also clear the observability planes ISSUE 12 added:
    the attribution report cache + pass markers, SLO evaluation state,
    and every live history writer's in-memory tail — WITHOUT touching
    the durable history segments (data-dir state, not process state)."""
    from spacedrive_tpu.telemetry import attrib, history, slo

    attrib.mark_pass("indexer", "t-reset", "settled", status="COMPLETED")
    attrib._cache_store("t-reset", {"trace_id": "t-reset"})
    w = history.HistoryWriter(
        str(tmp_path / "hist"), samplers={"x": lambda: 1.0})
    w.sample()
    slo.evaluate(w)
    assert attrib.last_pass_trace() == "t-reset"
    assert slo.REGISTRY.last_evaluation is not None
    assert len(w.tail) == 1

    telemetry.reset()

    assert attrib.last_pass_trace() is None
    assert attrib.cached_report("t-reset") is None
    assert slo.REGISTRY.last_evaluation is None
    assert len(w.tail) == 0
    assert len(history.read(w.dir)) == 1  # durable segments survive


def test_overflowing_ring_reports_drops_honestly():
    """A bounded ring that displaces events must SAY so: per-ring drop
    counter, the sd_ring_dropped_total{ring} series, and the debug
    bundle's ring_drops section."""
    from spacedrive_tpu.telemetry import events
    from spacedrive_tpu.telemetry.bundle import build_bundle

    telemetry.reset()
    ring = events.ring("overflow_probe", capacity=8)
    for i in range(20):
        ring.emit("tick", i=i)
    assert len(ring) == 8
    assert ring.dropped == 12
    assert telemetry.counter_value(
        "sd_ring_dropped_total", ring="overflow_probe") == 12
    assert events.drop_counts()["overflow_probe"] == 12
    # the debug bundle carries the same honesty
    bundle = build_bundle()
    assert bundle["ring_drops"]["overflow_probe"] == 12
    # federation ring digests flag the saturated ring mesh-wide
    from spacedrive_tpu.telemetry.federation import _ring_digests

    assert _ring_digests()["overflow_probe"]["dropped"] == 12
    # clear() resets the account alongside the payloads
    ring.clear()
    assert ring.dropped == 0
    telemetry.reset()


def test_ring_within_capacity_drops_nothing():
    from spacedrive_tpu.telemetry import events

    telemetry.reset()
    ring = events.ring("no_overflow_probe", capacity=8)
    for i in range(8):
        ring.emit("tick", i=i)
    assert ring.dropped == 0
    assert telemetry.counter_value(
        "sd_ring_dropped_total", ring="no_overflow_probe") == 0
    assert "no_overflow_probe" not in events.drop_counts()
    telemetry.reset()


# --- spans ----------------------------------------------------------------


def test_span_nesting_under_asyncio():
    async def run():
        telemetry.clear_recent()

        async def pipeline(tag):
            async with telemetry.span(tag):
                await asyncio.sleep(0.01)
                with telemetry.span("inner", nbytes=7) as sp:
                    # contextvars: each task sees only its own parent
                    assert telemetry.current_span() is sp
                    assert sp.path == f"{tag}.inner"

        await asyncio.gather(pipeline("a"), pipeline("b"))

    asyncio.run(run())
    stages = {s["stage"] for s in telemetry.recent_spans()}
    assert {"a", "b", "a.inner", "b.inner"} <= stages
    # byte accounting reached the counter
    assert tm.SPAN_BYTES.value(stage="a.inner") >= 7


def test_span_records_duration_and_error():
    telemetry.clear_recent()
    with pytest.raises(RuntimeError):
        with telemetry.span("boom"):
            raise RuntimeError("x")
    rec = telemetry.recent_spans()[-1]
    assert rec["stage"] == "boom"
    assert rec["error"] == "RuntimeError"
    assert rec["seconds"] >= 0


# --- dispatch-path instrumentation (dry-run identify+thumbnail) -----------


@pytest.fixture()
def corpus(tmp_path):
    from PIL import Image

    d = tmp_path / "corpus"
    d.mkdir()
    (d / "alpha.txt").write_bytes(b"a" * 5000)
    (d / "beta.bin").write_bytes(os.urandom(2000))
    Image.new("RGB", (64, 48), (40, 200, 40)).save(d / "real.png")
    return str(d)


def _metric_value(text: str, name: str) -> float | None:
    m = re.search(rf"^{name}(?:{{[^}}]*}})? (\S+)$", text, re.M)
    return float(m.group(1)) if m else None


def test_dry_run_index_pass_populates_dispatch_and_feeder_metrics(
    tmp_path, corpus
):
    async def run():
        import aiohttp

        from spacedrive_tpu.location.locations import (
            LocationCreateArgs, scan_location,
        )
        from spacedrive_tpu.node import Node

        before_h2d = tm.FEEDER_H2D_BYTES.value()
        before_occ = tm.TASK_BATCH_OCCUPANCY.stats()["count"]

        node = Node(os.path.join(tmp_path, "node"), use_device=False)
        node.config.config.p2p.enabled = False
        await node.start()
        lib = await node.create_library("telemetry-lib")
        loc = LocationCreateArgs(path=corpus, name="corpus").create(lib)
        await scan_location(lib, loc, node.jobs)
        await node.jobs.wait_idle()
        await node.thumbnailer.wait_library_batch(str(lib.id))
        try:
            port = await node.start_api()
            async with aiohttp.ClientSession() as http:
                async with http.get(
                    f"http://127.0.0.1:{port}/metrics"
                ) as resp:
                    assert resp.status == 200
                    assert resp.content_type == "text/plain"
                    text = await resp.text()
                async with http.post(
                    f"http://127.0.0.1:{port}/rspc/telemetry.snapshot",
                    json={},
                ) as resp:
                    snap = (await resp.json())["result"]
        finally:
            await node.shutdown()

        # the acceptance set: all present, all non-empty after the pass
        assert _metric_value(text, "sd_feeder_h2d_bytes_total") > before_h2d
        assert _metric_value(text, "sd_task_batch_occupancy_count") \
            > before_occ
        assert "sd_task_batch_occupancy_bucket" in text
        assert "sd_job_phase_seconds_bucket" in text
        assert _metric_value(text, "sd_udp_retransmits_total") is not None

        # job phases observed for the chain (indexer → identifier → …)
        phases = snap["metrics"]["sd_job_phase_seconds"]["series"]
        assert sum(s["count"] for s in phases) > 0
        jobs_seen = {s["labels"]["job"] for s in phases}
        assert "indexer" in jobs_seen or "file_identifier" in jobs_seen

        # pipeline spans flowed: walk + identify stages at minimum
        stages = {s["stage"] for s in snap["spans"]}
        assert "walk" in stages
        assert "identify.hash" in stages

        # identifier throughput counters moved
        ident = snap["metrics"]["sd_identifier_files_total"]["series"]
        assert ident and ident[0]["value"] > 0

    asyncio.run(run())
