"""Tier-1 observability smoke: boot a node on a tmp dir, index a
handful of files, then assert the three diagnostic surfaces are live
and leak-free — /metrics (Prometheus text), /trace (valid Chrome-trace
JSON with events), and the debug bundle (non-empty, planted secrets
redacted)."""

import json
import os

import pytest

from spacedrive_tpu import telemetry

PLANTED_KEY = "sk-PLANTED-SECRET-0badc0ffee"


@pytest.fixture()
def corpus(tmp_path):
    d = tmp_path / "corpus"
    d.mkdir()
    for i in range(5):
        (d / f"doc{i}.txt").write_bytes(os.urandom(1500))
    return str(d)


@pytest.mark.asyncio
async def test_metrics_trace_and_debug_bundle_end_to_end(tmp_path, corpus):
    import aiohttp

    from spacedrive_tpu.location.locations import LocationCreateArgs, scan_location
    from spacedrive_tpu.node import Node

    # the span ring is the process's: the first `walk` and the first
    # `identify.hash` below must be this pass's, not what an earlier
    # suite on this worker left behind (or what the ring half evicted)
    telemetry.reset()
    node = Node(os.path.join(tmp_path, "node"), use_device=False,
                with_labeler=False)
    node.config.config.p2p.enabled = False
    # plant a secret-bearing preference: the bundle must redact it
    node.config.config.preferences["cloud_api_token"] = PLANTED_KEY
    node.config.save()
    identity_hex = node.config.config.identity.to_bytes().hex()

    # secrets travel: leak the planted key (and the identity hex)
    # through an exception into the error ring — the value-scrub pass
    # must clean the ring copy inside the bundle too
    from spacedrive_tpu.telemetry.events import record_error

    try:
        raise RuntimeError(
            f"cloud api said 401: bad token {PLANTED_KEY} (id {identity_hex})"
        )
    except RuntimeError as e:
        record_error("excepthook", e)

    await node.start()
    try:
        lib = await node.create_library("obs-lib")
        loc = LocationCreateArgs(path=corpus).create(lib)
        await scan_location(lib, loc, node.jobs)
        await node.jobs.wait_idle()
        port = await node.start_api()
        async with aiohttp.ClientSession() as http:
            async with http.get(f"http://127.0.0.1:{port}/metrics") as resp:
                assert resp.status == 200
                metrics_text = await resp.text()
            async with http.get(f"http://127.0.0.1:{port}/trace") as resp:
                assert resp.status == 200
                trace_doc = json.loads(await resp.text())
            async with http.post(
                f"http://127.0.0.1:{port}/rspc/telemetry.debug_bundle",
                json={},
            ) as resp:
                assert resp.status == 200
                bundle = (await resp.json())["result"]
    finally:
        await node.shutdown()

    # /metrics: the dispatch path moved
    assert "sd_tasks_dispatched_total" in metrics_text
    assert "sd_identifier_files_total" in metrics_text

    # /trace: valid Chrome-trace JSON, >0 real span events, and the
    # indexing pipeline is present under one trace
    events = trace_doc["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    assert len(spans) > 0
    names = {e["name"] for e in spans}
    assert {"walk", "identify.hash", "task.dispatch"} <= names, names
    walk = next(e for e in spans if e["name"] == "walk")
    hash_ev = next(e for e in spans if e["name"] == "identify.hash")
    assert walk["args"]["trace_id"] == hash_ev["args"]["trace_id"]

    # debug bundle: non-empty sections…
    assert bundle["node_config"] and bundle["metrics"] and bundle["versions"]
    assert bundle["events"].get("jobs"), "job ring empty after an index pass"
    assert bundle["trace_summary"]["spans"] > 0
    # …and secret-free: the planted key, the node identity keypair, and
    # the library key material never appear anywhere in the serialized
    # artifact
    doc = json.dumps(bundle)
    assert PLANTED_KEY not in doc
    assert identity_hex not in doc
    assert bundle["node_config"]["identity"] == "[redacted]"
    assert bundle["node_config"]["preferences"]["cloud_api_token"] \
        == "[redacted]"
    # the leaked-through-exception copy was value-scrubbed, but the
    # error event itself survived redaction
    errors = bundle["events"]["errors"]
    assert any("bad token [redacted]" in e["fields"]["message"]
               for e in errors), errors


def test_offline_debug_bundle_cli_path(tmp_path):
    """`sdx debug-bundle` without a running node: built straight off
    the data dir, still redacted."""
    from spacedrive_tpu.node.config import ConfigManager
    from spacedrive_tpu.telemetry.bundle import build_bundle, render_bundle

    cm = ConfigManager(tmp_path)
    cm.config.preferences["api_password"] = PLANTED_KEY
    cm.save()
    identity_hex = cm.config.identity.to_bytes().hex()

    doc = render_bundle(data_dir=tmp_path)
    bundle = json.loads(doc)
    assert bundle["node_config"]["id"] == str(cm.config.id)
    assert PLANTED_KEY not in doc
    assert identity_hex not in doc

    # a data dir with no node.json still yields a bundle (config None)
    empty = build_bundle(data_dir=str(tmp_path / "nothing"))
    assert empty["node_config"] is None
    assert empty["versions"]


@pytest.mark.asyncio
async def test_slo_smoke_attribution_and_slo_surfaces(tmp_path, corpus,
                                                      monkeypatch):
    """`make slo-smoke`: boot a node, run a small pass, and assert a
    well-formed attribution report (buckets sum to the window, the
    critical path is non-empty, the pass is findable as "the last
    pass") plus a complete SLO evaluation over live history."""
    import aiohttp

    from spacedrive_tpu.location.locations import LocationCreateArgs, scan_location
    from spacedrive_tpu.node import Node

    # the objectives are env-tunable for rig variance — pin them so a
    # 5-file smoke corpus on a loaded 2-core box can't trip the
    # throughput/latency objectives (their burn semantics are separately
    # unit-tested in tests/test_slo_history.py; this test proves the
    # evaluation machinery end-to-end, not this box's speed)
    monkeypatch.setenv("SD_SLO_FILES_PER_S", "0.001")
    monkeypatch.setenv("SD_SLO_INTERACTIVE_P99_MS", "60000")
    from spacedrive_tpu import telemetry as _telemetry

    _telemetry.reset()  # earlier suites' series must not ride our history

    node = Node(os.path.join(tmp_path, "slo-node"), use_device=False,
                with_labeler=False)
    node.config.config.p2p.enabled = False
    await node.start()
    try:
        lib = await node.create_library("slo-lib")
        loc = LocationCreateArgs(path=corpus).create(lib)
        await scan_location(lib, loc, node.jobs)
        await node.jobs.wait_idle()
        node.history.sample()  # don't wait for the 10 s timer
        port = await node.start_api()
        async with aiohttp.ClientSession() as http:
            async with http.get(f"http://127.0.0.1:{port}/attrib") as resp:
                assert resp.status == 200
                report = json.loads(await resp.text())
            async with http.post(
                f"http://127.0.0.1:{port}/rspc/telemetry.slo", json={},
            ) as resp:
                assert resp.status == 200
                slo_doc = (await resp.json())["result"]
            async with http.post(
                f"http://127.0.0.1:{port}/rspc/telemetry.attrib",
                json={},
            ) as resp:
                assert resp.status == 200
                rspc_report = (await resp.json())["result"]
    finally:
        await node.shutdown()

    # attribution: resolved "the last pass" via the job-boundary
    # markers, with a sane partition and a non-empty critical path
    assert "error" not in report, report
    assert report["spans"] > 0
    assert report["wall_seconds"] > 0
    assert sum(report["buckets"].values()) == pytest.approx(
        report["wall_seconds"], abs=1e-4)  # per-bucket 6-dp rounding
    assert report["top_segments"], "empty critical path"
    assert set(report["buckets"]) == {
        "device", "host_cpu", "link", "queue_wait", "gap"}
    assert rspc_report["trace_id"] == report["trace_id"]

    # SLO: every default objective evaluated; nothing breached by a
    # healthy 5-file pass
    names = {s["name"] for s in slo_doc["slos"]}
    assert names == {"interactive_p99", "sync_lag", "pass_throughput",
                     "protected_sheds", "rss_growth", "fd_growth",
                     "tenant_fairness"}
    assert slo_doc["status"] in ("ok", "no_data"), slo_doc
