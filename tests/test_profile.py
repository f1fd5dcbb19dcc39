"""Continuous host profiling plane (telemetry/sampler.py) — the
ISSUE 13 tentpole's provability bar.

Four layers:

- unit: frame folding, the frame→group classifier, the wait/gil_wait
  leaf heuristics, folded-output format, trigger hysteresis;
- contract: ``SD_PROFILE=0`` is a true no-op (no thread, refused
  triggers, disabled exports) and pass output is bit-identical
  profiled or not;
- single node, REAL pass (the ``make profile-smoke`` gate): a profiled
  identify pass yields a non-empty folded profile whose named frame
  groups cover ≥70% of sampled wall, an attribution report whose gap
  bucket is gap-decomposed, and live ``GET /profile`` +
  folded + Chrome-trace-merge surfaces;
- two REAL nodes on the loopback duplex: each node's ``GET /mesh``
  shows the peer's profile summary, ``profile_pull`` returns a
  redaction-clean folded profile, and an injected ``p2p.profile_pull``
  vanish degrades the mesh view to partial instead of blocking.
"""

import asyncio
import json
import os
import time
import urllib.request

import pytest

from spacedrive_tpu import telemetry
from spacedrive_tpu.telemetry import attrib
from spacedrive_tpu.telemetry import sampler
from spacedrive_tpu.telemetry import trace as sdtrace
from spacedrive_tpu.utils import faults

from test_mesh_indexing import build_corpus

PLANTED_KEY = "sk-profile-plane-super-secret-value-1234567890"


# --- unit: folding + classification ----------------------------------------


def test_classify_stack_leafmost_family_wins():
    assert sampler.classify_stack(
        ["asyncio.base_events:_run_once", "jobs.manager:ingest",
         "location.indexer.journal:consult_many", "sqlite3:execute"]
    ) == "sql"
    assert sampler.classify_stack(
        ["asyncio.base_events:_run_once", "jobs.manager:ingest",
         "location.indexer.journal:consult_many"]
    ) == "journal"
    assert sampler.classify_stack(["selectors:select"]) == "loop_idle"
    assert sampler.classify_stack(["randommod:fn"]) == "other"
    # thread scaffolding must not name a group
    assert sampler.classify_stack(
        ["threading:_bootstrap", "threading:_bootstrap_inner",
         "threading:run", "randommod:fn"]
    ) == "other"


def test_wait_leaf_heuristics():
    assert sampler._leaf_is_waity(["threading:_wait_for_tstate_lock"])
    assert sampler._leaf_is_waity(["selectors:select"])
    assert sampler._leaf_is_waity(["socket:recv_into"])
    assert not sampler._leaf_is_waity(["location.indexer.journal:record"])


def test_module_of_strips_paths():
    # frame names must be module:function only — the redaction-clean-
    # by-construction contract profile_pull relies on
    assert sampler._module_of(
        "/home/user/repo/spacedrive_tpu/telemetry/sampler.py"
    ) == "telemetry.sampler"
    assert sampler._module_of("/usr/lib/python3.11/json/encoder.py") \
        == "json.encoder"
    assert sampler._module_of("/usr/lib/python3.11/threading.py") \
        == "threading"
    assert sampler._module_of(
        "/x/site-packages/msgpack/__init__.py") == "msgpack"
    assert "/" not in sampler._module_of("/tmp/whatever/thing.py")


def test_sampler_accumulates_and_folds():
    telemetry.reset()
    import threading

    s = sampler.Sampler(hz=150)
    assert s.start()
    stop = threading.Event()

    def burn():
        x = 0
        while not stop.is_set():
            for i in range(5000):
                x += i * i

    t = threading.Thread(target=burn, name="asyncio_burn", daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 5.0
        while s.profile()["samples"] < 20 and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        stop.set()
        t.join()
        s.stop()
    doc = s.profile()
    assert doc["enabled"] and doc["samples"] >= 20
    assert doc["threads"].get("worker", 0) > 0  # asyncio_* naming → worker
    assert sum(doc["states"].values()) == doc["samples"]
    folded = s.folded()
    assert folded
    for line in folded.strip().splitlines():
        stack, _, count = line.rpartition(" ")
        assert int(count) >= 1
        parts = stack.split(";")
        assert parts[0] in ("loop", "feeder", "worker", "other")
        assert parts[1] in sampler.STATES
        assert len(parts) >= 3
    # the sampler's own thread is exempt from its own accounting
    assert "telemetry.sampler:_tick" not in folded
    # summary digests only
    summary = s.summary()
    assert summary["samples"] == doc["samples"]
    assert "top_groups" in summary and "captures" in summary


def test_profile_disabled_is_true_noop(monkeypatch):
    monkeypatch.setenv("SD_PROFILE", "0")
    s = sampler.Sampler()
    assert s.start() is False
    assert not s.running()
    assert s.trigger("manual") is False
    assert s.profile() == {"enabled": False}
    assert s.summary() == {"enabled": False}
    s.stop()


# --- trigger hysteresis -----------------------------------------------------


def test_trigger_opens_exactly_one_window_under_flapping(monkeypatch):
    telemetry.reset()
    monkeypatch.setenv("SD_PROFILE_CAPTURE_S", "0.2")
    monkeypatch.setenv("SD_PROFILE_COOLDOWN_S", "3600")
    s = sampler.SAMPLER
    s.start()
    try:
        s.reset()
        opened = [s.trigger("slo_breach") for _ in range(10)]
        assert opened.count(True) == 1
        assert len(s.captures_snapshot()) == 1
        assert s.captures_snapshot()[0]["reason"] == "slo_breach"
        # a different reason inside the cooldown is still absorbed —
        # one incident, one window
        assert s.trigger("brownout") is False
        assert telemetry.counter_value("sd_profile_captures_total") == 1
    finally:
        s.stop()


def test_trigger_rearms_after_cooldown(monkeypatch):
    telemetry.reset()
    monkeypatch.setenv("SD_PROFILE_CAPTURE_S", "0.1")
    monkeypatch.setenv("SD_PROFILE_COOLDOWN_S", "0.3")
    s = sampler.SAMPLER
    s.start()
    try:
        s.reset()
        assert s.trigger("loop_lag") is True
        deadline = time.monotonic() + 5.0
        reopened = False
        while time.monotonic() < deadline:
            time.sleep(0.1)
            if s.trigger("loop_lag"):
                reopened = True
                break
        assert reopened, "cooldown expiry must re-arm the trigger"
    finally:
        s.stop()


def test_unknown_trigger_reason_rejected():
    s = sampler.SAMPLER
    s.start()
    try:
        with pytest.raises(ValueError):
            s.trigger("not_a_reason")
    finally:
        s.stop()


def test_loop_lag_degradation_opens_one_window(monkeypatch):
    """The loop-lag health trigger: a monitor seeing every sample over
    its warn threshold (warn_s=0) fires the trigger continuously — the
    hysteresis must fold the whole degradation episode into exactly ONE
    capture window."""
    telemetry.reset()
    monkeypatch.setenv("SD_PROFILE_CAPTURE_S", "30")
    monkeypatch.setenv("SD_PROFILE_COOLDOWN_S", "3600")
    from spacedrive_tpu.telemetry.events import LoopLagMonitor

    s = sampler.SAMPLER
    s.start()
    s.reset()

    async def run():
        mon = LoopLagMonitor(interval=0.01, warn_s=0.0)
        mon.start()
        await asyncio.sleep(0.4)
        await mon.stop()

    try:
        asyncio.run(run())
        caps = s.captures_snapshot()
        assert len(caps) == 1, caps
        assert caps[0]["reason"] == "loop_lag"
    finally:
        s.stop()


def test_slo_breach_opens_one_window(monkeypatch):
    """An injected SLO breach (zero-tolerance protected-shed counter
    increasing inside the fast window) opens exactly one capture window
    across repeated evaluations."""
    telemetry.reset()
    monkeypatch.setenv("SD_PROFILE_CAPTURE_S", "30")
    monkeypatch.setenv("SD_PROFILE_COOLDOWN_S", "3600")
    from spacedrive_tpu.telemetry import slo as _slo

    class BreachingHistory:
        def recent(self, seconds, now=None):
            now = now or time.time()
            return [
                {"ts": now - 60, "v": {"protected_sheds_total": 0.0}},
                {"ts": now - 30, "v": {"protected_sheds_total": 2.0}},
            ]

    s = sampler.SAMPLER
    s.start()
    s.reset()
    try:
        first = _slo.evaluate(BreachingHistory())
        assert first["status"] == _slo.BREACH
        _slo.evaluate(BreachingHistory())
        _slo.evaluate(BreachingHistory())
        caps = s.captures_snapshot()
        assert len(caps) == 1, caps
        assert caps[0]["reason"] == "slo_breach"
    finally:
        s.stop()


def test_reset_clears_sampler_state(monkeypatch):
    monkeypatch.setenv("SD_PROFILE_CAPTURE_S", "30")
    monkeypatch.setenv("SD_PROFILE_COOLDOWN_S", "3600")
    s = sampler.SAMPLER
    s.start()
    try:
        s.reset()  # the prior test's window/cooldown must not leak in
        deadline = time.monotonic() + 5.0
        while s.profile()["samples"] == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert s.trigger("manual") is True
        assert s.profile()["samples"] > 0
        assert s.captures_snapshot()
        telemetry.reset()
        assert s.profile()["samples"] == 0
        assert s.folded() == ""
        assert s.captures_snapshot() == []
        # trigger/cooldown state cleared too: a fresh window opens
        assert s.trigger("manual") is True
        # ...and the thread survived reset (lifecycle is not data)
        assert s.running()
    finally:
        s.stop()
        telemetry.reset()


# --- history integration ---------------------------------------------------


def test_history_samplers_include_profile_shares():
    telemetry.reset()
    from spacedrive_tpu.telemetry.history import default_samplers

    samplers = default_samplers()
    for group in sampler.HISTORY_GROUPS:
        name = f"profile_share_{group}"
        assert name in samplers
        v = samplers[name]()
        assert 0.0 <= v <= 1.0


def test_gap_bucket_decomposes_into_named_groups(monkeypatch):
    """The acceptance bar, deterministically: a span forest with a REAL
    uninstrumented Python burn between two spans yields a gap bucket
    that is ≥70% decomposed into named frame groups — the profiler
    names the code the span layer cannot see."""
    telemetry.reset()
    monkeypatch.setenv("SD_PROFILE_HZ", "150")
    s = sampler.SAMPLER
    s.start()
    try:
        s.reset()
        t0 = time.time()
        time.sleep(0.05)  # "walk" span body
        burn_start = time.time()
        x = 0
        while time.time() - burn_start < 0.6:  # the uninstrumented gap
            for i in range(20000):
                x += i * i
        t_end = time.time()
        spans = [
            {"stage": "walk", "t0": t0, "seconds": burn_start - t0,
             "span_id": "a", "parent_id": None, "trace_id": "tgap"},
            {"stage": "identify.db", "t0": t_end,
             "seconds": 0.02, "span_id": "b", "parent_id": None,
             "trace_id": "tgap"},
        ]
        time.sleep(0.02)
        doc = attrib.report("tgap", spans)
        assert doc["buckets"]["gap"] >= 0.5, doc["buckets"]
        gd = doc.get("gap_decomposition")
        assert gd is not None and gd["samples"] > 10, doc
        assert gd["coverage"] >= 0.7, gd
        # the burn itself names its module (dotted fallback → "tests")
        assert gd["groups"], gd
        assert abs(sum(gd["groups"].values())
                   - doc["buckets"]["gap"]) < 1e-3
    finally:
        s.stop()


# --- the golden no-op contract ---------------------------------------------


async def _tiny_identify_pass(data_dir, corpus):
    """Index + identify `corpus`; returns the path→cas_id map and the
    trace id the identify pass ran under."""
    from spacedrive_tpu.jobs.manager import JobBuilder
    from spacedrive_tpu.location.indexer.job import IndexerJob
    from spacedrive_tpu.location.locations import LocationCreateArgs
    from spacedrive_tpu.node import Node
    from spacedrive_tpu.object.file_identifier.job import FileIdentifierJob

    node = Node(data_dir, use_device=False, with_labeler=False)
    node.config.config.p2p.enabled = False
    await node.start()
    try:
        lib = await node.create_library("prof")
        loc = LocationCreateArgs(path=corpus).create(lib)
        await JobBuilder(IndexerJob({"location_id": loc["id"]})).spawn(
            node.jobs, lib)
        await node.jobs.wait_idle()
        ctx = sdtrace.new_context()
        with sdtrace.use(ctx):
            await JobBuilder(FileIdentifierJob(
                {"location_id": loc["id"], "backend": "cpu"}
            )).spawn(node.jobs, lib)
        await node.jobs.wait_idle()
        rows = lib.db.find("file_path")
        cas = {
            (r["materialized_path"], r["name"]): r.get("cas_id")
            for r in rows if not r.get("is_dir")
        }
        return node, cas, ctx.trace_id
    except BaseException:
        await node.shutdown()
        raise


def test_sd_profile_0_pass_output_bit_identical(tmp_path, monkeypatch):
    """The no-op golden: the same corpus identified with profiling on
    vs SD_PROFILE=0 produces the identical path→cas map, and under
    SD_PROFILE=0 the node starts no sampler at all."""
    telemetry.reset()
    corpus = os.path.join(tmp_path, "corpus")
    build_corpus(corpus, n=24)

    async def run(data_dir):
        node, cas, _tid = await _tiny_identify_pass(data_dir, corpus)
        started = node._profiler_started
        await node.shutdown()
        return cas, started

    cas_on, started_on = asyncio.run(run(os.path.join(tmp_path, "on")))
    assert started_on, "default SD_PROFILE must start the sampler"
    telemetry.reset()
    monkeypatch.setenv("SD_PROFILE", "0")
    cas_off, started_off = asyncio.run(run(os.path.join(tmp_path, "off")))
    assert started_off is False
    assert not sampler.SAMPLER.running()
    assert cas_on == cas_off
    assert len(cas_on) >= 24


# --- the profile-smoke gate (make profile-smoke) ---------------------------


def _http_get(url):
    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.read().decode()


def test_profile_smoke_full_pass(tmp_path, monkeypatch):
    """Boot a node → small identify pass → non-empty folded profile
    whose named frame groups cover ≥70% of sampled wall → a
    gap-decomposed attribution report → live /profile (JSON + folded)
    and /trace merge surfaces."""
    telemetry.reset()
    monkeypatch.setenv("SD_PROFILE_HZ", "97")  # sample density for a short pass
    monkeypatch.setenv("SD_PROFILE_CAPTURE_S", "0.3")
    monkeypatch.setenv("SD_PROFILE_COOLDOWN_S", "3600")
    corpus = os.path.join(tmp_path, "corpus")
    build_corpus(corpus, n=140)

    async def run():
        node, _cas, trace_id = await _tiny_identify_pass(
            os.path.join(tmp_path, "node"), corpus)
        try:
            port = await node.start_api(port=0)
            base = f"http://127.0.0.1:{port}"
            sampler.SAMPLER.trigger("manual")
            doc = attrib.report(trace_id)
            prof = json.loads(
                await asyncio.to_thread(_http_get, base + "/profile"))
            folded = await asyncio.to_thread(
                _http_get, base + "/profile?format=folded")
            trace_doc = json.loads(
                await asyncio.to_thread(_http_get, base + "/trace"))
            return doc, prof, folded, trace_doc
        finally:
            await node.shutdown()

    doc, prof, folded, trace_doc = asyncio.run(run())

    # the continuous profile is live and classified: named frame
    # groups must cover ≥70% of RUNNABLE samples (cpu + gil_wait —
    # parked daemon threads from earlier suites legitimately sit in
    # unclassifiable C-extension waits and don't count as wall)
    assert prof["enabled"] and prof["samples"] > 0, prof

    def runnable(states):
        return states.get("cpu", 0) + states.get("gil_wait", 0)

    runnable_total = runnable(prof["states"])
    named = sum(runnable(g["states"]) for g in prof["frame_groups"]
                if g["group"] != "other")
    # gate on WITNESSED runnable time, not a fixed sample count: the
    # old `samples > 50` floor flaked whenever the little pass outran
    # it (50 ticks at 97 Hz needs >0.5 s of sampled wall, which a fast
    # host doesn't spend here). runnable_total/hz is the runnable time
    # the profile itself measured — demand a small absolute floor of
    # it, which scales down with exactly the speed that starved the
    # old gate while still failing an enabled-but-dead sampler.
    elapsed_runnable_s = runnable_total / prof["hz"]
    assert elapsed_runnable_s >= 0.06, (runnable_total, prof["states"])
    assert named >= 0.7 * runnable_total, prof["frame_groups"]
    assert folded.strip(), "folded profile must be non-empty"
    assert ";" in folded and folded.strip().splitlines()[0].rpartition(
        " ")[2].isdigit()
    # frame names never carry filesystem paths
    assert str(tmp_path) not in folded

    # the attribution report decomposes its host-side buckets into
    # named code. On this small fast pass the spans cover nearly
    # everything, so the gap bucket can be a handful of milliseconds —
    # decomposition of a REAL gap is proven deterministically by
    # test_gap_bucket_decomposes_into_named_groups; here the witness is
    # the dominant host bucket
    hd = doc.get("host_cpu_decomposition")
    assert hd is not None and hd["samples"] > 0, doc
    assert hd["groups"], hd
    if doc["buckets"]["gap"] >= 0.25:
        gd = doc.get("gap_decomposition")
        assert gd is not None and gd["coverage"] >= 0.7, doc

    # the Chrome-trace merge carries the capture lane
    names = {e.get("name") for e in trace_doc["traceEvents"]}
    assert "capture:manual" in names, "triggered capture must ride /trace"

    # overhead self-accounting stays sane even at the boosted rate
    assert prof["overhead_ratio"] < 0.15, prof["overhead_ratio"]


def test_overhead_at_default_rate_under_5pct(tmp_path):
    """The ≤5% contract at the DEFAULT 19 Hz rate, self-measured over
    a real identify pass (the interleaved wall-clock A/B runs in the
    slow tier — this always-on witness rides tier-1)."""
    telemetry.reset()
    corpus = os.path.join(tmp_path, "corpus")
    build_corpus(corpus, n=80)

    async def run():
        node, _cas, _tid = await _tiny_identify_pass(
            os.path.join(tmp_path, "node"), corpus)
        try:
            return sampler.SAMPLER.profile()
        finally:
            await node.shutdown()

    prof = asyncio.run(run())
    assert prof["enabled"]
    assert prof["overhead_ratio"] < 0.05, prof["overhead_ratio"]


@pytest.mark.slow
def test_overhead_ab_interleaved(tmp_path, monkeypatch):
    """Interleaved A/B on the same corpus: profiled identify wall time
    within 5% of unprofiled (median of pairs, alternating order)."""
    telemetry.reset()
    corpus = os.path.join(tmp_path, "corpus")
    build_corpus(corpus, n=200)

    async def one_pass(data_dir):
        t0 = time.perf_counter()
        node, _cas, _tid = await _tiny_identify_pass(data_dir, corpus)
        wall = time.perf_counter() - t0
        await node.shutdown()
        return wall

    ratios = []
    for i in range(3):
        monkeypatch.setenv("SD_PROFILE", "0")
        off = asyncio.run(one_pass(os.path.join(tmp_path, f"off{i}")))
        monkeypatch.setenv("SD_PROFILE", "1")
        on = asyncio.run(one_pass(os.path.join(tmp_path, f"on{i}")))
        ratios.append(on / off)
    ratios.sort()
    assert ratios[1] <= 1.05, ratios


# --- mesh: federation summaries + profile_pull -----------------------------


def test_mesh_profile_summaries_and_pull(tmp_path):
    """Two loopback nodes: each /mesh shows the peer's profile summary,
    a profile_pull returns the peer's folded profile redaction-clean,
    and an injected p2p.profile_pull vanish degrades the mesh profile
    view to partial without blocking."""
    from spacedrive_tpu.p2p.loopback import make_mesh_pair
    from spacedrive_tpu.telemetry.federation import mesh_status

    telemetry.reset()

    async def run():
        a, b, _lib_a, _lib_b, _tasks = await make_mesh_pair(tmp_path)
        try:
            # plant a secret on the serving side: nothing pulled across
            # the mesh may embed it
            b.config.config.preferences["cloud_api_token"] = PLANTED_KEY
            # let the shared sampler accumulate a few ticks
            deadline = time.monotonic() + 5.0
            while sampler.SAMPLER.profile().get("samples", 0) < 5 \
                    and time.monotonic() < deadline:
                await asyncio.sleep(0.05)

            await a.p2p.refresh_federation(force=True)
            status = mesh_status(a)
            peers = status["mesh"]["peers"]
            assert peers, "peer must be federated"
            for entry in peers.values():
                prof = (entry["snapshot"] or {}).get("profile")
                assert prof is not None and prof.get("enabled")
                assert prof.get("samples", 0) >= 0
                assert "top_groups" in prof

            profiles, failures = await a.p2p.pull_remote_profiles()
            assert profiles and not failures, (profiles, failures)
            pulled = next(iter(profiles.values()))
            assert pulled["profile"]["enabled"]
            blob = json.dumps(pulled)
            assert PLANTED_KEY not in blob
            assert str(tmp_path) not in str(pulled.get("folded", ""))

            mesh_doc = await sampler.mesh_profile(a)
            assert mesh_doc["partial"] is False
            assert mesh_doc["mesh"], mesh_doc

            # the vanish chaos leg: peer closes the stream mid-pull
            from spacedrive_tpu.p2p import operations as _ops

            prev_timeout = _ops.TELEMETRY_TIMEOUT
            _ops.TELEMETRY_TIMEOUT = 1.5
            try:
                with faults.active(faults.FaultPlan.parse(
                    "p2p.profile_pull:vanish:times=inf"
                )):
                    t0 = time.monotonic()
                    partial = await sampler.mesh_profile(a)
                    elapsed = time.monotonic() - t0
            finally:
                _ops.TELEMETRY_TIMEOUT = prev_timeout
            assert partial["partial"] is True
            assert partial["pull_failures"], partial
            assert partial["local"]["enabled"]
            assert elapsed < 60.0, "partial mesh profile must not block"
            return True
        finally:
            await a.shutdown()
            await b.shutdown()

    assert asyncio.run(run())


def test_debug_bundle_carries_profile_section(tmp_path):
    telemetry.reset()
    from spacedrive_tpu.telemetry.bundle import build_bundle

    bundle = build_bundle()
    assert "profile" in bundle
    assert "doc" in bundle["profile"] and "folded" in bundle["profile"]
