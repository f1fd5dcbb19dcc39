"""Persistent telemetry history (telemetry/history.py) + the SLO
burn-rate engine (telemetry/slo.py) — the ISSUE 12 durability and
contract planes.

The acceptance bars proven here:

- history **survives restart**: a writer samples into a data dir, a
  second writer (a new node generation) continues the same series, and
  the offline reader (``sdx slo``) sees one continuous series across
  the boundary;
- a **sustained injected SLO violation** flips the ``slo`` health
  subsystem, and — because health rides every federation snapshot — a
  peer's ``GET /mesh`` shows it with zero new wire surface.
"""

import asyncio
import json
import os
import time

import pytest

from spacedrive_tpu import telemetry
from spacedrive_tpu.telemetry import history, slo
from spacedrive_tpu.telemetry import metrics as tm


def _writer(tmp_path, **kw) -> history.HistoryWriter:
    return history.HistoryWriter(os.path.join(tmp_path, "hist"), **kw)


def _fixed_samplers(values: dict) -> dict:
    return {name: (lambda v=v: v) for name, v in values.items()}


# --- history store ---------------------------------------------------------


def test_sample_read_roundtrip(tmp_path):
    w = _writer(tmp_path, samplers=_fixed_samplers({"files_per_s": 123.0}))
    for i in range(5):
        w.sample(now=1000.0 + i)
    recs = history.read(w.dir)
    assert len(recs) == 5
    assert [r["ts"] for r in recs] == [1000.0 + i for i in range(5)]
    assert all(r["v"]["files_per_s"] == 123.0 for r in recs)
    assert history.series(w.dir, "files_per_s")[0] == (1000.0, 123.0)


def test_history_survives_restart_as_one_series(tmp_path):
    """The acceptance bar: two writer generations on the same data dir
    produce ONE continuous series for every offline reader."""
    base = time.time() - 20  # recent: stays out of downsample range
    w1 = _writer(tmp_path, samplers=_fixed_samplers({"files_per_s": 100.0}))
    for i in range(4):
        w1.sample(now=base + i)
    del w1  # the node generation dies

    w2 = _writer(tmp_path, samplers=_fixed_samplers({"files_per_s": 90.0}))
    for i in range(4):
        w2.sample(now=base + 10 + i)

    series = history.series(w2.dir, "files_per_s")
    assert len(series) == 8
    assert [ts for ts, _ in series] == sorted(ts for ts, _ in series)
    assert {v for _, v in series} == {100.0, 90.0}


def test_segment_rotation_and_retention(tmp_path):
    w = _writer(tmp_path, samplers=_fixed_samplers({"x": 1.0}),
                segment_max_records=4, retention_bytes=400)
    for i in range(40):
        w.sample(now=3000.0 + i)
    segs = [n for n in os.listdir(w.dir) if n.startswith("seg-")]
    assert len(segs) > 1, "rotation never happened"
    total = sum(os.path.getsize(os.path.join(w.dir, n)) for n in segs)
    # retention holds the store near the budget (live segment excepted)
    assert total < 400 + 4 * 64
    # the newest samples survive; the oldest were retired
    series = history.series(w.dir, "x")
    assert series[-1][0] == 3039.0
    assert series[0][0] > 3000.0


def test_downsampling_compacts_old_segments(tmp_path):
    w = _writer(tmp_path, samplers=_fixed_samplers({"x": 2.0}),
                segment_max_records=8, downsample_after_s=100.0)
    base = time.time() - 10_000.0  # old enough to downsample
    for i in range(8):
        w.sample(now=base + i)
    # rotating twice triggers maintenance over the closed old segment
    for i in range(2):
        w.sample(now=time.time())
    recs = history.read(w.dir, until=base + 100)
    assert recs, "old samples vanished entirely"
    ds = [r for r in recs if r.get("ds")]
    assert ds, "no downsampled stripe produced"
    assert ds[0]["v"]["x"] == pytest.approx(2.0)
    assert ds[0]["v"]["x__max"] == pytest.approx(2.0)
    assert ds[0]["n"] > 1


def test_torn_tail_line_is_skipped(tmp_path):
    w = _writer(tmp_path, samplers=_fixed_samplers({"x": 5.0}))
    w.sample(now=4000.0)
    w.sample(now=4001.0)
    seg = [os.path.join(w.dir, n) for n in os.listdir(w.dir)][0]
    with open(seg, "a", encoding="utf-8") as f:
        f.write('{"ts": 4002.0, "v": {"x":')  # crash mid-append
    recs = history.read(w.dir)
    assert [r["ts"] for r in recs] == [4000.0, 4001.0]


def test_recent_prefers_tail_and_reset_clears_only_tail(tmp_path):
    w = _writer(tmp_path, samplers=_fixed_samplers({"x": 7.0}))
    now = time.time()
    for i in range(5):
        w.sample(now=now - 5 + i)
    assert len(w.recent(300.0, now=now)) == 5
    telemetry.reset()  # clears the in-memory tail…
    assert len(w.tail) == 0
    # …but NOT the durable segments: the disk fallback still answers
    assert len(w.recent(300.0, now=now)) == 5
    assert len(history.read(w.dir)) == 5


def test_default_samplers_read_live_registry(tmp_path):
    telemetry.reset()
    tm.SYNC_LAG.set(42.0, peer="aabbccdd")
    tm.GATE_REQUESTS.inc(klass="control", outcome="shed")
    w = _writer(tmp_path)
    rec = w.sample(now=time.time())
    assert rec["v"]["sync_lag_max_s"] == 42.0
    assert rec["v"]["protected_sheds_total"] == 1.0
    assert "interactive_p99_ms" in rec["v"]
    telemetry.reset()


# --- SLO engine ------------------------------------------------------------


def _samples_fn(pairs):
    return lambda seconds: pairs


def test_upper_slo_burn_and_status():
    s = slo.SLO("p99", series="interactive_p99_ms", objective=250.0,
                target=0.99)
    now = time.time()
    good = [(now - i, 100.0) for i in range(10)]
    bad = [(now - i, 400.0) for i in range(10)]
    doc = slo.evaluate_slo(s, _samples_fn(good))
    assert doc["status"] == slo.OK
    assert doc["windows"]["fast"]["burn"] == 0.0
    doc = slo.evaluate_slo(s, _samples_fn(bad))
    # all-bad: burn = 1.0/0.01 = 100 ≥ both thresholds → breach
    assert doc["status"] == slo.BREACH
    assert doc["windows"]["fast"]["burn"] == pytest.approx(100.0)
    doc = slo.evaluate_slo(s, _samples_fn([]))
    assert doc["status"] == slo.NO_DATA


def test_warn_needs_only_the_fast_window():
    s = slo.SLO("p99", series="x", objective=1.0, target=0.99)
    now = time.time()

    def samples_for(seconds):
        if seconds == s.fast_window_s:
            return [(now, 5.0)] * 10          # burning
        return [(now, 0.5)] * 500 + [(now, 5.0)] * 10  # slow window dilute

    doc = slo.evaluate_slo(s, samples_for)
    assert doc["status"] == slo.WARN


def test_lower_slo_ignores_idle_zeroes():
    s = slo.SLO("throughput", series="files_per_s", objective=50.0,
                kind="lower", target=0.95, ignore_zero=True)
    now = time.time()
    idle = [(now - i, 0.0) for i in range(20)]
    doc = slo.evaluate_slo(s, _samples_fn(idle))
    assert doc["status"] == slo.NO_DATA  # idle ≠ slow
    slow = [(now - i, 5.0) for i in range(20)]
    doc = slo.evaluate_slo(s, _samples_fn(slow))
    assert doc["status"] == slo.BREACH


def test_zero_tolerance_counter_semantics():
    s = slo.SLO("sheds", series="protected_sheds_total", objective=0.0,
                kind="zero_tolerance")
    now = time.time()
    doc = slo.evaluate_slo(s, _samples_fn([(now - 2, 3.0), (now - 1, 3.0)]))
    assert doc["status"] == slo.OK  # flat counter: no new sheds
    doc = slo.evaluate_slo(s, _samples_fn([(now - 2, 3.0), (now - 1, 4.0)]))
    assert doc["status"] == slo.BREACH
    # a restart re-baselines the cumulative counter downward — that is
    # monotonic bookkeeping, not a shed
    doc = slo.evaluate_slo(s, _samples_fn([(now - 2, 5.0), (now - 1, 2.0)]))
    assert doc["status"] == slo.OK


def test_evaluate_over_writer_and_directory(tmp_path):
    telemetry.reset()
    w = _writer(tmp_path, samplers=_fixed_samplers({
        "sync_lag_max_s": 1000.0,  # > the 600 s objective: violating
        "files_per_s": 0.0,
        "interactive_p99_ms": 10.0,
        "protected_sheds_total": 0.0,
    }))
    now = time.time()
    for i in range(12):
        w.sample(now=now - 12 + i)
    live = slo.evaluate(w, now=now)
    assert live["status"] == slo.BREACH
    by_name = {s["name"]: s for s in live["slos"]}
    assert by_name["sync_lag"]["status"] == slo.BREACH
    assert by_name["interactive_p99"]["status"] == slo.OK
    assert by_name["pass_throughput"]["status"] == slo.NO_DATA
    # the offline path (sdx slo after a restart) reads the same series
    offline = slo.evaluate(directory=w.dir, now=now)
    assert {s["name"]: s["status"] for s in offline["slos"]} == \
        {s["name"]: s["status"] for s in live["slos"]}
    assert slo.REGISTRY.last_evaluation is not None
    telemetry.reset()
    assert slo.REGISTRY.last_evaluation is None


def test_sdx_slo_reads_history_offline(tmp_path, capsys):
    """CLI contract: `sdx slo` with no --url evaluates the data dir's
    persistent history — continuous across node generations."""
    from spacedrive_tpu.cli import build_parser, cmd_slo

    data_dir = os.path.join(tmp_path, "node")
    hdir = history.history_dir(data_dir)
    w = history.HistoryWriter(hdir, samplers=_fixed_samplers(
        {"sync_lag_max_s": 1000.0}))
    now = time.time()
    for i in range(6):
        w.sample(now=now - 6 + i)
    del w
    w2 = history.HistoryWriter(hdir, samplers=_fixed_samplers(
        {"sync_lag_max_s": 1000.0}))
    for i in range(6):
        w2.sample(now=now)
    out = os.path.join(tmp_path, "slo.json")
    args = build_parser().parse_args(
        ["--data-dir", data_dir, "slo", "--out", out])
    assert cmd_slo(args) == 0
    doc = json.load(open(out))
    by_name = {s["name"]: s for s in doc["slos"]}
    assert by_name["sync_lag"]["status"] == slo.BREACH
    # the evaluation window saw BOTH generations' samples
    assert by_name["sync_lag"]["windows"]["fast"]["samples"] == 12


# --- the health subsystem + federation visibility --------------------------


def test_sustained_violation_flips_slo_health(tmp_path):
    from spacedrive_tpu.telemetry import health

    telemetry.reset()

    class FakeNode:
        history = _writer(tmp_path, samplers=_fixed_samplers(
            {"sync_lag_max_s": 2000.0}))

    now = time.time()
    for i in range(12):
        FakeNode.history.sample(now=now - 12 + i)
    verdict = health._slo(FakeNode)
    assert verdict["status"] == health.UNHEALTHY
    assert "sync_lag" in verdict["reason"]
    full = health.evaluate(FakeNode)
    assert full["subsystems"]["slo"]["status"] == health.UNHEALTHY
    assert full["status"] == health.UNHEALTHY
    telemetry.reset()


def test_slo_breach_visible_on_peer_mesh_view(tmp_path):
    """The federation bar: node A sustains an SLO violation; node B's
    GET /mesh (its FederationCache view) shows A's slo subsystem
    unhealthy — health rides every snapshot, no new wire surface."""
    from spacedrive_tpu.p2p.loopback import make_mesh_pair
    from spacedrive_tpu.telemetry.federation import mesh_status

    telemetry.reset()

    async def run():
        a, b, _lib_a, _lib_b, _tasks = await make_mesh_pair(tmp_path)
        try:
            # a sustained violation on A: its history records sync lag
            # far past the objective across the whole fast window
            a.history._samplers = _fixed_samplers(
                {"sync_lag_max_s": 5000.0})
            now = time.time()
            for i in range(12):
                a.history.sample(now=now - 12 + i)
            await b.p2p.refresh_federation(force=True)
            return mesh_status(b)
        finally:
            await a.shutdown()
            await b.shutdown()

    doc = asyncio.run(run())
    peers = doc["mesh"]["peers"]
    assert peers, "B pulled no snapshots"
    [entry] = peers.values()
    sub = entry["snapshot"]["health"]["subsystems"]["slo"]
    assert sub["status"] == "unhealthy"
    assert entry["verdict"] == "unhealthy"
    telemetry.reset()


def test_all_ok_rolls_up_ok_not_no_data(tmp_path):
    """Regression (live-drive find): four evaluated-and-met objectives
    must roll up "ok" — the rank-0 tie used to leave the initial
    "no_data" in place."""
    telemetry.reset()
    w = _writer(tmp_path, samplers=_fixed_samplers({
        "sync_lag_max_s": 1.0,
        "files_per_s": 500.0,
        "interactive_p99_ms": 10.0,
        "protected_sheds_total": 0.0,
        "tenant_fairness_index": 1.0,
    }))
    now = time.time()
    for i in range(6):
        w.sample(now=now - 6 + i)
    doc = slo.evaluate(w, now=now)
    # the resource trend SLOs have no series here and read no_data —
    # rank 0, so they must not drag the rollup back down either
    assert all(s["status"] == slo.OK for s in doc["slos"]
               if s["kind"] != "trend")
    assert doc["status"] == slo.OK
    telemetry.reset()
