"""Closed-loop autotuner — controller edge cases + static parity.

Covers the ISSUE-8 contract:

- cold start: no samples yet ⇒ every policy holds the static defaults;
- oscillation damping: alternating pad-heavy/full samples must NOT
  thrash the ladder rung (or any knob) every tick;
- DeviceLadder interaction: the autotuner may never promote the
  dispatch rung past what the demotion level allows;
- ``SD_AUTOTUNE=0``: policy reads equal the pre-autotuner static
  constants exactly, and the device pipeline's outputs (cas_ids and
  thumbnail bytes) are bit-identical to the reference paths;
- sizing changes never change bytes: a demoted-then-promoted policy
  produces the same cas_ids as the static config.
"""

from __future__ import annotations

import numpy as np
import pytest

from spacedrive_tpu.parallel import autotune
from spacedrive_tpu.parallel import mesh as _mesh
from spacedrive_tpu.parallel.autotune import (
    BATCH_LADDER,
    Controller,
    Sample,
    STARVED_WAIT_S,
    STEP_STREAK,
)
from spacedrive_tpu.parallel.feeder import pipeline_depth


@pytest.fixture(autouse=True)
def _isolated_autotune(monkeypatch):
    """Each test drives its own Controller; the process-wide one (and
    the device ladder) must come out untouched."""
    monkeypatch.delenv("SD_AUTOTUNE", raising=False)
    autotune.reset()
    _mesh.LADDER.reset()
    yield
    autotune.reset()
    _mesh.LADDER.reset()


def starved() -> Sample:
    return Sample(wait_mean_s=STARVED_WAIT_S * 4, wait_n=3)


def pad_heavy() -> Sample:
    s = Sample()
    s.occ_mean["blake3"] = 0.3
    s.occ_n["blake3"] = 2
    return s


def full_sample(occ: float = 0.95) -> Sample:
    s = Sample()
    s.occ_mean["blake3"] = occ
    s.occ_n["blake3"] = 2
    return s


# --- cold start -------------------------------------------------------------


def test_cold_start_holds_static_defaults():
    c = Controller(interval=999)
    pol = c.policies["identify"]
    assert pol.identify_window_rows(1) == 1024
    assert pol.identify_window_rows(8) == 8192
    assert pol.feeder_depth(1) == pipeline_depth(1)
    assert pol.dispatch_rows_per_device() == BATCH_LADDER[-1]
    # ticks with NO samples (registry idle): first tick primes the
    # baseline, later ticks see zero deltas — nothing may move
    for _ in range(10):
        assert c.tick() == []
    assert pol.snapshot() == {
        "rung": 2, "rows_per_device": 1024,
        "window_scale": 1.0, "depth_extra": 0,
    }


def test_empty_sample_holds_streaks():
    """An idle tick between two starved ticks must not reset the
    streak — no evidence is not contrary evidence."""
    c = Controller(interval=999)
    c.tick(starved())
    c.tick(Sample())  # idle tick: wait_mean_s None, no occupancy
    decisions = c.tick(starved())
    assert any(d["knob"] == "window_scale" and d["action"] == "promote"
               for d in decisions)


# --- AIMD directions --------------------------------------------------------


def test_starvation_widens_window_and_deepens_pipeline():
    c = Controller(interval=999)
    pol = c.policies["identify"]
    for _ in range(STEP_STREAK):
        c.tick(starved())
    assert pol.window_scale == 2.0
    assert pol.depth_extra == 1
    # keeps widening under sustained starvation, but stays bounded
    for _ in range(40):
        c.tick(starved())
    assert pol.window_scale <= autotune.SCALE_MAX
    assert pol.feeder_depth(1) <= autotune.FEEDER_DEPTH_CAP
    # and decays back toward static once the pipeline runs ahead
    comfortable = Sample(wait_mean_s=0.0001, wait_n=3)
    for _ in range(60):
        c.tick(comfortable)
    assert pol.window_scale == 1.0
    assert pol.depth_extra == 0


def test_rung_follows_dispatch_occupancy():
    """Chips hauling pad rows ⇒ the rung is oversized and demotes all
    the way down; full batches alone promote it back up (damped) — the
    rung must not be a demote-only ratchet."""
    c = Controller(interval=999)
    pol = c.policies["identify"]
    for _ in range(6 * STEP_STREAK):
        c.tick(pad_heavy())
    assert pol.rung == 0
    assert pol.dispatch_rows_per_device() == BATCH_LADDER[0]
    for _ in range(6 * STEP_STREAK):
        c.tick(full_sample())
    assert pol.rung == len(BATCH_LADDER) - 1


# --- oscillation damping ----------------------------------------------------


def test_alternating_signals_do_not_thrash():
    """Alternating pad-heavy/full samples: the streak resets on every
    direction flip, so the rung must hold still (and so must every
    other knob)."""
    c = Controller(interval=999)
    pol = c.policies["identify"]
    before = pol.snapshot()
    decisions = []
    for i in range(50):
        decisions += c.tick(pad_heavy() if i % 2 == 0 else full_sample())
    assert pol.snapshot() == before
    assert decisions == []


def test_sustained_signal_still_steps_after_damping():
    """Damping must delay, not disable: STEP_STREAK consecutive
    pad-heavy ticks step exactly once."""
    c = Controller(interval=999)
    pol = c.policies["identify"]
    for i in range(STEP_STREAK - 1):
        c.tick(pad_heavy())
        assert pol.rung == len(BATCH_LADDER) - 1, f"stepped early at {i}"
    c.tick(pad_heavy())
    assert pol.rung == len(BATCH_LADDER) - 2


# --- DeviceLadder interaction -----------------------------------------------


def test_never_promotes_past_device_ladder_demotion():
    c = Controller(interval=999)
    pol = c.policies["identify"]
    # demote the device ladder to the surviving-subset rung
    _mesh.LADDER._level = _mesh.LEVEL_SUBSET
    try:
        # the clamp lands on the next tick, undamped
        c.tick(full_sample())
        assert pol.rung == 1
        # sustained full-batch pressure must NOT promote past the cap
        for _ in range(10 * STEP_STREAK):
            c.tick(full_sample())
        assert pol.rung <= 1
        assert pol.dispatch_rows_per_device() <= BATCH_LADDER[1]
        # host-path demotion pins the bottom rung
        _mesh.LADDER._level = _mesh.LEVEL_HOST
        c.tick(full_sample())
        assert pol.dispatch_rows_per_device() == BATCH_LADDER[0]
        # ladder re-armed: promotion is allowed again (damped)
        _mesh.LADDER._level = _mesh.LEVEL_MESH
        for _ in range(10 * STEP_STREAK):
            c.tick(full_sample())
        assert pol.rung == len(BATCH_LADDER) - 1
    finally:
        _mesh.LADDER.reset()


def test_policy_read_clamps_even_between_ticks():
    """The clamp is enforced at READ time too: a demotion that lands
    between controller ticks must bound the very next dispatch."""
    pol = autotune.policy("identify")
    assert pol.dispatch_rows_per_device() == BATCH_LADDER[-1]
    _mesh.LADDER._level = _mesh.LEVEL_SUBSET
    try:
        assert pol.dispatch_rows_per_device() == BATCH_LADDER[1]
    finally:
        _mesh.LADDER.reset()


# --- telemetry surface ------------------------------------------------------


def test_decisions_land_on_ring_and_metrics():
    from spacedrive_tpu.telemetry import counter_value, gauge_value
    from spacedrive_tpu.telemetry.events import AUTOTUNE_EVENTS

    AUTOTUNE_EVENTS.clear()
    c = Controller(interval=999)
    for _ in range(STEP_STREAK):
        c.tick(starved())
    events = [e for e in AUTOTUNE_EVENTS.snapshot()
              if e.get("type") == "decision"]
    assert events, "decisions must land on the autotune ring"
    ev = events[0]["fields"]
    assert ev["workload"] == "identify"
    assert ev["action"] == "promote"
    assert ev["reason"] == "starved"
    assert counter_value("sd_autotune_decisions_total",
                         workload="identify", action="promote") >= 1
    assert gauge_value("sd_autotune_window_scale", workload="identify") == 2.0


def test_health_and_snapshot_carry_autotune_state():
    from spacedrive_tpu.telemetry import health

    out = health.evaluate()
    assert out["autotune"]["enabled"] is True
    assert "identify" in out["autotune"]["policies"]


# --- SD_AUTOTUNE=0 parity ---------------------------------------------------


def test_disabled_env_is_static_bit_for_bit(monkeypatch):
    monkeypatch.setenv("SD_AUTOTUNE", "0")
    c = Controller(interval=999)
    pol = c.policies["identify"]
    # a tick is a no-op and policy reads ignore any (stale) knob state
    assert c.tick(starved()) == []
    pol.window_scale = 4.0
    pol.depth_extra = 3
    pol.rung = 0
    assert pol.identify_window_rows(1) == 1024
    assert pol.identify_window_rows(8) == 8192
    assert pol.thumb_chunk_rows(1) == 32
    assert pol.feeder_depth(1) == pipeline_depth(1)
    assert pol.feeder_depth(8) == pipeline_depth(8)
    assert pol.dispatch_rows_per_device() == 1024
    # even a demoted device ladder does not alter the static path (the
    # pre-autotune code never consulted it for sizing)
    _mesh.LADDER._level = _mesh.LEVEL_SUBSET
    try:
        assert pol.dispatch_rows_per_device() == 1024
    finally:
        _mesh.LADDER.reset()


def test_disabled_env_cas_ids_identical_to_reference(monkeypatch):
    from spacedrive_tpu.ops import cas

    from spacedrive_tpu.ops.blake3_ref import StreamingBlake3

    rng = np.random.default_rng(7)
    msgs = [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
            for n in (1, 500, 1024, 3000, 40_000, cas.LARGE_MSG_LEN)]
    want = [StreamingBlake3().update(m).hexdigest()[:16] for m in msgs]
    monkeypatch.setenv("SD_AUTOTUNE", "0")
    assert cas.cas_ids_batched(msgs) == want


def test_sizing_changes_never_change_bytes():
    """Run the same batch through every rung the controller can pick —
    the cas_ids must be identical (sizing is a throughput knob, never a
    correctness knob)."""
    from spacedrive_tpu.ops import cas
    from spacedrive_tpu.ops.blake3_ref import StreamingBlake3

    rng = np.random.default_rng(9)
    msgs = [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
            for n in ([700] * 40 + [cas.LARGE_MSG_LEN] * 40)]
    want = [StreamingBlake3().update(m).hexdigest()[:16] for m in msgs]
    pol = autotune.policy("identify")
    for rung in range(len(BATCH_LADDER)):
        pol.rung = rung
        assert cas.cas_ids_batched(msgs) == want, f"rung {rung} diverged"
