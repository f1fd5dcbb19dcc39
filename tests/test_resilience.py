"""Unit tests for the resilience layer and the fault plane.

Covers the contracts everything else builds on: deterministic fault
plans, decorrelated-jitter retry bounds, circuit-breaker state
transitions (closed → open → half-open probe → closed/re-open),
deadline propagation, the device degradation ladder, and the
cancellation-vs-crash distinction in the job supervisor. The chaos
soak (tests/test_chaos.py) exercises the same pieces through the real
pipeline seams.
"""

import asyncio
import random
import time

import pytest

from spacedrive_tpu.parallel import mesh
from spacedrive_tpu.telemetry import counter_value, gauge_value
from spacedrive_tpu.telemetry.events import ring
from spacedrive_tpu.utils import faults, resilience
from spacedrive_tpu.utils.resilience import (
    PASS,
    RETRY,
    BreakerOpen,
    CircuitBreaker,
    DeadlineExceeded,
    ResiliencePolicy,
    RetryPolicy,
    deadline_remaining,
    deadline_scope,
)


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    faults.clear()
    resilience.reset_breakers()
    mesh.LADDER.reset()
    mesh.LADDER.reset_timeout = 30.0
    yield
    faults.clear()
    resilience.reset_breakers()
    mesh.LADDER.reset()
    mesh.LADDER.reset_timeout = 30.0


# --- fault plan ------------------------------------------------------------


def test_fault_plan_parse_and_counters():
    plan = faults.FaultPlan.parse(
        "device.blake3:raise:times=2,after=1;feeder.fetch:stall:delay_s=0.5"
    )
    assert [s.point for s in plan.specs] == ["device.blake3", "feeder.fetch"]
    assert plan.specs[0].times == 2 and plan.specs[0].after == 1
    assert plan.specs[1].delay_s == 0.5
    # first hit is skipped (after=1), then 2 fire, then exhausted
    assert plan.hit("device.blake3") is None
    assert plan.hit("device.blake3") is not None
    assert plan.hit("device.blake3") is not None
    assert plan.hit("device.blake3") is None
    assert plan.activations()["device.blake3"] == 2


def test_fault_plan_rejects_unknown_points_and_modes():
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("not.a.point:raise")
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("device.blake3:vanish")
    plan = faults.FaultPlan([])
    with pytest.raises(ValueError):
        plan.hit("not.a.point")


def test_fault_plan_probability_is_seed_deterministic():
    def firing_pattern(seed):
        plan = faults.FaultPlan.parse(
            "sync.ingest:poison:prob=0.5,times=100", seed=seed
        )
        return [plan.hit("sync.ingest") is not None for _ in range(50)]

    a, b = firing_pattern(7), firing_pattern(7)
    assert a == b  # same seed, same pattern
    assert firing_pattern(8) != a  # different seed, different pattern
    assert any(a) and not all(a)  # it is actually probabilistic


def test_fault_plan_arg_discrimination():
    plan = faults.FaultPlan.parse("device.probe:dead:arg=3,times=inf")
    assert plan.hit("device.probe", arg="0") is None
    assert plan.hit("device.probe", arg="3") is not None
    assert plan.hit("device.probe", arg="3") is not None  # times=inf


def test_fault_env_and_fixture_activation():
    assert faults.install_from_env({}) is None
    plan = faults.install_from_env(
        {"SD_FAULTS": "relay.http:500:times=1", "SD_FAULT_SEED": "3"}
    )
    assert plan is not None and faults.active_plan() is plan
    assert plan.seed == 3
    faults.clear()
    assert faults.hit("relay.http") is None
    with faults.active(faults.FaultPlan.parse("relay.http:500")):
        assert faults.hit("relay.http") is not None
    assert faults.active_plan() is None


def test_fault_activation_lands_on_ring_with_trace():
    from spacedrive_tpu.telemetry import trace as _trace

    before = len(ring("faults"))
    ctx = _trace.new_context()
    with _trace.use(ctx), faults.active(
        faults.FaultPlan.parse("relay.http:500")
    ):
        faults.hit("relay.http")
    events = ring("faults").snapshot()
    assert len(events) == before + 1
    last = events[-1]
    assert last["type"] == "injected"
    assert last["fields"]["point"] == "relay.http"
    assert last["fields"]["mode"] == "500"
    assert last["trace_id"] == ctx.trace_id


# --- retry policy ----------------------------------------------------------


def test_decorrelated_jitter_bounds():
    policy = RetryPolicy(max_attempts=50, base_delay=0.05, max_delay=2.0)
    sleeps = list(policy.sleeps(random.Random(1)))
    assert len(sleeps) == 49
    assert all(0.05 <= s <= 2.0 for s in sleeps)
    # jitter: not all equal
    assert len({round(s, 6) for s in sleeps}) > 5


@pytest.mark.asyncio
async def test_policy_retries_then_succeeds():
    policy = ResiliencePolicy(
        "t1", RetryPolicy(max_attempts=3, base_delay=0.001, max_delay=0.01)
    )
    calls = []

    async def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("boom")
        return "ok"

    before = counter_value("sd_resilience_retries_total")
    assert await policy.call("target", flaky) == "ok"
    assert len(calls) == 3
    assert counter_value("sd_resilience_retries_total") == before + 2
    assert policy.breaker("target").state == resilience.CLOSED
    assert policy.breaker("target").failures == 0


@pytest.mark.asyncio
async def test_policy_gives_up_after_max_attempts():
    policy = ResiliencePolicy(
        "t2", RetryPolicy(max_attempts=2, base_delay=0.001, max_delay=0.01)
    )
    calls = []

    async def dead():
        calls.append(1)
        raise ConnectionError("still dead")

    with pytest.raises(ConnectionError):
        await policy.call("target", dead)
    assert len(calls) == 2
    assert policy.breaker("target").failures == 2


@pytest.mark.asyncio
async def test_policy_pass_classification_skips_retry_and_breaker():
    policy = ResiliencePolicy(
        "t3",
        RetryPolicy(max_attempts=5, base_delay=0.001),
        classify=lambda e: PASS if isinstance(e, ValueError) else RETRY,
    )
    calls = []

    async def bad_request():
        calls.append(1)
        raise ValueError("a 4xx-shaped error")

    with pytest.raises(ValueError):
        await policy.call("target", bad_request)
    assert len(calls) == 1  # no retry
    assert policy.breaker("target").failures == 0  # no breaker count


# --- circuit breaker -------------------------------------------------------


def test_breaker_opens_half_opens_and_recovers():
    b = CircuitBreaker("x", failure_threshold=3, reset_timeout=0.05)
    assert b.allow()
    for _ in range(3):
        b.record_failure()
    assert b.state == resilience.OPEN
    assert not b.allow()  # still inside the reset window
    time.sleep(0.06)
    assert b.allow()  # the single half-open probe
    assert b.state == resilience.HALF_OPEN
    assert not b.allow()  # second caller rejected while probing
    b.record_success()
    assert b.state == resilience.CLOSED and b.allow()


def test_breaker_half_open_never_wedges():
    b = CircuitBreaker("x", failure_threshold=1, reset_timeout=0.05)
    b.record_failure()
    time.sleep(0.06)
    assert b.allow()  # probe admitted, then ABANDONED (no outcome)
    assert not b.allow()
    time.sleep(0.06)
    # an abandoned probe ages out: a fresh one is admitted instead of
    # the breaker staying HALF_OPEN (= fast-failing) forever
    assert b.allow()
    b.record_success()
    assert b.state == resilience.CLOSED


@pytest.mark.asyncio
async def test_pass_during_half_open_probe_closes_breaker():
    """A PASS-classified answer (4xx) during the half-open probe is
    proof of liveness: the breaker must close, not wedge."""
    policy = ResiliencePolicy(
        "t_pass_probe",
        RetryPolicy(max_attempts=1, base_delay=0.001),
        failure_threshold=1,
        reset_timeout=0.05,
        classify=lambda e: PASS if isinstance(e, ValueError) else RETRY,
    )

    async def dead():
        raise ConnectionError("down")

    with pytest.raises(ConnectionError):
        await policy.call("t", dead)
    assert policy.breaker("t").state == resilience.OPEN
    await asyncio.sleep(0.06)

    async def answers_404():
        raise ValueError("404")

    with pytest.raises(ValueError):
        await policy.call("t", answers_404)
    assert policy.breaker("t").state == resilience.CLOSED


def test_breaker_half_open_failure_reopens():
    b = CircuitBreaker("x", failure_threshold=1, reset_timeout=0.05)
    b.record_failure()
    assert b.state == resilience.OPEN
    time.sleep(0.06)
    assert b.allow()
    b.record_failure()  # the probe failed
    assert b.state == resilience.OPEN
    assert not b.allow()  # clock restarted


@pytest.mark.asyncio
async def test_policy_breaker_open_fast_fails_and_metrics():
    policy = ResiliencePolicy(
        "t4",
        RetryPolicy(max_attempts=1, base_delay=0.001),
        failure_threshold=2,
        reset_timeout=0.1,
    )

    async def dead():
        raise ConnectionError("down")

    for _ in range(2):
        with pytest.raises(ConnectionError):
            await policy.call("relay", dead)
    assert gauge_value("sd_breaker_open") >= 1.0
    calls = []

    async def should_not_run():
        calls.append(1)

    with pytest.raises(BreakerOpen):
        await policy.call("relay", should_not_run)
    assert calls == []  # fast-failed without touching the target
    # half-open probe after the reset window closes it again
    await asyncio.sleep(0.12)

    async def alive():
        return "ok"

    assert await policy.call("relay", alive) == "ok"
    assert policy.breaker("relay").state == resilience.CLOSED
    assert gauge_value("sd_breaker_open") == 0.0
    states = [
        e["fields"]["state"] for e in ring("resilience").snapshot()
        if e["type"] == "breaker"
    ]
    assert "open" in states and "half_open" in states and "closed" in states


# --- deadline propagation --------------------------------------------------


@pytest.mark.asyncio
async def test_deadline_scope_bounds_calls():
    policy = ResiliencePolicy(
        "t5", RetryPolicy(max_attempts=100, base_delay=0.02, max_delay=0.05)
    )

    async def dead():
        raise ConnectionError("down")

    t0 = time.monotonic()
    with deadline_scope(0.1):
        with pytest.raises((DeadlineExceeded, ConnectionError)):
            await policy.call("x", dead)
    assert time.monotonic() - t0 < 1.0  # nowhere near 100 attempts


@pytest.mark.asyncio
async def test_deadline_clips_attempt_timeout():
    policy = ResiliencePolicy(
        "t6", RetryPolicy(max_attempts=1, base_delay=0.001,
                          attempt_timeout=30.0)
    )

    async def slow():
        await asyncio.sleep(5)

    t0 = time.monotonic()
    with deadline_scope(0.05):
        with pytest.raises(TimeoutError):
            await policy.call("x", slow)
    assert time.monotonic() - t0 < 1.0


def test_deadline_scopes_nest_tightening_only():
    assert deadline_remaining() is None
    with deadline_scope(10.0):
        outer = deadline_remaining()
        assert outer is not None and outer <= 10.0
        with deadline_scope(99.0):
            inner = deadline_remaining()
            assert inner is not None and inner <= outer + 0.01
    assert deadline_remaining() is None


# --- device degradation ladder --------------------------------------------


def test_ladder_demotes_to_probed_subset_and_rearms():
    devs = mesh.dispatch_devices()
    assert len(devs) == 8  # conftest forces the 8-device virtual mesh
    ladder = mesh.DeviceLadder(reset_timeout=0.05)
    got, level = ladder.filter(devs)
    assert got == devs and level == mesh.LEVEL_MESH
    # device 3 reads as dead during the demotion probe
    with faults.active(
        faults.FaultPlan.parse("device.probe:dead:arg=3,times=inf")
    ):
        assert ladder.record_failure(mesh.LEVEL_MESH, devs) == mesh.LEVEL_SUBSET
    subset, level = ladder.filter(devs)
    assert level == mesh.LEVEL_SUBSET
    assert len(subset) == 7 and devs[3] not in subset
    assert gauge_value("sd_device_demotion_level") == 1.0
    # half-open probe after the reset window: success re-arms to mesh
    time.sleep(0.06)
    got, level = ladder.filter(devs)
    assert level == mesh.LEVEL_MESH
    ladder.record_success(level)
    assert ladder.level == mesh.LEVEL_MESH
    assert gauge_value("sd_device_demotion_level") == 0.0
    kinds = [e["type"] for e in ring("resilience").snapshot()]
    assert "device_demote" in kinds and "device_promote" in kinds


def test_ladder_all_dead_demotes_to_host():
    devs = mesh.dispatch_devices()
    ladder = mesh.DeviceLadder()
    with faults.active(faults.FaultPlan.parse("device.probe:dead:times=inf")):
        assert ladder.record_failure(mesh.LEVEL_MESH, devs) == mesh.LEVEL_HOST
    got, level = ladder.filter(devs)
    assert got == [] and level == mesh.LEVEL_HOST
    # a failure below mesh level always lands on host
    ladder2 = mesh.DeviceLadder()
    ladder2.record_failure(mesh.LEVEL_MESH, devs)
    assert ladder2.record_failure(mesh.LEVEL_SUBSET, devs) == mesh.LEVEL_HOST


# --- job supervisor: cancellation is not a crash ---------------------------


def test_status_for_forced_abortion_is_canceled():
    from spacedrive_tpu.jobs.job import status_for_result
    from spacedrive_tpu.jobs.report import JobStatus
    from spacedrive_tpu.tasks import TaskStatus

    assert status_for_result(TaskStatus.FORCED_ABORTION, False) \
        == JobStatus.CANCELED
    assert status_for_result(TaskStatus.ERROR, False) == JobStatus.FAILED


@pytest.mark.asyncio
async def test_shutdown_cancellation_records_no_spurious_failure(tmp_path):
    from spacedrive_tpu.jobs import JobManager, JobStatus
    from spacedrive_tpu.jobs.job import JobContext, StatefulJob, StepResult
    from spacedrive_tpu.node import Libraries
    from spacedrive_tpu.tasks import TaskSystem
    from spacedrive_tpu.telemetry.events import JOB_EVENTS

    class _Hang(StatefulJob):
        NAME = "hang_job"

        async def init_job(self, ctx: JobContext) -> None:
            self.steps.append({"kind": "hang"})

        async def execute_step(self, ctx, step, step_number) -> StepResult:
            await asyncio.sleep(30)
            return StepResult()

    libs = Libraries(tmp_path)
    library = libs.create("cancel-lib")
    mgr = JobManager(TaskSystem(1))
    job = _Hang()
    await mgr.ingest(job, library)
    await asyncio.sleep(0.05)  # let the step start hanging
    handle, _ctx = mgr._active[job.id]
    # node shutdown tearing the loop down cancels the running coroutine
    await mgr.system._force_abort(handle.task.id)
    report = await mgr.wait(job.id)
    assert report.status == JobStatus.CANCELED
    settled = [
        e for e in JOB_EVENTS.snapshot()
        if e["type"] == "settled" and e["fields"]["id"] == str(job.id)
    ]
    assert settled and settled[-1]["fields"]["status"] == "CANCELED"
    await mgr.system.shutdown()
    library.close()


# --- feeder producer restart ----------------------------------------------


def test_feeder_restarts_crashed_producer_once():
    from spacedrive_tpu.parallel import WindowPipeline

    def fetch(cursor):
        if cursor >= 5:
            return None
        return cursor + 1, [cursor]

    before = counter_value("sd_feeder_restarts_total")
    with faults.active(faults.FaultPlan.parse("feeder.fetch:crash:times=1")):
        pipe = WindowPipeline(fetch, 0, depth=2)
        windows = []
        while (w := pipe.take()) is not None:
            windows.append(w[0])
        pipe.close()
    assert windows == [0, 1, 2, 3, 4]  # the crashed window was re-fetched
    assert counter_value("sd_feeder_restarts_total") == before + 1
    assert any(
        e["type"] == "feeder_restart" for e in ring("resilience").snapshot()
    )


def test_feeder_second_crash_surfaces():
    from spacedrive_tpu.parallel import WindowPipeline

    def fetch(cursor):
        if cursor >= 5:
            return None
        return cursor + 1, [cursor]

    with faults.active(faults.FaultPlan.parse("feeder.fetch:crash:times=2")):
        pipe = WindowPipeline(fetch, 0, depth=2)
        with pytest.raises(faults.InjectedFault):
            while pipe.take() is not None:
                pass
        pipe.close()


def test_feeder_stall_delays_but_completes():
    from spacedrive_tpu.parallel import WindowPipeline

    def fetch(cursor):
        if cursor >= 3:
            return None
        return cursor + 1, [cursor]

    with faults.active(
        faults.FaultPlan.parse("feeder.fetch:stall:delay_s=0.05,times=1")
    ):
        pipe = WindowPipeline(fetch, 0, depth=2)
        windows = []
        while (w := pipe.take()) is not None:
            windows.append(w[0])
        pipe.close()
    assert windows == [0, 1, 2]


# --- health: breaker + demotion feed the verdicts --------------------------


def test_health_resilience_and_device_verdicts():
    from spacedrive_tpu.telemetry import health, metrics as _tm

    _tm.DEVICE_DEMOTION.set(0.0)
    verdict = health.evaluate()
    assert verdict["subsystems"]["resilience"]["status"] in (
        health.HEALTHY, health.DEGRADED,
    )
    b = ResiliencePolicy("t7", failure_threshold=1).breaker("dead-peer")
    b.record_failure()
    verdict = health.evaluate()
    assert verdict["subsystems"]["resilience"]["status"] == health.DEGRADED
    assert verdict["subsystems"]["resilience"]["signals"]["open_breakers"] >= 1
    _tm.DEVICE_DEMOTION.set(1.0)
    verdict = health.evaluate()
    assert verdict["subsystems"]["device"]["status"] == health.DEGRADED
    assert "subset" in verdict["subsystems"]["device"]["reason"]
    _tm.DEVICE_DEMOTION.set(2.0)
    assert "host" in health.evaluate()["subsystems"]["device"]["reason"]
    _tm.DEVICE_DEMOTION.set(0.0)
