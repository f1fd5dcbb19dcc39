"""Closed-loop autotuner — telemetry-driven batch/ladder/depth control.

Every batch size, pad-ladder rung, feeder depth, and pipeline depth
used to be a static constant. PRs 1–6 built the measurement plane
(occupancy, feeder depth/wait, event-loop lag, health verdicts); this
module spends it.

Two pieces:

- :class:`PipelinePolicy` — the ONE home for the previously scattered
  tuning constants (``batch_ladder`` rungs from ``ops/cas.py``, the
  thumbnailer's ``DEVICE_BATCH`` chunk sizing, the identifier's window
  size, the feeder's ``pipeline_depth``), one policy object per
  workload (``identify`` / ``thumbnail``). Every consumer reads its
  sizing through :func:`policy` — sdlint SD013 flags hard-coded
  batch/depth constants that bypass this seam.

- :class:`Controller` — periodically samples the existing telemetry
  (``sd_device_dispatch_occupancy``, feeder wait/fetch deltas,
  event-loop lag, the ``DeviceLadder`` demotion level) and adjusts
  each policy with AIMD-style damped steps: a knob only moves after
  ``STEP_STREAK`` consecutive ticks agree on the direction, so
  alternating samples hold instead of thrashing. Decisions land on the ``autotune`` flight
  ring (with the active trace id, like every ring emit) and update the
  ``sd_autotune_*`` gauges/counters.

Decision rules (docs/performance.md "Closed-loop autotuner"):

- **starved** (mean consumer wait per feeder take over the tick is
  high): the per-window cost — transfer latency, slow reads, an
  injected ``feeder.fetch`` stall — dominates, so AMORTIZE:
  widen the host window (multiplicative, ×2 up to ``SCALE_MAX``) and
  deepen the in-flight pipeline (+1 up to the feeder cap). This is the
  adaptive-batching shape inference servers use to ride varying load.
- **overbuffered** (waits are instant while the knobs sit above
  static): decay back toward the static defaults (halve the scale,
  −1 depth) — no reason to hold memory and latency hostage.
- **full batches** (mean dispatch occupancy ≥ ``OCC_HIGH``): promote
  the rung back toward saturating.
- **low occupancy** (chips mostly hauling pad rows): demote the rung —
  real batches aren't filling it anyway, so demotion costs nothing and
  stops shipping padding.
- **event-loop lag** past ``health.LOOP_LAG_DEGRADED``: stop deepening
  the pipeline and shed any depth boost — more in-flight windows are
  more loop work. The WINDOW deliberately does not shed on lag: a
  batch pass drags a small host's loop regardless, and wider windows
  mean fewer steps and DB commits per file (shrinking them under lag
  measurably slowed both arms of the A/B).
- the rung may NEVER exceed what the ``DeviceLadder`` demotion level
  allows (full mesh → top rung, surviving subset → middle, host path →
  bottom): a controller must not promote batches onto chips the
  resilience plane just demoted away from.

``SD_AUTOTUNE=0`` disables the controller AND makes every policy read
return the pre-autotuner static value bit-for-bit (golden-tested).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any

#: per-device cas dispatch pad rungs — at most 3 compiled programs per
#: bucket, and a 5-file tail pads to 32 rows, not 1024. Moved here from
#: ops/cas.py (which re-exports) so the autotuner owns the one copy.
BATCH_LADDER = (32, 256, 1024)

#: identifier host-window rows per device (was DEVICE_CHUNK_SIZE in
#: object/file_identifier/job.py) — device batches amortize dispatch
#: overhead, so the device window is the top ladder rung per chip
IDENTIFY_DEVICE_WINDOW = BATCH_LADDER[-1]
#: the reference's CPU parity chunk (ref:file_identifier/mod.rs:34)
IDENTIFY_CPU_WINDOW = 100

#: thumbnail images per device dispatch per accelerator (was
#: DEVICE_BATCH in object/media/thumbnail/actor.py)
THUMB_DEVICE_BATCH = 32

#: embedding images per device dispatch per accelerator (the semantic
#: search forward pass, ops/embed_jax.py — same quantum shape as the
#: thumbnailer's)
EMBED_DEVICE_BATCH = 32

#: feeder read-ahead: base depth and hard cap (parallel/feeder.py's
#: pipeline_depth shape function still derives the device scaling)
FEEDER_BASE_DEPTH = 3
FEEDER_DEPTH_CAP = 8

#: window-scale bounds: the static base is the floor (shrinking the
#: host window below it just multiplies per-window overhead — how
#: much padding rides the link is the dispatch RUNG's job); ≥8× static
#: stops amortizing anything real and only adds latency + host memory
SCALE_MIN = 1.0
SCALE_MAX = 8.0

#: mean consumer wait per take that counts as starved (a warm handoff
#: is <2 ms; 50 ms of blocking per window means the producer lost)
STARVED_WAIT_S = 0.05
#: an EXTREME wait: the consumer sat blocked for half a second on one
#: window — widening steps immediately (fast-start), damping would just
#: burn more half-second windows collecting confirmations
URGENT_WAIT_S = 0.5
#: mean wait under which the pipeline is comfortably ahead
OVERBUFFERED_WAIT_S = 0.002
#: dispatch-occupancy bands for rung control
OCC_LOW = 0.5
OCC_HIGH = 0.9

#: damping: a knob steps only after this many consecutive ticks agree
#: on the direction; ticks with no new samples hold the streak (an
#: idle pipeline is not evidence of anything)
STEP_STREAK = 2

#: per-stage lease-target hysteresis: the Controller republishes a
#: stage's lease target only when it moved ≥25% — lease sizing is a
#: fallback path, not a hot loop, and jittery targets would spam the
#: decision ring
STAGE_LEASE_HYSTERESIS = 0.25

WORKLOADS = ("identify", "thumbnail", "embed")


def enabled() -> bool:
    """SD_AUTOTUNE=0 → static config bit-for-bit (no controller, no
    policy deviation)."""
    return os.environ.get("SD_AUTOTUNE", "1") != "0"


def _ladder_rung_cap() -> int:
    """Max rung index the DeviceLadder's demotion level allows: the
    autotuner may never promote batches past the rung the resilience
    plane demoted to."""
    from . import mesh as _mesh

    level = _mesh.LADDER.level
    return max(0, len(BATCH_LADDER) - 1 - int(level))


@dataclass
class PipelinePolicy:
    """Per-workload tuning state. Static defaults ARE the pre-autotune
    constants; the controller nudges the knobs, consumers read the
    derived sizes through the methods below (the one seam)."""

    workload: str
    #: index into BATCH_LADDER — per-device rows per device dispatch
    rung: int = len(BATCH_LADDER) - 1
    #: multiplier on the static host window / chunk rows
    window_scale: float = 1.0
    #: additive adjustment to the feeder read-ahead depth
    depth_extra: int = 0

    def reset(self) -> None:
        self.rung = len(BATCH_LADDER) - 1
        self.window_scale = 1.0
        self.depth_extra = 0

    # ---- derived sizes (the seam every consumer reads) ---------------

    def dispatch_rows_per_device(self) -> int:
        """Per-device rows per device dispatch (ops/cas.cas_ids_begin's
        step = this × device count). Clamped to the DeviceLadder's
        demotion rung while autotuning."""
        if not enabled():
            return BATCH_LADDER[-1]
        return BATCH_LADDER[min(self.rung, _ladder_rung_cap())]

    def identify_window_rows(self, n_devices: int = 1) -> int:
        """Identifier cursor-window rows (device backends); the host
        window that becomes one feeder fetch."""
        base = IDENTIFY_DEVICE_WINDOW * max(1, n_devices)
        if not enabled():
            return base
        return max(BATCH_LADDER[0], int(base * self.window_scale))

    def thumb_chunk_rows(self, n_accel: int = 1) -> int:
        """Thumbnailer images per device chunk (the 3-deep software
        pipeline's quantum)."""
        base = THUMB_DEVICE_BATCH * max(1, n_accel)
        if not enabled():
            return base
        return max(1, int(base * self.window_scale))

    def embed_chunk_rows(self, n_accel: int = 1) -> int:
        """Embedding images per device chunk (the semantic-search
        forward pass quantum)."""
        base = EMBED_DEVICE_BATCH * max(1, n_accel)
        if not enabled():
            return base
        return max(1, int(base * self.window_scale))

    def feeder_depth(self, n_devices: int = 1) -> int:
        """In-flight feeder windows (read live by WindowPipeline, so a
        mid-job adjustment takes effect on the next fetch)."""
        from .feeder import pipeline_depth

        base = pipeline_depth(
            max(1, n_devices), base=FEEDER_BASE_DEPTH, cap=FEEDER_DEPTH_CAP
        )
        if not enabled():
            return base
        return max(2, min(FEEDER_DEPTH_CAP, base + self.depth_extra))

    def snapshot(self) -> dict[str, Any]:
        return {
            "rung": self.rung,
            "rows_per_device": self.dispatch_rows_per_device(),
            "window_scale": round(self.window_scale, 3),
            "depth_extra": self.depth_extra,
        }


@dataclass
class Sample:
    """One tick's telemetry deltas (cumulative reads diffed by the
    controller; tests may hand-build one and feed it to tick())."""

    wait_mean_s: float | None = None   # mean feeder wait per take
    wait_n: int = 0
    fetch_s: float = 0.0               # producer fetch time this tick
    fetch_n: int = 0
    h2d_bytes: float = 0.0
    occ_mean: dict[str, float | None] = field(default_factory=dict)
    occ_n: dict[str, int] = field(default_factory=dict)
    loop_lag_s: float = 0.0
    demotion_level: int = 0


#: which occupancy `op` label feeds each workload's rung control
_OCC_OP = {"identify": "blake3", "thumbnail": "thumbnail", "embed": "embed"}


class Controller:
    """Samples the registry on an interval and nudges the policies.

    ``tick()`` is synchronous and side-effect-complete, so tests and
    the bench drive it directly; ``start()``/``stop()`` run it on a
    supervised asyncio task (Node lifecycle), interval from
    ``SD_AUTOTUNE_INTERVAL_S`` (default 1.0)."""

    def __init__(self, interval: float | None = None):
        self.interval = interval if interval is not None else float(
            os.environ.get("SD_AUTOTUNE_INTERVAL_S", "1.0")
        )
        self.policies: dict[str, PipelinePolicy] = {
            w: PipelinePolicy(w) for w in WORKLOADS
        }
        self._lock = threading.Lock()
        self._prev: dict[str, Any] | None = None
        # (workload, knob) -> signed streak of same-direction wishes
        self._streaks: dict[tuple[str, str], int] = {}
        # execution-continuum outputs: per-stage observed rate (folded
        # from scheduler.RATES each tick) and the derived lease target
        # the WORK board falls back to when a claimer reports no rate
        self.stage_rates: dict[str, float] = {}
        self.stage_lease: dict[str, float] = {}
        self._task: Any = None
        self._tasks: set = set()
        self._stopped = False
        # CONTROLLER is process-global while Nodes start/stop it:
        # refcount so the first of two in-process nodes to shut down
        # doesn't kill the survivor's tuning
        self._starts = 0
        self.ticks = 0

    # ---- lifecycle (mirrors telemetry.events.LoopLagMonitor) ---------

    def start(self) -> None:
        import asyncio
        import logging

        from ..utils.tasks import supervise

        if not enabled():
            return
        self._starts += 1
        if self._task is not None and not self._task.done():
            # a never-done task on a CLOSED loop (a node torn down
            # without shutdown) would otherwise wedge start() forever —
            # drop it and adopt the tick loop onto the current loop; a
            # task on any still-open loop keeps ticking for everyone
            if not self._task.get_loop().is_closed():
                return
            self._task = None
        # surface the knob gauges immediately: a quiet controller that
        # never steps is invisible on /metrics otherwise
        for w, p in self.policies.items():
            self._export_gauges(w, p)
        self._stopped = False
        self._task = supervise(
            asyncio.get_running_loop().create_task(self._run()),
            self._tasks, logging.getLogger(__name__), "autotune controller",
        )

    async def stop(self) -> None:
        self._starts = max(0, self._starts - 1)
        if self._starts > 0:
            return  # another in-process node still depends on the loop
        self._stopped = True
        task = self._task
        self._task = None
        if task is not None and not task.done():
            task.cancel()
            try:
                await task
            except BaseException:  # noqa: BLE001 - cancellation cleanup
                pass

    async def _run(self) -> None:
        import asyncio

        while not self._stopped:
            await asyncio.sleep(self.interval)
            try:
                self.tick()
            except Exception:  # noqa: BLE001 - a bad tick must not kill the loop
                import logging

                logging.getLogger(__name__).exception("autotune tick failed")

    def reset(self) -> None:
        with self._lock:
            for p in self.policies.values():
                p.reset()
            self._prev = None
            self._streaks.clear()
            self.ticks = 0
            self.stage_rates.clear()
            self.stage_lease.clear()
        for w, p in self.policies.items():
            self._export_gauges(w, p)

    def stage_rate(self, stage_id: str) -> float:
        """The Controller's per-stage rate output (files/s) — 0.0 until
        the stage has executed shards here. The WORK board's lease
        fallback when a claimer self-reports no rate for a stage."""
        return self.stage_rates.get(stage_id, 0.0)

    def reset_stage_targets(self) -> None:
        """Clears the continuum state (scheduler.reset() fans out here;
        telemetry.reset() zeroes the gauges themselves)."""
        self.stage_rates.clear()
        self.stage_lease.clear()

    # ---- sampling ----------------------------------------------------

    def _cumulative(self) -> dict[str, Any]:
        from ..telemetry import metrics as _tm
        from ..telemetry.snapshot import gauge_value

        occ = {
            op: _tm.DEVICE_DISPATCH_OCCUPANCY.stats(op=op)
            for op in _OCC_OP.values()
        }
        return {
            "wait": _tm.FEEDER_WAIT_SECONDS.stats(),
            "fetch": _tm.FEEDER_FETCH_SECONDS.stats(),
            "h2d": _tm.FEEDER_H2D_BYTES.value(),
            "occ": occ,
            "lag": gauge_value("sd_event_loop_lag_seconds"),
        }

    def sample(self) -> Sample:
        """Diff the registry against the previous tick's cumulative
        snapshot. The first call primes the baseline and returns an
        empty sample (cold start ⇒ static defaults hold)."""
        from . import mesh as _mesh

        cur = self._cumulative()
        prev, self._prev = self._prev, cur
        s = Sample(
            loop_lag_s=cur["lag"],
            demotion_level=int(_mesh.LADDER.level),
        )
        if prev is None:
            return s
        dwait_n = int(cur["wait"]["count"] - prev["wait"]["count"])
        dwait_s = cur["wait"]["sum"] - prev["wait"]["sum"]
        if dwait_n > 0:
            s.wait_mean_s = dwait_s / dwait_n
            s.wait_n = dwait_n
        s.fetch_n = int(cur["fetch"]["count"] - prev["fetch"]["count"])
        s.fetch_s = cur["fetch"]["sum"] - prev["fetch"]["sum"]
        s.h2d_bytes = cur["h2d"] - prev["h2d"]
        for op in _OCC_OP.values():
            dn = int(cur["occ"][op]["count"] - prev["occ"][op]["count"])
            ds = cur["occ"][op]["sum"] - prev["occ"][op]["sum"]
            s.occ_n[op] = dn
            s.occ_mean[op] = (ds / dn) if dn > 0 else None
        return s

    # ---- the control law ---------------------------------------------

    def tick(self, sample: Sample | None = None) -> list[dict[str, Any]]:
        """One sampling + adjustment pass; returns the decisions made
        (also recorded on the ``autotune`` ring + metrics)."""
        if not enabled():
            return []
        with self._lock:
            if sample is None:
                sample = self.sample()
            self.ticks += 1
            decisions: list[dict[str, Any]] = []
            for workload, pol in self.policies.items():
                decisions.extend(self._tick_workload(workload, pol, sample))
            decisions.extend(self._tick_stages(sample))
        return decisions

    def _tick_workload(
        self, workload: str, pol: PipelinePolicy, s: Sample
    ) -> list[dict[str, Any]]:
        """Per-knob wishes are three-valued: ±1 asks for a damped step,
        0 is CONTRARY/neutral evidence (resets the streak — alternating
        samples therefore never step), None is NO
        evidence (an idle tick holds the streak — silence is not a
        counter-argument)."""
        out: list[dict[str, Any]] = []
        lagging = self._loop_lagging(s)
        occ = s.occ_mean.get(_OCC_OP[workload])

        # --- window scale (host window / chunk rows) ---
        # NOTE: event-loop lag deliberately does NOT shed the window: a
        # batch pass on a small host drags the loop regardless (the
        # work, not the window, is the cause), and a WIDER window means
        # fewer steps and fewer DB commits per file — shrinking it
        # under lag measurably made both arms of the A/B slower.
        want: int | None
        urgent = False
        reason = ""
        if workload == "identify":
            if s.wait_mean_s is None:
                want = None  # idle feeder: silence, not evidence
            elif s.wait_mean_s >= STARVED_WAIT_S:
                want = +1  # amortize the per-window cost
                urgent = s.wait_mean_s >= URGENT_WAIT_S
                reason = "starved"
            elif s.wait_mean_s <= OVERBUFFERED_WAIT_S \
                    and pol.window_scale > 1.0:
                want = -1  # decay toward static
                reason = "overbuffered"
            else:
                want = 0
        else:
            # no feeder on the thumbnail path: chunk sizing tracks how
            # full the device chunks actually run
            if occ is None:
                want = None
            elif occ >= OCC_HIGH:
                want = +1
                reason = "saturate"
            elif occ < OCC_LOW and pol.window_scale > 1.0:
                want = -1
                reason = "pad-waste"
            else:
                want = 0
        if self._step(workload, "window", want, urgent=urgent):
            new = pol.window_scale * (2.0 if want > 0 else 0.5)
            new = min(SCALE_MAX, max(SCALE_MIN, new))
            if new != pol.window_scale:
                out.append(self._apply(
                    workload, pol, "window_scale", pol.window_scale, new, s,
                    reason,
                ))
                pol.window_scale = new

        # --- feeder depth (identify only: the thumbnailer's software
        # pipeline is structurally 3-deep) ---
        if workload == "identify":
            if lagging:
                # in-flight windows are loop work: shed any boost
                # (never below the static base — lag on a small host
                # is the workload's fault, not the depth's)
                want = -1 if pol.depth_extra > 0 else 0
            elif s.wait_mean_s is None:
                want = None
            elif s.wait_mean_s >= STARVED_WAIT_S:
                want = +1
            elif s.wait_mean_s <= OVERBUFFERED_WAIT_S \
                    and pol.depth_extra > 0:
                want = -1
            else:
                want = 0
            if self._step(workload, "depth", want):
                new_extra = pol.depth_extra + (1 if want > 0 else -1)
                new_extra = max(0, min(FEEDER_DEPTH_CAP, new_extra))
                if new_extra != pol.depth_extra:
                    out.append(self._apply(
                        workload, pol, "depth_extra",
                        pol.depth_extra, new_extra, s,
                        "starved" if want > 0 else
                        ("loop-lag" if lagging else "overbuffered"),
                    ))
                    pol.depth_extra = new_extra

        # --- dispatch rung (identify only: the thumbnail resize pads
        # pow2 per size bucket, not the cas ladder) ---
        if workload == "identify":
            cap = _ladder_rung_cap()
            if pol.rung > cap:
                # demotion clamp applies immediately, undamped: the
                # resilience plane already proved those chips are gone
                out.append(self._apply(
                    workload, pol, "rung", pol.rung, cap, s,
                    "device-ladder-demotion",
                ))
                pol.rung = cap
                self._streaks.pop((workload, "rung"), None)
            if occ is None:
                want = None
            elif occ < OCC_LOW:
                want = -1 if pol.rung > 0 else 0
            elif occ >= OCC_HIGH:
                want = +1  # saturate (a no-op step at the cap)
            else:
                want = None
            if self._step(workload, "rung", want):
                new_rung = max(0, min(cap, pol.rung + (1 if want > 0 else -1)))
                if new_rung != pol.rung:
                    out.append(self._apply(
                        workload, pol, "rung", pol.rung, new_rung, s,
                        "pad-waste" if want < 0 else "saturate",
                    ))
                    pol.rung = new_rung
        return out

    def _tick_stages(self, s: Sample) -> list[dict[str, Any]]:
        """Per-stage lease targets (the continuum's WORK-board output):
        fold the scheduler's per-stage throughput EWMAs into the lease
        a default-sized shard would need at that rate, clamped to the
        board's lease law bounds. Republished only past the hysteresis
        band — lease sizing is a fallback path, not a hot loop."""
        from ..p2p import work as _work
        from . import scheduler as _scheduler

        out: list[dict[str, Any]] = []
        try:
            from ..location.indexer.mesh import shard_files_default

            files = shard_files_default()
        except Exception:  # noqa: BLE001 - sizing default is fine
            files = 128
        for stage_id in _scheduler.STAGES:
            rate = _scheduler.RATES.rate(stage_id)
            if rate <= 0:
                continue
            self.stage_rates[stage_id] = rate
            target = min(
                _work.LEASE_MAX_S,
                max(_work.LEASE_MIN_S, files / rate * _work.LEASE_SLACK),
            )
            old = self.stage_lease.get(stage_id)
            if old is not None and old > 0 \
                    and abs(target - old) <= STAGE_LEASE_HYSTERESIS * old:
                continue
            self.stage_lease[stage_id] = target
            from ..telemetry import metrics as _tm
            from ..telemetry.events import AUTOTUNE_EVENTS

            AUTOTUNE_EVENTS.emit(
                "stage-lease",
                stage=stage_id,
                rate_files_per_s=round(rate, 3),
                old=None if old is None else round(old, 3),
                new=round(target, 3),
            )
            # inline bounded conditional pins the label domain at the
            # emit site (SD007): the stage registry is the vocabulary
            _tm.WORK_STAGE_LEASE_TARGET.set(
                target,
                stage="identify.hash" if stage_id == "identify.hash" else (
                    "thumb" if stage_id == "thumb" else (
                        "media.extract" if stage_id == "media.extract" else (
                            "phash" if stage_id == "phash" else (
                                "embed" if stage_id == "embed"
                                else "other")))),
            )
            out.append({
                "knob": "stage_lease", "stage": stage_id,
                "from": old, "to": target,
                "rate_files_per_s": round(rate, 3),
            })
        return out

    @staticmethod
    def _loop_lagging(s: Sample) -> bool:
        from ..telemetry.health import LOOP_LAG_DEGRADED

        return s.loop_lag_s >= LOOP_LAG_DEGRADED

    def _step(self, workload: str, knob: str, want: int | None,
              urgent: bool = False) -> bool:
        """Damping: return True when `want` (±1) has persisted for
        STEP_STREAK consecutive deciding ticks. None (no evidence)
        holds the streak; 0 (contrary/neutral evidence) resets it; an
        opposite wish restarts it in the new direction. ``urgent``
        promotions (extreme waits) step immediately — the next
        confirmation would cost another extreme wait to collect."""
        key = (workload, knob)
        if want is None:
            return False
        if want == 0:
            self._streaks.pop(key, None)
            return False
        if urgent and want > 0:
            self._streaks[key] = 0
            return True
        streak = self._streaks.get(key, 0)
        streak = streak + want if (streak > 0) == (want > 0) or streak == 0 \
            else want
        if abs(streak) >= STEP_STREAK:
            self._streaks[key] = 0
            return True
        self._streaks[key] = streak
        return False

    def _apply(
        self, workload: str, pol: PipelinePolicy, knob: str,
        old: Any, new: Any, s: Sample, reason: str,
    ) -> dict[str, Any]:
        from ..telemetry import metrics as _tm
        from ..telemetry.events import AUTOTUNE_EVENTS

        action = "promote" if (new > old) else "demote"
        decision = {
            "workload": workload, "knob": knob, "action": action,
            "from": old, "to": new, "reason": reason,
        }
        AUTOTUNE_EVENTS.emit(
            "decision",
            workload=workload,
            knob=knob,
            action=action,
            old=old,
            new=new,
            reason=reason,
            wait_mean_s=None if s.wait_mean_s is None
            else round(s.wait_mean_s, 4),
            loop_lag_s=round(s.loop_lag_s, 4),
            demotion_level=s.demotion_level,
        )
        # inline bounded conditionals pin the label domains at the
        # emit site (SD007): WORKLOADS and the action verbs are the
        # entire vocabulary
        _tm.AUTOTUNE_DECISIONS.inc(
            workload="identify" if workload == "identify"
            else ("thumbnail" if workload == "thumbnail" else "embed"),
            action="promote" if action == "promote" else "demote",
        )
        self._export_gauges(workload, pol, knob, new)
        return decision

    def _export_gauges(
        self, workload: str, pol: PipelinePolicy,
        knob: str | None = None, new: Any = None,
    ) -> None:
        from ..telemetry import metrics as _tm

        scale = new if knob == "window_scale" else pol.window_scale
        rung = new if knob == "rung" else pol.rung
        extra = new if knob == "depth_extra" else pol.depth_extra
        # inline bounded conditionals pin the label domain at each
        # emit site (SD007): WORKLOADS is the entire vocabulary
        _tm.AUTOTUNE_WINDOW_SCALE.set(
            float(scale),
            workload="identify" if workload == "identify"
            else ("thumbnail" if workload == "thumbnail" else "embed"))
        _tm.AUTOTUNE_RUNG.set(
            float(rung),
            workload="identify" if workload == "identify"
            else ("thumbnail" if workload == "thumbnail" else "embed"))
        _tm.AUTOTUNE_DEPTH_EXTRA.set(
            float(extra),
            workload="identify" if workload == "identify"
            else ("thumbnail" if workload == "thumbnail" else "embed"))

    def snapshot(self) -> dict[str, Any]:
        """Current knob state — embedded in health.evaluate() so the
        federation snapshot carries autotune state onto GET /mesh,
        including the execution continuum's per-stage rates and lease
        targets (the Controller's WORK-board outputs)."""
        from . import scheduler as _scheduler

        return {
            "enabled": enabled(),
            "ticks": self.ticks,
            "policies": {
                w: p.snapshot() for w, p in self.policies.items()
            },
            "stages": {
                **_scheduler.snapshot(),
                "lease_targets": {
                    st: round(v, 3) for st, v in self.stage_lease.items()
                },
            },
        }


#: the process-wide controller + policies every consumer reads
CONTROLLER = Controller()


def policy(workload: str) -> PipelinePolicy:
    """The live policy object for a workload — THE seam. Unknown
    workloads fail loudly (a typo must not mint an untuned policy)."""
    return CONTROLLER.policies[workload]


def snapshot() -> dict[str, Any]:
    return CONTROLLER.snapshot()


def observed_files_per_s(workload: str = "identify") -> float | None:
    """Telemetry-derived throughput for a workload — the same registry
    series the controller ticks on, folded to one number. Used by the
    mesh work plane: a claiming peer self-reports this rate so the
    coordinator can size its lease (p2p/work.py), before the worker has
    any shard-measured rate of its own. None until the workload has
    processed anything here."""
    from ..telemetry import metrics as _tm

    if workload != "identify":
        return None
    files = _tm.IDENTIFIER_FILES.value()
    secs = (
        _tm.IDENTIFIER_STAGE_SECONDS.stats(stage="hash")["sum"]
        + _tm.IDENTIFIER_STAGE_SECONDS.stats(stage="db")["sum"]
    )
    if not files or secs <= 0:
        return None
    return files / secs


def reset() -> None:
    """Test/bench isolation: static knobs, cleared streaks/baselines."""
    CONTROLLER.reset()


__all__ = [
    "BATCH_LADDER",
    "CONTROLLER",
    "Controller",
    "FEEDER_BASE_DEPTH",
    "FEEDER_DEPTH_CAP",
    "IDENTIFY_CPU_WINDOW",
    "IDENTIFY_DEVICE_WINDOW",
    "EMBED_DEVICE_BATCH",
    "PipelinePolicy",
    "Sample",
    "THUMB_DEVICE_BATCH",
    "enabled",
    "policy",
    "reset",
    "snapshot",
]
