"""Unified stage-typed execution continuum — ONE scheduler vocabulary
for the two queues that used to schedule work separately:

- the task system's local threads (``tasks/system.py``),
- the mesh WORK shard plane (``p2p/work.py``), previously identify-only.

This module is the registry that fuses them: every distributable unit
of pipeline work is a **stage** with a stable id, and a mesh
:class:`~spacedrive_tpu.p2p.work.WorkShard` now carries its stage id so
any executor — local self-steal, remote peer — can route the shard to
the right execution leg (``location/indexer/stages.py``), consult its
own index journal first, and run the CPU-bound middle itself. The
registry also owns the **per-stage throughput EWMAs** the control loop
runs on: executors report ``(files, seconds)`` per shard here, the PR 8
``Controller`` folds the rates into per-stage lease targets every tick
(``parallel/autotune.py:_tick_stages``), and the WORK board sizes
leases per stage from the claimer's self-reported per-stage rates with
the Controller targets as the fallback — heterogeneous-fleet
scheduling: a peer with idle chips bids for device-heavy shards, a
CPU-rich peer takes the decode/encode stages.

Like the telemetry registry (the precedent for process-global state
shared by in-process test nodes), ``RATES`` is process-wide;
``telemetry.reset()`` clears it alongside every metric series.

sdlint scope: this module and the stage executors it routes to are
fully inside SD014 (P2P requests must ride a ResiliencePolicy — the
scheduler is NOT a defining module).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any

#: stage ids — the bounded vocabulary every `stage` metric label and
#: wire shard carries. Adding a stage means adding it HERE (and to the
#: inline label chains at the emit sites, per SD007).
STAGE_IDENTIFY = "identify.hash"
STAGE_THUMB = "thumb"
STAGE_MEDIA = "media.extract"
STAGE_PHASH = "phash"
STAGE_EMBED = "embed"

#: EWMA blend for per-stage throughput (same constant the mesh worker
#: has always used for its claim-sizing self-report)
EWMA_KEEP = 0.7


@dataclass(frozen=True)
class StageSpec:
    """One distributable pipeline stage.

    ``workload`` names the autotune :class:`PipelinePolicy` whose
    quanta size this stage's legs; ``journal_field`` documents which
    index-journal vouch the executor consults before touching a byte."""

    id: str
    workload: str
    journal_field: str


#: the stage registry — insertion order is the grant tie-break order
STAGES: dict[str, StageSpec] = {
    STAGE_IDENTIFY: StageSpec(STAGE_IDENTIFY, "identify", "cas_id"),
    STAGE_THUMB: StageSpec(STAGE_THUMB, "thumbnail", "thumb"),
    STAGE_MEDIA: StageSpec(STAGE_MEDIA, "identify", "media_digest"),
    STAGE_PHASH: StageSpec(STAGE_PHASH, "thumbnail", "phash"),
    STAGE_EMBED: StageSpec(STAGE_EMBED, "embed", "embed"),
}


def spec(stage_id: str) -> StageSpec:
    """The registry entry for a stage id — unknown stages fail loudly
    (a typo'd wire shard must not execute as the wrong stage)."""
    return STAGES[stage_id]


# --- per-stage throughput EWMAs (the control loop's input) -----------------


class StageRates:
    """Process-wide per-stage files/s EWMAs. Executors call
    :meth:`observe` once per executed shard (any stage, any origin —
    self-steal or remote claim); the Controller reads :meth:`rate` each
    tick to derive per-stage lease targets, and ``/mesh`` snapshots the
    whole table. Thread-safe: shard execution legs run in worker
    threads while the Controller ticks on the event loop."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._ewma: dict[str, float] = {}
        self._observed: dict[str, int] = {}

    def observe(self, stage_id: str, files: int, seconds: float) -> None:
        if files <= 0 or seconds <= 0:
            return
        rate = files / seconds
        with self._lock:
            prev = self._ewma.get(stage_id, 0.0)
            self._ewma[stage_id] = (
                rate if prev == 0.0
                else EWMA_KEEP * prev + (1.0 - EWMA_KEEP) * rate
            )
            self._observed[stage_id] = self._observed.get(stage_id, 0) + files
        from ..telemetry import metrics as _tm

        # inline bounded conditional pins the label domain at the emit
        # site (SD007): the stage registry is the entire vocabulary
        _tm.WORK_STAGE_RATE.set(
            self._ewma[stage_id],
            stage="identify.hash" if stage_id == "identify.hash" else (
                "thumb" if stage_id == "thumb" else (
                    "media.extract" if stage_id == "media.extract" else (
                        "phash" if stage_id == "phash" else (
                            "embed" if stage_id == "embed" else "other")))),
        )

    def rate(self, stage_id: str) -> float:
        """Observed files/s EWMA for a stage — 0.0 until the stage has
        executed anything in this process."""
        with self._lock:
            return self._ewma.get(stage_id, 0.0)

    def snapshot(self) -> dict[str, dict[str, Any]]:
        with self._lock:
            return {
                s: {
                    "files_per_s": round(self._ewma[s], 3),
                    "files_observed": self._observed.get(s, 0),
                }
                for s in self._ewma
            }

    def reset(self) -> None:
        with self._lock:
            self._ewma.clear()
            self._observed.clear()


#: the process-wide rate table (telemetry.reset() clears it)
RATES = StageRates()


def observed_files_per_s(stage_id: str) -> float:
    """Best available throughput estimate for a stage: the shard-
    measured EWMA when one exists, else the telemetry-derived workload
    rate (identify only — the other stages have no pre-shard series
    that reads as files/s), else 0.0."""
    rate = RATES.rate(stage_id)
    if rate > 0:
        return rate
    if stage_id == STAGE_IDENTIFY:
        from . import autotune as _autotune

        return _autotune.observed_files_per_s("identify") or 0.0
    return 0.0


def lease_seconds_for(stage_id: str, n_files: int, rate: float,
                      lease_max_s: float) -> float:
    """Per-stage lease sizing — the WORK board's one seam. ``rate`` is
    the claimer's self-reported files/s for this stage; with none, the
    Controller's per-stage target rate (its lease-sizing output,
    derived from the EWMAs each tick) stands in, and before ANY
    evidence the static default holds — restoring the pre-continuum
    lease law bit-for-bit."""
    from ..p2p import work as _work

    if rate <= 0:
        from . import autotune as _autotune

        rate = _autotune.CONTROLLER.stage_rate(stage_id)
    if rate <= 0:
        rate = _work.DEFAULT_FILES_PER_S
    lease = max(_work.LEASE_MIN_S, n_files / rate * _work.LEASE_SLACK)
    return min(lease, lease_max_s)


def snapshot() -> dict[str, Any]:
    """The continuum's state for ``/mesh`` (rides autotune.snapshot):
    per-stage rates + the registry vocabulary."""
    return {
        "stages": list(STAGES),
        "rates": RATES.snapshot(),
    }


def reset() -> None:
    """Test/bench isolation — clears the per-stage EWMAs AND the
    Controller's derived per-stage lease targets (telemetry.reset()
    calls this; the scheduler's state is registry-like)."""
    RATES.reset()
    from . import autotune as _autotune

    _autotune.CONTROLLER.reset_stage_targets()


__all__ = [
    "RATES",
    "STAGES",
    "STAGE_EMBED",
    "STAGE_IDENTIFY",
    "STAGE_MEDIA",
    "STAGE_PHASH",
    "STAGE_THUMB",
    "StageRates",
    "StageSpec",
    "lease_seconds_for",
    "observed_files_per_s",
    "reset",
    "snapshot",
    "spec",
]
