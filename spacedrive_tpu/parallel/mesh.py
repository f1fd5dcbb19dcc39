"""Device-mesh construction + canonical shardings.

SURVEY §2.4: the reference's parallelism (worker-per-core task system,
NCCL-free QUIC mesh) maps onto TPU primitives as batch-parallel
`shard_map`/`pjit` over a `jax.sharding.Mesh`. This module owns the
canonical axis vocabulary — `dp` (batch), `fsdp` (param shards), `tp`
(tensor) — and the helpers every call site shares, so meshes are built
one way everywhere (`__graft_entry__.dryrun_multichip` exercises the
same factoring).

Multi-host: `multihost_init()` wraps `jax.distributed.initialize` —
inside a pod/slice collectives ride ICI; across hosts, DCN. Library
metadata sync stays on the host-side CRDT/P2P plane (§5), never on
device collectives.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Any, Sequence

import numpy as np

AXES = ("dp", "fsdp", "tp")


def factor3(n: int) -> tuple[int, int, int]:
    """n devices → (dp, fsdp, tp), preferring tp=2 then fsdp=2 (the
    same factoring the driver dry-runs)."""
    tp = 2 if n % 2 == 0 else 1
    rem = n // tp
    fsdp = 2 if rem % 2 == 0 else 1
    return rem // fsdp, fsdp, tp


def make_mesh(
    devices: Sequence[Any] | None = None,
    shape: tuple[int, int, int] | None = None,
):
    """Standard 3-axis mesh over the available devices."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    dp, fsdp, tp = shape or factor3(len(devices))
    count = dp * fsdp * tp
    return Mesh(np.array(devices[:count]).reshape(dp, fsdp, tp), AXES)


def flat_mesh(devices: Sequence[Any] | None = None):
    """One-axis `dp` mesh — batch-parallel work (hashing, pHash rows)."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), ("dp",))


def batch_sharding(mesh: Any, *, all_axes: bool = False):
    """NamedSharding splitting dim 0 over dp (or every axis)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(tuple(mesh.axis_names)) if all_axes else P(mesh.axis_names[0])
    return NamedSharding(mesh, spec)


def replicated(mesh: Any):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def pad_to_multiple(arr: np.ndarray, multiple: int) -> tuple[np.ndarray, int]:
    """Pad dim 0 so sharded batches divide evenly; returns (arr, pad)."""
    pad = (-arr.shape[0]) % multiple
    if pad:
        arr = np.concatenate(
            [arr, np.zeros((pad, *arr.shape[1:]), arr.dtype)]
        )
    return arr, pad


_ACCEL_COUNT: list[int] | None = None


def accelerator_count() -> int:
    """Local non-CPU device count, 1 when only CPU is live.

    The batch/depth scale factor for dp dispatch: the identifier's
    chunk size, the thumbnailer's device chunk, and the feeder depth
    all multiply by this so one host window feeds the whole mesh.
    Virtual host-platform devices deliberately do NOT count — they
    share the same cores, so scaling host batches by them only makes
    batches slower. A backend that cannot initialise (a chip held by
    another process) raises: it must fail the start, not read as
    "CPU only"."""
    global _ACCEL_COUNT
    if _ACCEL_COUNT is None:
        import jax

        devs = jax.devices()
        _ACCEL_COUNT = [len(devs) if devs[0].platform != "cpu" else 1]
    return _ACCEL_COUNT[0]


def dispatch_devices() -> list:
    """All local JAX devices for dp-sharded dispatch. Unlike
    `accelerator_count`, virtual CPU devices DO appear here — sharding
    is a correctness surface the test suite exercises on the forced
    host platform. Raises when the backend cannot initialise."""
    import jax

    return list(jax.devices())


# --- graceful degradation ladder (utils/resilience + utils/faults) ---------

LEVEL_MESH = 0      # full dp mesh — every local device
LEVEL_SUBSET = 1    # surviving chip subset (per-device probe survivors)
LEVEL_HOST = 2      # host reference path — no device dispatch at all


class DeviceLadder:
    """Demotion ladder for device dispatch: all chips → surviving chip
    subset → host reference path — a failed batch degrades instead of
    failing the job.

    Callers take ``(devices, level)`` from :meth:`filter` and report
    the dispatch outcome back via :meth:`record_success` /
    :meth:`record_failure`. Demotion probes each device individually
    (one tiny transfer+readback, routed through the ``device.probe``
    fault point so chaos tests pick which chips "die") and keeps the
    survivors. After ``reset_timeout`` the ladder hands out ONE
    half-open probe dispatch at the next level up; its success re-arms
    (promotes), its failure restarts the clock — the same breaker
    discipline as ``utils.resilience.CircuitBreaker``, but over ladder
    rungs instead of a binary gate.

    Every transition updates ``sd_device_demotion_level`` and lands on
    the ``resilience`` flight ring, so a node quietly hashing on one
    chip (or on the CPU) is visible from /metrics, /health, and /mesh.
    """

    def __init__(self, reset_timeout: float = 30.0):
        self.reset_timeout = reset_timeout
        self._level = LEVEL_MESH
        self._subset_ids: frozenset | None = None
        self._demoted_at = 0.0
        self._probe_inflight = False
        self._probe_started = 0.0
        self._lock = threading.Lock()

    def reset(self) -> None:
        with self._lock:
            self._level = LEVEL_MESH
            self._subset_ids = None
            self._probe_inflight = False
        self._set_gauge(LEVEL_MESH)

    @property
    def level(self) -> int:
        return self._level

    @staticmethod
    def _set_gauge(level: int) -> None:
        from ..telemetry import metrics as _tm

        _tm.DEVICE_DEMOTION.set(float(level))

    def _probe_device(self, index: int, dev: Any) -> bool:
        from ..utils import faults as _faults

        if _faults.hit("device.probe", arg=str(index)) is not None:
            return False
        try:
            import jax

            back = np.asarray(jax.device_put(np.arange(4, dtype=np.int32), dev))
            return bool((back == np.arange(4)).all())
        except Exception:  # noqa: BLE001 - a dead chip raises anything
            return False

    def _survivors(self, devices: Sequence[Any]) -> list[Any]:
        return [
            d for i, d in enumerate(devices) if self._probe_device(i, d)
        ]

    def filter(self, devices: Sequence[Any]) -> tuple[list[Any], int]:
        """The device set + ladder level for the next dispatch. An
        empty list means the host path. When a demoted ladder's reset
        timeout has elapsed, ONE caller gets the promoted level as a
        half-open probe (it must report the outcome)."""
        devices = list(devices)
        now = time.monotonic()
        with self._lock:
            level = self._level
            if (
                level > LEVEL_MESH
                # an in-flight probe older than the reset window was
                # abandoned (its dispatch died without reporting) —
                # don't let it wedge re-arming forever
                and (not self._probe_inflight
                     or now - self._probe_started >= self.reset_timeout)
                and now - self._demoted_at >= self.reset_timeout
            ):
                level -= 1
                self._probe_inflight = True
                self._probe_started = now
            subset_ids = self._subset_ids
        if level == LEVEL_MESH:
            return devices, level
        if level == LEVEL_HOST:
            return [], level
        if subset_ids:
            subset = [d for d in devices if d.id in subset_ids]
        else:
            subset = self._survivors(devices)
            if subset:
                # cache the sweep (e.g. after a HOST→SUBSET re-arm left
                # no subset) — probing every device is a blocking
                # round-trip per chip and must not run per dispatch
                with self._lock:
                    if self._subset_ids is None:
                        self._subset_ids = frozenset(d.id for d in subset)
        return (subset or devices[:1]), level

    def record_success(self, level: int) -> None:
        """A dispatch at ``level`` completed — a half-open probe's
        success promotes (re-arms) the ladder to that level. Only the
        probe holder (level below current) touches probe bookkeeping:
        a concurrent same-level dispatch reporting in must not clear an
        in-flight probe it does not own."""
        from ..telemetry.events import RESILIENCE_EVENTS

        with self._lock:
            if level >= self._level:
                return
            self._probe_inflight = False
            self._level = level
            if level == LEVEL_MESH:
                self._subset_ids = None
        self._set_gauge(level)
        RESILIENCE_EVENTS.emit("device_promote", level=level)

    def probe_inconclusive(self, level: int) -> None:
        """A dispatch holding the half-open probe finished WITHOUT
        actually exercising the rung's devices (e.g. a tail batch too
        small to shard ran on the single default device) — release the
        probe slot without promoting, so the next real dispatch gets
        the probe instead of a false re-arm."""
        with self._lock:
            if level < self._level:
                self._probe_inflight = False

    def record_failure(self, level: int, devices: Sequence[Any]) -> int:
        """A dispatch at ``level`` failed — demote one rung (probing
        for survivors when leaving the full mesh) and return the new
        level."""
        from ..telemetry.events import RESILIENCE_EVENTS

        devices = list(devices)
        if level == LEVEL_MESH and len(devices) > 1:
            survivors = self._survivors(devices)
            next_level = LEVEL_SUBSET if survivors else LEVEL_HOST
            subset = frozenset(d.id for d in survivors)
        else:
            next_level = LEVEL_HOST
            subset = None
        with self._lock:
            if level < self._level:
                self._probe_inflight = False  # the probe itself failed
            if next_level <= self._level:
                # another dispatch already demoted at least this far;
                # just restart the re-arm clock
                self._demoted_at = time.monotonic()
                return self._level
            self._level = next_level
            self._subset_ids = subset
            self._demoted_at = time.monotonic()
        self._set_gauge(next_level)
        RESILIENCE_EVENTS.emit(
            "device_demote",
            level=next_level,
            survivors=len(subset) if subset is not None else 0,
            failed_level=level,
        )
        return next_level


#: the process-wide ladder every auto-policy dispatch consults
LADDER = DeviceLadder()


def ladder_devices() -> tuple[list[Any], int]:
    """``dispatch_devices()`` filtered through the degradation ladder:
    (devices, level) — an empty list (``LEVEL_HOST``) means use the
    host reference path. Callers MUST report the dispatch outcome back to ``LADDER``
    so demotion/re-arm bookkeeping stays truthful."""
    return LADDER.filter(dispatch_devices())


def multihost_init(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Join a multi-host JAX cluster (ref role: the NCCL/MPI backend of
    a conventional stack). No-ops when the env provides no cluster —
    single-host keeps working untouched."""
    import jax

    coordinator_address = coordinator_address or os.environ.get(
        "SD_COORDINATOR_ADDRESS"
    )
    if coordinator_address is None and num_processes is None:
        env = os.environ
        if not any(k in env for k in ("JAX_COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS")):
            return False
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        return True
    except Exception:
        return False
