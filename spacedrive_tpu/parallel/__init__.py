"""Parallel execution plane: meshes, shardings, host→device feeding.

SURVEY §2.4 — the reference's worker-per-core/work-stealing parallelism
maps to batch-parallel device meshes here; §7 hard part #2 — the
host-side read pipeline that keeps the device fed.
"""

from . import autotune
from .feeder import PipelineStats, WindowPipeline, pipeline_depth
from .mesh import (
    AXES,
    accelerator_count,
    batch_sharding,
    dispatch_devices,
    factor3,
    flat_mesh,
    make_mesh,
    multihost_init,
    pad_to_multiple,
    replicated,
)

__all__ = [
    "AXES",
    "PipelineStats",
    "autotune",
    "WindowPipeline",
    "accelerator_count",
    "batch_sharding",
    "dispatch_devices",
    "factor3",
    "flat_mesh",
    "make_mesh",
    "multihost_init",
    "pad_to_multiple",
    "pipeline_depth",
    "replicated",
]
