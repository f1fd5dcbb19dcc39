"""From a profiler trace to numbers: the device's busy union, its idle
gaps by the job that was running, op and module times under the names
the trace prints. Read with `jax.profiler.ProfileData` alone.

The harness wraps the measured window in a `bench.window` annotation;
everything is clipped to it, and it maps the host's wall clock (job
reports) onto the trace's clock.
"""

from __future__ import annotations

import glob
import os
from datetime import datetime

WINDOW_ANNOTATION = "bench.window"
JOBS = ("indexer", "file_identifier", "media_processor")
NAME_CHARS = 96


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def merged(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def job_intervals(passes: list[dict], wall_open: float) -> list[tuple]:
    """[(job name, start, end)] in seconds since the window opened, from
    the job reports in every timed pass's library database."""
    from .check import library_db

    out = []
    for data_dir in sorted({p["data_dir"] for p in passes}):
        db = library_db(data_dir)
        try:
            for job, started, completed in db.execute(
                    "SELECT name, date_started, date_completed FROM job "
                    "WHERE date_started IS NOT NULL AND "
                    "date_completed IS NOT NULL"):
                s = datetime.fromisoformat(started).timestamp() - wall_open
                e = datetime.fromisoformat(completed).timestamp() - wall_open
                if e > 0:
                    out.append((job, s, e))
        finally:
            db.close()
    return out


def attribute_gaps(gaps: list[tuple[float, float]], jobs: list[tuple]) -> dict:
    """{label: [summed idle seconds, longest single piece]}; what no job
    covers is `between_jobs`."""
    out: dict[str, list[float]] = {}

    def put(label: str, seconds: float) -> None:
        if seconds <= 0:
            return
        slot = out.setdefault(label, [0.0, 0.0])
        slot[0] += seconds
        slot[1] = max(slot[1], seconds)

    for g0, g1 in gaps:
        covered = []
        for job, s, e in jobs:
            lo, hi = max(g0, s), min(g1, e)
            if hi > lo:
                put(job, hi - lo)
                covered.append((lo, hi))
        at = g0
        for lo, hi in merged(covered):
            put("between_jobs", lo - at)
            at = max(at, hi)
        put("between_jobs", g1 - at)
    return out


def reduce_planes(planes, jobs: list[tuple], kernels: dict) -> dict:
    """`planes`: [(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])]. → busy_s, window_s, ops, modules, kernel seconds
    and dispatches, idle gaps, breakdown."""
    window = None
    for _plane, lines in planes:
        for _line, events in lines:
            for name, start, dur in events:
                if name == WINDOW_ANNOTATION:
                    window = (start / 1e9, (start + dur) / 1e9)
    device = [(p, lines) for p, lines in planes if p.startswith("/device:TPU:")]
    if not device:
        raise ValueError("the trace holds no /device:TPU: plane: nothing "
                         "ran on a TPU while it was taken")
    if window is None:
        starts = [s for _p, lines in device for _l, evs in lines
                  for _n, s, _d in evs]
        ends = [s + d for _p, lines in device for _l, evs in lines
                for _n, s, d in evs]
        window = (min(starts) / 1e9, max(ends) / 1e9)
    w0, w1 = window

    def clipped(events):
        for name, start, dur in events:
            s, e = max(w0, start / 1e9), min(w1, (start + dur) / 1e9)
            if e > s:
                yield name, s, e

    ops: dict[str, list[float]] = {}
    modules: dict[str, list[float]] = {}
    busy = []
    busy_intervals: list[tuple[float, float]] = []
    for _plane, lines in device:
        by_name = {line: events for line, events in lines}
        op_events = list(clipped(by_name.get("XLA Ops", [])))
        mod_events = list(clipped(by_name.get("XLA Modules", [])))
        for name, s, e in op_events:
            slot = ops.setdefault(name, [0, 0.0])
            slot[0] += 1
            slot[1] += e - s
        for name, s, e in mod_events:
            slot = modules.setdefault(name.split("(")[0], [0, 0.0])
            slot[0] += 1
            slot[1] += e - s
        spans = [(s, e) for _n, s, e in (op_events or mod_events)]
        busy.append(union_seconds(spans))
        busy_intervals += spans
    busy_s = sum(busy) / len(busy)

    # idle: where no chip ran anything, inside the window
    gaps, at = [], w0
    for s, e in merged(busy_intervals):
        if s > at:
            gaps.append((at - w0, s - w0))
        at = max(at, e)
    if w1 > at:
        gaps.append((at - w0, w1 - w0))
    idle = attribute_gaps(gaps, jobs)

    kernel = {}
    for kind, prefixes in kernels.items():
        if not isinstance(prefixes, list):
            continue
        hits = [v for name, v in modules.items()
                if any(name.startswith(p) for p in prefixes)]
        kernel[kind] = {"dispatches": sum(v[0] for v in hits),
                        "seconds": sum(v[1] for v in hits)}
    top = sorted(ops.items(), key=lambda kv: -kv[1][1])[:10]
    order = [*JOBS, "between_jobs"]
    idle_rows = [[label, idle[label][0]] for label in order if label in idle]
    idle_rows += [[f"{label}.longest_gap", idle[label][1]]
                  for label in order if label in idle]
    return {
        "busy_s": busy_s, "window_s": w1 - w0, "chips": len(device),
        "ops": ops, "modules": modules, "kernels": kernel, "idle": idle,
        "breakdown": {
            "device_ops": [[name[:NAME_CHARS], v[1]] for name, v in top],
            "idle_gaps": idle_rows[:10],
        },
    }


def read_planes(path: str) -> list:
    """The planes of an .xplane.pb as plain lists; of host planes only
    the window annotation is kept."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                      if on_device or e.name == WINDOW_ANNOTATION]
            if events:
                lines.append((line.name, events))
        if lines:
            planes.append((plane.name, lines))
    return planes


def reduce_file(path: str, jobs: list[tuple], kernels: dict) -> dict:
    return reduce_planes(read_planes(path), jobs, kernels)


def reduce_dir(trace_dir: str, jobs: list[tuple], kernels: dict) -> dict:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return {**reduce_file(paths[-1], jobs, kernels), "path": paths[-1]}
