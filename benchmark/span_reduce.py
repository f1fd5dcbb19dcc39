"""The program's spans beside the device's busy time, from one trace.

`telemetry.span` opens a `jax.profiler.TraceAnnotation` named
`sd.<dotted path>` for every span, so a traced run's `.xplane.pb` holds
the program's stages on its host lines, on the same clock as the device
planes' ops. `trace_reduce.py` keeps the device side and names an idle
gap by the job whose report covers it; this file keeps the `sd.*` events
too and names every idle gap by the **leaf span** that was open: of the
spans open at an instant, on any thread, those with no open descendant
(a path that extends theirs by a dot). A parent counts only where none
of its children is open, two threads may both cover a gap (as two jobs
may in `trace_reduce`), and the seconds of a gap that no span covers are
`unspanned`: the part of the host's time the program has no name for.

For a metric reader:

    from benchmark import span_reduce
    spans = span_reduce.for_run(ctx)      # parsed once per run, None
    if spans: ...                         # where there is nothing to read
    spans["idle_s"], spans["unspanned_s"]
    span_reduce.matching(spans["spans"], "db.txn")   # by last components

`for_run` finds the trace of the run (`harness.py` moves it to
`<work>/last.xplane.pb` before the readers run), reduces it, prints the
table below to stderr once, and caches the result on `ctx`. A trace with
no `sd.*` event (a program from before the spans) gives None.
`reduce_planes` takes plain lists, like `trace_reduce.reduce_planes`, so
it is tested without a chip. Counters of the same spans
(`sd_span_seconds{stage=<path>}`) are in `ctx["counters"]`; `counter`
sums those whose path ends in the components asked for, so a parent span
added later does not blind a reader.
"""

from __future__ import annotations

import os
import sys

from .trace_reduce import WINDOW_ANNOTATION, merged

SPAN_PREFIX = "sd."
UNSPANNED = "(no span)"
#: device lines whose events are the chip's busy time, first that exists
BUSY_LINES = ("XLA Ops", "XLA Modules")
LONGEST_GAPS = 3
UNSPANNED_ROWS = 8
_CACHE_KEY = "_span_reduce"


def ends_with(path: str, suffix: str) -> bool:
    """`a.b.c` ends with `b.c` and with `c`, not with `.c` of `xc`."""
    return path == suffix or path.endswith("." + suffix)


def matching(table: dict, suffix: str) -> dict:
    return {p: v for p, v in table.items() if ends_with(p, suffix)}


def counter(counters: dict, suffix: str, what: str = "sum") -> float | None:
    """Summed `sd_span_seconds{stage=<path>}.<what>` over the paths that
    end in `suffix`; None where the program has no such span."""
    head, tail = "sd_span_seconds{stage=", "}." + what
    hits = [v for k, v in counters.items()
            if k.startswith(head) and k.endswith(tail)
            and ends_with(k[len(head):-len(tail)], suffix)]
    return sum(hits) if hits else None


def leaves(open_paths) -> list[str]:
    """Of the paths open at one instant, those with no open descendant."""
    paths = list(open_paths)
    return [p for p in paths
            if not any(q.startswith(p + ".") for q in paths)]


def reduce_planes(planes) -> dict | None:
    """`planes`: [(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])], host planes with their `sd.*` events. → window_s,
    idle_s, unspanned_s, per span path {calls, seconds, idle_s,
    longest_gap_s}, and the longest gaps with what covered them; None
    where the trace holds no device plane or no `sd.*` event."""
    window = None
    spans: list[tuple[str, float, float]] = []
    busy: list[tuple[float, float]] = []
    device_seen = False
    for plane, lines in planes:
        on_device = plane.startswith("/device:")
        if on_device and not plane.startswith("/device:TPU:"):
            continue
        by_name = dict(lines)
        if on_device:
            device_seen = True
            line = next((n for n in BUSY_LINES if by_name.get(n)), None)
            busy += [(s / 1e9, (s + d) / 1e9)
                     for _n, s, d in by_name.get(line, [])]
            continue
        for _line, events in lines:
            for name, start, dur in events:
                if name == WINDOW_ANNOTATION:
                    window = (start / 1e9, (start + dur) / 1e9)
                elif name.startswith(SPAN_PREFIX):
                    spans.append((name[len(SPAN_PREFIX):], start / 1e9,
                                  (start + dur) / 1e9))
    if not device_seen or not spans:
        return None
    if window is None:
        if not busy:
            return None
        window = (min(s for s, _e in busy), max(e for _s, e in busy))
    w0, w1 = window

    table: dict[str, dict[str, float]] = {}
    edges: list[tuple[float, int, str]] = []  # (time, +1 open / -1 close, path)
    for path, s, e in spans:
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        row = table.setdefault(path, {"calls": 0, "seconds": 0.0,
                                      "idle_s": 0.0, "longest_gap_s": 0.0})
        row["calls"] += 1
        row["seconds"] += e - s
        edges.append((s, 1, path))
        edges.append((e, -1, path))
    edges.sort(key=lambda t: (t[0], t[1]))  # at one instant: close, then open

    gaps, at = [], w0
    for s, e in merged([(max(s, w0), min(e, w1)) for s, e in busy
                        if min(e, w1) > max(s, w0)]):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if w1 > at:
        gaps.append((at, w1))

    open_count: dict[str, int] = {}
    covering: list[str] = []
    unspanned = {"idle_s": 0.0, "longest_gap_s": 0.0}
    # where the uncovered seconds lie: by the span that closed last
    # before the stretch and the one that opens next after it
    between: dict[tuple[str, str], list[float]] = {}
    gap_rows = []
    i, n = 0, len(edges)
    for g0, g1 in gaps:
        inside: dict[str, float] = {}  # this gap's seconds by leaf

        def put(seconds: float) -> None:
            if seconds <= 0:
                return
            for label in covering or (UNSPANNED,):
                inside[label] = inside.get(label, 0.0) + seconds
            if not covering:
                slot = between.setdefault(
                    (edges[i - 1][2] if i else "window opens",
                     edges[i][2] if i < n else "window closes"),
                    [0.0, 0, 0.0])
                slot[0] += seconds
                slot[1] += 1
                slot[2] = max(slot[2], seconds)

        at = g0
        while True:
            # every edge up to `at` is applied; the next one ends a piece
            moved = i
            while i < n and edges[i][0] <= at:
                _t, step, path = edges[i]
                count = open_count.get(path, 0) + step
                if count:
                    open_count[path] = count
                else:
                    open_count.pop(path, None)
                i += 1
            if i != moved:
                covering = leaves(open_count)
            nxt = edges[i][0] if i < n else g1
            if nxt >= g1:
                put(g1 - at)
                break
            put(nxt - at)
            at = nxt
        for label, seconds in inside.items():
            row = unspanned if label == UNSPANNED else table[label]
            row["idle_s"] += seconds
            row["longest_gap_s"] = max(row["longest_gap_s"], seconds)
        gap_rows.append((g1 - g0, g0 - w0, inside))

    idle_s = sum(g1 - g0 for g0, g1 in gaps)
    gap_rows.sort(key=lambda r: -r[0])
    return {
        "window_s": w1 - w0, "idle_s": idle_s, "gaps": len(gaps),
        "unspanned_s": unspanned["idle_s"],
        "unspanned_longest_gap_s": unspanned["longest_gap_s"],
        "spans": table,
        "unspanned_between": [
            {"after": after, "before": before, "seconds": v[0],
             "pieces": v[1], "longest_s": v[2]}
            for (after, before), v in sorted(
                between.items(), key=lambda kv: -kv[1][0])[:UNSPANNED_ROWS]],
        "longest_gaps": [
            {"seconds": length, "at_s": start,
             "by": sorted(inside.items(), key=lambda kv: -kv[1])[:4]}
            for length, start, inside in gap_rows[:LONGEST_GAPS]],
    }


def read_planes(path: str) -> list:
    """The planes of an .xplane.pb as plain lists: the device's busy
    lines, and of the host planes the `sd.*` spans and the window."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if on_device:
                if line.name not in BUSY_LINES:
                    continue
                events = [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events]
            else:
                events = [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)
                          or e.name == WINDOW_ANNOTATION]
            if events:
                lines.append((line.name, events))
        if lines:
            planes.append((plane.name, lines))
    return planes


def table_lines(reduced: dict) -> list[str]:
    """span, calls, summed seconds, idle seconds covered as a leaf,
    longest gap covered; then where the uncovered seconds lie, and the
    longest gaps with what covered them."""
    rows = sorted(reduced["spans"].items(), key=lambda kv: -kv[1]["idle_s"])
    width = max([len(p) for p, _v in rows] + [len(UNSPANNED)])
    idle = reduced["idle_s"] or 1.0
    out = [f"spans: window {reduced['window_s']:.3f} s, idle "
           f"{reduced['idle_s']:.3f} s in {reduced['gaps']} gaps, no span "
           f"covers {reduced['unspanned_s']:.3f} s "
           f"({100.0 * reduced['unspanned_s'] / idle:.2f} %)",
           f"  {'span':<{width}} {'calls':>7} {'seconds':>10} "
           f"{'idle as leaf':>12} {'longest gap':>11}"]
    for path, v in rows:
        out.append(f"  {path:<{width}} {v['calls']:>7d} {v['seconds']:>10.3f} "
                   f"{v['idle_s']:>12.3f} {v['longest_gap_s']:>11.3f}")
    out.append(f"  {UNSPANNED:<{width}} {'':>7} {'':>10} "
               f"{reduced['unspanned_s']:>12.3f} "
               f"{reduced['unspanned_longest_gap_s']:>11.3f}")
    for row in reduced["unspanned_between"]:
        out.append(f"  no span for {row['seconds']:.3f} s in {row['pieces']} "
                   f"pieces (longest {row['longest_s']:.3f}) after "
                   f"{row['after']} and before {row['before']}")
    for gap in reduced["longest_gaps"]:
        by = ", ".join(f"{label} {s:.3f}" for label, s in gap["by"])
        out.append(f"  gap of {gap['seconds']:.3f} s at {gap['at_s']:.3f} s: {by}")
    return out


def trace_path(ctx: dict) -> str | None:
    """Where the run's trace is now: `harness.py` has moved it from
    `ctx["trace"]["path"]` to `last.xplane.pb` in its work directory,
    which is the directory above the run's."""
    old = (ctx.get("trace") or {}).get("path")
    if not old:
        return None
    if os.path.isfile(old):
        return old
    at = os.path.dirname(old)
    while at and at != os.path.dirname(at):
        moved = os.path.join(at, "last.xplane.pb")
        if os.path.isfile(moved):
            return moved
        at = os.path.dirname(at)
    return None


def for_run(ctx: dict) -> dict | None:
    """The reduction of this run's trace, made once and kept on `ctx`;
    None without a trace or without spans in it. Never raises: a reader
    that finds nothing reports nothing."""
    if _CACHE_KEY not in ctx:
        reduced = None
        try:
            path = trace_path(ctx)
            if path is not None:
                reduced = reduce_planes(read_planes(path))
        except Exception as exc:  # noqa: BLE001
            print(f"span_reduce: trace not read: {exc!r}", file=sys.stderr)
        if reduced is not None:
            print("\n".join(table_lines(reduced)), file=sys.stderr, flush=True)
        ctx[_CACHE_KEY] = reduced
    return ctx[_CACHE_KEY]
