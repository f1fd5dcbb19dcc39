"""Pipeline spans — per-stage wall time + byte accounting.

The walk → identify → hash → thumbnail pipeline reports its stage
timings through spans: a context manager (sync AND async — nesting
propagates through ``contextvars``, so concurrent asyncio tasks can't
cross-contaminate parentage) that on exit

- observes ``sd_span_seconds{stage=…}`` and, when bytes were attached,
  ``sd_span_bytes_total{stage=…}``;
- appends one record to the trace ring (``telemetry.trace``): the
  Chrome-trace export reads all of it, ``telemetry.snapshot`` its
  newest ``RECENT_SPANS`` records, so the explorer can show "where did
  the last index pass spend its time" without a scrape pipeline;
- debug-logs through the `utils.tracing` logging tree (target
  ``spacedrive_tpu.telemetry``), honoring SD_LOG filters.

Stages are dotted paths: a span opened inside another records as
``parent.child`` (e.g. ``identify.hash``), keeping label cardinality
proportional to the pipeline's actual shape.

Every span is also a ``jax.profiler.TraceAnnotation`` named
``sd.<dotted path>``: whenever a profiler session is running (the
benchmark's ``--trace 1``, an operator's ``SD_JAX_PROFILE``) the span
lands on a host line of the same ``.xplane.pb`` as the device's ops, on
the profiler's clock, so an idle gap of the chip can be laid against the
span that was open. With no session the annotation is one small object
and a flag read. It is bound on first use and only in a process that has
imported ``jax`` already: this module never imports it
(``parallel/procworker.py`` stays import-light).

Every span also carries distributed-trace identity (``trace_id``/
``span_id``/``parent_id``, see ``telemetry.trace``): a nested span
inherits its parent's trace; a root span adopts the ambient
``trace.current()`` context installed by a boundary (task dispatch, job
resume, a P2P header) or mints a fresh trace. Spans slower than
``events.SLOW_OP_SECONDS`` fire the slow-op watchdog ring.
"""

from __future__ import annotations

import contextvars
import logging
import sys
import time
from typing import Any

from . import events as _events
from . import metrics
from . import trace as _trace

logger = logging.getLogger(__name__)

RECENT_SPANS = 256  # how many of the trace ring's records a snapshot shows

_current: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "sd_current_span", default=None
)

#: prefix of every span's name on the profiler's host lines
ANNOTATION_PREFIX = "sd."
_annotation_cls: Any = None


def _bind_annotation() -> Any:
    """`jax.profiler.TraceAnnotation`, once `jax` is in `sys.modules`
    (and past its own import); None until then."""
    global _annotation_cls
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    cls = getattr(profiler, "TraceAnnotation", None)
    if cls is not None:
        _annotation_cls = cls
    return cls


class Span:
    """One timed pipeline stage. Use via ``span(...)``:

        with span("identify.hash", nbytes=len(batch)):
            ...
        async with span("walk"):
            ...
    """

    __slots__ = (
        "stage", "nbytes", "path", "_t0", "_t0_wall", "_token",
        "_trace_token", "duration", "trace_id", "span_id", "parent_id",
        "fields", "_annotation", "_outer", "_ctx",
    )

    def __init__(self, stage: str, nbytes: int = 0):
        self.stage = stage
        self.nbytes = int(nbytes)
        self.fields: dict[str, Any] | None = None
        self.path = stage  # parent-prefixed on enter
        self._t0 = 0.0
        self._t0_wall = 0.0
        self._token: contextvars.Token | None = None
        self._trace_token: contextvars.Token | None = None
        self.duration: float | None = None
        self.trace_id: str = ""
        self.span_id: str = ""
        self.parent_id: str | None = None
        self._annotation: Any = None
        self._outer: "Span | None" = None  # the live span this one opened under
        self._ctx: _trace.TraceContext | None = None

    def add_bytes(self, n: int) -> None:
        """Attribute more bytes mid-span (e.g. per-file in a loop)."""
        self.nbytes += int(n)

    def annotate(self, **fields: Any) -> None:
        """Attach small scalar fields to the span record (ring + trace
        export) — e.g. the index-journal verdict counts of an identify
        window. Keep values to scalars; this is NOT a payload channel."""
        if self.fields is None:
            self.fields = {}
        self.fields.update(fields)

    # -- sync protocol --

    def __enter__(self) -> "Span":
        parent = _current.get()
        while parent is not None and parent.duration is not None:
            # a task started inside a span keeps a copy of its context
            # after the span has ended (the actors `Node.start` spawns);
            # a span whose time is over is nobody's parent, and hands
            # back to the span it opened under
            parent = parent._outer
        self._outer = parent
        self.duration = None
        ambient = _trace.current()
        if parent is not None:
            self.path = f"{parent.path}.{self.stage}"
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
        elif ambient is not None:
            # no enclosing span: join the ambient trace context a
            # boundary installed (dispatch, resume, wire)
            self.trace_id = ambient.trace_id
            self.parent_id = ambient.span_id
        else:
            self.trace_id = _trace.new_trace_id()
        self.span_id = _trace.new_span_id()
        self._token = _current.set(self)
        self._ctx = _trace.TraceContext(self.trace_id, self.span_id,
                                        outer=ambient)
        self._trace_token = _trace.set_current(self._ctx)
        cls = _annotation_cls or _bind_annotation()
        if cls is not None:
            try:
                self._annotation = cls(ANNOTATION_PREFIX + self.path)
                self._annotation.__enter__()
            except Exception:  # noqa: BLE001 - a profiler fault never fails work
                self._annotation = None
        self._t0_wall = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.duration = time.perf_counter() - self._t0
        if self._annotation is not None:
            try:
                self._annotation.__exit__(None, None, None)
            except Exception:  # noqa: BLE001
                pass
            self._annotation = None
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        if self._trace_token is not None:
            _trace.reset_current(self._trace_token)
            self._trace_token = None
        if self._ctx is not None:
            self._ctx.closed = True  # copies of this context read past it
            self._ctx = None
        metrics.SPAN_SECONDS.observe(self.duration, stage=self.path)
        if self.nbytes:
            metrics.SPAN_BYTES.inc(self.nbytes, stage=self.path)
        rec = {
            "stage": self.path,
            "seconds": self.duration,
            "bytes": self.nbytes,
            "error": exc_type.__name__ if exc_type is not None else None,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "t0": self._t0_wall,
        }
        if self.fields:
            rec["fields"] = dict(self.fields)
        _trace.record_span(rec)
        if self.duration >= _events.SLOW_OP_SECONDS:
            _events.watchdog_slow_op(self.path, self.duration)
        logger.debug("span %s: %.3fms%s", self.path, self.duration * 1e3,
                     f" {self.nbytes}B" if self.nbytes else "")

    # -- async protocol (same semantics; contextvars carry across await) --

    async def __aenter__(self) -> "Span":
        return self.__enter__()

    async def __aexit__(self, exc_type, exc, tb) -> None:
        self.__exit__(exc_type, exc, tb)


def span(stage: str, nbytes: int = 0) -> Span:
    return Span(stage, nbytes)


def current_span() -> Span | None:
    return _current.get()


def recent_spans() -> list[dict[str, Any]]:
    """Most-recent-last completed spans: the newest `RECENT_SPANS`
    records of the trace ring."""
    return _trace.recent()[-RECENT_SPANS:]


def clear_recent() -> None:
    """Clears the trace ring: spans have no other."""
    _trace.clear()
