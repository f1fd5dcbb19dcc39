"""Optional ``jax.profiler`` session around a whole job chain.

When ``SD_JAX_PROFILE=<logdir>`` is set, the job manager takes a hold
on one profiler session when it dispatches a job and releases it when
the job has settled and its successors are dispatched, so an index pass
(indexer → file identifier → media processor) is ONE ``.xplane.pb``:
the device's ops and transfers on the device planes and every
``telemetry.span`` of the pass as ``sd.<dotted path>`` on the host
lines of the same file, on the same clock (``spans.py`` opens a
``TraceAnnotation`` per span). The Python function tracer is off: the
spans name the host side, and a whole pass of function calls would be
gigabytes. Everything here is no-op-safe: unset env, a missing/CPU-only
jax, or a profiler that refuses to start all degrade to "no profile",
never to a failed job. Start/stop is refcounted so overlapping drivers
(a chain + a watcher rescan) share one session instead of crashing on
double-start.
"""

from __future__ import annotations

import logging
import os
import threading

logger = logging.getLogger(__name__)

ENV_VAR = "SD_JAX_PROFILE"

_lock = threading.Lock()
_depth = 0
_active_dir: str | None = None


def profile_start(tag: str = "pipeline") -> bool:
    """Begin (or join) a device profile session. Returns True when a
    session is active after the call."""
    global _depth, _active_dir
    logdir = os.environ.get(ENV_VAR)
    if not logdir:
        return False
    with _lock:
        if _depth > 0:
            _depth += 1
            return True
        try:
            import jax

            make_options = getattr(jax.profiler, "ProfileOptions", None)
            if make_options is None:
                jax.profiler.start_trace(os.path.join(logdir, tag))
            else:
                options = make_options()
                options.python_tracer_level = 0
                jax.profiler.start_trace(os.path.join(logdir, tag),
                                         profiler_options=options)
        except Exception as e:  # noqa: BLE001 - profiling is best-effort
            logger.debug("jax profiler start failed: %s", e)
            return False
        _depth = 1
        _active_dir = logdir
        logger.info("jax profiler tracing into %s", logdir)
        return True


def profile_stop() -> None:
    """Release one hold on the session; the last release stops it."""
    global _depth, _active_dir
    with _lock:
        if _depth == 0:
            return
        _depth -= 1
        if _depth > 0:
            return
        _active_dir = None
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - profiling is best-effort
            logger.debug("jax profiler stop failed: %s", e)


def profiling_active() -> bool:
    with _lock:
        return _depth > 0
