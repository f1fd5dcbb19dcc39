"""What one `telemetry.span` costs: nanoseconds per enter/exit pair over a
loop, with no profiler session and inside one.

    python tools/span_cost.py [--root <checkout>] [--n 100000]

`--root` measures another checkout's `spacedrive_tpu` (the parent commit
unpacked beside this one), so before and after share a machine. Prints
one JSON line. System calls are dear on the chip machine's sandboxed
kernel (PERF.md finding 1), which is why a span draws its ids from a
counter and not from `os.urandom`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time


def loop(span, n: int, nested: bool) -> float:
    """ns per pair; `nested` opens the spans under one parent, which is
    how they sit on the index path (a child draws one id, a root two)."""
    def run() -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with span("cost.probe"):
                pass
        return (time.perf_counter() - t0) / n * 1e9

    if not nested:
        return run()
    with span("cost.parent"):
        return run()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--n", type=int, default=100_000)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))

    import jax

    from spacedrive_tpu import telemetry
    from spacedrive_tpu.telemetry import trace

    out = {"root": os.path.abspath(args.root), "n": args.n,
           "platform": jax.devices()[0].platform}
    loop(telemetry.span, 1000, False)  # warm: imports, histogram series
    out["off_root_ns"] = loop(telemetry.span, args.n, False)
    out["off_nested_ns"] = loop(telemetry.span, args.n, True)
    t0 = time.perf_counter()
    for _ in range(args.n):
        trace.new_span_id()
    out["span_id_ns"] = (time.perf_counter() - t0) / args.n * 1e9
    with tempfile.TemporaryDirectory() as logdir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            out["on_root_ns"] = loop(telemetry.span, args.n, False)
            out["on_nested_ns"] = loop(telemetry.span, args.n, True)
        finally:
            jax.profiler.stop_trace()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
