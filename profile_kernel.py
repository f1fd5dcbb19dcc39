"""Device profile of the BLAKE3 cas_id kernel.

Captures a jax.profiler trace of the production `hash_batch` path on the
chip and reports ON-DEVICE op timings: the XLA Ops lane in the trace is
stamped by the device clock, so host scheduling stays out of them.

Per batch size it reports:
  module_ms   — whole jitted hash program, per dispatch
  kernel_ms   — the Pallas chunk-stage custom call (incl. its in-VMEM
                transpose)
  other_ms    — everything else (output transpose, tree reduce, masks)
  gbps        — message bytes / module time
  files_per_s — batch rows / module time
  intops      — implied sustained int32 VPU ops/s (OPS_PER_BYTE model)

The int-op model: one 64-byte block = 7 rounds x 8 G; each G is 6 adds,
4 xors and 4 rotates (shift+shift+or = 3 ops each) = 22 vector ops, so
1232 ops/block + ~16 finalize ops -> 19.5 int32 ops per message byte.
Rotates may lower to fewer ops on hardware with funnel shifts; the model
is an upper bound on work, hence a LOWER bound when used to infer
utilization headroom.

Usage (on the chip, one process): python profile_kernel.py
Writes PROFILE.json (not committed — a record of one rig on one day).
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import sys
import tempfile
import time

import numpy as np

OPS_PER_BYTE = 19.5  # see module docstring
BATCH_SIZES = (512, 1024, 2048, 4096, 8192)
CHAIN = 4


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_trace(trace_dir: str) -> tuple[dict, dict]:
    """(modules, ops): name -> [count, total_us] from the device lanes."""
    path = sorted(glob.glob(
        os.path.join(trace_dir, "plugins/profile/*/*.trace.json.gz")
    ))[-1]
    with gzip.open(path) as f:
        d = json.load(f)
    evs = d.get("traceEvents", [])
    # device pid: the one whose process_name mentions TPU
    dev_pids = {
        e["pid"] for e in evs
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and "TPU" in (e.get("args", {}).get("name") or "")
    }
    tids = {
        (e["pid"], e["tid"]): e["args"].get("name")
        for e in evs
        if e.get("ph") == "M" and e.get("name") == "thread_name"
        and e.get("pid") in dev_pids
    }
    mods: dict = collections.defaultdict(lambda: [0, 0.0])
    ops: dict = collections.defaultdict(lambda: [0, 0.0])
    for e in evs:
        if e.get("ph") != "X" or e.get("pid") not in dev_pids:
            continue
        lane = tids.get((e["pid"], e["tid"]))
        if lane == "XLA Modules":
            name = e["name"].split("(")[0]
            mods[name][0] += 1
            mods[name][1] += e.get("dur", 0.0)
        elif lane == "XLA Ops":
            ops[e["name"]][0] += 1
            ops[e["name"]][1] += e.get("dur", 0.0)
    return dict(mods), dict(ops)


def profile_batch(n: int, max_chunks: int, msg_len: int) -> dict:
    import jax
    import jax.numpy as jnp

    from spacedrive_tpu.ops import blake3_jax

    rng = np.random.default_rng(n)
    arr = rng.integers(0, 256, size=(n, max_chunks * 1024), dtype=np.uint8)
    arr[:, msg_len:] = 0
    lens = np.full((n,), msg_len, np.int32)
    bufs = []
    for i in range(CHAIN):
        a = arr.copy()
        a[:, 0] = i  # distinct content per chained dispatch
        bufs.append(jax.device_put(a.view(np.uint32)))
    l = jax.device_put(lens)
    # warm/compile outside the trace
    np.asarray(jnp.sum(blake3_jax.hash_batch(bufs[0], l, max_chunks=max_chunks)))

    tdir = tempfile.mkdtemp(prefix=f"sd-profile-{n}-")
    jax.profiler.start_trace(tdir)
    acc = None
    for i in range(CHAIN):
        s = jnp.sum(blake3_jax.hash_batch(bufs[i], l, max_chunks=max_chunks))
        acc = s if acc is None else acc + s
    np.asarray(acc)
    jax.profiler.stop_trace()

    mods, ops = parse_trace(tdir)
    # the hash program is the dominant module in this trace
    mod_name, (mod_n, mod_us) = max(mods.items(), key=lambda kv: kv[1][1])
    module_ms = mod_us / mod_n / 1e3
    kernel_us = sum(v[1] for k, v in ops.items() if k.startswith("run"))
    kernel_ms = kernel_us / mod_n / 1e3
    batch_bytes = n * msg_len
    gbps = batch_bytes / (module_ms / 1e3) / 1e9
    return {
        "batch": n,
        "module": mod_name,
        "dispatches": mod_n,
        "module_ms": round(module_ms, 3),
        "kernel_ms": round(kernel_ms, 3),
        "other_ms": round(module_ms - kernel_ms, 3),
        "gbps": round(gbps, 2),
        "files_per_s": round(n / (module_ms / 1e3), 0),
        "intops_tops": round(gbps * OPS_PER_BYTE / 1e3, 2),
        "kernel_gbps": round(batch_bytes / (kernel_ms / 1e3) / 1e9, 2)
        if kernel_ms else None,
        "top_ops_ms": {
            k: round(v[1] / mod_n / 1e3, 3)
            for k, v in sorted(ops.items(), key=lambda kv: -kv[1][1])[:6]
        },
    }


def main() -> None:
    import jax

    from spacedrive_tpu.ops import configure_compilation_cache
    from spacedrive_tpu.ops.cas import LARGE_CHUNKS, LARGE_MSG_LEN

    configure_compilation_cache()
    dev = jax.devices()[0]
    log(f"device: {dev} (platform {dev.platform})")
    if dev.platform == "cpu":
        log("WARNING: profiling on CPU — these are not device numbers")

    results = []
    for n in BATCH_SIZES:
        t0 = time.perf_counter()
        r = profile_batch(n, LARGE_CHUNKS, LARGE_MSG_LEN)
        log(f"batch {n:5d}: module {r['module_ms']:7.3f} ms  "
            f"kernel {r['kernel_ms']:7.3f} ms  other {r['other_ms']:6.3f} ms  "
            f"{r['gbps']:6.2f} GB/s  {r['files_per_s']:>9,.0f} files/s  "
            f"(wall {time.perf_counter()-t0:.0f}s)")
        results.append(r)

    doc = {
        "device": str(dev),
        "msg_len": 57352,
        "ops_per_byte_model": OPS_PER_BYTE,
        "chain": CHAIN,
        "note": (
            "module/kernel times are DEVICE-clock op durations from the "
            "profiler trace; each dispatch hashes distinct content"
        ),
        "batches": results,
    }
    with open("PROFILE.json", "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps(doc, indent=2), flush=True)


if __name__ == "__main__":
    main()
