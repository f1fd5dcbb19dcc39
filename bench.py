"""Headline benchmark: batched cas_id BLAKE3 hashing, TPU vs multi-core CPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Workload = BASELINE.json config 2 (batched cas_id hashing of large-bucket
sampled messages — every file > 100 KiB hashes exactly 57,352 bytes,
ref:core/src/object/cas.rs:10-21). Baseline = the framework's own native
C BLAKE3 (the role the Rust `blake3` crate plays in the reference's
file_identifier hot loop, ref:core/src/object/file_identifier/mod.rs:105),
measured 1-core and scaled to the north star's 16-core host explicitly.

Method:
- every timing is a median over repeats with the spread reported, and
  ends in `block_until_ready` (dispatch is asynchronous);
- device compute is the MARGINAL cost of chained dispatches over
  DISTINCT pre-placed inputs, so per-call launch latency and transfers
  stay out of it;
- the JSON carries the device stamp (platform, kind, count) of the run.
  A number from a CPU run is not a device number.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _procpool_procs() -> int:
    """Live SD_PROCS pool size for the artifact's rig stamp."""
    from spacedrive_tpu.parallel.procpool import procs

    return procs()

CPU_BASELINE_CORES = 16  # the north star's CPU host (BASELINE.json)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def median_spread(samples: list[float]) -> tuple[float, float, float]:
    """(median, lo, hi); even counts average the middle pair so a
    2-sample run doesn't systematically record its slower sample."""
    s = sorted(samples)
    mid = len(s) // 2
    med = s[mid] if len(s) % 2 else (s[mid - 1] + s[mid]) / 2
    return med, s[0], s[-1]


def sweep_counts(n_devices: int) -> list[int]:
    """1, 2, 4, … up to (and always including) the full device count."""
    ks, k = [], 1
    while k < n_devices:
        ks.append(k)
        k *= 2
    ks.append(n_devices)
    return ks


def device_sweep(arr, lens, repeats: int, chain_k: int) -> list[dict]:
    """Measure sharded cas_id hashing at 1→N devices (jax.devices()
    subsets) on the SAME workload as the headline device-compute leg:
    marginal cost of chained distinct-input dispatches, inputs
    pre-placed with the dp sharding so the timed window is compute, not
    transfer. Returns one record per device count for the BENCH JSON's
    extras, with scaling efficiency relative to the 1-device number —
    the executed version of the ×N projection the round-3 verdict
    flagged as unmeasured."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from spacedrive_tpu.ops import blake3_jax
    from spacedrive_tpu.ops.cas import LARGE_CHUNKS

    devs = jax.devices()
    n = arr.shape[0]
    records: list[dict] = []
    base_fps = None
    words = np.ascontiguousarray(arr).view(np.uint32)

    # a tiny on-device mutation re-freshens every buffer between timed
    # windows (same trick as the headline leg) so no timed dispatch
    # ever re-hashes content the stack has seen — without re-paying
    # the transfer; output sharding follows the input's
    @jax.jit
    def freshen(a, tag):
        return a.at[:, 4].set(tag)

    for k in sweep_counts(len(devs)):
        if n % k:
            log(f"sweep: skipping {k} devices ({n} rows do not divide)")
            continue
        subset = devs[:k]
        bufs = []
        for i in range(chain_k):
            a = words.copy()
            a[:, 0] = i + 1  # distinct content per chained dispatch
            bufs.append(
                blake3_jax.shard_put(a, subset) if k > 1
                else jax.device_put(a, subset[0])
            )
        jax.block_until_ready(bufs[-1])

        def refresh(rep: int) -> None:
            for i in range(chain_k):
                bufs[i] = freshen(
                    bufs[i], np.uint32((rep * chain_k + i) % 251))
            jax.block_until_ready(bufs[-1])

        def chain(j: int) -> float:
            t0 = time.perf_counter()
            acc = None
            for b in bufs[:j]:
                w = blake3_jax.hash_batch(
                    b, lens, max_chunks=LARGE_CHUNKS,
                    devices=subset if k > 1 else None,
                    donate_input=False,  # buffers are reused next repeat
                )
                s = jnp.sum(w)
                acc = s if acc is None else acc + s
            jax.block_until_ready(acc)
            return time.perf_counter() - t0

        chain(chain_k)  # warm/compile this device count
        marginals = []
        for rep in range(repeats):
            refresh(2 * rep)
            t1 = chain(1)
            refresh(2 * rep + 1)
            tk = chain(chain_k)
            marginals.append(max(1e-9, (tk - t1) / (chain_k - 1)))
        med, lo, hi = median_spread(marginals)
        fps = n / med
        if base_fps is None:
            base_fps = fps
        eff = fps / (base_fps * k)
        records.append({
            "devices": k,
            "files_per_s": round(fps, 1),
            "ms_per_batch": round(med * 1e3, 2),
            "spread_ms": [round(lo * 1e3, 2), round(hi * 1e3, 2)],
            "scaling_efficiency": round(eff, 3),
        })
        log(f"sweep {k} device(s): {med*1e3:.1f} ms/batch  "
            f"{fps:,.0f} files/s  efficiency {eff:.2f}")
    return records


def main() -> None:
    from spacedrive_tpu import native, telemetry
    from spacedrive_tpu.ops import blake3_jax, configure_compilation_cache
    from spacedrive_tpu.ops.cas import LARGE_CHUNKS, LARGE_MSG_LEN
    from spacedrive_tpu.telemetry import metrics as tm

    import jax
    import jax.numpy as jnp

    configure_compilation_cache()
    n = int(os.environ.get("SD_BENCH_FILES", "4096"))
    repeats = int(os.environ.get("SD_BENCH_REPEATS", "5"))
    chain_k = max(2, int(os.environ.get("SD_BENCH_CHAIN", "8")))
    rng = np.random.default_rng(0)

    log(f"devices: {jax.devices()}")
    log(f"generating {n} large-bucket messages ({LARGE_MSG_LEN} B each)…")
    arr = rng.integers(0, 256, size=(n, LARGE_CHUNKS * 1024), dtype=np.uint8)
    arr[:, LARGE_MSG_LEN:] = 0  # zero pad beyond message length
    lens = np.full((n,), LARGE_MSG_LEN, np.int32)
    batch_bytes = n * LARGE_MSG_LEN

    # --- device compute: marginal cost of chained distinct-input batches
    lens_dev = jax.device_put(lens)
    distinct = []
    for i in range(chain_k):
        a = arr.copy()
        a[:, 0] = i  # distinct content per chained dispatch
        # u32 view = production's host-side reinterpret (hash_batch does
        # this for numpy callers); same bytes on the wire, and the
        # device skips the byte-pack pass
        distinct.append(jax.device_put(a.view(np.uint32)))
    jax.block_until_ready(distinct[-1])

    def chain(k: int) -> None:
        acc = None
        for i in range(k):
            w = blake3_jax.hash_batch(distinct[i], lens_dev, max_chunks=LARGE_CHUNKS)
            s = jnp.sum(w)
            acc = s if acc is None else acc + s
        jax.block_until_ready(acc)

    # a tiny on-device mutation re-freshens every buffer between repeats
    # (outside the timed window) so no timed dispatch ever re-hashes
    # content the stack has seen — without re-paying the transfer
    @jax.jit
    def freshen(a, tag):
        return a.at[:, 4].set(tag)

    def refresh_all(rep: int) -> None:
        for i in range(chain_k):
            distinct[i] = freshen(distinct[i], np.uint32((rep * chain_k + i) % 251))
        jax.block_until_ready(distinct[-1])

    chain(chain_k)  # warm/compile
    for rep in range(repeats):
        refresh_all(2 * rep)
        t0 = time.perf_counter()
        chain(1)
        t1 = time.perf_counter() - t0
        refresh_all(2 * rep + 1)
        t0 = time.perf_counter()
        chain(chain_k)
        tk = time.perf_counter() - t0
        tm.BENCH_DEVICE_BATCH_SECONDS.observe(
            max(1e-9, (tk - t1) / (chain_k - 1)))
    # per-batch device timings come back OUT of the registry — the
    # reported numbers and the scrapable histogram cannot diverge
    marginals = telemetry.histogram_recent("sd_bench_device_batch_seconds")
    dev_s, dev_lo, dev_hi = median_spread(marginals)
    dev_gbps = batch_bytes / dev_s / 1e9
    dev_fps = n / dev_s
    log(f"device compute (marginal, chained): {dev_s*1e3:.1f} ms/batch "
        f"[{dev_lo*1e3:.1f}–{dev_hi*1e3:.1f}]  {dev_fps:,.0f} files/s  {dev_gbps:.1f} GB/s")

    # --- device-count sweep: the ×N leg, executed instead of projected.
    # Runs whenever >1 device is visible (SD_BENCH_SWEEP=0 skips;
    # SD_BENCH_SWEEP=1 forces, e.g. on a forced-host-platform CI mesh).
    sweep_env = os.environ.get("SD_BENCH_SWEEP")
    sweep_records: list[dict] = []
    if sweep_env != "0" and (len(jax.devices()) > 1 or sweep_env == "1"):
        sweep_records = device_sweep(arr, lens, repeats, chain_k)

    # --- e2e: host memory → device → digests, pipelined like production
    pipe_depth = 3
    for rep_no in range(repeats):
        t0 = time.perf_counter()
        acc = None
        for i in range(pipe_depth):
            a = arr.copy()
            a[:, 1] = (rep_no * pipe_depth + i) % 251  # unseen content every rep
            w = blake3_jax.hash_batch(a, lens, max_chunks=LARGE_CHUNKS)
            s = jnp.sum(w)
            acc = s if acc is None else acc + s
        jax.block_until_ready(acc)
        tm.BENCH_E2E_BATCH_SECONDS.observe(
            (time.perf_counter() - t0) / pipe_depth)
    e2e = telemetry.histogram_recent("sd_bench_e2e_batch_seconds")
    e2e_s, e2e_lo, e2e_hi = median_spread(e2e)
    e2e_fps = n / e2e_s
    log(f"e2e (host→device, {pipe_depth} in flight): {e2e_s*1e3:.1f} ms/batch "
        f"[{e2e_lo*1e3:.1f}–{e2e_hi*1e3:.1f}]  {e2e_fps:,.0f} files/s  "
        f"{batch_bytes/e2e_s/1e9:.2f} GB/s")

    # --- CPU baseline: native C BLAKE3, 1 core measured, 16 scaled
    host_cores = os.cpu_count() or 1
    msgs = [arr[i, :LARGE_MSG_LEN].tobytes() for i in range(n)]
    cpu1_fps = None
    if native.available():
        native.blake3_many(msgs[:64], 1)  # warm
        cpu_times = []
        for _ in range(max(2, repeats - 2)):
            t0 = time.perf_counter()
            digests = native.blake3_many(msgs, 1)
            cpu_times.append(time.perf_counter() - t0)
        cpu_s, _, _ = median_spread(cpu_times)
        cpu1_fps = n / cpu_s
        log(f"cpu 1-core native C: {cpu_s*1e3:.1f} ms  {cpu1_fps:,.0f} files/s "
            f"(this host has {host_cores} core(s); 16-core baseline is a "
            f"linear projection: {cpu1_fps*CPU_BASELINE_CORES:,.0f} files/s)")
        # parity: device digests == native digests
        w = blake3_jax.hash_batch(arr, lens, max_chunks=LARGE_CHUNKS)
        hexes = blake3_jax.words_to_hex(w, 64)
        for i in (0, n // 2, n - 1):
            assert hexes[i] == digests[i].hex(), f"digest mismatch at {i}"
        log("parity: device digests match native CPU digests")
    else:
        log("native CPU baseline unavailable (no C compiler)")
    cpu16_fps = cpu1_fps * CPU_BASELINE_CORES if cpu1_fps else None

    out = {
        # headline: host memory → device → digests on this rig
        "metric": "cas_id_e2e_throughput",
        "value": round(e2e_fps, 1),
        "unit": "files/s",
        # honest baseline: 16-core-projected native C, per the north star
        "vs_baseline": round(e2e_fps / cpu16_fps, 3) if cpu16_fps else None,
        "device": {
            "platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        },
        "spread": {
            "e2e_ms": [round(e2e_lo * 1e3, 1), round(e2e_s * 1e3, 1), round(e2e_hi * 1e3, 1)],
            "device_ms": [round(dev_lo * 1e3, 1), round(dev_s * 1e3, 1), round(dev_hi * 1e3, 1)],
        },
        "extras": {
            "device_compute_files_per_s": round(dev_fps, 1),
            "device_compute_gbps": round(dev_gbps, 2),
            "device_vs_cpu16": round(dev_fps / cpu16_fps, 3) if cpu16_fps else None,
            "cpu_1core_files_per_s": round(cpu1_fps, 1) if cpu1_fps else None,
            "cpu_16core_projected_files_per_s": round(cpu16_fps, 1) if cpu16_fps else None,
            "host_cores": host_cores,
            "cpu_count": host_cores,
            "procpool_procs": _procpool_procs(),
            # per-device-count throughput + scaling efficiency
            # (device_sweep; [] on single-device rigs)
            "device_sweep": sweep_records,
        },
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
