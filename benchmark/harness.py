"""One run of one cell: set-up, a window of whole passes, the result.

The program gives the system under test (`cli.index_location` on a
started `Node`), its counters and its job reports. Everything that
decides a number lives here and in the files beside this one: traffic
(`traffic/*.json` read by `Traffic`), locations (`generators/`), the
window rule (`run_window`), per-layer readers (`metrics/`), the trace
reduction (`trace_reduce.py`), peaks (`peaks.json`) and the comparison
that decides `correct` (`check.py` over `reference/`).
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: everything a run writes (location, node data dirs, trace) goes here
#: and is removed when the run ends; `.gitignore` lists it
WORK = os.path.join(HERE, ".work")
LIBRARY = "bench"


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# --- finding a cell's files by name ----------------------------------------


def load_module(path: str):
    name = "bench_" + os.path.basename(path).rsplit(".", 1)[0].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """`BENCHMARK.json` of a checkout and the files it names."""

    def __init__(self, root: str = ROOT) -> None:
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)

    def find(self, *parts: str) -> str:
        """A file under one of `paths`, the first that has it."""
        for base in self.doc["paths"]:
            path = os.path.join(self.root, base, *parts)
            if os.path.isfile(path):
                return path
        raise FileNotFoundError(
            f"{os.path.join(*parts)} is under none of {self.doc['paths']}")

    def cell(self, workload: str) -> dict:
        cell = next((w for w in self.doc["workloads"]
                     if w["name"] == workload), None)
        if cell is None:
            raise SystemExit(f"benchmark: no workload {workload!r} in "
                             "BENCHMARK.json")
        entry = next(c for c in self.doc["configs"]
                     if c["name"] == cell["config"])
        with open(os.path.join(self.root, entry["file"])) as f:
            config = json.load(f)
        with open(self.find("traffic", cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
        return {"cell": cell, "config": config, "traffic": traffic}

    def generator(self, config: dict):
        return load_module(
            self.find("generators", config["generator"] + ".py"))

    def kinds(self, config: dict) -> dict:
        """name → module of every kind of file the configuration lists
        (`generators/common.py` says what an entry's `kind` means). A
        listed kind that no directory of `paths` holds ends the run."""
        out = {}
        for name in config.get("kinds", []):
            try:
                out[name] = load_module(self.find("kinds", name + ".py"))
            except FileNotFoundError:
                tried = [os.path.join(base, "kinds", name + ".py")
                         for base in self.doc["paths"]]
                raise SystemExit(
                    f"benchmark: the configuration lists the kind {name!r} "
                    f"and none of {tried} is there") from None
        return out

    def metrics_for(self, workload: str, kind: str) -> list[dict]:
        return [m for m in self.doc[kind]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        return load_module(self.find("metrics", metric + ".py")).read


# --- device, compiles, memory ----------------------------------------------


def require_chips(chips: int) -> dict:
    """The device stamp, or exit 2 with nothing on stdout: a measurement
    path that finds no chip fails, it does not fall back."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark: the cell needs {chips} TPU chip(s), JAX found "
              f"{len(devs)} × {devs[0].platform!r}; refusing to measure",
              file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileCounter:
    """Compile requests and persistent-cache hits per phase, through
    jax.monitoring (a copy of chip_smoke.py's)."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    #: what a program costs before the compiler (or the cache) is asked
    STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s"}

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.phase = "setup"
        self._lock = threading.Lock()
        self.counts: dict[str, dict[str, float]] = {}
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _slot(self) -> dict[str, float]:
        return self.counts.setdefault(
            self.phase, {"requests": 0, "cache_hits": 0, "seconds": 0.0,
                         "trace_s": 0.0, "lower_s": 0.0})

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self.REQUEST:
            with self._lock:
                slot = self._slot()
                slot["requests"] += 1
                slot["seconds"] += seconds
        elif event in self.STAGES:
            with self._lock:
                self._slot()[self.STAGES[event]] += seconds

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            with self._lock:
                self._slot()["cache_hits"] += 1

    def requests(self, phase: str) -> int:
        with self._lock:
            return int(self.counts.get(phase, {}).get("requests", 0))

    def report(self) -> dict:
        with self._lock:
            return {p: {"requests": int(c["requests"]),
                        "cache_hits": int(c["cache_hits"]),
                        "seconds": round(c["seconds"], 1),
                        "trace_s": round(c["trace_s"], 1),
                        "lower_s": round(c["lower_s"], 1)}
                    for p, c in self.counts.items()}


def host_memory_limit() -> int:
    limits = []
    for path in ("/sys/fs/cgroup/memory.max",
                 "/sys/fs/cgroup/memory/memory.limit_in_bytes"):
        try:
            with open(path) as f:
                raw = f.read().strip()
            if raw.isdigit():
                limits.append(int(raw))
        except OSError:
            pass
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemTotal"):
                limits.append(int(line.split()[1]) * 1024)
    return min(limits)


def _rss_with_children() -> int:
    """RSS of this process and of every process below it."""
    page = os.sysconf("SC_PAGE_SIZE")
    me = os.getpid()
    parent: dict[int, int] = {}
    rss: dict[int, int] = {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            parent[int(name)] = int(fields[1])
            rss[int(name)] = int(fields[21]) * page
        except (OSError, IndexError, ValueError):
            continue  # the process ended while we looked
    total = 0
    for pid in rss:
        p = pid
        while p not in (0, me) and p in parent:
            p = parent[p]
        if p == me:
            total += rss[pid]
    return total


class HostMemory:
    """Samples RSS (process and children); the peak per phase is a
    metric, and a run that nears the machine's limit ends itself before
    the kernel's OOM kill takes the chip down with it."""

    def __init__(self, limit: int) -> None:
        self.limit = limit
        self.phase = "setup"
        self.peaks: dict[str, int] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-rss",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            rss = _rss_with_children()
            self.peaks[self.phase] = max(self.peaks.get(self.phase, 0), rss)
            if rss > 0.85 * self.limit:
                log(f"host RSS {rss >> 20} MiB is over 85% of the "
                    f"{self.limit >> 20} MiB limit in phase {self.phase!r}; "
                    "ending the run before the OOM killer")
                os._exit(3)
            self._stop.wait(0.5)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)


def process_io() -> dict[str, int]:
    """`/proc/self/io`: write and read system calls and their bytes, of
    every thread of this process so far ({} where the file is absent)."""
    try:
        with open("/proc/self/io") as f:
            return {k: int(v) for k, v in (line.split(":") for line in f)}
    except OSError:
        return {}


class FullCollections:
    """Counts the interpreter's full (oldest-generation) collections and
    their seconds: the heap that tracing the warmed programs leaves makes
    one cost most of a second, and which pass it lands in is chance."""

    def __init__(self) -> None:
        import gc

        self.count, self.seconds, self._start = 0, 0.0, 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.count += 1
            self.seconds += time.perf_counter() - self._start

    def stop(self) -> None:
        import gc

        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)


def flat_counters() -> dict[str, float]:
    """The program's metrics registry as {"name{label=value}": number};
    a histogram gives `.sum` and `.count`."""
    from spacedrive_tpu.telemetry import REGISTRY

    out: dict[str, float] = {}
    for name, fam in REGISTRY.snapshot().items():
        for s in fam["series"]:
            labels = ",".join(f"{k}={v}" for k, v in sorted(s["labels"].items()))
            key = f"{name}{{{labels}}}" if labels else name
            if "value" in s:
                out[key] = float(s["value"])
            else:
                out[key + ".sum"] = float(s["sum"])
                out[key + ".count"] = float(s["count"])
    return out


# --- traffic ---------------------------------------------------------------


class Traffic:
    """The one general traffic generator: what happens to the location
    and the library between passes, from a traffic file's parameters and
    the run's seed."""

    def __init__(self, params: dict, config: dict, generator, seed: int,
                 location: str, manifest: list[dict]) -> None:
        import numpy as np

        from .generators.common import seed_words

        self.params = params
        self.config = config
        self.generator = generator
        self.seed = seed
        self.location = location
        self.manifest = manifest
        self.rng = np.random.default_rng(seed_words(seed, 0x74726166))
        self.serial = 0

    def data_dir(self, run_dir: str, index: int) -> str:
        if self.params["fresh_data_dir"]:
            return os.path.join(run_dir, f"node-{index:02d}")
        return os.path.join(run_dir, "node")

    def before_pass(self) -> dict | None:
        """Apply this pass's mutations to the location and the manifest;
        → {"rewritten", "added", "deleted"} lists of entries, or None."""
        from .generators.common import is_plain, write_plain

        shares = self.params.get("mutate")
        if not shares:
            return None
        plain = [e for e in self.manifest if is_plain(e)]
        n = len(plain)
        counts = {k: max(1, round(n * shares[k + "_share"]))
                  for k in ("rewrite", "add", "delete")}
        picked = self.rng.choice(n, counts["rewrite"] + counts["delete"],
                                 replace=False)
        rewritten = [plain[int(i)] for i in picked[:counts["rewrite"]]]
        deleted = [plain[int(i)] for i in picked[counts["rewrite"]:]]
        for e in rewritten:
            self.serial += 1
            e["content"] = [*e["content"][:2], 1 << 28 | self.serial]
            write_plain(os.path.join(self.location, e["rel"]), e["size"],
                        e["content"])
        # adds come before deletes: a filesystem hands a deleted file's
        # inode to the next file created, and the program's indexer fails
        # on a new row that carries the inode of a row it has not removed
        # yet (PERF.md §7); in this order no operation fails
        added = []
        for _ in range(counts["add"]):
            self.serial += 1
            e = self.generator.new_entry(self.config, self.rng, self.manifest,
                                         self.serial, self.seed)
            write_plain(os.path.join(self.location, e["rel"]), e["size"],
                        e["content"])
            added.append(e)
        gone = {id(e) for e in deleted}
        for e in deleted:
            os.remove(os.path.join(self.location, e["rel"]))
        self.manifest[:] = [e for e in self.manifest if id(e) not in gone]
        self.manifest.extend(added)
        return {"rewritten": [dict(e) for e in rewritten],
                "added": [dict(e) for e in added],
                "deleted": [dict(e) for e in deleted]}


# --- the pass and the window -----------------------------------------------


async def index_pass(data_dir: str, location: str) -> dict:
    """Node(use_device=True) → start → cli.index_location → shutdown:
    what `sdx index --backend tpu --no-p2p` does, as chip_smoke.py's
    `index_pass` drives it."""
    from spacedrive_tpu import cli
    from spacedrive_tpu.node import Node

    node = Node(data_dir, use_device=True)
    node.config.config.p2p.enabled = False  # no network on the machine
    await node.start()
    try:
        summary = await cli.index_location(node, location, LIBRARY, "tpu")
        summary["thumbnailer_generated"] = node.thumbnailer.generated
        return summary
    finally:
        await node.shutdown()


def run_window(seconds: float, cycle, clock=time.perf_counter) -> dict:
    """The window rule. `cycle(i)` runs one whole pass with everything
    around it and returns its record. A new pass starts only while fewer
    than `seconds` have elapsed since the window opened; the pass in
    progress is always finished; the window closes when it ends. Nothing
    between its first start and its last end is left out."""
    opened = clock()
    passes = []
    while True:
        start = clock()
        record = cycle(len(passes))
        end = clock()
        passes.append({**record, "start_s": start - opened,
                       "end_s": end - opened, "cycle_s": end - start})
        if end - opened >= seconds:
            return {"opened": opened, "closed": end,
                    "window_s": end - opened, "passes": passes}


def host_stamp() -> dict:
    return {"cores": os.cpu_count(), "loadavg": list(os.getloadavg())}


# --- one run ---------------------------------------------------------------


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, t_process: float | None = None,
             require=require_chips, work: str = WORK) -> dict:
    """Run one cell once and return the result object (`run.py` prints
    it). `require` is the look for the chip; tests hand in their own."""
    t_process = time.perf_counter() if t_process is None else t_process
    parts: dict[str, float] = {}
    mark = [t_process]

    def part(name: str) -> None:
        now = time.perf_counter()
        parts[name] = round(now - mark[0], 3)
        mark[0] = now

    bench = Bench(root)
    spec = bench.cell(workload)
    cell, config, traffic_params = spec["cell"], spec["config"], spec["traffic"]
    generator = bench.generator(config)
    kinds = bench.kinds(config)
    with open(bench.find("peaks.json")) as f:
        peaks = json.load(f)

    stamp = require(cell["chips"])
    if stamp["platform"] == "tpu" and stamp["kind"] not in peaks:
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{stamp['kind']!r} in peaks.json")
    import jax

    from spacedrive_tpu.ops import configure_compilation_cache

    cache_dir = configure_compilation_cache()
    compiles = CompileCounter()
    memory = HostMemory(host_memory_limit())
    part("runtime_start_s")

    from spacedrive_tpu import native

    if not native.available():
        raise SystemExit("benchmark: native BLAKE3 did not build "
                         "(no C compiler?)")
    part("native_build_s")

    from . import check, warm
    from .generators.common import entries_of, write_manifest

    run_dir = os.path.join(work, "run")
    shutil.rmtree(run_dir, ignore_errors=True)
    location = os.path.join(run_dir, "location")
    os.makedirs(location)
    breakdown = full_gc = None
    try:
        manifest = generator.plan(config, seed)
        write_manifest(location, manifest, kinds)
        warm_location = None
        if traffic_params.get("warmup_scale"):
            warm_location = os.path.join(run_dir, "warm-location")
            os.makedirs(warm_location)
            write_manifest(warm_location, generator.plan(
                config, seed + 1, scale=traffic_params["warmup_scale"]),
                kinds)
        part("location_s")
        log(f"location: {len(manifest)} files, "
            f"{sum(e['size'] for e in manifest) >> 20} MiB apparent, "
            f"{sum(1 for e in manifest if e.get('image'))} images"
            + "".join(f", {len(entries_of(manifest, k))} {k}" for k in kinds))

        n_dev = stamp["count"]
        hashes = warm.hash_programs([e["size"] for e in manifest], n_dev)
        media = warm.media_programs(
            [os.path.join(location, e["rel"]) for e in manifest
             if e.get("image")], n_dev)
        threads = warm.compile_threads(memory.limit, _rss_with_children())
        compiles.phase = memory.phase = "program_warm"
        own = [p for name, mod in kinds.items() if hasattr(mod, "programs")
               for p in mod.programs(entries_of(manifest, name), location,
                                     n_dev)]
        programs = warm.run_programs(hashes, media, n_dev, threads, own)
        warm.release_freed_heap()
        part("program_warm_s")
        log(f"programs: {len(programs)} warmed on {threads} threads, "
            f"compile requests {compiles.report().get('program_warm')}, "
            f"seconds each {sorted(programs, key=lambda p: -p[1])}")

        from spacedrive_tpu.parallel import autotune

        traffic = Traffic(traffic_params, config, generator, seed, location,
                          manifest)
        compiles.phase = memory.phase = "warm_pass"
        if warm_location is not None:
            autotune.reset()
            warmed = asyncio.run(index_pass(
                os.path.join(run_dir, "node-warm"), warm_location))
            log(f"warm-up pass: {warmed['files']} files in "
                f"{warmed['seconds']} s {warmed['job_seconds']}")
        if traffic_params.get("setup_index_pass"):
            autotune.reset()
            first = asyncio.run(index_pass(traffic.data_dir(run_dir, 0),
                                           location))
            log(f"set-up index pass: {first['files']} files in "
                f"{first['seconds']} s {first['job_seconds']}")
        part("warm_pass_s")

        # --- the window ---
        def cycle(index: int) -> dict:
            changes = traffic.before_pass()
            autotune.reset()  # each pass starts as a new process would
            data_dir = traffic.data_dir(run_dir, index)
            io_before, gc_before = process_io(), full_gc.seconds
            summary = asyncio.run(index_pass(data_dir, location))
            io = {k: v - io_before[k] for k, v in process_io().items()}
            record = {"summary": summary, "data_dir": data_dir,
                      "files": summary["files"], "index_s": summary["seconds"],
                      "offered": len(manifest), "io": io,
                      "full_gc_s": full_gc.seconds - gc_before}
            if changes is not None:
                record["changes"] = changes
                record["probe"] = check.probe(data_dir, location, changes)
            log(f"pass {index}: {summary['files']} files in "
                f"{summary['seconds']} s, jobs {summary['job_seconds']}, "
                f"write calls {io.get('syscw')} ({io.get('wchar', 0) >> 20} MiB)"
                f", full collections {record['full_gc_s']:.2f} s")
            return record

        trace_dir = os.path.join(run_dir, "trace")
        full_gc = FullCollections()
        before = flat_counters()
        stamp_open = host_stamp()
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        compiles.phase = memory.phase = "window"
        setup_s = time.perf_counter() - t_process
        wall_open = time.time()
        if trace:
            from .trace_reduce import WINDOW_ANNOTATION

            with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
                window = run_window(seconds, cycle)
            jax.profiler.stop_trace()
        else:
            window = run_window(seconds, cycle)
        compiles.phase = memory.phase = "after"
        full_gc.stop()
        stamp_close = host_stamp()
        counters = {k: v - before.get(k, 0.0)
                    for k, v in flat_counters().items()}
        memory_peak = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices())
        memory.stop()

        passes = window["passes"]
        for i, p in enumerate(passes):
            log(f"pass {i}: cycle {p['cycle_s']:.3f} s, index "
                f"{p['index_s']:.2f} s, start {p['start_s']:.3f} s")
        log(f"set-up parts: {json.dumps(parts)} (setup_s {setup_s:.3f}); "
            f"compile cache {cache_dir}; compiles {compiles.report()}")
        log(f"host: open {stamp_open} close {stamp_close}; full collections "
            f"in the window {full_gc.count} in {full_gc.seconds:.2f} s")

        files = sum(p["files"] for p in passes)
        ctx = {
            "workload": workload, "config": config, "traffic": traffic_params,
            "window_s": window["window_s"], "passes": passes,
            "counters": counters, "setup_parts": parts, "setup_s": setup_s,
            "compiles_in_window": compiles.requests("window"),
            "host_rss_peak_bytes": memory.peaks.get("window", 0),
            "peaks": peaks.get(stamp["kind"]), "device": stamp,
            "hashed": check.hashed_files(passes, manifest, traffic_params),
            "trace": None,
        }
        device = {**stamp, "memory_peak_bytes": memory_peak}
        if trace:
            from . import trace_reduce

            with open(bench.find("kernels.json")) as f:
                kernels = json.load(f)
            ctx["trace"] = reduced = trace_reduce.reduce_dir(
                trace_dir, trace_reduce.job_intervals(passes, wall_open),
                kernels)
            # the newest trace stays for whoever wants to look at it
            os.replace(reduced["path"], os.path.join(work, "last.xplane.pb"))
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = reduced["breakdown"]

        end_to_end = {"pass_rate": files / window["window_s"],
                      "setup_s": setup_s}
        metrics = {}
        if trace:
            for m in bench.metrics_for(workload, "per_layer"):
                value = bench.reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in bench.metrics_for(workload, "end_to_end"):
                metrics[m["name"]] = {"value": end_to_end[m["name"]],
                                      "unit": m["unit"]}

        verdict = check.decide(
            config, traffic_params, location, manifest, passes, seed,
            compiles_in_window=ctx["compiles_in_window"], stamp=stamp,
            kinds=kinds)
        result = {
            "correct": verdict["correct"],
            "attempted": sum(p["offered"] for p in passes),
            "failed": verdict["failed"],
            "metrics": metrics,
            "device": device,
            **({"breakdown": breakdown} if breakdown else {}),
            "workload": workload, "seed": seed,
            "window_s": window["window_s"],
            "pass_cycle_s": [p["cycle_s"] for p in passes],
            "pass_index_s": [p["index_s"] for p in passes],
            "setup_parts": parts,
            "check_s": verdict["seconds"],
            "compared": verdict["compared"],
        }
        for line in verdict["lines"]:
            print(line, file=sys.stderr)
        sys.stderr.flush()
        return result
    finally:
        memory.stop()
        if full_gc is not None:
            full_gc.stop()
        shutil.rmtree(run_dir, ignore_errors=True)
