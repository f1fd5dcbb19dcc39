"""bench_scale — million-file churn soak: growth as a gated number.

Every other bench in this repo answers "how fast"; this one answers
the production question ROADMAP open item 5 actually asks: *does the
node survive scale and time?* A synthetic corpus (sparse files — a
1M-file multi-TB library fits this rig because no byte is ever
materialized beyond the first block) is churned by a deterministic,
seed-controlled scenario driver through the REAL planes:

  touch    — mtime/size storms over a random sample (the watcher
             debounce + journal-invalidation surface)
  rename   — rename storms inside their directories (path-identity
             churn: journal rows must follow, not accumulate)
  reindex  — warm re-index passes over the whole corpus (the consult
             path at scale; per-pass files/s is the flatness series)
  reads    — serve-layer read swarms against the node's own HTTP API
             (admission gate + read caches under sustained load)
  orphan   — file deletions followed by a reindex + the batched
             orphan/journal clean-up (the bounded-prune path)
  p2p      — federation exchanges over an in-process loopback mesh
             pair (SD_SOAK_P2P=1; off by default — this rig's CI
             container lacks the crypto socket layer)
  faults   — a fault-plane chaos schedule around a read burst
             (SD_SOAK_FAULTS=1)

While the driver churns, the node's own telemetry does the judging:
the resource sampler (telemetry/resources.py) feeds RSS/fd/inventory
gauges into the history store, and the final verdict comes from the
SLO engine — burn rates AND the trend class (bounded growth slopes
after warmup). The soak passes only if zero SLOs breach, zero
protected-class sheds occur, fd/RSS deltas stay bounded, and files/s
stays flat across warm passes; a trend breach leaves a triggered
profile capture behind as the forensics artifact.

Output: ``BENCH_SCALE.json`` (schema ``bench-scale/v1``).

Knobs (script-scope; docs/telemetry.md): ``SD_SOAK_FILES`` (default
20000), ``SD_SOAK_SECONDS`` (default 120), ``SD_SOAK_SEED`` (default
7), ``SD_SOAK_MIX`` (``touch=4,rename=2,reindex=2,reads=3,orphan=1``),
``SD_SOAK_P2P``, ``SD_SOAK_FAULTS``. The tier-1 mini-soak
(``make soak-smoke``) runs this module's :func:`run_soak` with a small
corpus and accelerated sampler/SLO intervals; the full lane
(``make bench-scale``) runs it at 10⁶ files for hours.
"""

from __future__ import annotations

import asyncio
import json
import os
import platform
import random
import sys
import time
from typing import Any

SCHEMA = "bench-scale/v1"

# the bars
FD_DELTA_MAX = 32
RSS_DELTA_MAX_MB = 512.0
FLATNESS_MIN = 0.5

DEFAULT_MIX = "touch=4,rename=2,reindex=2,reads=3,orphan=1"

#: files touched/renamed per storm and deleted per orphan round —
#: scaled down automatically when the corpus is smaller
STORM_SIZE = 200
ORPHAN_SIZE = 20


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


def parse_mix(raw: str) -> dict[str, int]:
    """``touch=4,rename=2`` → weight dict; unknown names are ignored by
    the driver (a mix naming a disabled scenario just never fires)."""
    mix: dict[str, int] = {}
    for part in raw.split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        name, _, w = part.partition("=")
        try:
            weight = int(w)
        except ValueError:
            continue
        if weight > 0:
            mix[name.strip()] = weight
    return mix


# --- corpus ---------------------------------------------------------------


def make_corpus(root: str, files: int, seed: int) -> list[str]:
    """Sparse synthetic corpus: every file is a truncate to a synthetic
    size (nothing but inode metadata hits the disk), sharded 256-way so
    no directory holds an O(corpus) listing. Returns the path list —
    the driver's sampling universe."""
    rng = random.Random(seed)
    words = ("alpha", "beta", "gamma", "delta", "report", "photo",
             "invoice", "notes", "backup", "draft", "scan", "render")
    exts = (".txt", ".jpg", ".png", ".pdf", ".raw", ".mov")
    paths: list[str] = []
    os.makedirs(root, exist_ok=True)
    for shard in range(min(256, max(1, files // 64))):
        os.makedirs(os.path.join(root, f"s{shard:02x}"), exist_ok=True)
    nshards = min(256, max(1, files // 64))
    for i in range(files):
        p = os.path.join(
            root, f"s{i % nshards:02x}",
            f"{words[i % len(words)]}-{i:07d}{exts[i % len(exts)]}",
        )
        with open(p, "wb") as f:
            # sparse: multi-KB..multi-MB identities, ~zero disk blocks
            f.truncate(rng.randrange(1 << 10, 1 << 22))
        paths.append(p)
    return paths


# --- the scenarios --------------------------------------------------------


class SoakDriver:
    """Seed-controlled churn over one booted node. Every scenario is an
    async method named ``scenario_<name>``; the mix weights pick which
    fires each round, so a run is fully determined by (corpus seed,
    driver seed, mix, duration-measured-in-rounds)."""

    def __init__(self, node: Any, lib: Any, loc_id: int, corpus_root: str,
                 paths: list[str], rng: random.Random, base_url: str,
                 mesh: tuple | None):
        self.node = node
        self.lib = lib
        self.loc_id = loc_id
        self.corpus_root = corpus_root
        self.paths = paths
        self.rng = rng
        self.base_url = base_url
        self.mesh = mesh
        self.counts: dict[str, int] = {}
        self.passes: list[dict[str, float]] = []
        self._serial = 0

    def _sample_idx(self, k: int) -> list[int]:
        """Index samples, not path samples — O(k) mutation at any
        corpus size (a path search would be O(n) per file)."""
        k = min(k, len(self.paths))
        return self.rng.sample(range(len(self.paths)), k) if k else []

    async def scenario_touch(self) -> None:
        """mtime/size storm: the watcher/journal invalidation surface."""
        now = time.time()
        for i in self._sample_idx(
                min(STORM_SIZE, max(8, len(self.paths) // 20))):
            try:
                with open(self.paths[i], "r+b") as f:
                    f.truncate(self.rng.randrange(1 << 10, 1 << 22))
                os.utime(self.paths[i], (now, now - self.rng.random() * 3600))
            except OSError:
                continue
        await asyncio.sleep(0)

    async def scenario_rename(self) -> None:
        """Rename storm inside each file's shard: journal rows must
        track the new identity, not accumulate dead ones."""
        for i in self._sample_idx(min(STORM_SIZE // 2,
                                      max(4, len(self.paths) // 40))):
            self._serial += 1
            root, name = os.path.split(self.paths[i])
            name = name.split("-", 1)[-1]  # strip prior mv prefixes
            new = os.path.join(root, f"mv{self._serial:07d}-{name}")
            try:
                os.rename(self.paths[i], new)
            except OSError:
                continue
            self.paths[i] = new
        await asyncio.sleep(0)

    async def scenario_reindex(self) -> None:
        """Warm re-index + re-identify of the whole corpus — the
        per-pass files/s is the throughput-flatness series the verdict
        gates. The identify pass matters for the journal trend: the
        index journal is written (and consulted) by the identifier, so
        without it the journal_rows inventory would sit at zero and the
        "rows track corpus size, not pass count" property would go
        untested."""
        from spacedrive_tpu.jobs.manager import JobBuilder
        from spacedrive_tpu.location.indexer.job import IndexerJob
        from spacedrive_tpu.object.file_identifier.job import FileIdentifierJob

        t0 = time.monotonic()
        await JobBuilder(IndexerJob({"location_id": self.loc_id})).spawn(
            self.node.jobs, self.lib)
        await self.node.jobs.wait_idle()
        await JobBuilder(FileIdentifierJob(
            {"location_id": self.loc_id, "backend": "cpu"})).spawn(
            self.node.jobs, self.lib)
        await self.node.jobs.wait_idle()
        dt = max(1e-3, time.monotonic() - t0)
        self.passes.append({
            "files": len(self.paths),
            "seconds": round(dt, 3),
            "files_per_s": round(len(self.paths) / dt, 2),
        })

    async def scenario_reads(self) -> None:
        """Serve-layer read swarm against the node's own HTTP API (a
        short in-process burst: the soak only needs sustained read
        load, not capacity figures)."""
        import aiohttp

        args = [
            {"filter": {"search": "report"}, "take": 50},
            {"filter": {}, "take": 50, "orderBy": "name"},
            {"filter": {"search": f"{self.rng.randrange(1000):03d}"},
             "take": 25},
        ]
        async with aiohttp.ClientSession() as session:
            for _ in range(12):
                try:
                    async with session.post(
                        f"{self.base_url}/rspc/search.paths",
                        json={"library_id": str(self.lib.id),
                              "arg": args[self.rng.randrange(len(args))]},
                    ) as resp:
                        await resp.read()
                except Exception:  # noqa: BLE001 - load gen, not assertion
                    pass

    async def scenario_orphan(self) -> None:
        """Stationary delete/create churn: unlink a slice, create the
        same number of fresh files, reindex, then run the batched
        orphan + journal clean-up — the bounded-prune path under load.
        Net corpus size stays constant by construction; the journal-rows
        inventory must track it, not the accumulated churn count."""
        from spacedrive_tpu.object.orphan_remover import (
            process_clean_up_async,
        )

        for i in self._sample_idx(min(ORPHAN_SIZE,
                                      max(2, len(self.paths) // 100))):
            root = os.path.dirname(self.paths[i])
            try:
                os.unlink(self.paths[i])
            except OSError:
                pass
            self._serial += 1
            new = os.path.join(root, f"new-{self._serial:07d}.txt")
            try:
                with open(new, "wb") as f:
                    f.truncate(self.rng.randrange(1 << 10, 1 << 22))
            except OSError:
                continue
            self.paths[i] = new
        await self.scenario_reindex()
        await process_clean_up_async(self.lib.db)

    async def scenario_p2p(self) -> None:
        """Device join/leave over the loopback duplex: both mesh nodes
        refresh federation (real TELEMETRY wire exchanges), and every
        few rounds one side 'leaves' and 'rejoins' discovery."""
        if self.mesh is None:
            return
        a, b, lib_a, lib_b = self.mesh
        await a.p2p.refresh_federation(force=True)
        await b.p2p.refresh_federation(force=True)
        if self.counts.get("p2p", 0) % 4 == 3:
            # leave/rejoin: drop the peer from discovery, re-beacon
            ident = b.p2p.p2p.remote_identity
            a.p2p.p2p.peers.pop(ident, None)
            a.p2p.p2p.discovered(
                "soak", ident, {("127.0.0.1", 1)},
                {"name": b.config.config.name,
                 "libraries": str(lib_b.id),
                 "instances": str(lib_b.sync.instance)},
            )

    async def scenario_faults(self) -> None:
        """A chaos window: db.slow stalls around a read burst, cleared
        afterwards — resilience plumbing exercised mid-soak."""
        from spacedrive_tpu.utils import faults as _faults

        plan = _faults.FaultPlan.parse(
            "db.slow:stall:times=30,delay_s=0.002",
            seed=self.rng.randrange(1 << 30),
        )
        _faults.install(plan)
        try:
            await self.scenario_reads()
        finally:
            _faults.clear()

    async def run_round(self, mix: list[str]) -> None:
        name = self.rng.choice(mix)
        fn = getattr(self, f"scenario_{name}", None)
        if fn is None:
            return
        await fn()
        self.counts[name] = self.counts.get(name, 0) + 1


# --- the soak -------------------------------------------------------------


async def _boot(data_dir: str, corpus: str):
    from spacedrive_tpu.jobs.manager import JobBuilder
    from spacedrive_tpu.location.indexer.job import IndexerJob
    from spacedrive_tpu.location.locations import LocationCreateArgs
    from spacedrive_tpu.node.node import Node
    from spacedrive_tpu.object.file_identifier.job import FileIdentifierJob

    node = Node(data_dir, use_device=False, with_labeler=False)
    await node.start()
    lib = await node.create_library("bench-scale")
    loc = LocationCreateArgs(path=corpus).create(lib)
    t0 = time.monotonic()
    await JobBuilder(IndexerJob({"location_id": loc["id"]})).spawn(
        node.jobs, lib)
    await node.jobs.wait_idle()
    # identify pass: writes the index journal (record_many) so the
    # journal_rows inventory tracks corpus size from the first sample
    await JobBuilder(FileIdentifierJob(
        {"location_id": loc["id"], "backend": "cpu"})).spawn(node.jobs, lib)
    await node.jobs.wait_idle()
    port = await node.start_api()
    return node, lib, loc["id"], port, time.monotonic() - t0


def _flatness(passes: list[dict[str, float]]) -> float:
    """Last-half median files/s over first-half median: 1.0 is flat,
    below :data:`FLATNESS_MIN` means warm passes are getting slower —
    the classic O(rows-not-corpus) consult regression."""
    rates = [p["files_per_s"] for p in passes]
    if len(rates) < 2:
        return 1.0
    half = len(rates) // 2
    first, last = sorted(rates[:half] or rates[:1]), sorted(rates[half:])
    med = (lambda s: s[len(s) // 2])
    return round(med(last) / max(1e-9, med(first)), 4)


async def run_soak(files: int | None = None, seconds: float | None = None,
                   seed: int | None = None, out_path: str | None = None,
                   work_dir: str | None = None) -> dict:
    """Drive one full soak; returns (and writes) the BENCH_SCALE doc.
    Parameters default from the SD_SOAK_* knobs. Accelerated runs come
    from the CORE knobs (SD_HISTORY_INTERVAL_S, SD_RESOURCE_INTERVAL_S,
    SD_RESOURCE_WARMUP_S, SD_RESOURCE_TREND_WINDOW_S) — set them before
    this call; the SLO registry is re-seeded here so they take effect
    even after import."""
    import shutil
    import tempfile

    from spacedrive_tpu.telemetry import resources as _resources
    from spacedrive_tpu.telemetry import slo as _slo
    from spacedrive_tpu.telemetry.snapshot import counter_value

    files = files if files is not None else _env_int("SD_SOAK_FILES", 20000)
    seconds = seconds if seconds is not None \
        else float(os.environ.get("SD_SOAK_SECONDS", "120"))
    seed = seed if seed is not None else _env_int("SD_SOAK_SEED", 7)
    mix = parse_mix(os.environ.get("SD_SOAK_MIX", DEFAULT_MIX))
    p2p_on = os.environ.get("SD_SOAK_P2P", "0") == "1"
    faults_on = os.environ.get("SD_SOAK_FAULTS", "0") == "1"
    if p2p_on:
        mix.setdefault("p2p", 1)
    if faults_on:
        mix.setdefault("faults", 1)
    # weighted round-robin deck the rng draws from each round
    deck = [name for name, w in sorted(mix.items()) for _ in range(w)]
    if not deck:
        deck = ["reindex"]

    # re-seed the SLO registry so accelerated trend windows (env set by
    # the caller AFTER telemetry import) are live for this run
    _slo.REGISTRY.reset()

    tmp = work_dir or tempfile.mkdtemp(prefix="sd-bench-scale-")
    own_tmp = work_dir is None
    corpus = os.path.join(tmp, "corpus")
    log(f"bench-scale: {files} sparse files, {seconds:g}s churn, "
        f"seed {seed}, mix {'+'.join(deck)}")
    t_corpus = time.monotonic()
    paths = make_corpus(corpus, files, seed)
    log(f"  corpus built in {time.monotonic() - t_corpus:.1f}s")
    node, lib, loc_id, port, cold_s = await _boot(
        os.path.join(tmp, "node"), corpus)
    mesh = None
    mesh_tasks: set = set()
    try:
        if p2p_on:
            from spacedrive_tpu.p2p.loopback import make_mesh_pair

            a, b, lib_a, lib_b, mesh_tasks = await make_mesh_pair(
                os.path.join(tmp, "mesh"))
            mesh = (a, b, lib_a, lib_b)
        first = node.resources.sample_once()
        rss_peak = first.get("rss_bytes", 0.0)
        driver = SoakDriver(node, lib, loc_id, corpus, paths,
                            random.Random(seed * 7919 + 1),
                            f"http://127.0.0.1:{port}", mesh)
        driver.passes.append({
            "files": files, "seconds": round(cold_s, 3),
            "files_per_s": round(files / max(1e-3, cold_s), 2),
        })
        deadline = time.monotonic() + seconds
        rounds = 0
        while time.monotonic() < deadline:
            await driver.run_round(deck)
            rounds += 1
            rss_peak = max(rss_peak,
                           node.resources.last().get("rss_bytes", 0.0))
            await asyncio.sleep(0)
        last = node.resources.sample_once()
        rss_peak = max(rss_peak, last.get("rss_bytes", 0.0))
        evaluation = _slo.evaluate(node.history)
        trend_docs = {
            s["name"]: {"status": s["status"],
                        **(s.get("windows", {}).get("trend") or {})}
            for s in evaluation["slos"] if s["kind"] == "trend"
        }
        breaches = sorted(s["name"] for s in evaluation["slos"]
                          if s["status"] == _slo.BREACH)
        warns = sorted(s["name"] for s in evaluation["slos"]
                       if s["status"] == _slo.WARN)
        protected = int(
            counter_value("sd_gate_requests_total", klass="control",
                          outcome="shed")
            + counter_value("sd_gate_requests_total", klass="sync",
                            outcome="shed"))
        captures = int(counter_value("sd_profile_captures_total"))
        fd_delta = last.get("fds", 0.0) - first.get("fds", 0.0)
        rss_delta_mb = (last.get("rss_bytes", 0.0)
                        - first.get("rss_bytes", 0.0)) / 1e6
        flat = _flatness(driver.passes)
        doc = {
            "schema": SCHEMA,
            "ts": time.time(),
            "host": {"platform": platform.platform(),
                     "cpus": os.cpu_count()},
            "params": {"files": files, "seconds": seconds, "seed": seed,
                       "mix": mix, "p2p": p2p_on, "faults": faults_on,
                       "rounds": rounds,
                       "resources_enabled": _resources.enabled()},
            "bars": {"fd_delta_max": FD_DELTA_MAX,
                     "rss_delta_max_mb": RSS_DELTA_MAX_MB,
                     "flatness_min": FLATNESS_MIN},
            "scenarios": driver.counts,
            "throughput": {"passes": driver.passes, "flatness": flat},
            "resources": {
                "rss_first_mb": round(first.get("rss_bytes", 0.0) / 1e6, 2),
                "rss_last_mb": round(last.get("rss_bytes", 0.0) / 1e6, 2),
                "rss_peak_mb": round(rss_peak / 1e6, 2),
                "rss_delta_mb": round(rss_delta_mb, 2),
                "fd_first": int(first.get("fds", 0)),
                "fd_last": int(last.get("fds", 0)),
                "fd_delta": int(fd_delta),
                "journal_rows": last.get("journal_rows", 0.0),
                "oplog_rows": last.get("oplog_rows", 0.0),
                "history_bytes": last.get("history_bytes", 0.0),
            },
            "slo": {"status": evaluation["status"], "breaches": breaches,
                    "warns": warns, "trends": trend_docs},
            "protected_sheds": protected,
            "profile_captures": captures,
        }
        doc["verdict"] = {"pass": (
            not breaches
            and protected == 0
            and abs(fd_delta) <= FD_DELTA_MAX
            and rss_delta_mb <= RSS_DELTA_MAX_MB
            and flat >= FLATNESS_MIN
        )}
        out = out_path if out_path is not None else "BENCH_SCALE.json"
        if out:
            with open(out, "w") as f:
                f.write(json.dumps(doc, indent=2) + "\n")
        return doc
    finally:
        for t in mesh_tasks:
            t.cancel()
        if mesh is not None:
            await mesh[0].shutdown()
            await mesh[1].shutdown()
        await node.shutdown()
        if own_tmp:
            shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    doc = asyncio.run(run_soak())
    summary = {k: doc[k] for k in ("scenarios", "throughput", "resources",
                                   "slo", "protected_sheds",
                                   "profile_captures", "verdict")}
    print(json.dumps(summary, indent=2))
    log(f"bench-scale: {'PASS' if doc['verdict']['pass'] else 'FAIL'} "
        f"→ BENCH_SCALE.json")
    return 0 if doc["verdict"]["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
