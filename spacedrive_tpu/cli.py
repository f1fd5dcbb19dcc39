"""sdx — the command-line host.

Parity: two reference hosts in one binary — the headless server
(ref:apps/server/src/main.rs: node + HTTP API) and the crypto
inspector CLI (ref:apps/cli/src/main.rs: prints encrypted-file header
details). Plus the survey's build-plan surface (SURVEY §7 step 4):
`sdx index <path> --backend=tpu|cpu`.

Run as `python -m spacedrive_tpu <command>`.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from typing import Any

DEFAULT_DATA_DIR = os.path.expanduser("~/.spacedrive_tpu")


def _make_node(args: argparse.Namespace, **kwargs: Any):
    from .node import Node

    node = Node(
        args.data_dir,
        use_device=(getattr(args, "backend", "tpu") != "cpu"),
        **kwargs,
    )
    if getattr(args, "no_p2p", False):
        node.config.config.p2p.enabled = False
    return node


async def _get_or_create_library(node, name: str):
    for lib in node.libraries.libraries.values():
        if lib.name == name:
            return lib
    return await node.create_library(name)


# --- commands -------------------------------------------------------------


def device_report(node) -> dict[str, Any]:
    """What the pass actually ran on, not what was asked for: the JAX
    device stamp, where the degradation ladder ended, and every count
    of work that left the device path. All zeros/level 0 on a clean
    device pass; `sdx index` prints it and chip_smoke.py asserts it."""
    from .parallel import mesh as _mesh
    from .telemetry import counter_value
    from .telemetry.events import RESILIENCE_EVENTS

    out: dict[str, Any] = {"device": None}
    if node.use_device:
        import jax

        devs = jax.devices()
        out["device"] = {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        }
    out.update(
        ladder_level=int(_mesh.LADDER.level),
        cas_backend_fallbacks=int(
            counter_value("sd_cas_backend_fallback_total")),
        thumbnail_cpu_fallbacks=sum(
            1 for e in RESILIENCE_EVENTS.snapshot()
            if e["type"] == "thumbnail_cpu_fallback"
        ),
        thumbnail_errors=node.thumbnailer.errors,
    )
    return out


def chain_reports(lib, root_id) -> list:
    """JobReports of one spawned chain: the root job and every job
    chained under it (a FAILED job spawns no successor, so a short
    list is itself a finding)."""
    from .jobs.report import JobReport

    out, frontier = [], [root_id.bytes]
    while frontier:
        job_id = frontier.pop()
        row = lib.db.find_one("job", id=job_id)
        if row is not None:
            out.append(JobReport.from_row(row))
        frontier.extend(
            r["id"] for r in lib.db.query(
                "SELECT id FROM job WHERE parent_id = ?", (job_id,)
            )
        )
    return out


def _job_seconds(report) -> float | None:
    """Wall seconds a settled job ran, from its own report."""
    from datetime import datetime

    if not (report.started_at and report.completed_at):
        return None
    return round((datetime.fromisoformat(report.completed_at)
                  - datetime.fromisoformat(report.started_at)
                  ).total_seconds(), 2)


async def index_location(node, path: str, library: str, backend: str) -> dict:
    """Index one location on a STARTED node and summarize what ran —
    the body of `sdx index`, shared with chip_smoke.py so the smoke
    drives the same calls a user's command does."""
    from .jobs.report import JobStatus
    from .location.locations import LocationCreateArgs, scan_location
    from .node.statistics import update_statistics

    lib = await _get_or_create_library(node, library)
    existing = lib.db.find_one("location", path=os.path.abspath(path))
    t0 = time.perf_counter()
    loc = existing or LocationCreateArgs(path=path).create(lib)
    root_id = await scan_location(lib, loc, node.jobs, backend=backend)
    await node.jobs.wait_idle()
    await node.thumbnailer.wait_library_batch(str(lib.id))
    elapsed = time.perf_counter() - t0
    stats = update_statistics(lib.db, node.thumbnailer.data_dir)
    reports = chain_reports(lib, root_id)
    return {
        "library": lib.name,
        "library_id": str(lib.id),
        "location_id": loc["id"],
        "files": lib.db.count("file_path", "is_dir = 0"),
        "objects": stats["total_object_count"],
        "bytes": int(stats["total_bytes_used"]),
        "thumbnails": node.thumbnailer.generated,
        "labeled": node.image_labeler.labeled if node.image_labeler else 0,
        "backend": backend,
        **device_report(node),
        "jobs": {r.name: r.status.name for r in reports},
        "job_seconds": {r.name: _job_seconds(r) for r in reports},
        "jobs_failed": sum(r.status == JobStatus.FAILED for r in reports),
        "seconds": round(elapsed, 2),
    }


async def cmd_index(args: argparse.Namespace) -> int:
    node = _make_node(args)
    await node.start()
    try:
        summary = await index_location(
            node, args.path, args.library, args.backend)
        print(json.dumps(summary))
        return 1 if summary["jobs_failed"] else 0
    finally:
        await node.shutdown()


async def cmd_serve(args: argparse.Namespace) -> int:
    node = _make_node(args)
    await node.start()
    port = await node.start_api(host=args.host, port=args.port)
    print(f"sdx serving on http://{args.host}:{port}  (rspc: /rspc/<key>)")
    if node.p2p is not None:
        print(f"p2p on port {node.p2p.port}, identity {node.p2p.p2p.remote_identity}")
        if args.auto_accept_pairing:
            node.p2p.pairing.auto_accept = True
            print("pairing: auto-accept enabled")
    elif args.auto_accept_pairing:
        print("warning: --auto-accept-pairing ignored (p2p disabled)",
              file=sys.stderr)
    if args.cloud:
        # persist the origin even with zero libraries yet — libraries
        # created later enable against it via cloud.sync.enable
        node.config.config.preferences["cloud_api_origin"] = args.cloud
        node.config.save()
        enabled = 0
        for lib in list(node.libraries.libraries.values()):
            try:
                await node.enable_cloud_sync(lib)
                enabled += 1
            except Exception as e:
                print(f"cloud sync for {lib.name!r} failed: {e}", file=sys.stderr)
        print(f"cloud sync: {args.cloud} ({enabled} libraries enabled)")
    try:
        while True:
            await asyncio.sleep(3600)
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        await node.shutdown()
    return 0


async def cmd_relay(args: argparse.Namespace) -> int:
    """Run the standalone self-hosted relay: WAN sync collections over
    HTTP + the P2P rendezvous (authenticated listen/dial splicing) —
    the deployable form of what the reference's closed cloud provides."""
    from .cloud.relay import CloudRelay
    from .p2p.relay import RelayLimits

    relay = CloudRelay(p2p_limits=RelayLimits(
        max_pipes_per_target=args.max_pipes_per_target,
        max_pipes_total=args.max_pipes,
        pipe_rate_bytes_per_s=args.pipe_rate,
    ))
    port = await relay.start(host=args.host, port=args.port,
                             p2p_port=args.p2p_port)
    print(f"relay: sync API on http://{args.host}:{port}/api  "
          f"(point nodes' --cloud at http://{args.host}:{port})")
    print(f"relay: p2p rendezvous on {args.host}:{relay.p2p_port}  "
          f"(point nodes' p2p.relay at {args.host}:{relay.p2p_port})")
    try:
        while True:
            await asyncio.sleep(args.stats_interval or 3600)
            if args.stats_interval:
                s = relay.p2p_relay.stats.snapshot()
                print(f"relay stats: {json.dumps(s)}", flush=True)
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        await relay.shutdown()
    return 0


async def cmd_status(args: argparse.Namespace) -> int:
    node = _make_node(args, with_labeler=False)
    await node.start()
    try:
        out = await node.router.exec(node, "nodeState")
        out["libraries"] = []
        for lib in node.libraries.libraries.values():
            reports = await node.router.exec(
                node, "jobs.reports", library_id=str(lib.id)
            )
            out["libraries"].append(
                {
                    "id": str(lib.id),
                    "name": lib.name,
                    "file_paths": lib.db.count("file_path"),
                    "objects": lib.db.count("object"),
                    "recent_jobs": reports[:5],
                }
            )
        print(json.dumps(out, indent=2))
        return 0
    finally:
        await node.shutdown()


async def cmd_browse(args: argparse.Namespace) -> int:
    from .location.non_indexed import walk_dir

    node = _make_node(args, with_labeler=False)
    try:
        listing = walk_dir(node, args.path, with_hidden=args.hidden,
                           queue_thumbnails=False)
        for e in listing["entries"]:
            kind = "dir " if e["is_dir"] else "file"
            print(f"{kind}  {e['size_in_bytes']:>12}  {e['name']}"
                  + (f".{e['extension']}" if e["extension"] else ""))
        return 0
    finally:
        await node.shutdown()


async def cmd_duplicates(args: argparse.Namespace) -> int:
    from .jobs.manager import JobBuilder
    from .object.duplicates import DuplicateDetectorJob, find_duplicates

    node = _make_node(args, with_labeler=False)
    await node.start()
    try:
        lib = await _get_or_create_library(node, args.library)
        await JobBuilder(
            DuplicateDetectorJob({"threshold": args.threshold})
        ).spawn(node.jobs, lib)
        await node.jobs.wait_idle()
        groups = find_duplicates(lib, threshold=args.threshold)
        print(json.dumps(groups, indent=2))
        return 0
    finally:
        await node.shutdown()


async def cmd_search(args: argparse.Namespace) -> int:
    """Search an indexed library: plain name match by default,
    `--semantic` scores the query against the vector index (the query
    is an image path to embed, or a label name whose objects' centroid
    becomes the probe)."""
    from .api.search import search_paths, search_semantic

    node = _make_node(args, with_labeler=False)
    await node.start()
    try:
        lib = await _get_or_create_library(node, args.library)
        if args.semantic:
            out = search_semantic(
                lib, {"query": args.query, "take": args.take}
            )
            if not out.get("resolved"):
                print(
                    "query resolved to no probe vector (not an image "
                    "path or a stored label name)",
                    file=sys.stderr,
                )
                return 1
        else:
            out = search_paths(
                lib,
                {"filter": {"search": args.query}, "take": args.take},
            )
        print(json.dumps(out, indent=2, default=str))
        return 0
    finally:
        await node.shutdown()


import contextlib


@contextlib.asynccontextmanager
async def _mesh_node(args: argparse.Namespace):
    """Started node with p2p up and discovery settled, or SystemExit(1)."""
    node = _make_node(args, with_labeler=False)
    await node.start()
    try:
        if node.p2p is None:
            print("p2p is disabled in the node config", file=sys.stderr)
            raise SystemExit(1)
        await asyncio.sleep(args.wait)  # let discovery settle
        yield node
    finally:
        await node.shutdown()


async def cmd_peers(args: argparse.Namespace) -> int:
    """Discover mesh peers for a few seconds and list them."""
    async with _mesh_node(args) as node:
        peers = node.p2p.p2p.discovered_peers()
        for p in peers:
            print(
                json.dumps(
                    {
                        "identity": str(p.identity),
                        "name": p.metadata.get("name"),
                        "os": p.metadata.get("operating_system"),
                        "libraries": [
                            x for x in p.metadata.get("libraries", "").split(",") if x
                        ],
                        "addrs": sorted(f"{h}:{pt}" for h, pt in p.addrs),
                    }
                )
            )
        if not peers:
            print("no peers discovered", file=sys.stderr)
        return 0


async def cmd_pair(args: argparse.Namespace) -> int:
    """Join a peer's library over the mesh (consent happens on the peer)."""
    import uuid

    from .p2p.identity import RemoteIdentity

    async with _mesh_node(args) as node:
        try:
            lib = await node.p2p.pairing.join(
                node.p2p.p2p,
                RemoteIdentity.from_str(args.identity),
                uuid.UUID(args.library) if args.library else None,
            )
        except PermissionError as e:
            print(f"rejected: {e}", file=sys.stderr)
            return 1
        except asyncio.TimeoutError:
            print("peer did not respond (offline, or consent timed out)",
                  file=sys.stderr)
            return 1
        except FileExistsError as e:
            print(str(e), file=sys.stderr)
            return 1
        except (ValueError, ConnectionError) as e:
            print(f"pairing failed: {e}", file=sys.stderr)
            return 1
        print(json.dumps({"library": str(lib.id), "name": lib.name}))
        # give the first sync pull a moment before tearing down
        await asyncio.sleep(2)
        return 0


async def cmd_spacedrop(args: argparse.Namespace) -> int:
    """Send files to a peer (they accept/reject on their end)."""
    from .p2p.identity import RemoteIdentity

    async with _mesh_node(args) as node:
        try:
            drop_id = await node.p2p.spacedrop.send(
                RemoteIdentity.from_str(args.identity), list(args.files)
            )
        except PermissionError as e:
            print(f"rejected: {e}", file=sys.stderr)
            return 1
        except asyncio.TimeoutError:
            print("peer did not respond", file=sys.stderr)
            return 1
        except (ValueError, ConnectionError, OSError) as e:
            print(f"spacedrop failed: {e}", file=sys.stderr)
            return 1
        print(json.dumps({"drop_id": str(drop_id), "sent": len(args.files)}))
        return 0


def _http_get(url: str, timeout: float = 30.0) -> str:
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


async def cmd_mesh_status(args: argparse.Namespace) -> int:
    """Mesh-wide observability: every known peer's latest telemetry
    snapshot with staleness marking, plus this node's own health.
    With --url, reads a running node's GET /mesh; otherwise boots an
    ephemeral mesh node, discovers peers, and pulls directly."""
    if args.url:
        import urllib.error

        url = args.url.rstrip("/") + "/mesh"
        if args.no_refresh:
            url += "?refresh=0"
        try:
            doc = await asyncio.to_thread(_http_get, url)
        except (urllib.error.URLError, OSError) as e:
            print(f"mesh-status: cannot reach {url}: {e}", file=sys.stderr)
            print("is a node running? start one with `sdx serve`",
                  file=sys.stderr)
            return 1
        _write_or_print(json.dumps(json.loads(doc), indent=2), args.out)
        return 0

    from .telemetry.federation import mesh_status

    async with _mesh_node(args) as node:
        await node.p2p.refresh_federation(force=True)
        status = mesh_status(node)
        _write_or_print(json.dumps(status, indent=2, default=str), args.out)
        peers = status["mesh"]["peers"]
        if not peers:
            print("no peers in the federation cache (none discovered?)",
                  file=sys.stderr)
        return 0


async def cmd_serve_status(args: argparse.Namespace) -> int:
    """Serve-layer posture: admission-gate mode, per-class
    inflight/queued/shed counts, and read-cache occupancy. With --url,
    reads a running node's rspc telemetry.serve; otherwise boots an
    ephemeral node and reports its (idle) gate state."""
    if args.url:
        import urllib.error
        import urllib.request

        url = args.url.rstrip("/") + "/rspc/telemetry.serve"
        req = urllib.request.Request(
            url, data=b"{}", headers={"Content-Type": "application/json"},
            method="POST",
        )

        def post() -> str:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.read().decode()

        try:
            doc = await asyncio.to_thread(post)
        except (urllib.error.URLError, OSError) as e:
            print(f"serve-status: cannot reach {url}: {e}", file=sys.stderr)
            print("is a node running? start one with `sdx serve`",
                  file=sys.stderr)
            return 1
        _write_or_print(
            json.dumps(json.loads(doc).get("result"), indent=2), args.out
        )
        return 0

    from .node import Node
    from .serve import runtime_for

    node = Node(args.data_dir, use_device=False, with_labeler=False)
    try:
        serve = runtime_for(node)
        doc = (
            {"enabled": False} if serve is None
            else {"enabled": True, **serve.snapshot()}
        )
        _write_or_print(json.dumps(doc, indent=2, default=str), args.out)
        return 0
    finally:
        await node.shutdown()


async def cmd_tenants(args: argparse.Namespace) -> int:
    """Per-tenant accounting: the space-saving heavy-hitter sketches
    (telemetry/tenants.py) — per-surface totals, resident top-K with
    error bounds, fairness index, dominant share. Tenant keys are
    hashed labels, never raw UUIDs. With --url, reads a running
    node's GET /tenants; with --peer, shows the named mesh peer's
    federated tenant digest; otherwise boots an ephemeral mesh node
    and shows the mesh-wide digests."""
    if args.url:
        import urllib.error

        url = args.url.rstrip("/") + "/tenants"
        try:
            doc = await asyncio.to_thread(_http_get, url)
        except (urllib.error.URLError, OSError) as e:
            print(f"tenants: cannot reach {url}: {e}", file=sys.stderr)
            print("is a node running? start one with `sdx serve`",
                  file=sys.stderr)
            return 1
        _write_or_print(json.dumps(json.loads(doc), indent=2), args.out)
        return 0

    from .telemetry.federation import mesh_status

    async with _mesh_node(args) as node:
        await node.p2p.refresh_federation(force=True)
        mesh = mesh_status(node)["mesh"]
        from .telemetry import tenants as _tenants_mod

        peers = {
            pid: {
                "peer_label": p.get("peer_label"),
                "stale": p.get("stale"),
                "tenants": (p.get("snapshot") or {}).get("tenants"),
            }
            for pid, p in mesh.get("peers", {}).items()
        }
        if args.peer:
            want = args.peer
            match = {
                pid: p for pid, p in peers.items()
                if want in (pid, p.get("peer_label"))
                or pid.startswith(want)
            }
            if not match:
                print(f"tenants: no mesh peer matches {want!r} "
                      f"(known: {sorted(peers)})", file=sys.stderr)
                return 1
            doc: dict = {"peers": match}
        else:
            doc = {"local": _tenants_mod.snapshot(), "peers": peers}
        _write_or_print(json.dumps(doc, indent=2, default=str), args.out)
        return 0


def cmd_crypto(args: argparse.Namespace) -> int:
    from .crypto import FileHeader, decrypt_file, encrypt_file

    if args.crypto_cmd == "inspect":
        # ref:apps/cli/src/main.rs — print header details
        with open(args.file, "rb") as f:
            header, raw = FileHeader.from_reader(f)
        print(
            json.dumps(
                {
                    "version": header.version,
                    "algorithm": header.algorithm.name,
                    "keyslots": [
                        {
                            "hashing": ks.hashing_algorithm.kind,
                            "params": int(ks.hashing_algorithm.params),
                        }
                        for ks in header.keyslots
                    ],
                    "has_metadata": header.metadata is not None,
                    "has_preview_media": header.preview_media is not None,
                    "header_bytes": len(raw),
                },
                indent=2,
            )
        )
    elif args.crypto_cmd == "encrypt":
        import getpass

        pw = args.password or getpass.getpass("password: ")
        encrypt_file(args.file, args.file + ".sdenc", pw.encode())
        print(f"wrote {args.file}.sdenc")
    elif args.crypto_cmd == "decrypt":
        import getpass

        pw = args.password or getpass.getpass("password: ")
        out = (
            args.file[: -len(".sdenc")]
            if args.file.endswith(".sdenc")
            else args.file + ".decrypted"
        )
        meta = decrypt_file(args.file, out, pw.encode())
        print(f"wrote {out}" + (f"  metadata: {meta}" if meta else ""))
    return 0


def cmd_labeler(args: argparse.Namespace) -> int:
    """Provision/inspect the image-labeler model artifact.

    The reference downloads pretrained YOLOv8 before labeling can run
    (ref:crates/ai/src/image_labeler/model/yolov8.rs:45-88); offline
    deployments instead train a checkpoint here (`sdx labeler train`)
    or drop any `.onnx` classifier at <data-dir>/image_labeler/model.onnx.
    """
    labeler_dir = os.path.join(args.data_dir, "image_labeler")
    if args.labeler_cmd == "provision":
        from .models import provision

        try:
            classes = None
            if args.classes:
                with open(args.classes) as f:
                    classes = [ln.strip() for ln in f if ln.strip()]
            if args.bundled:
                if args.src or args.url or args.sha256 or args.classes:
                    raise ValueError(
                        "--bundled installs the pinned in-package artifact; "
                        "it cannot combine with --from/--url/--sha256/--classes"
                    )
                info = provision.install_bundled(labeler_dir)
            elif args.src:
                info = provision.import_artifact(
                    args.src, labeler_dir, classes=classes,
                    sha256=args.sha256,
                )
            else:
                url = args.url or provision.DEFAULT_MODEL_URL
                print(f"downloading {url}…", file=sys.stderr, flush=True)
                info = provision.fetch(url, labeler_dir, classes=classes,
                                       sha256=args.sha256)
        except Exception as e:  # noqa: BLE001 - CLI contract: JSON + rc 1
            print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
            return 1
        print(json.dumps(info, indent=2))
        return 0
    if args.labeler_cmd == "status":
        from .models.labeler_actor import ImageLabeler

        actor = ImageLabeler(labeler_dir)
        artifact = actor.resolve_artifact()
        info = {"artifact": None, "enabled": False}
        if artifact is not None:
            info = {"artifact": {"kind": artifact[0], "path": artifact[1]},
                    "enabled": True}
            if artifact[0] == "checkpoint":
                from .models import checkpoint

                _params, meta = checkpoint.load(artifact[1])
                info["classes"] = len(meta["classes"])
                info["image_size"] = meta["image_size"]
                info["metrics"] = meta.get("metrics", {})
        print(json.dumps(info, indent=2))
        return 0
    if args.labeler_cmd == "train":
        from .models.train import TrainConfig, train_folder

        cfg = TrainConfig(
            image_size=args.image_size, batch_size=args.batch_size,
            steps=args.steps, learning_rate=args.lr,
            use_device=args.backend != "cpu",
        )
        out = args.out or os.path.join(labeler_dir, "weights.npz")
        metrics = train_folder(
            args.dataset, out, cfg,
            progress=lambda step, loss: print(
                f"step {step}/{cfg.steps}  loss {loss:.4f}", flush=True
            ),
        )
        print(json.dumps({"checkpoint": out, "metrics": metrics}, indent=2))
        return 0
    if args.labeler_cmd == "train-demo":
        import numpy as np

        from .models import checkpoint as ckpt_mod
        from .models.train import (
            TrainConfig, array_batches, digits_demo_dataset, train,
        )

        cfg = TrainConfig(
            image_size=32, widths=(8, 16, 32, 32, 32), depths=(1, 1, 1, 1),
            batch_size=64, steps=args.steps,
            use_device=args.backend != "cpu",
        )
        (tr_x, tr_y), (ev_x, ev_y), classes = digits_demo_dataset(cfg.image_size)
        params, _model, metrics = train(
            array_batches(tr_x, tr_y, cfg.batch_size), classes, cfg,
            eval_set=(ev_x, ev_y),
            progress=lambda step, loss: print(
                f"step {step}/{cfg.steps}  loss {loss:.4f}", flush=True
            ),
        )
        out = args.out or os.path.join(labeler_dir, "weights.npz")
        ckpt_mod.save(out, params, classes=classes, image_size=cfg.image_size,
                      widths=cfg.widths, depths=cfg.depths,
                      extra={"metrics": metrics, "trained_on": "sklearn-digits"})
        print(json.dumps({"checkpoint": out, "metrics": metrics}, indent=2))
        return 0
    return 2


def _write_or_print(doc: str, out: str | None) -> None:
    if out:
        with open(out, "w") as f:
            f.write(doc + "\n")
        print(f"wrote {out} ({len(doc)} bytes)", file=sys.stderr)
    else:
        print(doc)


def cmd_trace_export(args: argparse.Namespace) -> int:
    """Fetch a running node's Chrome-trace JSON (GET /trace) — load the
    output in Perfetto (ui.perfetto.dev) or chrome://tracing."""
    import urllib.error
    import urllib.request

    url = args.url.rstrip("/") + "/trace"
    if args.trace_id:
        url += f"?trace_id={args.trace_id}"
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            doc = resp.read().decode()
    except (urllib.error.URLError, OSError) as e:
        print(f"trace-export: cannot reach {url}: {e}", file=sys.stderr)
        print("is a node running? start one with `sdx serve`", file=sys.stderr)
        return 1
    # refuse to write a non-trace artifact (a proxy error page, a
    # different server on that port) — with a message, not a traceback
    try:
        parsed = json.loads(doc)
        events = parsed["traceEvents"]
    except (ValueError, TypeError, KeyError):
        print(f"trace-export: {url} did not return Chrome-trace JSON "
              f"(is that really an sdx node?)", file=sys.stderr)
        return 1
    print(f"trace-export: {len(events)} events", file=sys.stderr)
    _write_or_print(json.dumps(parsed, indent=2), args.out)
    return 0


def cmd_attrib(args: argparse.Namespace) -> int:
    """Critical-path attribution report from a running node: where the
    last pass's (or --trace-id's) wall-clock went — device / host_cpu /
    link / queue_wait / unattributed-gap — with executor-side spans
    pulled from mesh peers (docs/observability.md "Attribution,
    history, and SLOs")."""
    import urllib.error
    import urllib.parse
    import urllib.request

    url = args.url.rstrip("/") + "/attrib"
    query = {}
    if args.trace_id:
        query["trace_id"] = args.trace_id
    if args.refresh:
        query["refresh"] = "1"
    if query:
        url += "?" + urllib.parse.urlencode(query)
    try:
        with urllib.request.urlopen(url, timeout=30) as resp:
            doc = json.loads(resp.read().decode())
    except (urllib.error.URLError, OSError, ValueError) as e:
        print(f"attrib: cannot reach {url}: {e}", file=sys.stderr)
        print("is a node running? start one with `sdx serve`",
              file=sys.stderr)
        return 1
    if doc.get("error"):
        print(f"attrib: {doc['error']}", file=sys.stderr)
        return 1
    _write_or_print(json.dumps(doc, indent=2), args.out)
    buckets = doc.get("buckets") or {}
    if buckets:
        wall = doc.get("wall_seconds") or 0.0
        split = "  ".join(
            f"{k}={v:.2f}s" for k, v in sorted(
                buckets.items(), key=lambda kv: kv[1], reverse=True)
        )
        print(f"attrib: {wall:.2f}s critical path — {split}",
              file=sys.stderr)
    return 0


async def cmd_profile_peer(args: argparse.Namespace) -> int:
    """Pull a MESH PEER's host profile over the TELEMETRY wire
    (profile_pull — the same library-members-only trust bar as
    trace_pull; frame names are module:function only, so nothing
    needing redaction rides the wire)."""
    from .p2p.identity import RemoteIdentity
    from .p2p.manager import SYNC_POLICY
    from .p2p.operations import request_profile
    from .utils.resilience import BreakerOpen

    async with _mesh_node(args) as node:
        try:
            doc = await SYNC_POLICY.call(
                args.peer,
                lambda: request_profile(
                    node.p2p.p2p, RemoteIdentity.from_str(args.peer)
                ),
            )
        except PermissionError as e:
            print(f"profile: peer refused: {e}", file=sys.stderr)
            return 1
        except (BreakerOpen, ValueError, ConnectionError, OSError,
                EOFError, asyncio.TimeoutError) as e:
            print(f"profile: cannot reach peer: {e}", file=sys.stderr)
            return 1
        if args.folded:
            _write_or_print(str(doc.get("folded", "")).rstrip("\n"),
                            args.out)
        else:
            _write_or_print(json.dumps(doc.get("profile"), indent=2),
                            args.out)
        return 0


def cmd_profile(args: argparse.Namespace) -> int:
    """Host-profile read path: the continuous sampler's collapsed-stack
    view from a running node (--url, default), or pulled from a mesh
    peer (--peer). --folded emits flamegraph.pl collapsed-stack text —
    pipe it into flamegraph.pl / speedscope."""
    if args.peer:
        return asyncio.run(cmd_profile_peer(args))
    import urllib.error

    url = args.url.rstrip("/") + "/profile"
    if args.folded:
        url += "?format=folded"
    elif args.mesh:
        url += "?mesh=1"
    try:
        doc = _http_get(url)
    except (urllib.error.URLError, OSError) as e:
        print(f"profile: cannot reach {url}: {e}", file=sys.stderr)
        print("is a node running? start one with `sdx serve`",
              file=sys.stderr)
        return 1
    if args.folded:
        _write_or_print(doc.rstrip("\n"), args.out)
        return 0
    try:
        parsed = json.loads(doc)
    except ValueError:
        print(f"profile: {url} did not return JSON "
              f"(is that really an sdx node?)", file=sys.stderr)
        return 1
    _write_or_print(json.dumps(parsed, indent=2), args.out)
    local = parsed.get("local") if args.mesh else parsed
    if isinstance(local, dict) and local.get("enabled"):
        groups = local.get("frame_groups") or []
        split = "  ".join(
            f"{g['group']}={g['share']:.0%}" for g in groups[:5]
        )
        print(f"profile: {local.get('samples', 0)} samples over "
              f"{local.get('duration_s', 0)}s — {split}", file=sys.stderr)
    return 0


def cmd_slo(args: argparse.Namespace) -> int:
    """SLO burn-rate posture. With --url, the live evaluation from a
    running node (rspc telemetry.slo); otherwise evaluated offline over
    the data dir's persistent telemetry history — which survives
    restarts, so this reads a continuous series across node
    generations."""
    if args.url:
        import urllib.error
        import urllib.request

        url = args.url.rstrip("/") + "/rspc/telemetry.slo"
        req = urllib.request.Request(
            url, data=b"{}", headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                payload = json.loads(resp.read().decode())
        except (urllib.error.URLError, OSError, ValueError) as e:
            print(f"slo: cannot reach {url}: {e}", file=sys.stderr)
            print("is a node running? start one with `sdx serve`",
                  file=sys.stderr)
            return 1
        doc = payload.get("result")
    else:
        from .telemetry import slo as _slo
        from .telemetry.history import history_dir

        doc = _slo.evaluate(directory=history_dir(args.data_dir))
    _write_or_print(json.dumps(doc, indent=2), args.out)
    if isinstance(doc, dict):
        for s in doc.get("slos") or []:
            print(f"slo: {s['name']}: {s['status']}"
                  + (f"  (current {s['current']:g})"
                     if isinstance(s.get("current"), (int, float)) else ""),
                  file=sys.stderr)
    return 0


async def cmd_debug_bundle_peer(args: argparse.Namespace) -> int:
    """Pull a REMOTE node's debug bundle across the mesh. The bundle is
    built — and fully redacted — by the OWNING node before anything
    touches the wire (telemetry.bundle runs there); this side only
    receives the already-clean artifact. The peer must have the
    remoteRspc feature enabled."""
    from .p2p.identity import RemoteIdentity
    from .p2p.rspc import RSPC_POLICY, RemoteRspcError, remote_exec

    async with _mesh_node(args) as node:
        try:
            bundle = await RSPC_POLICY.call(
                args.peer,
                lambda: remote_exec(
                    node.p2p.p2p,
                    RemoteIdentity.from_str(args.peer),
                    "telemetry.debug_bundle",
                ),
            )
        except RemoteRspcError as e:
            print(f"debug-bundle: peer refused: {e} (code {e.code})",
                  file=sys.stderr)
            if e.code == 403:
                print("the peer must enable the remoteRspc feature "
                      "(toggleFeature remoteRspc)", file=sys.stderr)
            return 1
        except (ValueError, ConnectionError, OSError, EOFError,
                asyncio.TimeoutError) as e:
            print(f"debug-bundle: cannot reach peer: {e}", file=sys.stderr)
            return 1
        _write_or_print(json.dumps(bundle, indent=2), args.out)
        return 0


def cmd_debug_bundle(args: argparse.Namespace) -> int:
    """The redacted debug bundle: from a running node (--url) with live
    metrics/rings, from a mesh peer (--peer, redacted on the owning
    node), or offline straight off the data dir."""
    from .telemetry.bundle import render_bundle

    if args.peer:
        return asyncio.run(cmd_debug_bundle_peer(args))
    if args.url:
        import urllib.error
        import urllib.request

        url = args.url.rstrip("/") + "/rspc/telemetry.debug_bundle"
        req = urllib.request.Request(
            url, data=b"{}", headers={"Content-Type": "application/json"},
            method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                payload = json.loads(resp.read().decode())
        except (urllib.error.URLError, OSError) as e:
            print(f"debug-bundle: cannot reach {url}: {e}", file=sys.stderr)
            return 1
        doc = json.dumps(payload.get("result"), indent=2)
    else:
        doc = render_bundle(data_dir=args.data_dir)
    _write_or_print(doc, args.out)
    return 0


# --- argument parsing -----------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="sdx", description=__doc__)
    p.add_argument("--data-dir", default=DEFAULT_DATA_DIR)
    p.add_argument(
        "--faults", metavar="PLAN", default=None,
        help="arm the fault-injection plane for this invocation "
             "(chaos testing): \"point:mode[:k=v,...][;...]\" — see "
             "docs/robustness.md; SD_FAULTS/SD_FAULT_SEED are the env "
             "equivalents",
    )
    p.add_argument("--fault-seed", type=int, default=0,
                   help="deterministic seed for --faults probabilities")
    sub = p.add_subparsers(dest="cmd", required=True)

    ix = sub.add_parser("index", help="index a directory into a library")
    ix.add_argument("path")
    ix.add_argument("--backend", choices=["tpu", "cpu", "auto"], default="auto")
    ix.add_argument("--library", default="default")
    ix.add_argument("--no-p2p", action="store_true")

    sv = sub.add_parser("serve", help="run the node + HTTP API")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=8080)
    sv.add_argument("--backend", choices=["tpu", "cpu"], default="tpu")
    sv.add_argument("--auto-accept-pairing", action="store_true",
                    help="headless nodes: accept library joins without a prompt")
    sv.add_argument("--cloud", metavar="ORIGIN",
                    help="enable cloud sync for all libraries against this relay")

    st = sub.add_parser("status", help="node + library status")
    st.add_argument("--no-p2p", action="store_true", default=True)

    lic = sub.add_parser(
        "licenses",
        help="dependency + license inventory (the deps-generator role)",
    )
    lic.add_argument("--out", help="write JSON here instead of stdout")

    br = sub.add_parser("browse", help="ephemeral (non-indexed) listing")
    br.add_argument("path")
    br.add_argument("--hidden", action="store_true")

    du = sub.add_parser("duplicates", help="find duplicate images")
    du.add_argument("--library", default="default")
    du.add_argument("--threshold", type=int, default=8)
    du.add_argument("--no-p2p", action="store_true", default=True)

    se = sub.add_parser("search", help="search an indexed library")
    se.add_argument("query", help="name substring; with --semantic, an "
                    "image path or stored label name")
    se.add_argument("--library", default="default")
    se.add_argument("--semantic", action="store_true",
                    help="vector-index cosine top-k instead of name match")
    se.add_argument("--take", type=int, default=10)
    se.add_argument("--no-p2p", action="store_true", default=True)

    pe = sub.add_parser("peers", help="discover and list mesh peers")
    pe.add_argument("--wait", type=float, default=3.0)

    pa = sub.add_parser("pair", help="join a peer's library")
    pa.add_argument("identity", help="the peer's identity string (sdx peers)")
    pa.add_argument("--library", help="library uuid (default: peer's first)")
    pa.add_argument("--wait", type=float, default=3.0)

    sd = sub.add_parser("spacedrop", help="send files to a peer")
    sd.add_argument("identity")
    sd.add_argument("files", nargs="+")
    sd.add_argument("--wait", type=float, default=3.0)

    cr = sub.add_parser("crypto", help="encrypted-file tools")
    crs = cr.add_subparsers(dest="crypto_cmd", required=True)
    for name in ("inspect", "encrypt", "decrypt"):
        c = crs.add_parser(name)
        c.add_argument("file")
        if name != "inspect":
            c.add_argument("--password")

    lb = sub.add_parser("labeler", help="image-labeler model artifacts")
    lbs = lb.add_subparsers(dest="labeler_cmd", required=True)
    lbs.add_parser("status", help="show the provisioned model artifact")
    lp = lbs.add_parser(
        "provision",
        help="install a pretrained model: download (default) or import a local file",
    )
    lp.add_argument(
        "--from", dest="src",
        help="local .onnx classifier or .npz checkpoint to import "
             "(default: download --url)",
    )
    lp.add_argument(
        "--bundled", action="store_true",
        help="install the in-package offline artifact (trained digits "
             "classifier, sha256-pinned) — works air-gapped",
    )
    lp.add_argument(
        "--url", default=None,
        help="ONNX download URL (default: the official YOLOv8n release asset)",
    )
    lp.add_argument(
        "--sha256", default=None,
        help="pin the download's sha256; mismatch aborts before install",
    )
    lp.add_argument(
        "--classes",
        help="text file of class names, one per line (stored as classes.json)",
    )
    lt = lbs.add_parser("train", help="train a checkpoint on a folder-per-class dataset")
    lt.add_argument("dataset", help="root dir: <root>/<class_name>/*.jpg")
    lt.add_argument("--out", help="checkpoint path (default: <data-dir>/image_labeler/weights.npz)")
    lt.add_argument("--image-size", type=int, default=96)
    lt.add_argument("--batch-size", type=int, default=32)
    lt.add_argument("--steps", type=int, default=600)
    lt.add_argument("--lr", type=float, default=1e-3)
    lt.add_argument("--backend", choices=["tpu", "cpu"], default="tpu")
    ld = lbs.add_parser("train-demo", help="self-contained demo: train on bundled digit scans")
    ld.add_argument("--out")
    ld.add_argument("--steps", type=int, default=300)
    ld.add_argument("--backend", choices=["tpu", "cpu"], default="tpu")

    rl = sub.add_parser(
        "relay", help="run the standalone sync relay + P2P rendezvous"
    )
    rl.add_argument("--host", default="0.0.0.0")
    rl.add_argument("--port", type=int, default=8490)
    rl.add_argument("--p2p-port", type=int, default=8491)
    rl.add_argument("--max-pipes-per-target", type=int, default=8,
                    help="concurrent relayed pipes per listening identity")
    rl.add_argument("--max-pipes", type=int, default=256,
                    help="concurrent relayed pipes across the relay")
    rl.add_argument("--pipe-rate", type=int, default=None, metavar="BYTES_PER_S",
                    help="per-direction byte-rate cap per pipe (default unlimited)")
    rl.add_argument("--stats-interval", type=float, default=60.0,
                    help="seconds between stats log lines (0 disables)")

    te = sub.add_parser(
        "trace-export",
        help="export a running node's span ring as Perfetto-loadable "
             "Chrome-trace JSON",
    )
    te.add_argument("--url", default="http://127.0.0.1:8080",
                    help="the node's HTTP API origin (sdx serve)")
    te.add_argument("--trace-id", default=None,
                    help="filter to one trace id (hex)")
    te.add_argument("--out", help="write JSON here instead of stdout")

    db = sub.add_parser(
        "debug-bundle",
        help="redacted diagnostic bundle: config (secrets stripped), "
             "metrics, spans, flight-recorder rings, versions/env",
    )
    db.add_argument("--url", default=None,
                    help="pull the bundle from a running node instead of "
                         "building offline from --data-dir")
    db.add_argument("--peer", default=None, metavar="IDENTITY",
                    help="pull a MESH PEER's bundle (redacted on the owning "
                         "node before it rides the wire; the peer must have "
                         "remoteRspc enabled)")
    db.add_argument("--wait", type=float, default=3.0,
                    help="discovery settle time before dialing --peer")
    db.add_argument("--out", help="write JSON here instead of stdout")

    at = sub.add_parser(
        "attrib",
        help="critical-path attribution: where the last pass's "
             "wall-clock went (device / host_cpu / link / queue_wait / "
             "unattributed-gap), mesh-wide",
    )
    at.add_argument("trace_id", nargs="?", default=None,
                    help="trace id (hex; default: the last completed pass)")
    at.add_argument("--url", default="http://127.0.0.1:8080",
                    help="the node's HTTP API origin (sdx serve)")
    at.add_argument("--refresh", action="store_true",
                    help="bypass the report cache and re-pull mesh peers")
    at.add_argument("--out", help="write JSON here instead of stdout")

    pf = sub.add_parser(
        "profile",
        help="continuous host profile: collapsed-stack frame groups, "
             "on-CPU vs GIL-wait split, triggered deep captures "
             "(flamegraph.pl text with --folded)",
    )
    pf.add_argument("--url", default="http://127.0.0.1:8080",
                    help="the node's HTTP API origin (sdx serve)")
    pf.add_argument("--peer", default=None, metavar="IDENTITY",
                    help="pull a MESH PEER's profile over the TELEMETRY "
                         "wire (library members only, like trace_pull)")
    pf_fmt = pf.add_mutually_exclusive_group()
    pf_fmt.add_argument("--folded", action="store_true",
                        help="emit flamegraph.pl collapsed-stack text "
                             "instead of the JSON document")
    pf_fmt.add_argument("--mesh", action="store_true",
                        help="with --url: include every reachable peer's "
                             "profile (partial on pull failures)")
    pf.add_argument("--wait", type=float, default=3.0,
                    help="discovery settle time before dialing --peer")
    pf.add_argument("--out", help="write output here instead of stdout")

    so = sub.add_parser(
        "slo",
        help="SLO burn-rate posture: per-objective status over the "
             "persistent telemetry history (multi-window burn rates)",
    )
    so.add_argument("--url", default=None,
                    help="read a running node's rspc telemetry.slo "
                         "instead of evaluating the data dir's history "
                         "offline")
    so.add_argument("--out", help="write JSON here instead of stdout")

    ms = sub.add_parser(
        "mesh-status",
        help="mesh-wide observability: every peer's latest telemetry "
             "snapshot (freshness-marked) + this node's health",
    )
    ms.add_argument("--url", default=None,
                    help="read a running node's GET /mesh instead of booting "
                         "an ephemeral mesh node")
    ms.add_argument("--no-refresh", action="store_true",
                    help="with --url: serve the cached mesh view without "
                         "re-pulling peers")
    ms.add_argument("--wait", type=float, default=3.0,
                    help="discovery settle time (ephemeral-node mode)")
    ms.add_argument("--out", help="write JSON here instead of stdout")

    ss = sub.add_parser(
        "serve-status",
        help="serve-layer posture: admission-gate mode, per-class "
             "inflight/shed counts, read-cache occupancy",
    )
    ss.add_argument("--url", default=None,
                    help="read a running node's rspc telemetry.serve "
                         "instead of booting an ephemeral node")
    ss.add_argument("--out", help="write JSON here instead of stdout")

    tn = sub.add_parser(
        "tenants",
        help="per-tenant accounting: heavy-hitter sketches per surface "
             "(serve/relay/p2p/sync), fairness index, dominant share — "
             "hashed tenant labels, never raw UUIDs",
    )
    tn.add_argument("--url", default=None,
                    help="read a running node's GET /tenants instead of "
                         "booting an ephemeral mesh node")
    tn.add_argument("--peer", default=None, metavar="LABEL",
                    help="show one mesh peer's federated tenant digest "
                         "(peer_label or instance-id prefix)")
    tn.add_argument("--wait", type=float, default=3.0,
                    help="discovery settle time (ephemeral-node mode)")
    tn.add_argument("--out", help="write JSON here instead of stdout")

    dk = sub.add_parser(
        "desktop",
        help="managed desktop host: single instance, browser UI, "
             "deep links, background node (ref:apps/desktop/src-tauri)",
    )
    dk.add_argument("--host", default="127.0.0.1")
    dk.add_argument("--port", type=int, default=0)
    dk.add_argument("--open-path", default=None, metavar="PATH",
                    help="open the explorer on PATH (deep link; targets "
                         "the running instance if one exists)")
    dk.add_argument("--no-open", action="store_true",
                    help="don't launch a browser (headless/CI)")
    dk.add_argument("--quit", action="store_true",
                    help="stop the running instance for this data dir")
    dk.add_argument("--register", action="store_true",
                    help="write the XDG launcher/'Open with' entry and exit")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    from .utils import faults as _faults

    if getattr(args, "faults", None):
        _faults.install(
            _faults.FaultPlan.parse(args.faults, seed=args.fault_seed)
        )
    else:
        _faults.install_from_env()
    if args.cmd == "index":
        return asyncio.run(cmd_index(args))
    if args.cmd == "serve":
        return asyncio.run(cmd_serve(args))
    if args.cmd == "relay":
        return asyncio.run(cmd_relay(args))
    if args.cmd == "status":
        return asyncio.run(cmd_status(args))
    if args.cmd == "browse":
        return asyncio.run(cmd_browse(args))
    if args.cmd == "duplicates":
        return asyncio.run(cmd_duplicates(args))
    if args.cmd == "search":
        return asyncio.run(cmd_search(args))
    if args.cmd == "peers":
        return asyncio.run(cmd_peers(args))
    if args.cmd == "pair":
        return asyncio.run(cmd_pair(args))
    if args.cmd == "spacedrop":
        return asyncio.run(cmd_spacedrop(args))
    if args.cmd == "crypto":
        return cmd_crypto(args)
    if args.cmd == "labeler":
        return cmd_labeler(args)
    if args.cmd == "trace-export":
        return cmd_trace_export(args)
    if args.cmd == "attrib":
        return cmd_attrib(args)
    if args.cmd == "profile":
        return cmd_profile(args)
    if args.cmd == "slo":
        return cmd_slo(args)
    if args.cmd == "debug-bundle":
        return cmd_debug_bundle(args)
    if args.cmd == "mesh-status":
        return asyncio.run(cmd_mesh_status(args))
    if args.cmd == "serve-status":
        return asyncio.run(cmd_serve_status(args))
    if args.cmd == "tenants":
        return asyncio.run(cmd_tenants(args))
    if args.cmd == "desktop":
        from . import desktop

        if args.register:
            path = desktop.register_xdg()
            print(f"registered {path}")
            return 0
        return asyncio.run(desktop.run_or_forward(
            args.data_dir, open_path=args.open_path,
            quit_running=args.quit, host=args.host, port=args.port,
            open_browser=not args.no_open,
        ))
    if args.cmd == "licenses":
        from .utils.deps import collect

        doc = json.dumps(collect(), indent=2)
        if args.out:
            with open(args.out, "w") as f:
                f.write(doc + "\n")
        else:
            print(doc)
        return 0
    return 2


if __name__ == "__main__":
    sys.exit(main())
