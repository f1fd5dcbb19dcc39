"""Node — the root runtime object every host embeds.

Parity: ref:core/src/lib.rs:82-250 `Node::new(data_dir, env)` builds
config manager, libraries, job system, thumbnailer, event bus,
notifications, optional image-labeler and P2P, then performs an
ordered start (lib.rs:163-177: locations → libraries.init → jobs →
p2p) and exposes `shutdown` (lib.rs:240-250). The API layer mounts on
top of this object (api::mount, ref:core/src/api/mod.rs:124).
"""

from __future__ import annotations

import os
import uuid
from typing import Any

from ..jobs.manager import JobManager
from ..object.media.thumbnail.actor import Thumbnailer
from ..parallel import autotune as _autotune
from ..object.orphan_remover import OrphanRemoverActor
from ..tasks.system import TaskSystem
from ..telemetry import span
from ..telemetry.events import LoopLagMonitor
from ..utils.events import EventBus
from ..utils.tracing import init_logger, install_loop_excepthook
from .actors import Actors
from .config import BackendFeature, ConfigManager, NodeConfig
from .library import Libraries, Library
from .notifications import Notifications


class Node:
    """Owns every long-lived service; one per process (ref:lib.rs:60-80)."""

    def __init__(
        self,
        data_dir: str | os.PathLike,
        *,
        use_device: bool = True,
        with_logger: bool = False,
        with_labeler: bool = True,
    ):
        with span("node.init"):
            self._build(data_dir, use_device, with_logger, with_labeler)

    def _build(self, data_dir: str | os.PathLike, use_device: bool,
               with_logger: bool, with_labeler: bool) -> None:
        self.data_dir = os.fspath(data_dir)
        os.makedirs(self.data_dir, exist_ok=True)
        if with_logger:
            init_logger(self.data_dir)
        if use_device:
            from ..ops import configure_compilation_cache

            configure_compilation_cache()

        self.config = ConfigManager(self.data_dir)
        self.event_bus = EventBus()
        self.notifications = Notifications(self.event_bus)
        self.task_system = TaskSystem()
        self.jobs = JobManager(self.task_system)
        self.libraries = Libraries(self.data_dir, node=self)
        self.actors = Actors()
        from ..location.manager import LocationManager

        self.location_manager = LocationManager(self)
        self.thumbnailer = Thumbnailer(
            os.path.join(self.data_dir, "thumbnails"),
            event_bus=self.event_bus,
            use_device=use_device,
        )
        self.use_device = use_device
        # ref:lib.rs:142 ImageLabeler::new [feature ai] — on by default,
        # disable with with_labeler=False (the reference's feature gate)
        self.image_labeler: Any = None
        if with_labeler:
            from ..models.labeler_actor import ImageLabeler

            self.image_labeler = ImageLabeler(
                os.path.join(self.data_dir, "image_labeler"),
                use_device=use_device,
            )
            # version string tracks the provisioned artifact, mirroring
            # the reference's image_labeler_version (node/config.rs) —
            # "none" means no weights yet, labeling is off
            artifact = self.image_labeler.resolve_artifact()
            version = f"{artifact[0]}:{os.path.basename(artifact[1])}" if artifact else "none"
            if self.config.config.image_labeler_version != version:
                self.config.update(image_labeler_version=version)
        self.p2p: Any = None  # P2PManager, attached by start() when enabled
        self.http: Any = None  # ApiServer handle from start_api()
        # the serve layer (admission gate + read-path caches): absent
        # entirely under SD_SERVE_GATE=0, and every consumer treats a
        # missing runtime as "take the ungated pre-serve path"
        from ..serve import ServeRuntime, enabled as _serve_enabled

        self.serve: Any = ServeRuntime() if _serve_enabled() else None
        from ..api.namespaces import mount

        self.router = mount()  # ref:lib.rs Node::new returns (node, router)
        self.loop_monitor = LoopLagMonitor()
        # persistent telemetry history: sampled allowlisted series into
        # an append-only segment store under the data dir — constructed
        # unconditionally so offline readers (sdx slo)
        # can open the same directory; sampling only starts with the
        # node and only when SD_HISTORY != 0
        from ..telemetry.history import HistoryWriter, history_dir

        self.history = HistoryWriter(history_dir(self.data_dir))
        # the process-wide closed-loop autotuner: started with the node
        # so pipeline policies adapt while jobs run (SD_AUTOTUNE=0 keeps
        # every policy at the static defaults and starts nothing)
        self.autotuner = _autotune.CONTROLLER
        # the process-wide continuous host profiler (telemetry/sampler):
        # refcounted like the autotuner so two in-process nodes share
        # one sampling thread; SD_PROFILE=0 starts nothing (true no-op)
        from ..telemetry import sampler as _sampler

        self.profiler = _sampler.SAMPLER
        self._profiler_started = False
        # the multi-process execution plane (parallel/procpool.py):
        # spawn-started with the node, refcounted like the sampler so
        # two in-process nodes share one worker set. SD_PROCS=0 (the
        # default) starts nothing — the golden single-process path.
        from ..parallel import procpool as _procpool

        self.procpool = _procpool.POOL
        self._procpool_started = False
        # the process-wide resource-growth sampler (telemetry/resources):
        # refcounted like the profiler; SD_RESOURCES=0 starts nothing
        # (true no-op). Inventory providers that need node state
        # (journal/oplog rows, serve caches, history bytes) register at
        # start and unregister at shutdown.
        from ..telemetry import resources as _resources

        self.resources = _resources.SAMPLER
        self._resources_started = False
        self._started = False

    # --- identity ------------------------------------------------------

    @property
    def id(self) -> uuid.UUID:
        return self.config.config.id

    @property
    def identity(self):
        return self.config.config.identity

    def is_feature_enabled(self, feature: BackendFeature) -> bool:
        return feature in self.config.config.features

    def toggle_feature(self, feature: BackendFeature, enabled: bool) -> None:
        """ref:core/src/api/mod.rs:66-81 `toggleFeatureFlag`."""
        feats = self.config.config.features
        if enabled and feature not in feats:
            feats.append(feature)
        if not enabled and feature in feats:
            feats.remove(feature)
        self.config.save()

    # --- lifecycle -----------------------------------------------------

    async def start(self) -> None:
        """Ordered start (ref:lib.rs:163-177; ordering is
        deadlock-sensitive in the reference: locations actor first, then
        libraries init — which cold-resumes jobs — then p2p listeners)."""
        if self._started:
            return
        self._started = True
        async with span("node.start"):
            # observability: orphaned-task crashes reach the log + error
            # ring, and the loop-lag sampler feeds the flight recorder
            import asyncio

            install_loop_excepthook(asyncio.get_running_loop())
            self.loop_monitor.start()
            self.history.start()
            self.autotuner.start()
            # host profiling: tag THIS thread as the event-loop thread so
            # samples classify as loop vs feeder vs worker, then take a
            # refcounted hold on the process sampler
            self.profiler.register_loop_thread()
            self._profiler_started = self.profiler.start()
            # worker processes up before any job runs, so the first shard's
            # pool batches never pay spawn latency inside a measured pass
            self._procpool_started = self.procpool.start()
            # resource growth surfaces: node-state inventories registered
            # before the sampler's hold so the first tick reads them all
            from ..telemetry import resources as _resources

            for name, fn in _resources.node_providers(self).items():
                self.resources.register_provider(name, fn)
            self._resources_started = self.resources.start()
            # bind the thumbnailer to THIS loop up front: enqueues arrive
            # from worker threads (non-indexed walker) and can only wake the
            # actor thread-safely once it knows its owning loop
            self.thumbnailer._ensure_started()
            for lib in self.libraries.load_all():
                await self._init_library(lib)
            if self.config.config.p2p.enabled:
                from ..p2p.manager import P2PManager

                self.p2p = P2PManager(self)
                await self.p2p.start()

    async def _init_library(self, lib: Library) -> None:
        """Per-library wiring done at load (ref:library/manager/mod.rs:387-535):
        orphan-remover actor started, ingest actor wired when a sync
        transport attaches (p2p/cloud), then cold job resume."""
        lib.node = self
        lib.orphan_remover = OrphanRemoverActor(lib.db)
        lib.orphan_remover.start()
        self.location_manager.ignore_paths.add(self.thumbnailer.data_dir)
        if self.image_labeler is not None:
            self.image_labeler.register_library(lib)
        for loc in lib.db.find("location"):
            await self.location_manager.add(lib, loc)
        await self.jobs.cold_resume(lib)

    async def create_library(self, name: str, description: str = "") -> Library:
        lib = self.libraries.create(
            name,
            description,
            node_pub_id=self.id.bytes,
            node_name=self.config.config.name,
        )
        await self._init_library(lib)
        if self.p2p is not None:
            self.p2p.register_library(lib)
        return lib

    async def enable_cloud_sync(self, lib: Library, api_origin: str | None = None):
        """Start the cloud sender/receiver/ingester trio for a library
        (ref:core/src/cloud/sync/mod.rs:14 declare_actors; the origin
        persists in node preferences like the reference's sd-cloud-api
        env)."""
        from ..cloud.api import CloudClient
        from ..cloud.sync import CloudSync

        prev_origin = self.config.config.preferences.get("cloud_api_origin")
        if api_origin is not None and api_origin != prev_origin:
            self.config.config.preferences["cloud_api_origin"] = api_origin
            self.config.save()
        origin = self.config.config.preferences.get("cloud_api_origin")
        if not origin:
            raise ValueError("no cloud api origin configured")
        existing = getattr(lib, "cloud_sync", None)
        if existing is not None:
            if existing.client.origin == origin.rstrip("/"):
                return existing
            # origin changed: move sync to the new relay
            await existing.shutdown()
            await existing.client.close()
            lib.cloud_sync = None
        client = CloudClient(origin)
        cloud = CloudSync(lib, client)
        try:
            await cloud.start()
        except BaseException:
            await cloud.shutdown()
            await client.close()
            raise
        lib.cloud_sync = cloud
        if BackendFeature.CLOUD_SYNC not in self.config.config.features:
            self.toggle_feature(BackendFeature.CLOUD_SYNC, True)
        return cloud

    async def close_library(self, lib_id: uuid.UUID) -> None:
        """Tear down one loaded library: stop its actors, persist and stop
        its jobs, close the DB, drop it from the registry (the per-library
        half of shutdown(); used by delete/restore)."""
        from ..jobs.manager import shutdown_jobs

        lib = self.libraries.get(lib_id)
        if lib is None:
            return
        cloud = getattr(lib, "cloud_sync", None)
        if cloud is not None:
            await cloud.shutdown()
            await cloud.client.close()
        await shutdown_jobs(self.jobs, lib)
        remover = getattr(lib, "orphan_remover", None)
        if remover is not None:
            await remover.stop()
        ingest = getattr(lib, "ingest", None)
        if ingest is not None:
            await ingest.stop()
        lib.close()
        self.libraries.libraries.pop(lib_id, None)

    async def start_api(self, host: str = "127.0.0.1", port: int = 0) -> int:
        """Serve /rspc + custom-URI over HTTP (ref:apps/server/src/main.rs)."""
        from ..api.server import ApiServer

        self.http = ApiServer(self, self.router)
        return await self.http.start(host, port)

    async def shutdown(self) -> None:
        """ref:lib.rs:240-250: stop jobs (persisting state), thumbnailer
        (persisting queues), actors, p2p, then close libraries."""
        from ..jobs.manager import shutdown_jobs

        async with span("node.shutdown"):
            if self.http is not None:
                await self.http.shutdown()
                self.http = None

            for lib in list(self.libraries.libraries.values()):
                await shutdown_jobs(self.jobs, lib)
                remover = getattr(lib, "orphan_remover", None)
                if remover is not None:
                    await remover.stop()
                cloud = getattr(lib, "cloud_sync", None)
                if cloud is not None:
                    await cloud.shutdown()
                    await cloud.client.close()
            await self.loop_monitor.stop()
            await self.history.stop()
            await self.autotuner.stop()
            if self._profiler_started:
                self.profiler.stop()
                self._profiler_started = False
            if self._procpool_started:
                self.procpool.stop()
                self._procpool_started = False
            if self._resources_started:
                self.resources.stop()
                self._resources_started = False
            if not self.resources.running():
                # last hold released (or sampling disabled): drop the
                # node-state closures so a dead node can't be read. While a
                # sibling in-process node still holds the sampler, its own
                # registrations (last-wins) stay live instead.
                from ..telemetry import resources as _resources

                for name in _resources.node_providers(self):
                    self.resources.unregister_provider(name)
            await self.thumbnailer.shutdown()
            if self.image_labeler is not None:
                await self.image_labeler.shutdown()
            await self.location_manager.shutdown()
            await self.actors.shutdown()
            if self.p2p is not None:
                await self.p2p.shutdown()
            await self.task_system.shutdown()
            for lib in list(self.libraries.libraries.values()):
                lib.close()
            self._started = False
