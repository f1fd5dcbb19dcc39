"""Mesh-wide observability: sync/replication instrumentation, telemetry
federation with staleness, health verdicts, and mesh-pulled debug
bundles — the PR 5 surface, end to end.

The two-node test builds two REAL ``Node``s sharing one library and
links their ``P2PManager``s over an in-process duplex transport that
drives the real wire protocol (``Header`` TELEMETRY/SYNC/SYNC_REQUEST,
msgpack frames) without the encrypted socket layer — the same
loopback-transport strategy the sync suite uses, upgraded to the full
manager stack, so it runs in the dep-less CI container where
``cryptography`` is absent.

Note: both nodes live in one process and therefore share the global
metrics registry and flight-recorder rings — per-peer series stay
distinguishable because every label is the instance's ``peer_label``
short-hash.
"""

import asyncio
import json
import shutil
import time
import uuid

import pytest

from spacedrive_tpu import telemetry
from spacedrive_tpu.telemetry import counter_value, gauge_value
from spacedrive_tpu.telemetry.events import SYNC_EVENTS
from spacedrive_tpu.telemetry.peers import peer_label

PLANTED_KEY = "sk-MESH-PLANTED-SECRET-deadbeef01"


# --- sync instrumentation (unit, loopback instances) -----------------------


class _Instance:
    """Minimal in-process sync instance (the sync suite's harness)."""

    def __init__(self, name: str):
        from spacedrive_tpu.db import LibraryDb
        from spacedrive_tpu.db.database import now_iso
        from spacedrive_tpu.sync.ingest import IngestActor
        from spacedrive_tpu.sync.manager import SyncManager
        from spacedrive_tpu.utils.events import EventBus

        self.id = uuid.uuid4()
        self.db = LibraryDb(None, memory=True)
        now = now_iso()
        self.db.insert(
            "instance", pub_id=self.id.bytes, identity=b"", node_id=b"",
            node_name=name, node_platform=0, last_seen=now, date_created=now,
        )
        self.bus = EventBus()
        self.sync = SyncManager(self.db, self.id, event_bus=self.bus)
        self.peers: list["_Instance"] = []

        async def request_ops(timestamps, count):
            ops, has_more = [], False
            for peer in self.peers:
                got = peer.sync.get_ops(count=count, clocks=timestamps)
                ops.extend(got)
                has_more = has_more or len(got) == count
            return ops, has_more

        self.actor = IngestActor(self.sync, request_ops)


def _connect(a: _Instance, b: _Instance) -> None:
    from spacedrive_tpu.db.database import now_iso

    for x, y in ((a, b), (b, a)):
        if x.db.find_one("instance", pub_id=y.id.bytes) is None:
            now = now_iso()
            x.db.insert(
                "instance", pub_id=y.id.bytes, identity=b"", node_id=b"",
                node_name="", node_platform=0, last_seen=now, date_created=now,
            )
    a.peers.append(b)
    b.peers.append(a)
    for src, dst in ((a, b), (b, a)):
        src.bus.on(
            lambda ev, dst=dst: dst.actor.notify()
            if ev in (("SyncMessage", "Created"), ("SyncMessage", "Ingested"))
            else None
        )


async def _settle(*instances: _Instance) -> None:
    for _ in range(3):
        for inst in instances:
            await inst.actor.wait_idle()
        await asyncio.sleep(0.05)


@pytest.mark.asyncio
async def test_sync_ingest_metrics_and_flight_ring():
    telemetry.reset()
    a, b = _Instance("a"), _Instance("b")
    _connect(a, b)
    tag_pub = uuid.uuid4().bytes.hex()
    a.sync.write_ops(
        a.sync.shared_create("tag", tag_pub, [("name", "x"), ("color", "#0f0")])
    )
    await _settle(a, b)
    await a.actor.stop()
    await b.actor.stop()

    # ops applied on b, counted by outcome
    assert counter_value("sd_sync_ops_total", result="applied") >= 3
    # lag converged: b just applied a's ops, so b's view of a is ~fresh
    lag = gauge_value("sd_sync_lag_seconds", default=-1.0,
                      peer=peer_label(a.id))
    assert 0.0 <= lag < 5.0, lag
    wm = gauge_value("sd_sync_watermark_seconds", peer=peer_label(a.id))
    assert abs(wm - time.time()) < 10.0
    # backlog gauge drained back to zero
    assert gauge_value("sd_sync_ingest_backlog") == 0.0
    # the sync flight ring recorded the batch
    types = [e["type"] for e in SYNC_EVENTS.snapshot()]
    assert "ingest_batch" in types, types


@pytest.mark.asyncio
async def test_stale_op_counted_and_transitions_recorded():
    from spacedrive_tpu.sync.crdt import CRDTOperation, CRDTOperationData
    from spacedrive_tpu.sync.hlc import NTP64
    from spacedrive_tpu.sync.ingest import receive_crdt_operation

    telemetry.reset()
    a, b = _Instance("a"), _Instance("b")
    _connect(a, b)
    tag_pub = uuid.uuid4().bytes.hex()
    a.sync.write_ops(a.sync.shared_create("tag", tag_pub, [("name", "new")]))
    await _settle(a, b)
    await a.actor.stop()
    await b.actor.stop()

    # an old update for the same field loses LWW and counts as stale
    stale = CRDTOperation(
        instance=a.id,
        timestamp=NTP64(1),
        id=uuid.uuid4(),
        model="tag",
        record_id=tag_pub,
        data=CRDTOperationData.update("name", "ancient"),
    )
    before = counter_value("sd_sync_ops_total", result="stale")
    assert receive_crdt_operation(b.sync, stale) is False
    assert counter_value("sd_sync_ops_total", result="stale") == before + 1


@pytest.mark.asyncio
async def test_delta_guard_rejects_and_records():
    from spacedrive_tpu.sync.crdt import CRDTOperation, CRDTOperationData
    from spacedrive_tpu.sync.hlc import NTP64
    from spacedrive_tpu.sync.ingest import receive_crdt_operation

    telemetry.reset()
    a, b = _Instance("a"), _Instance("b")
    _connect(a, b)
    future_ts = NTP64.from_unix(time.time() + 3600)  # way past max_drift
    op = CRDTOperation(
        instance=a.id,
        timestamp=future_ts,
        id=uuid.uuid4(),
        model="tag",
        record_id=uuid.uuid4().bytes.hex(),
        data=CRDTOperationData.create(),
    )
    before_guard = counter_value("sd_hlc_delta_guard_total")
    assert receive_crdt_operation(b.sync, op) is False
    assert counter_value("sd_hlc_delta_guard_total") == before_guard + 1
    # watermark must NOT advance to the far-future timestamp
    assert b.sync.timestamps.get(a.id, NTP64(0)) < future_ts
    # the trip landed on the sync flight ring with the peer short-hash
    trips = [e for e in SYNC_EVENTS.snapshot() if e["type"] == "delta_guard"]
    assert trips and trips[-1]["fields"]["peer"] == peer_label(a.id)
    # observed skew gauge carries the (hashed) peer label too
    skew = gauge_value("sd_hlc_clock_skew_seconds", peer=peer_label(a.id))
    assert skew > 3000


# --- health + federation (unit) --------------------------------------------


def test_health_rollup_thresholds():
    from spacedrive_tpu.telemetry import health, metrics

    telemetry.reset()
    assert health.evaluate()["status"] in ("healthy",)

    metrics.EVENT_LOOP_LAG.set(2.0)
    v = health.evaluate()
    assert v["subsystems"]["event_loop"]["status"] == health.UNHEALTHY
    assert v["status"] == health.UNHEALTHY

    metrics.EVENT_LOOP_LAG.set(0.3)
    v = health.evaluate()
    assert v["subsystems"]["event_loop"]["status"] == health.DEGRADED
    assert v["status"] == health.DEGRADED

    # raw wall-clock lag alone NEVER drives the sync verdict: it grows
    # on a perfectly healthy idle mesh, and a probe acting on /health's
    # 503 would drain idle-but-fine nodes. It rides along as a signal.
    telemetry.reset()
    metrics.SYNC_LAG.set(700.0, peer="aabbccdd")
    v = health.evaluate()
    assert v["subsystems"]["sync"]["status"] == health.HEALTHY
    assert v["subsystems"]["sync"]["signals"]["lag_seconds"] == \
        {"aabbccdd": 700.0}
    telemetry.reset()


def test_health_sync_gap_corroborated_by_federation():
    """The sync verdict acts on the federation-corroborated head gap:
    a fresh peer snapshot whose library head is far ahead of ours means
    this replica demonstrably holds less than the mesh does."""
    import types

    from spacedrive_tpu.sync.hlc import NTP64
    from spacedrive_tpu.telemetry import health
    from spacedrive_tpu.telemetry.federation import FederationCache

    telemetry.reset()
    lib_id = str(uuid.uuid4())
    now = time.time()

    def _node(our_head: float, peer_head: float):
        cache = FederationCache()
        cache.store("peer-x", {
            "v": 1, "ts": now, "health": {"status": "healthy"},
            "node": {"id": "x", "name": "x", "libraries": {
                lib_id: {"instance_label": "cafecafe",
                         "head_seconds": peer_head},
            }},
        })
        lib = types.SimpleNamespace(
            id=lib_id,
            sync=types.SimpleNamespace(
                observe_replication_lag=lambda: {},
                clock=types.SimpleNamespace(
                    peek_last=lambda: NTP64.from_unix(our_head)),
            ),
        )
        return types.SimpleNamespace(
            libraries=types.SimpleNamespace(libraries={lib_id: lib}),
            p2p=types.SimpleNamespace(federation=cache),
        )

    # converged (idle or busy): heads match → healthy
    v = health.evaluate(_node(now, now))
    assert v["subsystems"]["sync"]["status"] == health.HEALTHY

    # peer's head 700 s ahead of ours → we are genuinely behind
    v = health.evaluate(_node(now - 700, now))
    sync = v["subsystems"]["sync"]
    assert sync["status"] == health.UNHEALTHY
    assert "not yet applied" in sync["reason"]
    telemetry.reset()


def test_federation_cache_staleness_rules():
    from spacedrive_tpu.telemetry.federation import (
        SNAPSHOT_VERSION,
        FederationCache,
        local_snapshot,
        snapshot_compatible,
    )

    telemetry.reset()
    snap = local_snapshot()
    assert snap["v"] == SNAPSHOT_VERSION
    assert snapshot_compatible(snap)
    assert not snapshot_compatible({"v": SNAPSHOT_VERSION + 1})
    assert not snapshot_compatible("nonsense")

    cache = FederationCache(stale_after=0.4, refresh_interval=0.1)
    cache.store("peer-1", snap)
    m = cache.mesh()["peers"]["peer-1"]
    assert m["stale"] is False and m["verdict"] == snap["health"]["status"]
    assert not cache.needs_refresh("peer-1")

    # a pull failure keeps the last snapshot but records the error
    cache.record_failure("peer-1", "connection refused")
    m = cache.mesh()["peers"]["peer-1"]
    assert m["snapshot"] is not None and m["error"] == "connection refused"

    time.sleep(0.45)
    m = cache.mesh()["peers"]["peer-1"]
    assert m["stale"] is True and m["verdict"] == "unhealthy"
    assert cache.needs_refresh("peer-1")

    # relayed copies are backdated by their relay-side age
    cache.store("peer-2", snap, transport="relay", age_seconds=999.0)
    m = cache.mesh()["peers"]["peer-2"]
    assert m["stale"] is True and m["transport"] == "relay"

    # an old relay copy must NOT clobber a fresher direct pull: the
    # peer was just proven alive over P2P
    cache.store("peer-3", snap, transport="p2p")
    cache.store("peer-3", snap, transport="relay", age_seconds=999.0)
    m = cache.mesh()["peers"]["peer-3"]
    assert m["stale"] is False and m["transport"] == "p2p"


# --- cloud-relay federation fallback ---------------------------------------


@pytest.mark.asyncio
async def test_relay_telemetry_push_pull_roundtrip():
    from spacedrive_tpu.cloud.api import CloudClient
    from spacedrive_tpu.cloud.relay import CloudRelay
    from spacedrive_tpu.telemetry.federation import local_snapshot

    telemetry.reset()
    relay = CloudRelay()
    port = await relay.start()
    client = CloudClient(f"http://127.0.0.1:{port}")
    try:
        lib_id = str(uuid.uuid4())
        inst_a, inst_b = str(uuid.uuid4()), str(uuid.uuid4())
        await client.create_library(lib_id, "fed")
        await client.add_instance(lib_id, inst_a)
        await client.add_instance(lib_id, inst_b)

        snap = json.loads(json.dumps(local_snapshot(), default=str))
        await client.push_telemetry(lib_id, inst_a, snap)

        # the pusher does not see its own snapshot; the other does
        assert await client.pull_telemetry(lib_id, inst_a) == []
        rows = await client.pull_telemetry(lib_id, inst_b)
        assert len(rows) == 1
        assert rows[0]["instance_uuid"] == inst_a
        assert rows[0]["snapshot"]["v"] == snap["v"]
        assert rows[0]["age_seconds"] >= 0.0
    finally:
        await client.close()
        await relay.shutdown()


# --- wire format -----------------------------------------------------------


@pytest.mark.asyncio
async def test_telemetry_header_roundtrip():
    from spacedrive_tpu.p2p.protocol import Header, HeaderType

    pipe = _Pipe()
    trace = {"trace_id": "ab" * 16, "span_id": "cd" * 8}
    await Header(HeaderType.TELEMETRY, trace=trace).write(pipe)
    back = await Header.read(pipe)
    assert back.type == HeaderType.TELEMETRY
    assert back.trace == trace

    # without a trace context the wire carries {} and decodes to None
    await Header(HeaderType.TELEMETRY).write(pipe)
    back = await Header.read(pipe)
    assert back.type == HeaderType.TELEMETRY and back.trace is None


# --- the two-node end-to-end loop ------------------------------------------


# the in-process duplex + two-node pair now live in the production
# harness module (p2p/loopback.py) so the mesh-parallel index tests
# drive the SAME transport as this suite
from spacedrive_tpu.p2p.loopback import (  # noqa: E402
    DuplexEnd as _DuplexEnd,
    Pipe as _Pipe,
    make_mesh_pair as _make_mesh_pair,
)


@pytest.mark.asyncio
async def test_two_node_mesh_observability_end_to_end(tmp_path):
    """The acceptance loop: sync lag converges after replication,
    GET /mesh aggregates both peers with staleness marking, a
    partitioned peer goes stale-then-unhealthy, and a mesh-pulled
    debug bundle is secret-free."""
    import aiohttp

    from spacedrive_tpu.node.config import BackendFeature
    from spacedrive_tpu.p2p.rspc import remote_exec

    telemetry.reset()
    a, b, lib_a, lib_b, _server_tasks = await _make_mesh_pair(tmp_path)
    try:
        # plant secrets on beta: the bundle pulled across the mesh must
        # arrive clean (redaction runs on beta before the wire)
        b.config.config.preferences["cloud_api_token"] = PLANTED_KEY
        b.config.save()
        b_identity_hex = b.config.config.identity.to_bytes().hex()
        from spacedrive_tpu.telemetry.events import record_error

        try:
            raise RuntimeError(f"relay said 401: bad token {PLANTED_KEY}")
        except RuntimeError as e:
            record_error("excepthook", e)

        # --- replication: alpha writes, beta converges -----------------
        tag_pub = uuid.uuid4().bytes.hex()
        lib_a.sync.write_ops(
            lib_a.sync.shared_create("tag", tag_pub, [("name", "mesh")])
        )
        for _ in range(100):
            if lib_b.db.find_one("tag", pub_id=bytes.fromhex(tag_pub)):
                break
            await asyncio.sleep(0.05)
        row = lib_b.db.find_one("tag", pub_id=bytes.fromhex(tag_pub))
        assert row is not None and row["name"] == "mesh"

        # lag converged to ~0 (beta just applied alpha's fresh ops)
        lags = lib_b.sync.observe_replication_lag()
        a_label = peer_label(lib_a.sync.instance)
        assert a_label in lags and lags[a_label] < 5.0, lags
        assert gauge_value("sd_sync_lag_seconds", default=-1.0,
                           peer=a_label) == pytest.approx(lags[a_label])

        # --- GET /mesh: both peers, fresh snapshots --------------------
        a.p2p.federation.refresh_interval = 0.0
        port = await a.start_api()
        async with aiohttp.ClientSession() as http:
            async with http.get(f"http://127.0.0.1:{port}/mesh") as resp:
                assert resp.status == 200
                mesh_doc = await resp.json()
            async with http.get(f"http://127.0.0.1:{port}/health") as resp:
                assert resp.status in (200, 503)
                health_doc = await resp.json()

        assert "sync" in health_doc["subsystems"]
        local = mesh_doc["local"]
        assert local["v"] == 1 and local["node"]["name"] == "alpha"
        peers = mesh_doc["mesh"]["peers"]
        b_key = str(b.p2p.p2p.remote_identity)
        assert b_key in peers, list(peers)
        entry = peers[b_key]
        assert entry["stale"] is False
        assert entry["snapshot"]["node"]["name"] == "beta"
        assert entry["verdict"] == entry["snapshot"]["health"]["status"]
        # beta's snapshot reports ITS replication view, labeled by hash
        beta_lib = entry["snapshot"]["node"]["libraries"][str(lib_a.id)]
        assert a_label in beta_lib["lag_seconds"]

        # --- membership gate: strangers get a refusal, not a snapshot --
        from spacedrive_tpu.p2p.identity import Identity
        from spacedrive_tpu.p2p.protocol import Header, HeaderType
        from spacedrive_tpu.p2p.wire import Reader

        stranger = Identity().to_remote_identity()
        c2s, s2c = _Pipe(), _Pipe()
        client = _DuplexEnd(s2c, c2s, a.p2p.p2p.remote_identity)
        server = _DuplexEnd(c2s, s2c, stranger)  # not a library member
        await Header(HeaderType.TELEMETRY).write(client)
        serve_task = asyncio.ensure_future(a.p2p._handle_stream(server))
        refusal = await Reader(client).msgpack()
        await serve_task
        assert refusal.get("error") and "v" not in refusal, refusal

        # --- debug bundle across the mesh, redacted at the source ------
        b.toggle_feature(BackendFeature.REMOTE_RSPC, True)
        bundle = await remote_exec(
            a.p2p.p2p, b.p2p.p2p.remote_identity, "telemetry.debug_bundle"
        )
        doc = json.dumps(bundle)
        assert bundle["node_config"] and bundle["metrics"]
        assert PLANTED_KEY not in doc
        assert b_identity_hex not in doc
        assert bundle["node_config"]["preferences"]["cloud_api_token"] \
            == "[redacted]"
        # the sync ring rode along (flight-recorder satellite)
        assert "sync" in bundle["events"]

        # --- partition: beta goes stale, then unhealthy ----------------
        a.p2p.federation.stale_after = 0.5

        async def refuse(identity, timeout=10.0):
            raise ConnectionError("partitioned")

        a.p2p.p2p.new_stream = refuse
        await asyncio.sleep(0.6)
        mesh2 = await a.p2p.refresh_federation(force=True)
        entry2 = mesh2["peers"][b_key]
        assert entry2["stale"] is True
        assert entry2["verdict"] == "unhealthy"
        assert entry2["error"]  # the failed re-pull was recorded
        # last-known snapshot is retained for the operator
        assert entry2["snapshot"]["node"]["name"] == "beta"
    finally:
        await a.shutdown()
        await b.shutdown()
    telemetry.reset()
