"""API layer: router semantics, normalized cache, invalidation,
search DSL, namespace procedures, HTTP/WS host, custom-URI serving.

Parity targets: ref:core/src/api (router + namespaces + invalidation),
crates/cache, core/src/custom_uri, apps/server.
"""

import asyncio
import json
import os
import uuid

import pytest

from spacedrive_tpu.api import RspcError, mount
from spacedrive_tpu.api.cache import normalise
from spacedrive_tpu.api.router import CoreEventKind


@pytest.fixture()
def corpus(tmp_path):
    from PIL import Image

    d = tmp_path / "corpus"
    d.mkdir()
    (d / "alpha.txt").write_bytes(b"a" * 1000)
    (d / "beta.bin").write_bytes(os.urandom(2000))
    (d / "photo.jpg").write_bytes(b"\xff\xd8\xff\xe0" + os.urandom(500))
    Image.new("RGB", (48, 36), (200, 40, 40)).save(d / "real.png")
    sub = d / "nested"
    sub.mkdir()
    (sub / "gamma.txt").write_bytes(b"g" * 300)
    return str(d)


async def _scanned_node(tmp_path, corpus):
    from spacedrive_tpu.location.locations import LocationCreateArgs, scan_location
    from spacedrive_tpu.node import Node

    node = Node(os.path.join(tmp_path, "node"), use_device=False)
    node.config.config.p2p.enabled = False
    await node.start()
    lib = await node.create_library("api-lib")
    loc = LocationCreateArgs(path=corpus, name="corpus").create(lib)
    await scan_location(lib, loc, node.jobs)
    await node.jobs.wait_idle()
    return node, lib, loc


# --- router semantics -----------------------------------------------------


def test_router_keys_unique_and_library_resolution(tmp_path):
    async def run():
        from spacedrive_tpu.node import Node

        router = mount()
        assert len(router.keys()) > 70
        node = Node(tmp_path, use_device=False)
        node.config.config.p2p.enabled = False
        await node.start()
        info = await router.exec(node, "buildInfo")
        assert info["version"]
        with pytest.raises(RspcError):
            await router.exec(node, "nope.nothing")
        # library-scoped procedure demands a library id
        with pytest.raises(RspcError):
            await router.exec(node, "locations.list")
        with pytest.raises(RspcError):
            await router.exec(node, "locations.list", library_id=str(uuid.uuid4()))
        await node.shutdown()

    asyncio.run(run())


def test_normalised_cache_shape():
    rows = [{"id": 1, "name": "x", "pub_id": b"\x01\x02"}]
    out = normalise("tag", rows)
    assert out["items"] == [{"__type": "tag", "__id": 1}]
    assert out["nodes"][0]["pub_id"] == "0102"  # bytes hexed for the wire


# --- end-to-end over procedures ------------------------------------------


def test_api_full_flow(tmp_path, corpus):
    async def run():
        node, lib, loc = await _scanned_node(tmp_path, corpus)
        r = node.router
        lid = str(lib.id)
        try:
            # locations
            locs = await r.exec(node, "locations.list", library_id=lid)
            assert len(locs["items"]) == 1

            # search DSL: filter by extension, ordering, cursor paging
            res = await r.exec(
                node,
                "search.paths",
                {"filter": {"extension": "txt"}, "orderBy": "name"},
                library_id=lid,
            )
            names = [n["name"] for n in res["nodes"]]
            assert names == ["alpha", "gamma"]
            page1 = await r.exec(
                node, "search.paths", {"take": 2, "filter": {}}, library_id=lid
            )
            assert len(page1["items"]) == 2 and page1["cursor"] is not None
            page2 = await r.exec(
                node,
                "search.paths",
                {"take": 50, "cursor": page1["cursor"]},
                library_id=lid,
            )
            ids1 = {n["__id"] for n in page1["items"]}
            ids2 = {n["__id"] for n in page2["items"]}
            assert not ids1 & ids2

            # keyset pagination walks every row exactly once, in order,
            # for both text and (LE-blob) size orderings
            for order in ("name", "sizeInBytes"):
                seen, cursor, vals = [], None, []
                while True:
                    page = await r.exec(
                        node,
                        "search.paths",
                        {"take": 2, "orderBy": order, "cursor": cursor},
                        library_id=lid,
                    )
                    seen += [n["__id"] for n in page["items"]]
                    vals += [
                        n["name" if order == "name" else "size_in_bytes"]
                        for n in page["nodes"]
                    ]
                    cursor = page["cursor"]
                    if cursor is None:
                        break
                assert len(seen) == len(set(seen)) == lib.db.count("file_path")
                assert vals == sorted(vals)

            # tags: create → assign → filter search by tag
            fp = lib.db.find_one("file_path", name="alpha")
            tag_id = await r.exec(
                node, "tags.create", {"name": "keep", "color": "#f00"}, library_id=lid
            )
            await r.exec(
                node,
                "tags.assign",
                {"tag_id": tag_id, "object_ids": [fp["object_id"]]},
                library_id=lid,
            )
            tagged = await r.exec(
                node,
                "search.paths",
                {"filter": {"tags": [tag_id]}},
                library_id=lid,
            )
            assert [n["name"] for n in tagged["nodes"]] == ["alpha"]
            for_obj = await r.exec(
                node, "tags.getForObject", fp["object_id"], library_id=lid
            )
            assert for_obj["nodes"][0]["name"] == "keep"

            # favorites via files.setFavorite + objects search
            await r.exec(
                node,
                "files.setFavorite",
                {"id": fp["id"], "favorite": True},
                library_id=lid,
            )
            favs = await r.exec(
                node,
                "search.objects",
                {"filter": {"favorite": True}},
                library_id=lid,
            )
            assert len(favs["items"]) == 1

            # rename mutates disk + DB + emits sync ops
            await r.exec(
                node,
                "files.renameFile",
                {"id": fp["id"], "new_name": "alpha-renamed.txt"},
                library_id=lid,
            )
            assert os.path.exists(os.path.join(corpus, "alpha-renamed.txt"))
            assert lib.db.find_one("file_path", name="alpha-renamed") is not None

            # jobs.reports shows the scan chain
            reports = await r.exec(node, "jobs.reports", library_id=lid)
            assert {rep["name"] for rep in reports} >= {
                "indexer",
                "file_identifier",
                "media_processor",
            }

            # statistics / volumes / preferences / notifications
            stats = await r.exec(node, "library.statistics", library_id=lid)
            assert stats["total_object_count"] > 0
            vols = await r.exec(node, "volumes.list")
            assert vols
            await r.exec(
                node, "preferences.update", {"explorer": {"layout": "grid"}},
                library_id=lid,
            )
            prefs = await r.exec(node, "preferences.get", library_id=lid)
            assert prefs["explorer"]["layout"] == "grid"

            # saved searches
            sid = await r.exec(
                node,
                "search.saved.create",
                {"name": "txts", "filters": json.dumps({"extension": "txt"})},
                library_id=lid,
            )
            saved = await r.exec(node, "search.saved.list", library_id=lid)
            assert saved["nodes"][0]["id"] == sid

            # invalidation events fired for the mutations above
            # (collect through a fresh subscription round-trip)
            seen = []
            sub = node.event_bus.subscribe()
            await r.exec(node, "tags.create", {"name": "x"}, library_id=lid)
            await asyncio.sleep(0.05)
            for ev in sub.poll():
                if isinstance(ev, tuple) and ev[0] == CoreEventKind.INVALIDATE_OPERATION:
                    seen.append(ev[1].key)
            assert "tags.list" in seen

            # spaces + albums CRUD over existing objects
            for ns in ("spaces", "albums"):
                cid = await r.exec(
                    node, f"{ns}.create", {"name": f"my-{ns}"}, library_id=lid
                )
                await r.exec(
                    node,
                    f"{ns}.addObjects",
                    {"id": cid, "object_ids": [fp["object_id"]]},
                    library_id=lid,
                )
                objs = await r.exec(node, f"{ns}.getObjects", cid, library_id=lid)
                assert len(objs["items"]) == 1
                listing = await r.exec(node, f"{ns}.list", library_id=lid)
                assert listing["nodes"][0]["name"] == f"my-{ns}"
                await r.exec(
                    node,
                    f"{ns}.addObjects",
                    {"id": cid, "object_ids": [fp["object_id"]], "remove": True},
                    library_id=lid,
                )
                objs = await r.exec(node, f"{ns}.getObjects", cid, library_id=lid)
                assert objs["items"] == []
                await r.exec(node, f"{ns}.delete", cid, library_id=lid)
                assert (await r.exec(node, f"{ns}.list", library_id=lid))["items"] == []

            # ephemeral browse of a non-indexed dir
            eph = await r.exec(node, "ephemeralFiles.list", {"path": corpus})
            assert any(e["name"] == "nested" and e["is_dir"] for e in eph["entries"])

            # ephemeral mutations (ref:api/ephemeral_files.rs)
            scratch = os.path.join(str(corpus), "..", "scratch")
            os.makedirs(scratch, exist_ok=True)
            folder = await r.exec(
                node, "ephemeralFiles.createFolder",
                {"path": scratch, "name": "made-here"},
            )
            assert os.path.isdir(folder)
            open(os.path.join(scratch, "loose.txt"), "w").write("x")
            renamed = await r.exec(
                node, "ephemeralFiles.renameFile",
                {"path": os.path.join(scratch, "loose.txt"), "new_name": "kept.txt"},
            )
            assert os.path.exists(renamed)
            out = await r.exec(
                node, "ephemeralFiles.deleteFiles",
                {"paths": [renamed, folder, "/nonexistent/zzz"]},
            )
            assert out["deleted"] == 2 and out["errors"] == []
            assert not os.path.exists(folder)

            # mediaDate range filter rides media_data.epoch_time
            lib.db.upsert(
                "media_data", {"object_id": fp["object_id"]}, epoch_time=1_700_000_000
            )
            hits = await r.exec(
                node, "search.paths",
                {"filter": {"mediaDate": {"from": 1_600_000_000, "to": 1_800_000_000}}},
                library_id=lid,
            )
            assert [n_["__id"] for n_ in hits["items"]] == [fp["id"]]
            none = await r.exec(
                node, "search.paths",
                {"filter": {"mediaDate": {"from": 1_900_000_000}}},
                library_id=lid,
            )
            assert none["items"] == []

            # backups roundtrip: backup, mutate, restore, verify rollback
            backup_id = await r.exec(node, "backups.backup", library_id=lid)
            await r.exec(node, "tags.create", {"name": "doomed"}, library_id=lid)
            assert lib.db.find_one("tag", name="doomed") is not None
            backups = await r.exec(node, "backups.getAll")
            assert backups and backups[0]["id"] == backup_id
            await r.exec(node, "backups.restore", {"path": backups[0]["path"]})
            lib2 = node.libraries.get(lib.id)
            assert lib2.db.find_one("tag", name="doomed") is None
            assert lib2.db.find_one("tag", name="keep") is not None
        finally:
            await node.shutdown()

    asyncio.run(run())


# --- HTTP host ------------------------------------------------------------


def test_overview_favorites_recents_api(tmp_path, corpus):
    """The overview/favorites/recents routes' backing procedures
    (ref:core/src/api/libraries.rs kindStatistics, files.rs
    updateAccessTime, interface favorites.tsx/recents.tsx filters)."""

    async def run():
        node, lib, loc = await _scanned_node(tmp_path, corpus)
        r = node.router
        lid = str(lib.id)
        try:
            # kindStatistics: real counts + byte totals per kind
            ks = await r.exec(node, "library.kindStatistics", library_id=lid)
            stats = {s["name"]: s for s in ks["statistics"]}
            assert stats["Text"]["count"] == 2  # alpha.txt, gamma.txt
            assert int(stats["Text"]["total_bytes"]) == 1300
            assert all(s["count"] > 0 for s in ks["statistics"])

            # favorites over search.paths (the favorites route's query)
            fp = lib.db.find_one("file_path", name="alpha")
            await r.exec(node, "files.setFavorite",
                         {"id": fp["id"], "favorite": True}, library_id=lid)
            favs = await r.exec(node, "search.paths",
                                {"filter": {"favorite": True}}, library_id=lid)
            assert [n["name"] for n in favs["nodes"]] == ["alpha"]

            # recents: nothing accessed yet
            rec = await r.exec(node, "search.paths",
                               {"filter": {"accessed": True}}, library_id=lid)
            assert rec["nodes"] == []

            # open two files (in order), then query the recents route:
            # accessed-only, most recent first
            beta = lib.db.find_one("file_path", name="beta")
            await r.exec(node, "files.updateAccessTime",
                         {"ids": [fp["id"]]}, library_id=lid)
            await asyncio.sleep(0.01)  # distinct ISO timestamps
            # unknown ids are skipped, not fatal mid-batch
            await r.exec(node, "files.updateAccessTime",
                         {"ids": [999999, beta["id"]]}, library_id=lid)
            rec = await r.exec(
                node, "search.paths",
                {"filter": {"accessed": True},
                 "orderBy": "dateAccessed", "orderDir": "desc"},
                library_id=lid,
            )
            assert [n["name"] for n in rec["nodes"]] == ["beta", "alpha"]
            assert all(n["object_date_accessed"] for n in rec["nodes"])

            # unfiltered dateAccessed ASC: never-accessed rows sort LAST
            # (regression: COALESCE to '' put them first under ASC)
            allrows = await r.exec(
                node, "search.paths",
                {"orderBy": "dateAccessed", "orderDir": "asc"},
                library_id=lid,
            )
            accessed_flags = [bool(n["object_date_accessed"])
                              for n in allrows["nodes"]]
            assert accessed_flags[:2] == [True, True]
            assert not any(accessed_flags[2:])
            assert [n["name"] for n in allrows["nodes"][:2]] == ["alpha", "beta"]

            # search.objects must agree on dateAccessed semantics
            objs = await r.exec(
                node, "search.objects",
                {"orderBy": "dateAccessed", "orderDir": "asc"},
                library_id=lid,
            )
            obj_flags = [bool(o.get("date_accessed")) for o in objs["nodes"]]
            assert obj_flags[:2] == [True, True]
            assert not any(obj_flags[2:])

            # job outcomes surface as persisted notifications: the
            # scan chain's terminus emitted exactly one "ok" row
            notifs = await r.exec(node, "notifications.get")
            jobs_notified = [n for n in notifs
                             if n["data"].get("job") == "media_processor"]
            assert len(jobs_notified) == 1
            assert jobs_notified[0]["data"]["kind"] == "ok"

            # inspector media section: decoded EXIF facts for an image
            png = lib.db.find_one("file_path", name="real")
            md = await r.exec(node, "files.getMediaData",
                              png["object_id"], library_id=lid)
            assert md["resolution"] == [48, 36]
            # a text file has no media_data row → null, not an error
            assert await r.exec(node, "files.getMediaData",
                                fp["object_id"], library_id=lid) is None
        finally:
            await node.shutdown()

    asyncio.run(run())


def test_http_server_and_custom_uri(tmp_path, corpus):
    async def run():
        import aiohttp

        node, lib, loc = await _scanned_node(tmp_path, corpus)
        try:
            port = await node.start_api()
            base = f"http://127.0.0.1:{port}"
            async with aiohttp.ClientSession() as http:
                # explorer web UI at the root
                async with http.get(f"{base}/") as resp:
                    assert resp.status == 200
                    page = await resp.text()
                    assert "spacedrive-tpu explorer" in page
                    # live updates + API calls ride the generated client
                    assert "/rspc/client.js" in page
                async with http.get(f"{base}/rspc/client.js") as resp:
                    assert resp.status == 200
                    js = await resp.text()
                    assert "SdSocket" in js and "/rspc/ws" in js
                    assert '"paths"' in js  # search namespace emitted

                # rspc over HTTP
                async with http.post(f"{base}/rspc/buildInfo", json={}) as resp:
                    assert resp.status == 200
                    assert (await resp.json())["result"]["version"]
                async with http.post(
                    f"{base}/rspc/search.paths",
                    json={"library_id": str(lib.id), "arg": {"take": 5}},
                ) as resp:
                    body = await resp.json()
                    assert resp.status == 200 and body["result"]["items"]
                async with http.post(f"{base}/rspc/unknown.key", json={}) as resp:
                    assert resp.status == 404

                # custom-uri file serving with range
                fp = lib.db.find_one("file_path", name="beta")
                url = f"{base}/spacedrive/file/{lib.id}/{loc['id']}/beta.bin"
                async with http.get(url) as resp:
                    assert resp.status == 200
                    full = await resp.read()
                    assert len(full) == 2000
                async with http.get(
                    url, headers={"Range": "bytes=100-199"}
                ) as resp:
                    assert resp.status == 206
                    part = await resp.read()
                    assert part == full[100:200]
                    assert "bytes 100-199/2000" in resp.headers["Content-Range"]
                # traversal guarded
                bad = f"{base}/spacedrive/file/{lib.id}/{loc['id']}/../../etc/passwd"
                async with http.get(bad) as resp:
                    assert resp.status in (400, 404)

                # websocket transport: query + subscription
                async with http.ws_connect(f"{base}/rspc/ws") as ws:
                    await ws.send_str(
                        json.dumps({"id": "1", "type": "query", "key": "buildInfo"})
                    )
                    msg = json.loads((await ws.receive()).data)
                    assert msg["id"] == "1" and msg["result"]["version"]
                    await ws.send_str(
                        json.dumps(
                            {
                                "id": "2",
                                "type": "subscriptionAdd",
                                "key": "invalidation.listen",
                            }
                        )
                    )
                    await asyncio.sleep(0.1)
                    await node.router.exec(
                        node, "tags.create", {"name": "ws"}, library_id=str(lib.id)
                    )
                    msg = json.loads((await ws.receive()).data)
                    assert msg["id"] == "2" and msg["event"]["key"] == "tags.list"
        finally:
            await node.shutdown()

    asyncio.run(run())


def test_job_progress_and_invalidation_reach_node_bus(tmp_path, corpus):
    """Live-UI contract: job progress events surface on the NODE bus
    (jobs.progress subscription) and completed scan jobs invalidate
    their queries (the reference's invalidate_query! in job finalize)
    — a fresh scan must produce both without any explicit mutation."""

    async def run():
        node, lib, loc = await _scanned_node(tmp_path, corpus)
        try:
            sub = node.event_bus.subscribe()
            open(os.path.join(corpus, "fresh.txt"), "w").write("new content")
            await node.router.exec(
                node, "locations.fullRescan",
                {"location_id": loc["id"]}, library_id=str(lib.id),
            )
            await node.jobs.wait_idle()
            progress, invalidated = [], []
            for ev in sub.poll():
                if isinstance(ev, tuple) and ev[0] == "JobProgress":
                    progress.append(ev[1])
                if isinstance(ev, tuple) and ev[0] == CoreEventKind.INVALIDATE_OPERATION:
                    invalidated.append(ev[1].key)
            assert progress, "no JobProgress on the node bus"
            assert progress[0].name  # event carries the job name
            assert "search.paths" in invalidated
            assert "locations.list" in invalidated
        finally:
            await node.shutdown()

    asyncio.run(run())


def test_host_header_guard_blocks_dns_rebinding(tmp_path, corpus):
    """ADVICE r5: a DNS-rebinding page (attacker domain resolving to
    127.0.0.1) could read /spacedrive/local and the ephemeralFiles.*
    procedures through the victim's browser. The Host-validating
    middleware must 403 any non-local Host while leaving every
    localhost spelling working."""

    async def run():
        import aiohttp

        node, lib, loc = await _scanned_node(tmp_path, corpus)
        try:
            port = await node.start_api()
            base = f"http://127.0.0.1:{port}"
            async with aiohttp.ClientSession() as http:
                # the rebinding read path is closed
                async with http.get(
                    f"{base}/spacedrive/local",
                    params={"path": os.path.abspath(__file__)},
                    headers={"Host": "attacker.example.com"},
                ) as resp:
                    assert resp.status == 403
                # rspc procedures (ephemeralFiles.* included) equally
                async with http.post(
                    f"{base}/rspc/buildInfo", json={},
                    headers={"Host": "attacker.example.com:1234"},
                ) as resp:
                    assert resp.status == 403
                # every local spelling still passes
                for h in (f"127.0.0.1:{port}", f"localhost:{port}",
                          "127.0.0.1", "[::1]:8080"):
                    async with http.post(
                        f"{base}/rspc/buildInfo", json={},
                        headers={"Host": h},
                    ) as resp:
                        assert resp.status == 200, h
                # and the legitimate local read path still works
                async with http.get(
                    f"{base}/spacedrive/local",
                    params={"path": os.path.join(corpus, "alpha.txt")},
                ) as resp:
                    assert resp.status == 200
        finally:
            await node.shutdown()

    asyncio.run(run())


def test_keys_unlock_wrong_password_retry_keeps_vault_intact(tmp_path):
    """ADVICE r5: keys.unlock on an ALREADY-unlocked vault used to
    clobber the good master before the probe, so a typo'd retry called
    km.lock() and unmounted every key out from under its consumers.
    The failed retry must restore the previous master and leave every
    mounted key mounted."""
    pytest.importorskip("cryptography")  # AEAD/Argon2id are hard-gated

    async def run():
        from spacedrive_tpu.node import Node

        node = Node(os.path.join(tmp_path, "node"), use_device=False)
        node.config.config.p2p.enabled = False
        await node.start()
        try:
            lib = await node.create_library("keys-lib")
            r = node.router
            lid = str(lib.id)
            await r.exec(node, "keys.unlock", {"password": "hunter2"},
                         library_id=lid)
            added = await r.exec(node, "keys.add", {"automount": True},
                                 library_id=lid)
            # keys.add stores the flag and mounts nothing (a key
            # automounts at keys.unlock): mount it as a user would
            await r.exec(node, "keys.mount", added["uuid"], library_id=lid)
            st = await r.exec(node, "keys.state", None, library_id=lid)
            assert st["unlocked"] and st["keys"][0]["mounted"]

            with pytest.raises(RspcError):
                await r.exec(node, "keys.unlock", {"password": "wrong"},
                             library_id=lid)
            st = await r.exec(node, "keys.state", None, library_id=lid)
            assert st["unlocked"], "wrong-password retry locked the vault"
            assert all(k["mounted"] for k in st["keys"]), \
                "wrong-password retry unmounted keys"
            # the true password still unlocks (master wasn't corrupted)
            out = await r.exec(node, "keys.unlock", {"password": "hunter2"},
                               library_id=lid)
            assert out is not None
        finally:
            await node.shutdown()

    asyncio.run(run())


def test_keys_unlock_retry_logic_with_stub_manager(tmp_path):
    """Same ADVICE r5 regression, crypto-free: the namespace's
    snapshot/restore control flow driven through a stub KeyManager, so
    the logic is pinned even in containers without `cryptography`."""

    async def run():
        from spacedrive_tpu.crypto.keys import CryptoError
        from spacedrive_tpu.node import Node

        class StubKey:
            def __init__(self, uuid):
                self.uuid = uuid
                self.automount = True
                self.algorithm = 0

        class StubKM:
            """KeyManager surface keys.* touches; mount() only accepts
            the true password."""

            def __init__(self):
                self._master = None
                self.stored = {"k1": StubKey("k1")}
                self._mounted = set()

            @property
            def unlocked(self):
                return self._master is not None

            def set_master_password(self, pw):
                self._master = bytearray(pw)

            def mounted_uuids(self):
                return list(self._mounted)

            def mount(self, u):
                if bytes(self._master or b"") != b"hunter2":
                    raise CryptoError("wrong master password")
                self._mounted.add(u)

            def unmount(self, u):
                self._mounted.discard(u)

            def automount(self):
                n = 0
                for sk in self.stored.values():
                    if sk.automount and sk.uuid not in self._mounted:
                        self.mount(sk.uuid)
                        n += 1
                return n

            def lock(self):
                self._mounted.clear()
                self._master = None

        node = Node(os.path.join(tmp_path, "node"), use_device=False)
        node.config.config.p2p.enabled = False
        await node.start()
        try:
            lib = await node.create_library("keys-stub-lib")
            km = StubKM()
            lib.key_manager = km  # _key_manager() returns the cached one
            r = node.router
            lid = str(lib.id)
            out = await r.exec(node, "keys.unlock",
                               {"password": "hunter2"}, library_id=lid)
            # the probe already mounted the automount key, so the
            # automount sweep finds nothing left to do
            assert out["automounted"] == 0
            assert km.unlocked and km.mounted_uuids() == ["k1"]

            with pytest.raises(RspcError):
                await r.exec(node, "keys.unlock", {"password": "wrong"},
                             library_id=lid)
            # the regression: retry must restore the master AND leave
            # the mounted key alone (previously: km.lock() wiped both)
            assert km.unlocked, "retry locked the vault"
            assert km.mounted_uuids() == ["k1"], "retry unmounted keys"
            assert bytes(km._master) == b"hunter2"
        finally:
            await node.shutdown()

    asyncio.run(run())
