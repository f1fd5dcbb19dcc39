"""sdx CLI smoke: index → status → browse → duplicates → crypto.

Parity targets: ref:apps/server (headless host), apps/cli (crypto
inspector), SURVEY §7 step 4 CLI surface.
"""

import json
import os

from spacedrive_tpu.cli import build_parser, main


def test_parser_covers_commands():
    p = build_parser()
    args = p.parse_args(["index", "/x", "--backend", "cpu"])
    assert args.cmd == "index" and args.backend == "cpu"
    args = p.parse_args(["crypto", "inspect", "/y"])
    assert args.crypto_cmd == "inspect"
    for cmd in (
        ["serve"],
        ["status"],
        ["browse", "/x"],
        ["duplicates"],
        ["peers"],
        ["pair", "someidentity"],
        ["spacedrop", "someidentity", "/tmp/f"],
    ):
        assert p.parse_args(cmd).cmd == cmd[0]


def test_cli_index_browse_crypto(tmp_path, capsys):
    corpus = tmp_path / "corpus"
    corpus.mkdir()
    (corpus / "a.txt").write_bytes(b"hello world" * 100)
    (corpus / "b.bin").write_bytes(os.urandom(4096))
    data_dir = str(tmp_path / "home")

    rc = main(
        ["--data-dir", data_dir, "index", str(corpus), "--backend", "cpu", "--no-p2p"]
    )
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["files"] == 2 and out["objects"] == 2 and out["backend"] == "cpu"

    rc = main(["--data-dir", data_dir, "browse", str(corpus)])
    assert rc == 0
    listing = capsys.readouterr().out
    assert "a.txt" in listing and "b.bin" in listing

    rc = main(["--data-dir", data_dir, "status"])
    assert rc == 0
    status = json.loads(capsys.readouterr().out)
    assert status["libraries"][0]["file_paths"] >= 2
    assert {j["name"] for j in status["libraries"][0]["recent_jobs"]} >= {
        "indexer",
        "file_identifier",
    }

    # crypto roundtrip through the CLI (reference apps/cli surface)
    secret = tmp_path / "s.txt"
    secret.write_text("classified")
    rc = main(
        ["--data-dir", data_dir, "crypto", "encrypt", str(secret), "--password", "pw"]
    )
    assert rc == 0
    capsys.readouterr()
    rc = main(["--data-dir", data_dir, "crypto", "inspect", str(secret) + ".sdenc"])
    assert rc == 0
    info = json.loads(capsys.readouterr().out)
    assert info["algorithm"] == "XCHACHA20_POLY1305" and len(info["keyslots"]) == 1
    secret.unlink()
    rc = main(
        [
            "--data-dir",
            data_dir,
            "crypto",
            "decrypt",
            str(secret) + ".sdenc",
            "--password",
            "pw",
        ]
    )
    assert rc == 0
    assert secret.read_text() == "classified"


def _index_with_faults(tmp_path, capsys, plan: str):
    """One `sdx index --backend tpu` pass under a fault plan; returns
    (exit code, the printed JSON summary)."""
    from spacedrive_tpu.parallel import mesh
    from spacedrive_tpu.utils import faults

    corpus = tmp_path / "corpus"
    corpus.mkdir()
    for i in range(6):
        (corpus / f"f{i}.bin").write_bytes(os.urandom(2000 + i))
    mesh.LADDER.reset()
    try:
        rc = main([
            "--data-dir", str(tmp_path / "home"), "--faults", plan,
            "index", str(corpus), "--backend", "tpu", "--no-p2p",
        ])
    finally:
        faults.clear()
        mesh.LADDER.reset()
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_cli_index_reports_what_ran_not_what_was_asked(tmp_path, capsys):
    """`--backend tpu` with the device hash forced to fail: the ladder
    finishes the pass on the host (exit 0, every job COMPLETED) and the
    summary says so — device stamp, final ladder level, fallback
    counts — instead of echoing the flag."""
    import jax

    from spacedrive_tpu.parallel import mesh
    from spacedrive_tpu.telemetry import counter_value
    from spacedrive_tpu.telemetry.events import RESILIENCE_EVENTS

    # the counts are process-wide (a CLI process starts at zero; this
    # test process may not)
    before = counter_value("sd_cas_backend_fallback_total")
    thumb_before = sum(e["type"] == "thumbnail_cpu_fallback"
                       for e in RESILIENCE_EVENTS.snapshot())
    rc, out = _index_with_faults(
        tmp_path, capsys, "device.blake3:raise:times=inf")
    assert rc == 0 and out["jobs_failed"] == 0
    assert out["jobs"] == {
        "indexer": "COMPLETED", "file_identifier": "COMPLETED",
        "media_processor": "COMPLETED",
    }
    assert out["files"] == 6 and out["backend"] == "tpu"
    assert set(out["job_seconds"]) == set(out["jobs"])
    assert all(s >= 0 for s in out["job_seconds"].values())
    assert out["device"] == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind, "count": 8,
    }
    assert out["ladder_level"] == mesh.LEVEL_HOST
    assert out["cas_backend_fallbacks"] > before
    assert out["thumbnail_cpu_fallbacks"] == thumb_before
    assert out["thumbnail_errors"] == 0


def test_cli_index_exits_nonzero_when_a_job_fails(tmp_path, capsys):
    """A FAILED job in the chain is the command's failure: the feeder's
    producer crashing past its one restart fails the identifier, no
    successor spawns, and `sdx index` returns 1."""
    rc, out = _index_with_faults(
        tmp_path, capsys, "feeder.fetch:crash:times=inf")
    assert rc == 1
    assert out["jobs"] == {
        "indexer": "COMPLETED", "file_identifier": "FAILED",
    }
    assert out["jobs_failed"] == 1


def test_relay_command_serves_rendezvous(tmp_path):
    """`sdx relay` runs the standalone relay: sync HTTP API up AND the
    P2P rendezvous accepting authenticated registrations."""
    import asyncio
    import socket

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    async def run():
        import aiohttp

        from spacedrive_tpu.cli import cmd_relay
        from spacedrive_tpu.p2p.identity import Identity
        from spacedrive_tpu.p2p.relay import (
            _LISTEN_CONTEXT, read_frame, write_frame,
        )

        class Args:
            host = "127.0.0.1"
            port = free_port()
            p2p_port = free_port()
            max_pipes_per_target = 8
            max_pipes = 256
            pipe_rate = None
            stats_interval = 0.0

        task = asyncio.ensure_future(cmd_relay(Args()))
        try:
            async with aiohttp.ClientSession() as http:
                for _ in range(100):
                    try:
                        async with http.post(
                            f"http://127.0.0.1:{Args.port}/api/libraries",
                            json={"uuid": "u", "name": "n"},
                        ) as resp:
                            assert resp.status == 200
                            break
                    except aiohttp.ClientConnectorError:
                        await asyncio.sleep(0.05)
                else:
                    raise TimeoutError("relay HTTP never came up")

            ident = Identity()
            r, w = await asyncio.open_connection("127.0.0.1", Args.p2p_port)
            write_frame(w, {
                "cmd": "listen",
                "identity": str(ident.to_remote_identity()),
                "meta": {},
            })
            await w.drain()
            ch = await read_frame(r)
            write_frame(w, {"sig": ident.sign(
                _LISTEN_CONTEXT + bytes.fromhex(ch["challenge"])).hex()})
            await w.drain()
            assert (await read_frame(r)).get("ok") is True
            w.close()
        finally:
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass

    asyncio.run(run())


def test_licenses_inventory(tmp_path):
    """The deps-generator role (ref:crates/deps-generator): a real
    dependency + license inventory for both dependency planes."""
    import json
    import subprocess
    import sys

    out = tmp_path / "licenses.json"
    rc = subprocess.run(
        [sys.executable, "-m", "spacedrive_tpu.cli", "--data-dir",
         str(tmp_path / "d"), "licenses", "--out", str(out)],
        capture_output=True, text=True, timeout=120,
    )
    assert rc.returncode == 0, rc.stderr
    doc = json.loads(out.read_text())
    py = {d["name"].lower(): d for d in doc["python"]}
    # the core runtime deps resolve with real versions
    for name in ("jax", "numpy", "aiohttp", "cryptography"):
        assert name in py and py[name]["version"], name
    assert any(d["license"] != "unknown" for d in doc["python"])
    native = {d["name"]: d for d in doc["native"]}
    assert "cairo" in native and "freetype" in native
    # every native row reports either a real shared object or the
    # documented degraded-feature marker — never an empty field
    assert all(d["resolved"] for d in doc["native"])
