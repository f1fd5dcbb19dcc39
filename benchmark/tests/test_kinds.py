"""A kind of file found by name, proven over the toy kinds of
`benchmark/tests/toy/` brought as a second `paths` directory: what a
later PR's new files and entries get from the harness with no edit to
it. The whole-run cases drive `harness.run_cell` on the CPU as
`test_harness_cpu.py` does."""

import json
import os
import sqlite3

import numpy as np
import pytest

from benchmark import check, control, harness
from benchmark.generators.common import entries_of, write_manifest
from benchmark.tests.conftest import ROOT, cpu_stamp

SEED = 2147483999
TOY = os.path.join(ROOT, "benchmark", "tests", "toy")


def toy_config(**changes) -> dict:
    with open(os.path.join(TOY, "configs", "toybox.json")) as f:
        return {**json.load(f), **changes}


def make_root(tmp_path, config: dict) -> str:
    """A checkout's worth of benchmark files whose `paths` also hold the
    toy's directory, with one configuration and its two cells."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"].append("benchmark_toy")
    doc["configs"] = [{"name": "toybox", "source": "benchmark/tests",
                       "file": "toybox.json", "reduced": [], "why": "a test"}]
    doc["workloads"] = [
        {"name": f"toybox.{short}", "config": "toybox", "traffic": traffic,
         "chips": 1, "why": "a test"}
        for short, traffic in (("cold", "cold_add"), ("rescan", "rescan_1pct"))]
    (tmp_path / "toybox.json").write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    os.symlink(TOY, tmp_path / "benchmark_toy")
    return str(tmp_path)


@pytest.fixture()
def toy_root(tmp_path):
    return make_root(tmp_path, toy_config())


def written(root: str, tmp_path) -> tuple[str, list[dict], dict]:
    bench = harness.Bench(root)
    config = bench.cell("toybox.cold")["config"]
    kinds = bench.kinds(config)
    location = str(tmp_path / "location")
    manifest = bench.generator(config).plan(config, SEED)
    write_manifest(location, manifest, kinds)
    return location, manifest, kinds


def run(root, tmp_path, workload="toybox.cold"):
    return harness.run_cell(workload, SEED, 1.0, False, root=root,
                            require=cpu_stamp, work=str(tmp_path / "work"))


def failing(result) -> set:
    return {k for k, (v, lim) in result["compared"].items() if v > lim}


def embedding_blobs(monkeypatch, pad: int) -> None:
    """Every pass over the location dispatches the program a pass over
    its blobs would; the small warm-up location never reaches that batch."""
    from spacedrive_tpu.ops import embed_jax

    real = harness.index_pass

    async def with_blobs(data_dir, location):
        if os.path.basename(location) == "location":
            embed_jax.embed_batch(np.zeros((pad, 32, 32, 3), np.float32))
        return await real(data_dir, location)

    monkeypatch.setattr(harness, "index_pass", with_blobs)


# --- the four functions, one by one -----------------------------------------


def test_the_writer_is_called_and_the_size_learned(toy_root, tmp_path):
    location, manifest, kinds = written(toy_root, tmp_path)
    blobs = entries_of(manifest, "blob")
    assert len(blobs) == 5
    for e in blobs:
        with open(os.path.join(location, e["rel"]), "rb") as f:
            data = f.read()
        assert data == kinds["blob"].reference_bytes(e)
        assert e["size"] == len(data) > 8
    # a kind that brings no writer: as a plain file, the size as planned
    for e in entries_of(manifest, "bare"):
        assert os.path.getsize(os.path.join(location, e["rel"])) == e["size"]


def test_the_cas_id_reference_is_of_the_bytes_on_disk(toy_root, tmp_path):
    from benchmark.reference import blake3_np, cas_layout

    location, manifest, kinds = written(toy_root, tmp_path)
    want = check.reference_cas(location, manifest, kinds)
    blobs, bares = entries_of(manifest, "blob"), entries_of(manifest, "bare")
    on_disk = blake3_np.hash_many(
        [cas_layout.message(os.path.join(location, e["rel"])) for e in blobs],
        cas_layout.CAS_HEX // 2)
    assert [want[e["rel"]] for e in blobs] == [d.hex() for d in on_disk]
    # as it was, from size and seed alone: bytes a blob never held
    as_it_was = {e["rel"]: d.hex() for e, d in zip(blobs, blake3_np.hash_many(
        [check.plain_message(e) for e in blobs], cas_layout.CAS_HEX // 2))}
    assert all(as_it_was[e["rel"]] != want[e["rel"]] for e in blobs)
    # a kind with no writer keeps the plain reference
    plain = blake3_np.hash_many([check.plain_message(e) for e in bares],
                                cas_layout.CAS_HEX // 2)
    assert [want[e["rel"]] for e in bares] == [d.hex() for d in plain]


def test_a_name_that_is_held_is_refused():
    c = check.Compared()
    blob = c.scoped("blob")
    blob.worst("blob_size_off", 0, 0)
    blob.add("blob_size_off", 1, 0)
    assert c.numbers == {"blob_size_off": [1, 0]}
    for name in ("cas_mismatch", "thumbnail_pixel_gap", "rescan_stale"):
        with pytest.raises(SystemExit, match=f"'blob'.*{name}.*check.py"):
            blob.worst(name, 0.0, 1e9)
    with pytest.raises(SystemExit, match="'other'.*blob_size_off.*blob"):
        c.scoped("other").add("blob_size_off", 0, 5)
    assert c.numbers == {"blob_size_off": [1, 0]}


def test_the_control_of_a_kind_fails_beside_the_others(toy_root, tmp_path):
    bench = harness.Bench(toy_root)
    config = bench.cell("toybox.cold")["config"]
    r = control.readings(config, bench.generator(config), SEED, str(tmp_path),
                         bench.kinds(config))
    assert r["blob_size_off"] == [8, 0]
    assert control.not_correct(r) == {
        "thumbnail_pixel_gap": True, "embedding_gap": True,
        "cas_mismatch": True, "blob_size_off": True}


def test_churn_never_touches_a_file_with_a_kind(toy_root, tmp_path):
    location, manifest, _kinds = written(toy_root, tmp_path)
    bench = harness.Bench(toy_root)
    spec = bench.cell("toybox.rescan")
    kept = {e["rel"]: open(os.path.join(location, e["rel"]), "rb").read()
            for e in manifest if e.get("kind") or e.get("image")}
    traffic = harness.Traffic(spec["traffic"], spec["config"],
                              bench.generator(spec["config"]), SEED, location,
                              manifest)
    # 12 plain files, one rewritten, one added and one deleted a pass
    for _ in range(10):
        changes = traffic.before_pass()
        assert [len(changes[k]) for k in ("rewritten", "added", "deleted")] \
            == [1, 1, 1]
        assert not any(e.get("kind") or e.get("image")
                       for part in changes.values() for e in part)
    for rel, data in kept.items():
        with open(os.path.join(location, rel), "rb") as f:
            assert f.read() == data, rel


def test_a_listed_kind_that_is_not_found_ends_the_run(tmp_path):
    root = make_root(tmp_path, toy_config(kinds=["blob", "ghost"]))

    def never(_chips):
        raise AssertionError("set-up began")

    with pytest.raises(SystemExit, match="'ghost'.*benchmark/kinds/ghost.py.*"
                                         "benchmark_toy/kinds/ghost.py"):
        harness.run_cell("toybox.cold", SEED, 1.0, False, root=root,
                         require=never, work=str(tmp_path / "work"))


def test_a_kind_on_an_entry_that_is_not_listed_ends_the_run(tmp_path):
    root = make_root(tmp_path, toy_config(kinds=["bare"]))
    with pytest.raises(SystemExit, match="blobs/b_000.blob.*'blob'.*bare"):
        run(root, tmp_path)
    assert not os.path.exists(tmp_path / "work" / "run")


# --- whole runs -------------------------------------------------------------


def test_sound_run_holds_the_kinds_numbers(toy_root, tmp_path, monkeypatch,
                                           capfd):
    embedding_blobs(monkeypatch, 64)
    r = run(toy_root, tmp_path)
    assert r["correct"] is True, failing(r)
    assert r["failed"] == 0 and r["attempted"] >= 22
    assert r["compared"]["blob_size_off"] == [0, 0]
    assert r["compared"]["cas_mismatch"] == [0, 0]
    # the kind's program ran in set-up, so the passes' dispatch of it
    # found it compiled
    assert r["compared"]["compiles_in_window"] == [0, 0]
    err = capfd.readouterr().err
    assert "location: 22 files" in err and "2 images, 5 blob, 3 bare" in err
    programs = err.split("programs: ")[1].splitlines()[0]
    assert "9 warmed" in programs and "('blob_embed_pad64', " in programs
    assert "compared blob_size_off = 0 (limit 0) ok" in err


def test_a_program_left_out_compiles_in_the_window(tmp_path, monkeypatch):
    """The same kind without `programs`, at a batch no other test of
    this process has compiled."""
    root = make_root(tmp_path, toy_config(
        blob={"records": 20, "record_bytes": [64, 9000]}))
    real = harness.Bench.kinds

    def without_programs(self, config):
        kinds = real(self, config)
        del kinds["blob"].programs
        return kinds

    monkeypatch.setattr(harness.Bench, "kinds", without_programs)
    embedding_blobs(monkeypatch, 128)
    r = run(root, tmp_path)
    assert r["correct"] is False
    assert failing(r) == {"compiles_in_window"}


def test_a_number_over_its_limit_is_not_correct(toy_root, tmp_path,
                                                monkeypatch):
    """A blob's size altered where it is produced: the row as the pass
    left it, but for the header."""
    real = harness.index_pass

    async def short_by_the_header(data_dir, location):
        summary = await real(data_dir, location)
        db = check.library_db(data_dir)
        path = db.execute("PRAGMA database_list").fetchone()["file"]
        db.close()
        with sqlite3.connect(path) as rw:
            size, = rw.execute(
                "SELECT size_in_bytes_bytes FROM file_path WHERE name = "
                "'b_000' AND extension = 'blob'").fetchone()
            rw.execute(
                "UPDATE file_path SET size_in_bytes_bytes = ? WHERE name = "
                "'b_000'", ((int.from_bytes(size, "little") - 8)
                            .to_bytes(8, "little"),))
        rw.close()
        return summary

    monkeypatch.setattr(harness, "index_pass", short_by_the_header)
    r = run(toy_root, tmp_path)
    assert r["correct"] is False
    assert failing(r) == {"blob_size_off"}
    assert r["compared"]["blob_size_off"] == [8, 0]
    assert r["failed"] == len(r["pass_cycle_s"]) >= 1


def test_the_reference_as_it_was_reads_every_blob_wrong(toy_root, tmp_path,
                                                        monkeypatch):
    """`reference_cas` with the rule it had: from disk for images alone."""
    monkeypatch.setattr(check, "read_from_disk",
                        lambda entry, _kinds: bool(entry.get("image")))
    r = run(toy_root, tmp_path)
    assert r["correct"] is False and failing(r) == {"cas_mismatch"}
    assert r["compared"]["cas_mismatch"][0] == 5 * len(r["pass_cycle_s"])


def test_sound_rescan_leaves_the_kinds_files_alone(toy_root, tmp_path):
    r = run(toy_root, tmp_path, "toybox.rescan")
    assert r["correct"] is True, failing(r)
    assert r["compared"]["blob_size_off"] == [0, 0]
    assert r["compared"]["rescan_stale"] == [0, 0]
    assert r["failed"] == 0
