"""The harness end to end at a tiny size on the CPU, driven through its
functions: a sound run is correct, and a run with the timed path broken
underneath is not, once for each fault a cell can have."""

import copy

import pytest

from benchmark import check, harness
from benchmark.tests.conftest import cpu_stamp


def run(tiny_root, tmp_path, workload, seconds=1.0):
    return harness.run_cell(workload, 2147483999, seconds, False,
                            root=tiny_root, require=cpu_stamp,
                            work=str(tmp_path / "work"))


def failing(result) -> set:
    return {k for k, (v, lim) in result["compared"].items() if v > lim}


@pytest.mark.parametrize("workload", ["homedir.cold", "photolib.cold",
                                      "homedir.rescan", "photolib.raw"])
def test_sound_run_is_correct(tiny_root, tmp_path, workload):
    r = run(tiny_root, tmp_path, workload)
    assert r["correct"] is True, failing(r)
    assert r["failed"] == 0 and r["attempted"] >= 8
    assert set(r["metrics"]) == {"pass_rate", "setup_s"}
    assert r["metrics"]["pass_rate"]["value"] > 0
    assert list(r)[-1] == "compared"
    # every name compared is one a kind of file may not add again
    assert set(r["compared"]) <= check.OWN_NAMES
    # whole passes only: the window ends with its last pass
    assert r["window_s"] == pytest.approx(sum(r["pass_cycle_s"]), rel=0.02)
    assert r["window_s"] >= 1.0


def test_altered_answer_is_not_correct(tiny_root, tmp_path, monkeypatch):
    """A cas_id altered where it is produced."""
    from spacedrive_tpu.ops import blake3_jax

    real = blake3_jax.words_to_hex

    def altered(words, hex_chars=64):
        out = real(words, hex_chars)
        out[0] = ("0" if out[0][0] != "0" else "1") + out[0][1:]
        return out

    monkeypatch.setattr(blake3_jax, "words_to_hex", altered)
    r = run(tiny_root, tmp_path, "homedir.cold")
    assert r["correct"] is False
    assert "cas_mismatch" in failing(r) and r["failed"] > 0


def test_half_the_batch_left_out_is_not_correct(tiny_root, tmp_path,
                                               monkeypatch):
    """Every second image never reaches the thumbnailer."""
    from spacedrive_tpu.object.media.thumbnail.actor import Thumbnailer

    real = Thumbnailer.new_indexed_thumbnails_batch

    def half(self, library_id, entries, background=False):
        return real(self, library_id, list(entries)[::2], background)

    monkeypatch.setattr(Thumbnailer, "new_indexed_thumbnails_batch", half)
    r = run(tiny_root, tmp_path, "photolib.cold")
    assert r["correct"] is False
    assert "thumbnail_missing" in failing(r) and r["failed"] > 0


def test_state_left_unchanged_is_not_correct(tiny_root, tmp_path, monkeypatch):
    """A rescan pass that returns without having scanned."""
    real = harness.index_pass
    seen = []

    async def unchanged(data_dir, location):
        if not seen:
            seen.append(await real(data_dir, location))
        return copy.deepcopy(seen[0])

    monkeypatch.setattr(harness, "index_pass", unchanged)
    r = run(tiny_root, tmp_path, "homedir.rescan")
    assert r["correct"] is False
    assert {"rescan_stale", "rescan_not_deleted"} <= failing(r)


def test_compile_in_the_window_is_not_correct(tiny_root, tmp_path, monkeypatch):
    """A program the warm-up did not know compiles inside the window."""
    import jax
    import jax.numpy as jnp

    real = harness.index_pass

    async def compiling(data_dir, location):
        jax.jit(lambda x: x * 3 + len(data_dir))(jnp.ones((7, 3))).block_until_ready()
        return await real(data_dir, location)

    monkeypatch.setattr(harness, "index_pass", compiling)
    r = run(tiny_root, tmp_path, "photolib.cold")
    assert r["correct"] is False and "compiles_in_window" in failing(r)
