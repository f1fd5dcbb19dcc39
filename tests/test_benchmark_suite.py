"""The benchmark's own tests (`benchmark/tests`, run apart from these:
their conftest differs) held by tier-1, one case a file, each `python3
-m pytest <file> -q` in a process of its own under a time limit: PRs
27-30 left the whole-run cases dead for want of this (PERF.md §7)."""

import glob
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(os.path.relpath(p, ROOT) for p in glob.glob(
    os.path.join(ROOT, "benchmark", "tests", "test_*.py")))
#: seconds one file may take; the slowest, the whole runs of
#: `test_harness_cpu.py`, takes 85 s alone on the sandbox's CPU
LIMIT_S = 900


#: cases left out, each with the tier-1 case that holds its substance
#: instead. Only a `benchmark` PR may edit a file of the benchmark, so a
#: case there that pins what a later PR of another kind changes in the
#: program waits here for one (PERF.md §7).
LEFT_OUT = {
    # pins the clips' warm-up names to `x4`, the RGBA frame PR 32's
    # decoder handed on; the frame is RGB since PR 33 and the name
    # follows it: tests/test_photolib_video.py::
    # test_a_clips_programs_are_named_by_the_channels_its_decode_hands_on
    "benchmark/tests/test_video_kind_cpu.py":
        ["test_programs_are_named_from_the_programs_own_tables"],
    # pins four channels for the frame `images.decode_heif` hands on; a
    # photo's is RGB since PR 35 (the handle reports no alpha channel):
    # tests/test_photolib_heic.py::
    # test_the_decoder_hands_on_the_displayed_picture holds the boxes
    # and the decode, test_exif_comes_back_field_for_field the EXIF
    "benchmark/tests/test_heic_kind_cpu.py":
        ["test_written_photos_are_what_the_plan_says"],
    # pins the end of `per_layer` to PR 36's thirteen entries; PR 37
    # appended `fetch_fd_identity_share` after them: benchmark/tests/
    # test_fetch_fd_identity_share.py::
    # test_appended_and_nothing_before_it_moved holds the thirteen to
    # their places
    "benchmark/tests/test_index_path_readers.py":
        ["test_the_thirteen_are_appended_and_nothing_before_them_moved",
         # these four pin the `workloads` of the transaction's readers
         # to the six cells of PR 36; PR 38 appended `photolib.hires` to
         # every list `photolib.heic` stands in: benchmark/tests/
         # test_hires_kind_cpu.py::
         # test_the_lists_pr36_pinned_are_as_they_were_with_this_cell_appended
         # holds each entry as it was with the new cell after it
         *(f"test_declared_with_its_cells_and_found_by_name[{name}]"
           for name in ("db_commit_us_per_file", "db_changes_per_file",
                        "db_reads_per_file", "db_read_us_per_file"))],
}


def test_there_are_files_to_hold():
    assert len(FILES) >= 16


@pytest.mark.parametrize("path", FILES)
def test_benchmark_tests_pass(path):
    # as from a shell: one CPU device (this suite's conftest forces
    # eight), no word of the worker that runs this case
    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS" and not k.startswith("PYTEST_")}
    left_out = [f"--deselect={path}::{case}" for case in LEFT_OUT.get(path, [])]
    done = subprocess.run(
        [sys.executable, "-m", "pytest", path, "-q", "-p", "no:cacheprovider",
         *left_out],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=LIMIT_S)
    assert done.returncode == 0, (done.stdout[-3000:], done.stderr[-1000:])
    assert " passed" in done.stdout and " failed" not in done.stdout
