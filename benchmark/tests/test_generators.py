"""Locations and traffic are a function of the seed alone."""

import copy
import os

import pytest

from benchmark import harness
from benchmark.generators import camera_roll, common, home_tree
from benchmark.tests.conftest import tiny_configs

BIG = 3000000019  # more than 32 signed bits hold


@pytest.mark.parametrize("gen,name", [(home_tree, "homedir"),
                                      (camera_roll, "photolib")])
def test_plan_is_deterministic_in_the_seed(gen, name):
    config = tiny_configs()[name]
    assert gen.plan(config, BIG) == gen.plan(config, BIG)
    assert gen.plan(config, BIG) != gen.plan(config, BIG + 1)
    assert len({e["rel"] for e in gen.plan(config, BIG)}) == len(gen.plan(config, BIG))


def test_home_tree_has_what_the_cell_needs():
    config = tiny_configs()["homedir"]
    config["files"] = 400
    m = home_tree.plan(config, 7)
    assert len(m) == 400
    plain = [e for e in m if not e.get("image")]
    contents = [(e["size"], tuple(e["content"])) for e in plain]
    assert len(contents) - len(set(contents)) == 40      # the copies
    assert sum(1 for e in m if e.get("image")) == 16
    assert {102399, 102400, 102401} <= {e["size"] for e in m}


def test_files_on_disk_are_the_same_bytes_for_the_same_seed(tmp_path):
    config = tiny_configs()["photolib"]
    for d in ("a", "b"):
        os.makedirs(tmp_path / d)
        common.write_manifest(str(tmp_path / d), camera_roll.plan(config, 5))
    for e in camera_roll.plan(config, 5):
        with open(tmp_path / "a" / e["rel"], "rb") as f, \
                open(tmp_path / "b" / e["rel"], "rb") as g:
            assert f.read() == g.read()


def test_traffic_mutations_are_deterministic_and_keep_the_manifest_true(tmp_path):
    config = tiny_configs()["homedir"]
    config["files"] = 400
    params = {"fresh_data_dir": False,
              "mutate": {"rewrite_share": 0.01, "add_share": 0.005,
                         "delete_share": 0.005}}
    logs = []
    for d in ("a", "b"):
        loc = str(tmp_path / d)
        os.makedirs(loc)
        m = home_tree.plan(config, 9)
        common.write_manifest(loc, m)
        t = harness.Traffic(params, config, home_tree, 9, loc, m)
        logs.append([copy.deepcopy(t.before_pass()) for _ in range(3)])
        on_disk = {os.path.relpath(os.path.join(r, n), loc)
                   for r, _d, names in os.walk(loc) for n in names}
        assert on_disk == {e["rel"] for e in m}
    assert logs[0] == logs[1]
    first = logs[0][0]
    assert len(first["rewritten"]) == 4 and len(first["added"]) == 2 \
        and len(first["deleted"]) == 2


def test_a_seed_changes_the_answers_and_not_the_work():
    """Every seed gets the same tree, the same set of sizes and the same
    images, with other bytes and the sizes in another order."""
    config = tiny_configs()["homedir"]
    config["files"] = 400
    a, b = home_tree.plan(config, 1), home_tree.plan(config, BIG)
    assert [e["rel"] for e in a] == [e["rel"] for e in b]
    assert sorted(e["size"] for e in a) == sorted(e["size"] for e in b)
    assert [e["size"] for e in a] != [e["size"] for e in b]
    assert [e.get("image") for e in a] == [e.get("image") for e in b]
    assert all(x["content"] != y["content"] for x, y in zip(a, b))
