"""A cell, a configuration, a traffic mix, a generator and a per-layer
metric added as files and entries only are found by name."""

import json
import os

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_new_files_are_found_by_name(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    extra = tmp_path / "benchmark_more"
    for d in ("configs", "traffic", "metrics", "generators"):
        os.makedirs(extra / d)
    (extra / "configs" / "mailbox.json").write_text(json.dumps(
        {"name": "mailbox", "generator": "maildir", "messages": 12}))
    (extra / "traffic" / "cold_twice.json").write_text(json.dumps(
        {"name": "cold_twice", "fresh_data_dir": True, "mutate": None}))
    (extra / "generators" / "maildir.py").write_text(
        "def plan(config, seed, scale=1.0):\n"
        "    return [{'rel': f'cur/{i}.eml', 'size': 100 + i,\n"
        "             'content': [seed, 0, i]} for i in range(config['messages'])]\n")
    (extra / "metrics" / "walk_ms.mail.py").write_text(
        "def read(ctx):\n    return 1e3 * ctx['walk_s']\n")
    doc["paths"].append("benchmark_more")
    doc["configs"].append({"name": "mailbox", "source": "x", "reduced": [],
                           "file": "benchmark_more/configs/mailbox.json",
                           "why": "y"})
    doc["workloads"].append({"name": "mailbox.cold", "config": "mailbox",
                             "traffic": "cold_twice", "chips": 1, "why": "z"})
    doc["per_layer"].append({"name": "walk_ms.mail", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "jobs", "moves": "pass_rate",
                             "workloads": ["mailbox.cold"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    bench = harness.Bench(str(tmp_path))
    spec = bench.cell("mailbox.cold")
    assert spec["config"]["messages"] == 12
    assert spec["traffic"]["name"] == "cold_twice"
    manifest = bench.generator(spec["config"]).plan(spec["config"], 3)
    assert len(manifest) == 12
    names = [m["name"] for m in bench.metrics_for("mailbox.cold", "per_layer")]
    assert names == ["walk_ms.mail"]
    assert bench.reader("walk_ms.mail")({"walk_s": 0.25}) == 250.0
    # the cells that were there are found as before
    assert bench.cell("homedir.rescan")["traffic"]["mutate"]["add_share"] == 0.0025
    assert [m["name"] for m in bench.metrics_for("homedir.rescan", "end_to_end")] \
        == ["pass_rate", "setup_s"]


def test_every_metric_of_benchmark_json_has_its_reader_and_every_cell_its_files():
    bench = harness.Bench(ROOT)
    for m in bench.doc["per_layer"]:
        assert callable(bench.reader(m["name"]))
        assert m["moves"] in {e["name"] for e in bench.doc["end_to_end"]}
    for w in bench.doc["workloads"]:
        spec = bench.cell(w["name"])
        assert hasattr(bench.generator(spec["config"]), "plan")
        assert bench.metrics_for(w["name"], "per_layer")
