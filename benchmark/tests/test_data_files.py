"""Every data file loads, and every name and unit keeps to the
characters BENCHMARK.json allows; every cell finds its files by name."""

import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(path):
    with open(path) as f:
        return json.load(f)


def test_every_data_file_loads():
    files = [p for d in ("configs", "traffic", "layer_metrics", "readers")
             for p in glob.glob(os.path.join(BENCH, d, "*.json"))]
    assert len(files) >= 5
    for p in files:
        assert isinstance(load(p), dict), p
        assert NAME.match(os.path.basename(p)), p


def test_benchmark_json_names_units_and_files():
    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for c in b["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        conf = load(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) == set(conf["reduced"])
        assert conf["guarantees"]
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    # every metric has a file of its own and every file an entry; which
    # cells report it is said once, in BENCHMARK.json
    on_file = {load(p)["name"]: load(p) for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.json"))}
    assert set(on_file) == {m["name"] for m in b["per_layer"]}
    for m in b["per_layer"]:
        spec = on_file[m["name"]]
        assert "workloads" not in spec
        for k in ("layer", "unit", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["source"]["reader"] + ".py"))
        moved = e2e[m["moves"]]
        # each listed cell reports the end-to-end metric it should move
        assert set(m.get("workloads", ())) <= set(moved.get("workloads", cells))


def test_configs_and_traffic_state_the_lap():
    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    confs = {c["name"]: load(os.path.join(ROOT, c["file"]))
             for c in b["configs"]}
    for w in b["workloads"]:
        conf = confs[w["config"]]
        t = load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert set(conf["ring_rows_per_capacity_row"]) == {
            "span", "annotation", "binary"}
        assert 0 < conf["retained_whole_share"] < 1
        # the window runs on full rings
        assert t["prefill_laps"] >= 1.0


def test_no_cell_name_in_harness_code():
    b = load(os.path.join(ROOT, "BENCHMARK.json"))
    names = ({w["name"] for w in b["workloads"]}
             | {c["name"] for c in b["configs"]})
    for p in glob.glob(os.path.join(BENCH, "*.py")) + glob.glob(
            os.path.join(BENCH, "readers", "*.py")):
        text = open(p).read()
        for n in names:
            assert n not in text, (p, n)
