"""BENCHMARK.json names only files that exist, with names and units the
driver accepts."""
import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names():
    b = load()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in b[k]}) == len(b[k])
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_files_exist_for_every_name():
    b = load()
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert os.path.isfile(os.path.join(BENCH, "configs",
                                           f"{c['name']}.py"))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           f"{cfg['driver']}.py"))
    for w in b["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           f"{w['traffic']}.json"))
        assert w["chips"] in (1, 4)
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py"))


def test_every_cell_reports_setup_another_metric_and_a_layer():
    b = load()
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        mine = [m for m in b["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2
        layers = [m for m in b["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])]
        assert layers and all(m["moves"] in e2e for m in layers)
