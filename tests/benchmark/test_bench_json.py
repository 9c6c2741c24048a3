"""``BENCHMARK.json`` against the rules its readers rely on: names and
units in the allowed characters, every file it names present, every
per-layer metric moving an end-to-end metric each of its cells reports,
and every metric with a reader under ``bench/metrics``."""
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(bench, metric, cell):
    entry = {m["name"]: m for m in bench["end_to_end"]}[metric]
    return cell in entry.get("workloads", [cell])


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert len(bench["command"]) <= 32
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_and_units(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    for c in bench["configs"]:
        names += c["reduced"]
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
               ) == len(bench["end_to_end"]) + len(bench["per_layer"])


def test_files_exist_and_every_config_is_used(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        with open(os.path.join(REPO, c["file"])) as f:
            json.load(f)
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            REPO, "bench", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            REPO, "bench", "cells", w["name"] + ".json"))
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)


def test_every_metric_has_a_reader(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "setup_s":
            continue
        assert os.path.exists(os.path.join(
            REPO, "bench", "metrics", m["name"] + ".py")), m["name"]


def test_end_to_end_bounds(bench):
    names = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in names
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in bench["workloads"]:
        reported = [m for m in bench["end_to_end"]
                    if _reports(bench, m["name"], w["name"])]
        assert len(reported) >= 2, w["name"]


def test_per_layer_moves_an_end_to_end_metric_of_each_cell(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m["workloads"]:
            assert cell in cells
            assert _reports(bench, m["moves"], cell), (m["name"], cell)
    for cell in cells:
        assert any(cell in m["workloads"] for m in bench["per_layer"])
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"], []).append(m["name"])
    assert all("\n" not in layer for layer in layers)


def test_mfu_beside_every_trained_cell(bench):
    trained = {c for m in bench["end_to_end"]
               if m["name"] == "train_tokens_per_s" for c in m["workloads"]}
    mfu = {c for m in bench["per_layer"] if "mfu" in m["name"]
           for c in m["workloads"]}
    assert trained <= mfu
