"""BENCHMARK.json and every file it names, against the rules of the
benchmark's contract that can be checked without a chip."""

import json
import os
import re

import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|ffn|latent|head_size"
                   r"|head_dim|state_size|proj|expansion|experts_per")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load("BENCHMARK.json")


def line(s, limit=200):
    return isinstance(s, str) and 1 <= len(s) <= limit \
        and "\n" not in s and "\t" not in s


def test_top_level_keys_command_paths_and_run_seconds(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(manifest["command"]) <= 32
    assert all(line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in manifest["command"]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in manifest["paths"])
            assert os.path.isfile(os.path.join(ROOT, word))
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 10 <= rs <= 51
    # a full check with all 24 cells has to fit
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_file_under_paths_has_a_plain_name(manifest):
    for p in manifest["paths"]:
        for dirpath, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                if f.endswith(".pyc"):
                    continue
                assert PATH.match(rel), rel


def test_configs(manifest):
    cfgs = manifest["configs"]
    assert 1 <= len(cfgs) <= 24
    names = [c["name"] for c in cfgs]
    assert len(set(names)) == len(names)
    assert len({c["file"] for c in cfgs}) == len(cfgs)
    used = {w["config"] for w in manifest["workloads"]}
    for c in cfgs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert isinstance(load(c["file"]), dict)
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
        for family in ("gpt-oss", "gemma", "llama", "qwen3.5"):
            assert family not in c["source"].lower()


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in manifest["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        body = load("benchmark", "workloads", w["name"] + ".json")
        # one place for each fact: the entry's are not said again
        assert not set(body) & set(w) - {"traffic"}, set(body) & set(w)
        assert isinstance(body["traffic"], dict)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "drivers", body["driver"] + ".py"))
        assert set(body["limits"]) >= {"window_compiles"}
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def cells_reporting(manifest, metric):
    """The cells a metric names; without the key, every cell (end to end) or
    every cell that reports the metric it moves (per layer)."""
    if "workloads" in metric:
        return metric["workloads"]
    if "moves" in metric:
        return cells_reporting(manifest, next(
            m for m in manifest["end_to_end"] if m["name"] == metric["moves"]))
    return [w["name"] for w in manifest["workloads"]]


def test_end_to_end_metrics(manifest):
    e2e = manifest["end_to_end"]
    assert 1 <= len(e2e) <= 16
    cells = {w["name"] for w in manifest["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(cells_reporting(manifest, m)) <= cells
    setup = [m for m in e2e if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    for cell in cells:      # set-up and at least one more
        assert sum(1 for m in e2e
                   if cell in cells_reporting(manifest, m)) >= 2


def test_per_layer_metrics(manifest):
    per = manifest["per_layer"]
    assert 1 <= len(per) <= 128
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    names = [m["name"] for m in per] + list(e2e)
    assert len(set(names)) == len(names)
    covered = set()
    for m in per:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        # PR 22 was refused for a layer written as words with spaces
        assert NAME.match(m["layer"]), m["layer"]
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        moved = set(cells_reporting(manifest, e2e[m["moves"]]))
        mine = set(cells_reporting(manifest, m))
        assert mine and mine <= moved, (m["name"], mine - moved)
        covered |= mine
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        assert callable(run.load_reader(m["name"]))
    assert covered == {w["name"] for w in manifest["workloads"]}
    # beside each kernel roofline, the whole step's share of the peak
    for m in per:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(cells_reporting(manifest, m))
                       <= set(cells_reporting(manifest, o)) for o in per)


def test_a_new_cell_is_data_alone(manifest):
    """PERF.md's first Open-questions cell, added as a later PR may add it:
    a file of its own and appended entries. No entry or file that is there
    changes but the cell lists of the end-to-end metrics it reports, and it
    reports every per-layer metric that moves one of them."""
    new, like = "bert-base-decoder.short-16", "bert-base-decoder.closed-64"
    grown = json.loads(json.dumps(manifest))
    entry = dict(run.find(manifest["workloads"], like, "workload"),
                 name=new, traffic="short-16", why="prompts 4-16")
    grown["workloads"].append(entry)
    for m in grown["end_to_end"]:
        if like in m.get("workloads", []):
            m["workloads"].append(new)
    assert grown["per_layer"] == manifest["per_layer"]
    for section in ("end_to_end", "per_layer"):
        names = lambda man, cell: [m["name"] for m in run.cell_metrics(  # noqa: E731
            man, section, cell)]
        assert names(grown, new) == names(manifest, like)
        for w in manifest["workloads"]:      # and the others' are as before
            assert names(grown, w["name"]) == names(manifest, w["name"])
    assert "train.collective_share" not in [
        m["name"] for m in run.cell_metrics(
            manifest, "per_layer", "bert-large-mlm.train-16x512")]


def test_run_py_names_no_cell_config_or_metric(manifest):
    with open(os.path.join(ROOT, "benchmark", "run.py")) as f:
        text = f.read()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            if e["name"] != "setup_s":
                assert e["name"] not in text, e["name"]
