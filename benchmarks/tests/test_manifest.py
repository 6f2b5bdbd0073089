"""BENCHMARK.json against its contract and against the files it names."""
import glob
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load(os.path.join(ROOT, "BENCHMARK.json"))


def test_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert manifest["paths"] == ["benchmarks"]
    assert manifest["command"][1].startswith("benchmarks/")
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    cells = len(manifest["workloads"])
    assert 1 <= cells <= 24
    # a full check must fit with the full 24 cells
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(
        1, cells // 4)


def test_names_units_and_entry_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for group in (metrics, manifest["workloads"], manifest["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    for text in [c["why"] for c in manifest["configs"]] \
            + [c["source"] for c in manifest["configs"]] \
            + manifest["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def cells_of(metric, manifest):
    return set(metric.get("workloads")
               or [w["name"] for w in manifest["workloads"]])


def test_every_cell_reports_what_it_must(manifest):
    for w in manifest["workloads"]:
        mine = [m["name"] for m in manifest["end_to_end"]
                if w["name"] in cells_of(m, manifest)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w["name"] in cells_of(m, manifest)
                   for m in manifest["per_layer"])
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def test_moves_names_an_end_to_end_metric_of_the_same_cells(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        assert cells_of(m, manifest) <= cells_of(e2e[m["moves"]], manifest), m


def names_a_width(key):
    """A key that ``reduced`` may never name: a size ending in ``_dim`` or
    ``_rank``, a head's, or any ``_size`` but the vocabulary's, which is a
    count of rows and no width."""
    return key.endswith(("_dim", "_rank")) or "head" in key \
        or (key.endswith("_size") and key != "vocab_size")


@pytest.mark.parametrize("key,width", [
    ("vocab_size", False), ("num_hidden_layers", False),
    ("n_routed_experts", False), ("layer_types", False),
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("kv_lora_rank", True),
    ("qk_rope_head_dim", True), ("num_attention_heads", True)])
def test_reduced_may_name_the_vocabulary_and_no_width(key, width):
    assert names_a_width(key) is width


def test_every_named_file_exists_and_agrees(manifest):
    for c in manifest["configs"]:
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        cfg = load(os.path.join(ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
        assert all(k in cfg["published"] and cfg["published"][k] != cfg[k]
                   for k in c["reduced"])
        assert not any(names_a_width(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        cell = load(os.path.join(BENCH, "workloads", f"{w['name']}.json"))
        assert {k: cell[k] for k in ("name", "config", "traffic", "chips")} \
            == {k: w[k] for k in ("name", "config", "traffic", "chips")}
        traffic = load(os.path.join(BENCH, "traffic",
                                    f"{w['traffic']}.json"))
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           f"{traffic['driver']}.py"))


def test_metric_files_and_manifest_say_the_same(manifest):
    files = {}
    for path in glob.glob(os.path.join(BENCH, "metrics", "*.json")):
        m = load(path)
        assert os.path.basename(path) == m["name"] + ".json"
        files[m["name"]] = m
        module, _, function = m["reader"].partition(":")
        reader = os.path.join(ROOT, *module.split(".")) + ".py"
        assert os.path.exists(reader)
        assert f"def {function}(run)" in open(reader).read()
    listed = {m["name"]: (level, m) for level in ("end_to_end", "per_layer")
              for m in manifest[level]}
    assert set(files) == set(listed)
    for name, (level, m) in listed.items():
        f = files[name]
        assert f["level"] == level
        for key in ("unit", "better", "source", "layer", "moves"):
            assert f.get(key) == m.get(key), (name, key)


def test_no_cell_or_configuration_is_named_in_code(manifest):
    names = [w["name"] for w in manifest["workloads"]] \
        + [c["name"] for c in manifest["configs"]] \
        + [w["traffic"] for w in manifest["workloads"]]
    for path in glob.glob(os.path.join(BENCH, "**", "*.py"), recursive=True):
        if os.sep + "tests" + os.sep in path:
            continue
        text = open(path).read()
        assert not [n for n in names if n in text], path
