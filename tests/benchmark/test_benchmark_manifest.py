"""`BENCHMARK.json` against the contract's rules, and every name it holds
resolved to its files."""

import copy
import json
import os

import pytest

import benchmark_testlib as lib
from benchmark.harness import manifest as mf

ROOT = lib.ROOT


@pytest.fixture(scope="module")
def man():
    return mf.load_manifest(ROOT)


def test_manifest_passes_the_contracts_rules(man):
    mf.validate(man, ROOT)
    assert set(man) == mf.TOP_KEYS
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert man["paths"] == ["benchmark", "tests/benchmark"]
    assert "setup_s" in {m["name"] for m in man["end_to_end"]}
    # a full check fits the driver's budget with all 24 cells
    rs = man["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_every_name_and_unit_keeps_to_the_character_rules(man):
    names = ([c["name"] for c in man["configs"]]
             + [x for w in man["workloads"]
                for x in (w["name"], w["config"], w["traffic"])]
             + [m["name"] for m in man["end_to_end"] + man["per_layer"]]
             + [k for c in man["configs"] for k in c["reduced"]])
    for n in names:
        assert mf.NAME_RE.match(n), n
    for m in man["end_to_end"] + man["per_layer"]:
        assert mf.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert len(set(m["name"] for m in man["end_to_end"] + man["per_layer"])
               ) == len(man["end_to_end"]) + len(man["per_layer"])
    for d, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in d:
            continue
        for f in files:
            assert mf.NAME_RE.match(f), f"file name {f}"


@pytest.mark.parametrize(
    "name", [w["name"] for w in mf.load_manifest(ROOT)["workloads"]])
def test_every_workload_resolves_to_its_files_by_name(man, name):
    w = mf.find(man, "workloads", name)
    cfg = mf.load_config(man, w["config"], ROOT)
    traffic = mf.load_traffic(w["traffic"], ROOT)
    ref = mf.load_reference(cfg.get("reference", w["config"]), ROOT)
    assert hasattr(ref, "loss")
    builder = mf.load_builder(cfg["builder"], ROOT).Builder(cfg)
    for attr in ("family", "vocab_size", "ref_cfg", "model", "param_shapes",
                 "loss_fn", "make_batch", "train_flops_per_token"):
        assert hasattr(builder, attr), (cfg["builder"], attr)
    assert cfg["_root"] == ROOT
    assert traffic["kind"] in ("train", "serve_open", "serve_closed")
    assert len(traffic["why"]) > 40
    limits = json.load(open(os.path.join(
        ROOT, "benchmark", "limits", w["name"] + ".json")))
    assert all(isinstance(v, (int, float, str)) for v in limits.values())
    entry = mf.find(man, "configs", w["config"])
    for key in entry["reduced"]:
        assert key in cfg, f"reduced key {key} not in the config file"
    assert cfg["source"] == entry["source"]
    e2e = mf.cell_metrics(man, w["name"], "end_to_end")
    assert len(e2e) >= 2 and any(m["name"] == "setup_s" for m in e2e)
    layers = mf.cell_metrics(man, w["name"], "per_layer")
    assert layers
    for m in layers:
        # the metric's own file says what it READS and nothing that
        # BENCHMARK.json says already: a later cell of the same class is
        # one more name in the manifest's list, and no edit to a file
        spec = mf.load_layer_metric(m["name"], ROOT)
        assert set(spec) <= {"read", "what", "_module"}, m["name"]
        assert "read" in spec or "_module" in spec
        assert m["moves"] in {x["name"] for x in e2e}


def test_layer_metric_files_and_manifest_entries_are_the_same_set(man):
    files = {f[:-5] for f in os.listdir(
        os.path.join(ROOT, "benchmark", "layer_metrics"))
        if f.endswith(".json")}
    assert files == {m["name"] for m in man["per_layer"]}
    layers = {m["layer"] for m in man["per_layer"]}
    assert all(len(name.split()) <= 5 for name in layers)


@pytest.mark.parametrize("breakage", [
    "bad_name", "bad_unit", "unknown_config", "unused_config", "bound",
    "extra_key", "two_four_chip", "moves_unknown", "no_setup"])
def test_validation_refuses_a_broken_manifest(man, breakage):
    m = copy.deepcopy(man)
    if breakage == "bad_name":
        m["workloads"][0]["name"] = "has space"
    elif breakage == "bad_unit":
        m["end_to_end"][0]["unit"] = "tokens per second"
    elif breakage == "unknown_config":
        m["workloads"][0]["config"] = "nope"
    elif breakage == "unused_config":
        m["configs"].append(dict(m["configs"][0], name="spare",
                                 file="benchmark/configs/spare.json"))
    elif breakage == "bound":
        m["end_to_end"][0]["bound"] = 0.5
    elif breakage == "extra_key":
        m["per_layer"][0]["why"] = "not allowed on a metric"
    elif breakage == "two_four_chip":
        for w in m["workloads"]:
            w["chips"] = 4
        if len(m["workloads"]) < 2:
            pytest.skip("one cell only")
    elif breakage == "moves_unknown":
        m["per_layer"][0]["moves"] = "nothing"
    elif breakage == "no_setup":
        m["end_to_end"] = [x for x in m["end_to_end"]
                           if x["name"] != "setup_s"]
    with pytest.raises((mf.ManifestError, KeyError)):
        mf.validate(m, ROOT)


@pytest.mark.parametrize("missing", ["builder", "reference"])
def test_validation_refuses_a_configuration_whose_files_are_missing(
        tmp_path, missing):
    """A configuration's builder and plain reference are found by name; a
    manifest that names one with no file is refused before any run."""
    root = lib.make_root(str(tmp_path / "r"), cells=())
    cfg = dict(lib.TINY_GPT2)
    cfg[missing] = "no-such-file"
    with open(os.path.join(root, "benchmark/configs/orphan.json"), "w") as f:
        json.dump(cfg, f)
    man = mf.load_manifest(root)
    man["configs"].append({"name": "orphan", "source": "test",
                           "reduced": [], "why": "tiny",
                           "file": "benchmark/configs/orphan.json"})
    man["workloads"].append(dict(man["workloads"][0], name="orphan_train",
                                 config="orphan"))
    with open(os.path.join(root, "benchmark/limits/orphan_train.json"),
              "w") as f:
        json.dump(lib.TRAIN_LIMITS, f)
    with pytest.raises(mf.ManifestError, match="no-such-file.py"):
        mf.validate(man, root)
