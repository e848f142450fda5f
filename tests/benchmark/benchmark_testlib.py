"""Shared by the benchmark's tests: a throw-away copy of the benchmark's
DATA (manifest, configurations, traffic, limits, metric files, references)
in a temp root, with tiny cells added to it purely as NEW files and NEW
entries — no file that is there is edited. The harness code itself is the
repo's."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest as mf  # noqa: E402

TINY_GPT2 = {"builder": "gpt2", "reference": "gpt2-medium", "n_layer": 2,
             "n_head": 4, "n_embd": 128, "vocab_size": 256,
             "n_positions": 128, "resid_pdrop": 0.0}
TINY_BERT = {"builder": "bert_pretrain", "reference": "bert-large",
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "hidden_size": 64, "intermediate_size": 128,
             "vocab_size": 256, "max_position_embeddings": 128,
             "type_vocab_size": 2, "hidden_dropout_prob": 0.0}
TINY_ENGINE = {"max_slots": 4, "max_len": 96, "prefill_chunk": 16,
               "eos_id": 255, "max_queue": 64}
TRAIN_LIMITS = {"loss_abs_gap": 0.01, "first_grad_gap": 0.05,
                "first_grad_rel_diff": 0.1, "param_change_gap": 0.1}
SERVE_LIMITS = {"logit_gap": 0.02}

#: tiny cell -> (config, traffic, chips, the real cell whose per-layer
#: metrics it reports or None where no real cell is of its kind, the
#: end-to-end metric it reports)
TINY_CELLS = {
    "tiny_train": ("gpt2-tiny", "tiny_train", 1, "gpt2m_train",
                   "train_tok_s_chip"),
    "tiny_ddp": ("bert-tiny", "tiny_ddp", 4, None, "train_tok_s_chip"),
    "tiny_chat": ("gpt2-tiny", "tiny_chat", 1, "gpt2m_serve_chat",
                  "tpot_p50_ms"),
    "tiny_docs": ("gpt2-tiny", "tiny_docs", 1, None, "serve_tok_s"),
}
#: the closed-loop kind has no real cell yet (PERF.md, open question 1):
#: its tiny cell brings its end-to-end metric as one more NEW entry, as
#: the later PR that adds the real cell will
NEW_END_TO_END = {"serve_tok_s": {
    "name": "serve_tok_s", "unit": "tokens/s", "better": "higher",
    "bound": 0.05, "source": "host_clock", "workloads": []}}


def _traffic(name: str, **changes) -> dict:
    t = mf.load_traffic(name, ROOT)
    t.pop("_name")
    t.update(changes)
    return t


def tiny_files() -> dict:
    """relative path -> content of every NEW file the tiny cells need."""
    serve = dict(engine=TINY_ENGINE, check_requests=3,
                 trace={"seconds": 1})
    files = {
        "benchmark/configs/gpt2-tiny.json": TINY_GPT2,
        "benchmark/configs/bert-tiny.json": TINY_BERT,
        "benchmark/traffic/tiny_train.json": _traffic(
            "pretrain_1k", seq_len=64, per_chip_batch=4,
            reference_block_rows=2),
        "benchmark/traffic/tiny_ddp.json": _traffic(
            "mlm_512_ddp4", seq_len=32, per_chip_batch=2),
        "benchmark/traffic/tiny_chat.json": _traffic(
            "chat_steady", arrivals={"rate_rps": 20.0, "cv": 1.0},
            prompt_len={"dist": "lognormal", "median": 20, "sigma": 0.5,
                        "min": 4, "max": 48},
            output_len={"dist": "uniform", "min": 4, "max": 12},
            ramp={"requests": 3, "seconds": 0.5}, **serve),
        "benchmark/traffic/tiny_docs.json": _traffic(
            "docs_closed", callers=6,
            prompt_len={"dist": "lognormal", "median": 40, "sigma": 0.2,
                        "min": 20, "max": 64},
            output_len={"dist": "uniform", "min": 4, "max": 8},
            ramp={"requests": 6, "seconds": 0.5}, **serve),
    }
    for name, (_, traffic, *_) in TINY_CELLS.items():
        kind = files[f"benchmark/traffic/{traffic}.json"]["kind"]
        files[f"benchmark/limits/{name}.json"] = (
            TRAIN_LIMITS if kind == "train" else SERVE_LIMITS)
    return files


def make_root(tmp: str, cells=tuple(TINY_CELLS)) -> str:
    """Copy the benchmark's data into `tmp`, then ADD the tiny cells:
    new files, new `configs` and `workloads` entries, and each tiny
    cell's name appended to the `workloads` lists of the metrics its kind
    reports (all in BENCHMARK.json: a metric's own file says only what it
    reads). Returns `tmp`."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(tmp, p), "rb").read()
              for p in _data_files(tmp)}
    man = mf.load_manifest(ROOT)
    man["workloads"] = [w for w in man["workloads"] if w["chips"] == 1]
    man["configs"] = [c for c in man["configs"] if any(
        w["config"] == c["name"] for w in man["workloads"])]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if any(
                x["name"] == w for x in man["workloads"])]
    files = tiny_files()
    real = mf.load_manifest(ROOT)
    wanted = set()
    for name in cells:
        config, traffic, chips, like, e2e = TINY_CELLS[name]
        wanted |= {f"benchmark/configs/{config}.json",
                   f"benchmark/traffic/{traffic}.json",
                   f"benchmark/limits/{name}.json"}
        if not any(c["name"] == config for c in man["configs"]):
            man["configs"].append({
                "name": config, "source": "test", "reduced": [],
                "file": f"benchmark/configs/{config}.json", "why": "tiny"})
        man["workloads"].append({"name": name, "config": config,
                                 "traffic": traffic, "chips": chips,
                                 "why": "tiny preset for the CPU tests"})
        if not any(m["name"] == e2e for m in man["end_to_end"]):
            man["end_to_end"].append(json.loads(json.dumps(
                NEW_END_TO_END[e2e])))
        mf.find(man, "end_to_end", e2e)["workloads"].append(name)
        for m, r in zip(man["per_layer"], real["per_layer"]):
            if like is not None and like in r.get("workloads", ()):
                m["workloads"].append(name)
    for rel in sorted(wanted):
        path = os.path.join(tmp, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            json.dump(files[rel], f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    for p, content in before.items():       # nothing that was there moved
        assert open(os.path.join(tmp, p), "rb").read() == content, p
    return tmp


def _data_files(root: str) -> list:
    out = []
    for d, _, names in os.walk(os.path.join(root, "benchmark")):
        out += [os.path.relpath(os.path.join(d, n), root) for n in names]
    return out


def run_tiny(tmp: str, cell: str, *extra, seed: int = 3000000019,
             seconds: float = 1.0, trace: int = 0):
    """(exit code, result dict) of a CPU rehearsal of one tiny cell."""
    from benchmark import run as brun
    return brun.run_cell(
        ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), *extra], root=tmp, allow_cpu=True)
