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
            # set-up is every cell's; the rest are its class of cell's
            if m["moves"] == "setup_s" or (
                    like is not None and like in r.get("workloads", ())):
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


# ---- a model FAMILY the benchmark does not have, as new files only -----

TOY_CONFIG = {"builder": "toy_decoder", "layers": 2, "heads": 4,
              "width": 128, "vocab": 256, "positions": 128}

TOY_BUILDER = '''"""A decoder family under names of its own (`layers`, `width`, ...): no
table of the harness knows it. It happens to be built from the system's
GPT-2 blocks, which is the test's business, not the harness's."""

import jax
import jax.numpy as jnp


class Builder:
    family = "toy_decoder"

    def __init__(self, cfg):
        self.cfg = cfg
        self.vocab_size = cfg["vocab"]
        self.ref_cfg = {"n_layer": cfg["layers"], "n_head": cfg["heads"],
                        "n_embd": cfg["width"], "vocab_size": cfg["vocab"]}

    def model(self, opt_level="O2"):
        from apex1_tpu.core.policy import get_policy
        from apex1_tpu.models.gpt2 import GPT2, GPT2Config
        c = self.cfg
        return GPT2(GPT2Config(
            vocab_size=c["vocab"], max_seq_len=c["positions"],
            num_layers=c["layers"], num_heads=c["heads"],
            hidden_size=c["width"], dropout=0.0,
            policy=get_policy(opt_level)))

    def param_shapes(self, model):
        probe = jax.ShapeDtypeStruct((1, 8), jnp.int32)
        return jax.eval_shape(model.init, jax.random.key(0),
                              probe)["params"]

    def loss_fn(self, model):
        from apex1_tpu.models.gpt2 import gpt2_loss_fn
        f = gpt2_loss_fn(model)
        return lambda params, batch: f(params, batch["tokens"])

    def make_batch(self, key, rows, seq_len, traffic):
        return {"tokens": jax.random.randint(
            key, (rows, seq_len), 0, self.vocab_size, jnp.int32)}

    def decoder(self, model):
        from apex1_tpu.models.generate import gpt2_decoder
        return gpt2_decoder(model)

    def train_flops_per_token(self, seq_len):
        c = self.cfg
        return 6.0 * (12 * c["layers"] * c["width"] ** 2
                      + c["vocab"] * c["width"])
'''

#: the same family laid over four chips by its OWN `shard_step`
TOY_SHARDED_BUILDER = '''"""The toy family with a layout of its own over the chips."""

import os

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from benchmark.harness.manifest import load_module

_toy = load_module(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "toy_decoder.py"), "toy_sharded_base")


class Builder(_toy.Builder):
    family = "toy_sharded"

    def shard_step(self, raw_step, devices, traffic):
        print(f"toy_sharded.shard_step: {len(devices)} devices", flush=True)
        mesh = Mesh(list(devices), ("dp",))
        step = jax.shard_map(raw_step, mesh=mesh, in_specs=(P(), P("dp")),
                             out_specs=(P(), P()), check_vma=False)
        return step, NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
'''

#: the family's plain reference: the published GPT-2 mathematics, which
#: the file beside it already writes down
TOY_REFERENCE = '''"""Plain reference of the toy family (the GPT-2 mathematics)."""

import os

from benchmark.harness.manifest import load_module

_gpt2 = load_module(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "gpt2-medium.py"), "toy_reference_gpt2")
loss, logits = _gpt2.loss, _gpt2.logits
BLOCKABLE, LEAF_PARTS = _gpt2.BLOCKABLE, _gpt2.LEAF_PARTS
'''

#: a per-layer metric of its own, whose reader takes what `run_cell` hands
#: a metric's own file: the trace's reduction and file, the cell with its
#: configuration and traffic, the device
TOY_METRIC = '''"""toy.layers_traced.train: the configuration's depth, where a trace was
reduced on a device the run names."""


def read(ctx):
    if "trace" not in ctx or not ctx["xplane"].endswith(".xplane.pb"):
        return None
    assert ctx["trace"]["n_devices"] >= 0 and ctx["device"]["kind"]
    assert ctx["cell"]["name"] == "toy_train"
    assert ctx["traffic"]["seq_len"] == 64
    return float(ctx["cfg"]["layers"])
'''

#: cell -> (config, traffic, chips, the end-to-end metric it reports)
TOY_CELLS = {"toy_train": ("toy-tiny", "toy_train", 1, "train_tok_s_chip"),
             "toy_chat": ("toy-tiny", "toy_chat", 1, "tpot_p50_ms"),
             "toy_dp4": ("toy-sharded", "toy_dp4", 4, "train_tok_s_chip")}


def add_toy_family(root: str) -> list:
    """Into a root made by `make_root`: the toy family with a training
    cell, a serving cell and a four-chip cell of its own layout, as NEW
    files and NEW entries of BENCHMARK.json only. Returns the files
    added."""
    before = {p: open(os.path.join(root, p), "rb").read()
              for p in _data_files(root)}
    tiny = tiny_files()
    files = {
        "benchmark/builders/toy_decoder.py": TOY_BUILDER,
        "benchmark/builders/toy_sharded.py": TOY_SHARDED_BUILDER,
        "benchmark/references/toy-tiny.py": TOY_REFERENCE,
        "benchmark/references/toy-sharded.py": TOY_REFERENCE,
        "benchmark/configs/toy-tiny.json": TOY_CONFIG,
        "benchmark/configs/toy-sharded.json": dict(
            TOY_CONFIG, builder="toy_sharded"),
        "benchmark/traffic/toy_train.json":
            tiny["benchmark/traffic/tiny_train.json"],
        "benchmark/traffic/toy_chat.json":
            tiny["benchmark/traffic/tiny_chat.json"],
        "benchmark/traffic/toy_dp4.json": dict(
            tiny["benchmark/traffic/tiny_train.json"], per_chip_batch=2,
            ddp=True),
        "benchmark/limits/toy_train.json": TRAIN_LIMITS,
        "benchmark/limits/toy_chat.json": SERVE_LIMITS,
        "benchmark/limits/toy_dp4.json": TRAIN_LIMITS,
        "benchmark/layer_metrics/toy.layers_traced.train.json": {
            "what": "the configuration's depth, where a trace was reduced"},
        "benchmark/layer_metrics/toy.layers_traced.train.py": TOY_METRIC,
    }
    for rel, content in files.items():
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        with open(path, "w") as f:
            if isinstance(content, str):
                f.write(content)
            else:
                json.dump(content, f)
    man = mf.load_manifest(root)
    for config in ("toy-tiny", "toy-sharded"):
        man["configs"].append({
            "name": config, "source": "test", "reduced": [],
            "file": f"benchmark/configs/{config}.json",
            "why": "a family the benchmark does not have"})
    for name, (config, traffic, chips, e2e) in TOY_CELLS.items():
        man["workloads"].append({"name": name, "config": config,
                                 "traffic": traffic, "chips": chips,
                                 "why": "a new family's cell"})
        mf.find(man, "end_to_end", e2e)["workloads"].append(name)
        for m in man["per_layer"]:
            if m["moves"] == "setup_s":
                m["workloads"].append(name)
    man["per_layer"].append({
        "name": "toy.layers_traced.train", "unit": "layers",
        "better": "higher", "source": "device_trace", "layer": "model",
        "moves": "train_tok_s_chip", "workloads": ["toy_train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    for p, content in before.items():       # nothing that was there moved
        assert open(os.path.join(root, p), "rb").read() == content, p
    return sorted(files)


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


# ---- a decoder as large as one chip serves, as shapes alone --------------

#: "a 5-layer decoder of hidden 7680 with 8 experts of width 2048": one
#: dense layer of width 18432 and four expert layers with a shared expert,
#: latent attention of 128 heads (q 1536, k/v 512 + 64 shared rope
#: values), an eighth of a 153600 vocabulary: 3.41 B parameters; 4.92 B
#: with 16 experts (ISSUE 28's table)
BIG_DECODER = {"hidden": 7680, "dense_layers": 1, "expert_layers": 4,
               "experts": 8, "experts_total": 256, "expert_width": 2048,
               "dense_width": 18432, "heads": 128, "q_rank": 1536,
               "kv_rank": 512, "rope": 64, "nope": 128, "v_head": 128,
               "vocab": 19200}
#: the same layers at a size the CPU tests hold
SMALL_DECODER = dict(BIG_DECODER, hidden=256, experts=4, experts_total=8,
                     expert_width=128, dense_width=512, heads=4,
                     q_rank=64, kv_rank=32, rope=16, nope=32, v_head=32,
                     vocab=512, expert_layers=2)


def decoder_shapes(c: dict) -> dict:
    """The parameter tree of such a decoder as `ShapeDtypeStruct`s: norm
    weights named `*scale`, experts stacked on a leading axis."""
    import jax
    import jax.numpy as jnp

    def f(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    h, heads = c["hidden"], c["heads"]

    def mlp(width, *lead):
        return {"gate": f(*lead, h, width), "up": f(*lead, h, width),
                "down": f(*lead, width, h)}

    def layer(expert):
        out = {
            "attn": {"q_a": f(h, c["q_rank"]),
                     "q_a_norm_scale": f(c["q_rank"]),
                     "q_b": f(c["q_rank"], heads * (c["nope"] + c["rope"])),
                     "kv_a": f(h, c["kv_rank"] + c["rope"]),
                     "kv_a_norm_scale": f(c["kv_rank"]),
                     "kv_b": f(c["kv_rank"],
                               heads * (c["nope"] + c["v_head"])),
                     "o": f(heads * c["v_head"], h)},
            "in_norm_scale": f(h), "post_attn_norm_scale": f(h),
            "pre_mlp_norm_scale": f(h), "post_mlp_norm_scale": f(h)}
        if expert:
            out.update(router=f(h, c["experts_total"]),
                       shared=mlp(c["expert_width"]),
                       experts=mlp(c["expert_width"], c["experts"]))
        else:
            out["mlp"] = mlp(c["dense_width"])
        return out

    n = c["dense_layers"] + c["expert_layers"]
    tree = {f"layer{i}": layer(i >= c["dense_layers"]) for i in range(n)}
    tree.update(embed=f(c["vocab"], h), head=f(h, c["vocab"]),
                final_norm_scale=f(h))
    return tree


class StandInReference:
    """A stand-in for such a decoder's plain reference, for what a check
    at size must show: it reads every weight AS STORED and upcasts a block
    at a time (a head group, a column block, an expert), one row of the
    batch at a time, and gives logits at `positions` alone where asked.
    Its attention mixes no positions (it is no model): each token goes
    through every product a real forward makes of it."""

    def __init__(self, c: dict, block: int = 2048):
        self.c, self.block = c, block
        self.seen = None        # the dtypes of the leaves it was handed
        self.asked = []         # the shape of `positions` in each call

    def _row(self, p, toks, q):
        import jax
        import jax.numpy as jnp
        c = self.c
        f32 = jnp.float32

        def mm(a, w):
            if q is not None:
                a, w = a.astype(q), w.astype(q)
            return a.astype(f32) @ w.astype(f32)

        def norm(x, w):
            return x * jax.lax.rsqrt(jnp.mean(
                x * x, -1, keepdims=True) + 1e-6) * w.astype(f32)

        def blocks(width):
            return [(a, min(a + self.block, width))
                    for a in range(0, width, self.block)]

        def mlp(x, w, pick=lambda a: a):
            y = 0.0
            for a, b in blocks(pick(w["gate"]).shape[-1]):
                g = jax.nn.silu(mm(x, pick(w["gate"])[:, a:b]))
                y = y + mm(g * mm(x, pick(w["up"])[:, a:b]),
                           pick(w["down"])[a:b])
            return y

        def attn(x, w):
            qk, v = c["nope"] + c["rope"], c["v_head"]
            c_q = norm(mm(x, w["q_a"]), w["q_a_norm_scale"])
            kv = mm(x, w["kv_a"])
            c_kv = norm(kv[:, :c["kv_rank"]], w["kv_a_norm_scale"])
            y = 0.0
            group = max(1, self.block // (c["nope"] + v))
            for a in range(0, c["heads"], group):
                b = min(a + group, c["heads"])
                qh = mm(c_q, w["q_b"][:, a * qk:b * qk]).reshape(
                    -1, b - a, qk)
                kvh = mm(c_kv, w["kv_b"][:, a * (c["nope"] + v):
                                         b * (c["nope"] + v)]).reshape(
                    -1, b - a, c["nope"] + v)
                s = jnp.sum(qh[..., :c["nope"]] * kvh[..., :c["nope"]], -1) \
                    + jnp.einsum("sgr,sr->sg", qh[..., c["nope"]:],
                                 kv[:, c["kv_rank"]:])
                out = jax.nn.sigmoid(s)[..., None] * kvh[..., c["nope"]:]
                y = y + mm(out.reshape(-1, (b - a) * v),
                           w["o"][a * v:b * v])
            return y

        x = p["embed"][toks].astype(f32)
        for i in range(c["dense_layers"] + c["expert_layers"]):
            w = p[f"layer{i}"]
            x = x + norm(attn(norm(x, w["in_norm_scale"]), w["attn"]),
                         w["post_attn_norm_scale"])
            y = norm(x, w["pre_mlp_norm_scale"])
            if "mlp" in w:
                z = mlp(y, w["mlp"])
            else:
                s = jax.nn.sigmoid(mm(y, w["router"]))[:, :c["experts"]]
                z = mlp(y, w["shared"])
                for e in range(c["experts"]):
                    z = z + s[:, e:e + 1] * mlp(y, w["experts"],
                                                lambda a: a[e])
            x = x + norm(z, w["post_mlp_norm_scale"])
        return norm(x, p["final_norm_scale"])

    def logits(self, params, tokens, cfg, quant=None, positions=None):
        import jax
        import jax.numpy as jnp
        self.seen = {a.dtype for a in jax.tree_util.tree_leaves(params)}
        self.asked.append(None if positions is None else positions.shape)

        def row(toks, pos=None):
            h = self._row(params, toks, quant)
            if pos is not None:
                h = h[pos]
            return h @ params["head"].astype(jnp.float32)

        if positions is None:
            return jax.lax.map(row, tokens)
        return jax.lax.map(lambda a: row(*a), (tokens, positions))

    def without_positions(self):
        """The same reference under the protocol of before: all logits."""
        import types
        return types.SimpleNamespace(
            logits=lambda params, tokens, cfg, quant=None: self.logits(
                params, tokens, cfg, quant))


def weight_programs(shapes, dtype, sharding=None):
    """[(label, lowered)]: every distinct program that
    `builders.make_params` runs for this tree, lowered for `sharding`'s
    device (a described chip does) and not run."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import builders

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    windows, n_chunks = builders.param_windows(shapes)
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0),
                                                   n_chunks))
    i32 = spec((), jnp.int32)
    window = spec((builders._WINDOW_CHUNKS * builders._DRAW_ROWS, 1024),
                  jnp.float32)
    out = [("draw", builders._draw_window.lower(
        spec(keys.shape, keys.dtype), i32, i32))]
    leaves = jax.tree_util.tree_leaves(shapes)
    seen = set()
    for _, _, pieces in windows:
        for leaf, lead, shape, _, scale in pieces:
            if (shape, scale) not in seen:
                seen.add((shape, scale))
                out.append((f"cut{shape}", builders._cut_piece.lower(
                    window, i32, shape=shape, scale=scale,
                    dtype=jnp.dtype(dtype))))
            whole = tuple(leaves[leaf].shape)
            if lead is not None and (whole, shape) not in seen:
                seen.add((whole, shape))
                out.append((f"place{shape}in{whole}",
                            builders._place_piece.lower(
                                spec(whole, dtype), spec(shape, dtype),
                                i32)))
    return out
