"""The yardstick's arithmetic: the traffic generator, exact-interval
counting, percentiles, the spread, the logical FLOP counts of each family
(kept with its builder) and of each kernel (kept with its roofline metric)
against derivations written out here, and the roofline share itself."""

import json
import os

import numpy as np
import pytest

import benchmark_testlib as lib
from benchmark.harness import builders, flops, loadgen, roofline, stats

ROOT = lib.ROOT


def _traffic(name):
    return lib.mf.load_traffic(name, ROOT)


@pytest.mark.parametrize("name", ["chat_steady", "docs_closed"])
def test_trace_shape_is_byte_identical_whatever_the_seed(name):
    t = _traffic(name)
    a = loadgen.make_trace(t, 300)
    b = loadgen.make_trace(t, 300)
    for x, y in zip(a, b):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
    # --seed only reaches token_ids, never the trace
    ids1 = loadgen.token_ids(a, 1, 50257)
    ids2 = loadgen.token_ids(a, 2, 50257)
    ids1_again = loadgen.token_ids(b, 1, 50257)
    assert [len(x) for x in ids1] == [len(x) for x in ids2]
    assert any(not np.array_equal(x, y) for x, y in zip(ids1, ids2))
    assert all(np.array_equal(x, y) for x, y in zip(ids1, ids1_again))
    assert all(len(x) == n for x, n in zip(ids1, a.prompt_len))


def test_longer_trace_extends_and_never_changes_its_prefix():
    t = _traffic("chat_steady")
    short, long = loadgen.make_trace(t, 100), loadgen.make_trace(t, 500)
    n = len(short.arrival_s)
    assert np.array_equal(long.arrival_s[:n], short.arrival_s)
    assert np.array_equal(long.prompt_len[:n], short.prompt_len)
    assert np.array_equal(long.output_len[:n], short.output_len)


def test_other_shape_seed_gives_another_trace():
    t = _traffic("chat_steady")
    other = dict(t, shape_seed=t["shape_seed"] + 1)
    a, b = loadgen.make_trace(t, 200), loadgen.make_trace(other, 200)
    assert not np.array_equal(a.prompt_len, b.prompt_len)


@pytest.mark.parametrize("name", ["chat_steady", "docs_closed"])
def test_lengths_stay_inside_the_files_ranges_and_fit_a_slot(name):
    t = _traffic(name)
    tr = loadgen.make_trace(t, 2000)
    n0 = tr.n_ramp
    assert tr.prompt_len.min() >= t["prompt_len"]["min"]
    assert tr.prompt_len.max() <= t["prompt_len"]["max"]
    assert tr.output_len[n0:].min() >= t["output_len"]["min"]
    assert tr.output_len.max() <= t["output_len"]["max"]
    assert (tr.prompt_len + tr.output_len).max() <= t["engine"]["max_len"]
    med = np.median(tr.prompt_len[n0:])
    assert abs(med - t["prompt_len"]["median"]) < 0.1 * t[
        "prompt_len"]["median"]
    if "arrivals" in t:
        rate = (len(tr.arrival_s) - n0) / tr.arrival_s[-1]
        assert abs(rate - t["arrivals"]["rate_rps"]) < 0.1 * t[
            "arrivals"]["rate_rps"]
        assert np.all(np.diff(tr.arrival_s[n0:]) >= 0)
        assert np.all(tr.arrival_s[:n0] == 0)


def test_bursty_arrivals_and_shared_prefixes_are_data_not_code():
    t = dict(_traffic("chat_steady"),
             arrivals={"rate_rps": 5.0, "cv": 3.0},
             prefix={"share": 0.5, "groups": 2, "len": 16})
    tr = loadgen.make_trace(t, 2000)
    gaps = np.diff(tr.arrival_s[tr.n_ramp:])
    assert gaps.std() / gaps.mean() > 2.0          # cv 3: bursty
    ids = loadgen.token_ids(tr, 7, 1000)
    g0 = [ids[i][:16] for i in range(len(ids)) if tr.prefix_group[i] == 0]
    assert len(g0) > 10 and all(np.array_equal(g0[0], x) for x in g0)
    assert 0.4 < np.mean(tr.prefix_group >= 0) < 0.6


def test_exact_interval_counting_on_a_synthetic_step_log():
    # boundaries every 0.15 s from t=0.1; 10 units of work per step
    bounds = [0.1 + 0.15 * k for k in range(100)]
    work = [10.0] * 100
    w = stats.exact_window(bounds, earliest=1.05, seconds=3.0)
    assert w.i_open == 7 and abs(w.t_open - 1.15) < 1e-9
    assert w.t_close >= w.t_open + 3.0
    assert w.t_close - (w.t_open + 3.0) < 0.15 + 1e-9
    n_steps = w.i_close - w.i_open
    assert stats.count_in_window(work, w) == 10.0 * n_steps
    # the rate is over the MEASURED interval: exactly one step's work per
    # step's time, whatever the nominal length was
    assert stats.rate(work, w) == pytest.approx(10.0 / 0.15)
    # a nominal-length division would be off by the overshoot
    assert 10.0 * n_steps / 3.0 != pytest.approx(10.0 / 0.15)
    assert stats.exact_window(bounds, 1.0, 30.0) is None   # log too short
    assert stats.exact_window(bounds, 99.0, 1.0) is None


def test_percentile_states_its_sample_count():
    p = stats.percentile([5.0, 1.0, 3.0, 2.0, 4.0], 50)
    assert p.value == 3.0 and p.n == 5
    assert stats.percentile(list(range(101)), 95) == (95.0, 101)
    assert stats.percentile([1.0, 2.0], 50).value == 1.5
    empty = stats.percentile([], 50)
    assert empty.n == 0 and np.isnan(empty.value)


def test_spread_is_the_contracts_quartile_distance():
    import statistics
    xs = [100.0, 101.0, 99.0, 100.5, 99.5, 102.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == (q3 - q1) / statistics.median(xs)


def test_gpt2_medium_flops_per_token_against_a_written_out_derivation():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark/configs/gpt2-medium.json")))
    H, L, V, S = 1024, 24, 50257, 1024
    assert (cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]) == (H, L, V)
    # matmul parameters: qkv 3H^2, proj H^2, fc_in 4H^2, fc_out 4H^2 per
    # layer; the tied head V*H once
    mm = L * (3 * H * H + H * H + 4 * H * H + 4 * H * H) + V * H
    assert mm == 353_453_056
    # forward 2 FLOPs a parameter a token, backward twice that: 6*mm.
    # attention: QK^T and PV are 2*S*H multiply-adds a token a layer
    # = 4*S*H FLOPs forward, 12*S*H with the backward, halved: causal
    attn = L * 12 * S * H // 2
    assert attn == 150_994_944
    want = 6 * mm + attn
    assert want == 2_271_713_280                      # ~2.27 GFLOP/token
    # the count lives with the family's builder, found by name
    assert lib.mf.load_builder("gpt2", ROOT).gpt2_train_flops_per_token(
        cfg, S) == want
    assert builders.get(cfg).train_flops_per_token(S) == want


def test_bert_large_flops_per_token_against_a_written_out_derivation():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark/configs/bert-large.json")))
    E, I, L, V, S = 1024, 4096, 24, 30522, 512
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["vocab_size"]) == (E, I, L, V)
    # qkv 3E^2, attn_out E^2, ffn_in E*I, ffn_out I*E per layer; the MLM
    # head as the model applies it to every position: transform E^2 and
    # the tied decoder V*E
    mm = L * (4 * E * E + 2 * E * I) + E * E + V * E
    assert mm == 334_292_992
    attn = L * 12 * S * E                              # bidirectional
    want = 6 * mm + attn
    assert want == 2_156_752_896                      # ~2.16 GFLOP/token
    assert lib.mf.load_builder(
        "bert_pretrain", ROOT).bert_train_flops_per_token(cfg, S) == want
    assert builders.get(cfg).train_flops_per_token(S) == want


def test_mfu_is_tokens_times_flops_over_peak():
    assert flops.mfu_pct(35_000.0, 2_271_713_280, 197e12) == pytest.approx(
        100 * 35_000 * 2_271_713_280 / 197e12)


def test_unknown_device_kind_is_an_error_not_a_default():
    from benchmark.harness import device
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks("_source")


V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_roofline_share_against_hand_numbers():
    # bound by operations: 197e9 FLOP is 1 ms at the peak; in 2 ms, 50 %
    assert roofline.share_pct(197e9, 1e6, 2e-3, V5E) == pytest.approx(50.0)
    # bound by bytes: 819e6 bytes are 1 ms of the memory's peak; the few
    # operations beside them do not matter
    assert roofline.share_pct(1e9, 819e6, 4e-3, V5E) == pytest.approx(25.0)
    # a time understated by 5/6 overstates the share by 6/5 (what the old
    # divisor did): 90 % would have read 108 %
    assert roofline.share_pct(197e9, 0, 1e-3 / 0.9 * 5 / 6, V5E) \
        == pytest.approx(108.0)


def _kernel_metric(kernel):
    return lib.mf.load_layer_metric(
        f"kernel.{kernel}.roofline_pct.train", ROOT)["_module"]


def _gpt2m_train():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark/configs/gpt2-medium.json")))
    return cfg, _traffic("pretrain_1k")


@pytest.mark.parametrize("kernel,products", [
    ("flash_fwd", 2), ("flash_dq", 3), ("flash_dkv", 4)])
def test_flash_kernel_counts_against_a_written_out_derivation(kernel,
                                                              products):
    cfg, traffic = _gpt2m_train()
    B, h, S, d, L = 8, 16, 1024, 64, 24
    assert (traffic["per_chip_batch"], cfg["n_head"], traffic["seq_len"],
            cfg["n_embd"] // cfg["n_head"], cfg["n_layer"]) == (B, h, S, d, L)
    # one product (QK^T, PV, dO V^T, ...) of one layer: B*h heads, S x S
    # scores, d multiply-adds each, 2 FLOPs a multiply-add; causal, so
    # counted once: half the square. d is the published 64, not 128 lanes
    product = 2 * B * h * S * S * d // 2
    assert product == 8_589_934_592                       # 8.59 GFLOP
    # fwd: scores and PV; dq: scores again, dP, dQ; dkv: scores again,
    # dV, dP, dK -- what each kernel itself computes, all 24 layers
    ops = products * product * L
    assert ops == {2: 412_316_860_416, 3: 618_475_290_624,
                   4: 824_633_720_832}[products]
    # bytes: q, k, v (and dO in the backward) read and the results written
    # in bfloat16, B*h*S*d elements each; per row a float32 log-sum-exp
    # written (fwd) or it and the row sum read (backward)
    tensor, row = B * h * S * d * 2, B * h * S * 4
    bytes_ = L * {2: 4 * tensor + row, 3: 5 * tensor + 2 * row,
                  4: 6 * tensor + 2 * row}[products]
    m = _kernel_metric(kernel)
    assert m.KERNEL == "apex1_" + kernel
    assert m.count(cfg, traffic) == (ops, bytes_)
    # all three are bound by operations on a v5e, the forward narrowly
    assert ops / 197e12 > bytes_ / 819e9


@pytest.mark.parametrize("kernel,products,out", [
    ("linear_xent_fwd", 1, "loss"), ("linear_xent_dx", 2, "dx"),
    ("linear_xent_dw", 2, "dw")])
def test_linear_xent_kernel_counts_against_a_written_out_derivation(
        kernel, products, out):
    cfg, traffic = _gpt2m_train()
    N, H, V = 8 * 1024, 1024, 50257         # published rows, not 50304
    assert (traffic["per_chip_batch"] * traffic["seq_len"], cfg["n_embd"],
            cfg["vocab_size"]) == (N, H, V)
    # one product over tokens x hidden x vocabulary: the logits (fwd, and
    # again in each backward kernel, which stores none), g W (dx), g^T x
    product = 2 * N * H * V
    assert product == 843_172_544_512                     # 843 GFLOP
    ops = products * product
    # x and W read in bfloat16; 4 bytes a token for each of: targets, and
    # loss + log-sum-exp written (fwd) or log-sum-exp + upstream gradient
    # read (backward); the result written in bfloat16
    result = {"loss": 0, "dx": N * H * 2, "dw": V * H * 2}[out]
    bytes_ = (N * H + V * H) * 2 + 3 * N * 4 + result
    m = _kernel_metric(kernel)
    assert m.KERNEL == "apex1_" + kernel
    assert m.count(cfg, traffic) == (ops, bytes_)


def test_kernel_share_reads_the_trace_and_leaves_out_what_is_not_there(
        capsys):
    cfg, traffic = _gpt2m_train()
    m = _kernel_metric("linear_xent_fwd")
    ctx = {"cfg": cfg, "traffic": traffic,
           "device": {"kind": "TPU v5 lite", "peaks": V5E},
           "trace": {"kernels": {"apex1_linear_xent_fwd":
                                 [5, 0.0374, 7.48]}}}
    # 843.19 GFLOP over 7.48 ms = 112.7 TFLOP/s of 197
    assert m.read(ctx) == pytest.approx(
        100 * 843_172_544_512 / 197e12 / 7.48e-3)
    assert 57.0 < m.read(ctx) < 57.5
    assert "bound by operations" in capsys.readouterr().out
    # nothing to read: an untraced run, a trace without the kernel, a
    # device without peaks (the CPU rehearsal) -- left out, never 0
    assert m.read({k: v for k, v in ctx.items() if k != "trace"}) is None
    assert m.read(dict(ctx, trace={"kernels": {}})) is None
    assert m.read(dict(ctx, device={"kind": "cpu", "peaks": None})) is None
