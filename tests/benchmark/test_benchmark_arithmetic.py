"""The yardstick's arithmetic: the traffic generator, exact-interval
counting, percentiles, the spread, and the logical FLOP counts against a
derivation written out here."""

import json
import os

import numpy as np
import pytest

import benchmark_testlib as lib
from benchmark.harness import flops, loadgen, stats

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
    assert flops.gpt2_train_flops_per_token(cfg, S) == want


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
    assert flops.bert_train_flops_per_token(cfg, S) == want


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
