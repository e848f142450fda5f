"""The one general traffic generator. A traffic mix is a data file
(`benchmark/traffic/<name>.json`); this module turns it into a trace.

The SHAPE of the traffic — arrival times, prompt and output lengths,
which requests share a prefix — comes from the file's own `shape_seed`
and is byte-identical in every run, whatever `--seed` is: `--seed`
decides the weights and every token id, never how much work a window
holds. Each stream (arrivals, prompt lengths, output lengths, prefix
groups, ramp residuals) has a generator of its own, so asking for a longer
trace extends every stream and changes none of its prefix.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

_STREAMS = {"arrivals": 0, "prompt_len": 1, "output_len": 2, "prefix": 3,
            "ramp": 4}


class Trace(NamedTuple):
    arrival_s: np.ndarray      # (N,) due times, seconds from trace zero
    prompt_len: np.ndarray     # (N,) tokens, prefix included
    output_len: np.ndarray     # (N,) tokens to generate
    prefix_group: np.ndarray   # (N,) -1 = no shared prefix
    prefix_len: int
    n_ramp: int                # the first n_ramp requests are the ramp burst


def _rng(shape_seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(shape_seed), _STREAMS[stream]])


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    dist = spec.get("dist", "fixed")
    if dist == "fixed":
        x = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    elif dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", np.inf)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _arrivals(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Inter-arrival gaps are gamma with mean 1/rate and coefficient of
    variation `cv`: cv = 1 is a Poisson process, cv > 1 is bursty."""
    rate = float(spec["rate_rps"])
    cv = float(spec.get("cv", 1.0))
    shape = 1.0 / (cv * cv)
    gaps = rng.gamma(shape, 1.0 / (rate * shape), n)
    return np.cumsum(gaps)


def make_trace(traffic: dict, n_requests: int) -> Trace:
    """`n_requests` trace requests after the ramp burst. The ramp burst
    (``ramp.requests``) is due at time zero with output lengths cut to a
    seeded residual share, so slots fill at once and then free staggered,
    as in steady state."""
    seed = traffic["shape_seed"]
    ramp = traffic.get("ramp", {})
    n_ramp = int(ramp.get("requests", 0))
    n = n_ramp + int(n_requests)
    prompt = _lengths(traffic["prompt_len"], n, _rng(seed, "prompt_len"))
    output = _lengths(traffic["output_len"], n, _rng(seed, "output_len"))
    if n_ramp:
        resid = _rng(seed, "ramp").uniform(
            float(ramp.get("min_share", 0.05)), 1.0, n_ramp)
        output[:n_ramp] = np.maximum(
            np.floor(output[:n_ramp] * resid), 2).astype(np.int64)
    if "arrivals" in traffic:
        arr = _arrivals(traffic["arrivals"], n - n_ramp,
                        _rng(seed, "arrivals"))
        arrival = np.concatenate([np.zeros(n_ramp), arr])
    else:                      # closed loop: due when a caller is free
        arrival = np.zeros(n)
    pre = traffic.get("prefix")
    if pre:
        r = _rng(seed, "prefix")
        group = np.where(r.random(n) < float(pre["share"]),
                         r.integers(0, int(pre["groups"]), n), -1)
        plen = int(pre["len"])
        prompt = np.where(group >= 0, np.maximum(prompt, plen + 1), prompt)
    else:
        group, plen = np.full(n, -1), 0
    return Trace(arrival, prompt, output, group.astype(np.int64), plen,
                 n_ramp)


def n_requests_for(traffic: dict, seconds: float) -> int:
    """How many trace requests a run of `seconds` can need (a generous
    count: the streams are prefix-stable, so surplus costs nothing)."""
    horizon = float(traffic.get("ramp", {}).get("seconds", 0)) + seconds + 5
    if "arrivals" in traffic:
        return int(traffic["arrivals"]["rate_rps"] * horizon * 1.5) + 64
    return int(traffic.get("max_rps_hint", 40) * horizon) + 256


def token_ids(trace: Trace, seed: int, vocab_size: int) -> list:
    """Prompt token ids, from `--seed`: request i's ids are a function of
    (seed, i) alone; requests of one prefix group share its first
    `prefix_len` ids."""
    prefixes = {}
    out = []
    for i in range(len(trace.prompt_len)):
        rng = np.random.default_rng([int(seed), 1, i])
        ids = rng.integers(0, vocab_size, int(trace.prompt_len[i]),
                           dtype=np.int64).astype(np.int32)
        g = int(trace.prefix_group[i])
        if g >= 0:
            if g not in prefixes:
                prefixes[g] = np.random.default_rng(
                    [int(seed), 2, g]).integers(
                        0, vocab_size, trace.prefix_len,
                        dtype=np.int64).astype(np.int32)
            ids[:trace.prefix_len] = prefixes[g]
        out.append(ids)
    return out
