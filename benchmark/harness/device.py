"""The device under the benchmark: what it is, its peaks, its memory, the
compile cache, and a meter of compilations."""

from __future__ import annotations

import json
import os

from benchmark.harness.manifest import ROOT

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


class NoChip(RuntimeError):
    pass


def cache_dir() -> str:
    """JAX's persistent compile cache: where `JAX_COMPILATION_CACHE_DIR`
    says, else a FIXED directory inside the checkout (the path is part of
    the cache key, so a directory that moves never hits)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")


def enable_cache() -> str:
    import jax
    path = cache_dir()
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", path)
    # every program, however small: a warm run then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(n: int, allow_cpu: bool = False) -> list:
    """The `n` devices the cell runs on. Anything but a TPU with at least
    `n` chips is an error — there is no fallback; `allow_cpu` is for the
    tests' rehearsal only and such a run prints no result line."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs a TPU, found platform {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} chips, found {len(devs)}")
    return list(devs[:n])


def peaks(kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise KeyError(f"device kind {kind!r} is not in {_PEAKS}: add it "
                       f"with its source, there is no default")
    return table[kind]


def info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does not
    report it, as the CPU rehearsal's does not)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileMeter:
    """Backend compilations and persistent-cache traffic, read off
    `jax.monitoring`: `mark()` then `since(mark)` counts what a phase
    compiled — the measured window must show 0 misses."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return (self.seconds, self.compiles, self.hits, self.misses)

    def since(self, mark: tuple) -> dict:
        return {"seconds": self.seconds - mark[0],
                "compiles": self.compiles - mark[1],
                "hits": self.hits - mark[2],
                "misses": self.misses - mark[3]}
