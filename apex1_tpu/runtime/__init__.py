"""Host-side native runtime — ctypes bindings over ``_runtime.cpp``.

Reference: ``csrc/flatten_unflatten.cpp :: flatten/unflatten`` (the
``apex_C`` extension backing DDP bucket flattening) and
``examples/imagenet/main_amp.py :: data_prefetcher`` (side-stream input
normalization + prefetch). See `_runtime.cpp` for the TPU-native design
rationale. The library is compiled from ``_runtime.cpp`` on the machine
that imports it (the artefact's name carries `_build_key`, so one built
from other source or on another host is never loaded); every entry
point has a NumPy fallback so the package works without a toolchain —
a failed build warns, and `native_available` says which path is live.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import queue
import subprocess
import threading
import time as _time
import warnings
from typing import Callable, Iterable, Iterator, Optional, Sequence

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "_runtime.cpp")
_CXX = ["g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
        "-pthread"]
_N_THREADS = max(1, (os.cpu_count() or 4) // 2)


def _build_key() -> str:
    """What the artefact is a function of: the source bytes, the compile
    command, and — because ``-march=native`` bakes in this CPU's
    instruction set and a checkout's ignored files get copied between
    machines — the host and its CPU flags. A ``.so`` under any other key
    is never loaded."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXX).encode())
    h.update(platform.node().encode())
    h.update(platform.machine().encode())
    try:
        with open("/proc/cpuinfo") as f:
            h.update(next((ln for ln in f if ln.startswith("flags")),
                          "").encode())
    except OSError:
        pass
    return h.hexdigest()[:16]


def _build_library() -> Optional[str]:
    lib_path = os.path.join(_DIR, f"_runtime.{_build_key()}.so")
    if os.path.exists(lib_path):
        return lib_path
    tmp = f"{lib_path}.tmp.{os.getpid()}"   # per-pid: concurrent imports
    try:                                    # must not interleave writes
        subprocess.run([*_CXX, _SRC, "-o", tmp], check=True,
                       capture_output=True, timeout=120)
        os.replace(tmp, lib_path)           # atomic publish
    except (OSError, subprocess.SubprocessError) as e:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        # the NumPy path keeps the package usable without a toolchain,
        # but never silently: say why the native library is absent
        stderr = (getattr(e, "stderr", None) or b"").decode(
            errors="replace")[-400:]
        warnings.warn(
            f"apex1_tpu.runtime: building _runtime.cpp failed ({e!r}) "
            f"{stderr}; using the NumPy fallbacks", RuntimeWarning,
            stacklevel=2)
        return None
    for old in glob.glob(os.path.join(_DIR, "_runtime.*.so")):
        if old != lib_path:                 # other sources / machines
            try:
                os.unlink(old)
            except OSError:
                pass
    return lib_path


def _load() -> Optional[ctypes.CDLL]:
    path = _build_library()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
        # gate BEFORE touching any symbol: a stale .so from an older source
        # must fall back to NumPy, and ctypes raises AttributeError (not
        # OSError) for missing symbols
        lib.apex1_runtime_abi_version.restype = ctypes.c_int
        if lib.apex1_runtime_abi_version() != 4:
            return None
        i64, vp = ctypes.c_int64, ctypes.c_void_p
        lib.apex1_flatten.argtypes = [ctypes.POINTER(vp),
                                      ctypes.POINTER(i64), i64, vp,
                                      ctypes.c_int]
        lib.apex1_unflatten.argtypes = [vp, ctypes.POINTER(i64), i64,
                                        ctypes.POINTER(vp), ctypes.c_int]
        lib.apex1_normalize_u8_f32.argtypes = [
            vp, vp, i64, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), i64, ctypes.c_int]
        lib.apex1_f32_to_bf16.argtypes = [vp, vp, i64, ctypes.c_int]
        lib.apex1_bf16_to_f32.argtypes = [vp, vp, i64, ctypes.c_int]
        lib.apex1_loader_open.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                          i64, i64, ctypes.c_uint64,
                                          ctypes.c_int]
        lib.apex1_loader_open.restype = vp
        lib.apex1_loader_num_sequences.argtypes = [vp]
        lib.apex1_loader_num_sequences.restype = i64
        lib.apex1_loader_next.argtypes = [vp, i64, vp, ctypes.c_int]
        lib.apex1_loader_next.restype = ctypes.c_int
        lib.apex1_loader_fetch.argtypes = [vp, i64, vp]
        lib.apex1_loader_fetch.restype = ctypes.c_int
        lib.apex1_loader_close.argtypes = [vp]
        lib.apex1_pack_fill.argtypes = [
            vp, vp, vp, vp, vp, vp, vp, i64, vp, vp, vp, i64, i64,
            ctypes.c_int32, ctypes.c_int]
        lib.apex1_pack_plan.argtypes = [
            vp, vp, i64, i64, ctypes.c_int, vp, vp, vp, vp, vp, vp]
        lib.apex1_pack_plan.restype = i64
        return lib
    except (OSError, AttributeError):
        return None


_LIB = _load()


def native_available() -> bool:
    return _LIB is not None


def _as_contig(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a)


def flatten(arrays: Sequence[np.ndarray]) -> np.ndarray:
    """Pack arrays into one contiguous byte buffer (``apex_C.flatten``).
    Returns a uint8 view; pair with `unflatten` + the original specs."""
    arrays = [_as_contig(np.asarray(a)) for a in arrays]
    sizes = [a.nbytes for a in arrays]
    out = np.empty(sum(sizes), np.uint8)
    if _LIB is not None and arrays:
        n = len(arrays)
        srcs = (ctypes.c_void_p * n)(
            *[a.ctypes.data for a in arrays])
        csizes = (ctypes.c_int64 * n)(*sizes)
        _LIB.apex1_flatten(srcs, csizes, n, out.ctypes.data, _N_THREADS)
    else:
        off = 0
        for a, s in zip(arrays, sizes):
            out[off:off + s] = a.view(np.uint8).reshape(-1)
            off += s
    return out


def unflatten(flat: np.ndarray,
              specs: Sequence[tuple[tuple[int, ...], np.dtype]]
              ) -> list[np.ndarray]:
    """Inverse of `flatten`: ``specs`` is [(shape, dtype), ...]
    (``apex_C.unflatten``)."""
    flat = _as_contig(np.asarray(flat)).view(np.uint8)
    outs = [np.empty(shape, dtype) for shape, dtype in specs]
    sizes = [o.nbytes for o in outs]
    if sum(sizes) != flat.nbytes:
        raise ValueError(f"flat buffer holds {flat.nbytes} bytes, specs "
                         f"need {sum(sizes)}")
    if _LIB is not None and outs:
        n = len(outs)
        dsts = (ctypes.c_void_p * n)(*[o.ctypes.data for o in outs])
        csizes = (ctypes.c_int64 * n)(*sizes)
        _LIB.apex1_unflatten(flat.ctypes.data, csizes, n, dsts, _N_THREADS)
    else:
        off = 0
        for o, s in zip(outs, sizes):
            o.view(np.uint8).reshape(-1)[:] = flat[off:off + s]
            off += s
    return outs


def normalize_images(batch_u8: np.ndarray, mean: Sequence[float],
                     std: Sequence[float]) -> np.ndarray:
    """uint8 NHWC -> fp32 ``(x/255 - mean) / std`` per channel — the
    reference prefetcher's side-stream normalize, on host threads."""
    batch_u8 = _as_contig(np.asarray(batch_u8, np.uint8))
    c = batch_u8.shape[-1]
    if len(mean) != c or len(std) != c:
        raise ValueError(f"mean/std length must equal channels ({c})")
    out = np.empty(batch_u8.shape, np.float32)
    if _LIB is not None:
        fmean = (ctypes.c_float * c)(*[float(m) for m in mean])
        fstd = (ctypes.c_float * c)(*[float(s) for s in std])
        _LIB.apex1_normalize_u8_f32(batch_u8.ctypes.data, out.ctypes.data,
                                    batch_u8.size, fmean, fstd, c,
                                    _N_THREADS)
    else:
        out[:] = (batch_u8.astype(np.float32) / 255.0
                  - np.asarray(mean, np.float32)) / np.asarray(
                      std, np.float32)
    return out


def f32_to_bf16_bits(x: np.ndarray) -> np.ndarray:
    """fp32 -> bf16 bit patterns (uint16), round-to-nearest-even — host
    staging for bf16 comm/checkpoint buffers."""
    x = _as_contig(np.asarray(x, np.float32))
    out = np.empty(x.shape, np.uint16)
    if _LIB is not None:
        _LIB.apex1_f32_to_bf16(x.ctypes.data, out.ctypes.data, x.size,
                               _N_THREADS)
    else:
        bits = x.view(np.uint32)
        rounding = 0x7FFF + ((bits >> 16) & 1)
        rounded = ((bits + rounding) >> 16).astype(np.uint16)
        # NaN: carry out of the mantissa would corrupt to ±0 — quiet it
        nan = (bits & 0x7FFFFFFF) > 0x7F800000
        out[:] = np.where(nan, ((bits >> 16) | 0x0040).astype(np.uint16),
                          rounded)
    return out


def bf16_bits_to_f32(bits: np.ndarray) -> np.ndarray:
    bits = _as_contig(np.asarray(bits, np.uint16))
    out = np.empty(bits.shape, np.float32)
    if _LIB is not None:
        _LIB.apex1_bf16_to_f32(bits.ctypes.data, out.ctypes.data,
                               bits.size, _N_THREADS)
    else:
        out.view(np.uint32)[:] = bits.astype(np.uint32) << 16
    return out


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 over uint64 — must match ``mix64`` in `_runtime.cpp`."""
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
        x = ((x ^ (x >> np.uint64(30)))
             * np.uint64(0xBF58476D1CE4E5B9)).astype(np.uint64)
        x = ((x ^ (x >> np.uint64(27)))
             * np.uint64(0x94D049BB133111EB)).astype(np.uint64)
        return x ^ (x >> np.uint64(31))


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _epoch_perm(epoch: np.ndarray, i: np.ndarray, *, seed: int, n: int,
                pow2: int) -> np.ndarray:
    """Exact permutation of [0, n) per epoch (cycle-walked affine map over
    the pow2 ring) — the math of ``TokenLoader::perm``, vectorized."""
    seed = np.uint64(seed)
    a = (_mix64(seed ^ _mix64(epoch)) | np.uint64(1))
    c = _mix64(seed ^ _mix64(epoch ^ np.uint64(0xD1B54A32D192ED03)))
    m = np.uint64(pow2 - 1)
    x = i.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (a * x + c) & m
        todo = x >= np.uint64(n)
        while np.any(todo):
            x[todo] = (a[todo] * x[todo] + c[todo]) & m
            todo = x >= np.uint64(n)
    return x.astype(np.int64)


class TokenDataset:
    """Deterministic LM-pretraining batches from a flat binary token file.

    TPU-native design (vs. the reference's stateful torch DataLoader
    iterators): ``batch_at(step)`` is a pure function of (file, seed,
    step) — checkpoint/resume stores only the step counter, matching the
    framework's functional train-state story, and prefetch workers can
    fetch any step. Shuffling is an exact per-epoch permutation (affine
    map over the next power of two with cycle-walking — O(1) memory for
    arbitrarily large corpora). Backed by the memory-mapped native loader
    in `_runtime.cpp`; the NumPy fallback reproduces the identical
    permutation bit-for-bit.

    The file is raw little-endian tokens, uint16 (vocab < 65536) or
    int32/uint32. For next-token training use ``seq_len = S + 1`` and
    shift in the loss.
    """

    def __init__(self, path: str, *, seq_len: int, batch_size: int,
                 dtype=np.uint16, seed: int = 0, shuffle: bool = True):
        self.path = str(path)
        self.seq_len = int(seq_len)
        self.batch_size = int(batch_size)
        self.dtype = np.dtype(dtype)
        if self.dtype.itemsize not in (2, 4):
            raise ValueError("token dtype must be 2 or 4 bytes")
        # wrap to uint64 so native (C cast) and NumPy fallback agree for
        # negative / oversized seeds
        self.seed = int(seed) & ((1 << 64) - 1)
        self.shuffle = bool(shuffle)
        self._closed = False
        self._handle = None
        self._finalizer = None
        if _LIB is not None:
            self._handle = _LIB.apex1_loader_open(
                self.path.encode(), self.dtype.itemsize, self.seq_len,
                self.batch_size, ctypes.c_uint64(self.seed),
                int(self.shuffle))
            if self._handle:
                import weakref
                self._finalizer = weakref.finalize(
                    self, _LIB.apex1_loader_close, self._handle)
        if self._handle:
            self.num_sequences = int(
                _LIB.apex1_loader_num_sequences(self._handle))
            self._tokens = None
        else:
            self._tokens = np.memmap(self.path, dtype=self.dtype, mode="r")
            self.num_sequences = len(self._tokens) // self.seq_len
        if self.num_sequences < 1:
            raise ValueError(
                f"{path}: fewer than one {seq_len}-token sequence")
        self._pow2 = _next_pow2(self.num_sequences)

    @property
    def native(self) -> bool:
        return self._handle is not None

    def steps_per_epoch(self) -> int:
        return self.num_sequences // self.batch_size

    def _perm(self, epoch: np.ndarray, i: np.ndarray) -> np.ndarray:
        """Vectorized epoch permutation — mirrors TokenLoader::perm."""
        if not self.shuffle:
            return i.astype(np.int64)
        return _epoch_perm(epoch, i, seed=self.seed, n=self.num_sequences,
                           pow2=self._pow2)

    def fetch(self, seq_index: int, out=None) -> np.ndarray:
        """One raw sequence by index (no permutation) — the building
        block `ShardedTokenDataset` routes its global shuffle through.
        ``out``: optional int32 (seq_len,) buffer to fill in place (the
        sharded batch loop passes batch rows, avoiding per-row allocs)."""
        if self._closed:
            raise RuntimeError("TokenDataset is closed")
        if not 0 <= seq_index < self.num_sequences:
            raise IndexError(seq_index)
        if out is None:
            out = np.empty((self.seq_len,), np.int32)
        if self._handle:
            rc = _LIB.apex1_loader_fetch(self._handle, seq_index,
                                         out.ctypes.data)
            if rc != 0:
                raise RuntimeError(f"loader_fetch failed ({seq_index})")
            return out
        lo = seq_index * self.seq_len
        out[:] = self._tokens[lo:lo + self.seq_len]
        return out

    def batch_at(self, step: int) -> np.ndarray:
        """(batch_size, seq_len) int32 tokens of global step ``step``."""
        if self._closed:
            raise RuntimeError("TokenDataset is closed")
        if step < 0:
            raise ValueError("step must be >= 0")
        out = np.empty((self.batch_size, self.seq_len), np.int32)
        if self._handle:
            rc = _LIB.apex1_loader_next(self._handle, step,
                                        out.ctypes.data, _N_THREADS)
            if rc != 0:
                raise RuntimeError(f"loader_next failed (step={step})")
            return out
        g = np.uint64(step) * np.uint64(self.batch_size) + np.arange(
            self.batch_size, dtype=np.uint64)
        epoch = g // np.uint64(self.num_sequences)
        s = self._perm(epoch, g % np.uint64(self.num_sequences))
        for r in range(self.batch_size):
            lo = int(s[r]) * self.seq_len
            out[r] = self._tokens[lo:lo + self.seq_len]
        return out

    def iter_from(self, step: int = 0) -> Iterator[np.ndarray]:
        """Endless step-indexed batch stream (wrap in `PrefetchLoader` to
        overlap host work with device compute)."""
        while True:
            yield self.batch_at(step)
            step += 1

    def close(self):
        self._closed = True
        if self._finalizer is not None:
            self._finalizer()  # idempotent: detaches + closes the handle
            self._finalizer = None
        self._handle = None
        self._tokens = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class ShardedTokenDataset:
    """`TokenDataset` over a sharded corpus (many flat token files) —
    real pretraining datasets ship as shards. Same contract: pure
    ``batch_at(step)``, exact global shuffle (one permutation over the
    CONCATENATED sequence pool, so epoch boundaries and resume semantics
    are corpus-global, not per-shard), NumPy fallback bit-identical.
    Shards are mmapped native loaders; rows route to their shard via the
    cumulative sequence counts. Shard order is the CALLER's order (pass
    a sorted list for a canonical corpus — no silent re-sorting)."""

    def __init__(self, paths: Sequence[str], *, seq_len: int,
                 batch_size: int, dtype=np.uint16, seed: int = 0,
                 shuffle: bool = True):
        if not paths:
            raise ValueError("need at least one shard path")
        self.seq_len = int(seq_len)
        self.batch_size = int(batch_size)
        self.seed = int(seed) & ((1 << 64) - 1)
        self.shuffle = bool(shuffle)
        self._shards = [TokenDataset(str(p), seq_len=seq_len,
                                     batch_size=1, dtype=dtype, seed=0,
                                     shuffle=False) for p in paths]
        counts = [s.num_sequences for s in self._shards]
        self._starts = np.concatenate([[0], np.cumsum(counts)])
        self.num_sequences = int(self._starts[-1])
        self._pow2 = _next_pow2(self.num_sequences)

    @property
    def native(self) -> bool:
        return all(s.native for s in self._shards)

    def steps_per_epoch(self) -> int:
        return self.num_sequences // self.batch_size

    def batch_at(self, step: int) -> np.ndarray:
        if step < 0:
            raise ValueError("step must be >= 0")
        g = np.uint64(step) * np.uint64(self.batch_size) + np.arange(
            self.batch_size, dtype=np.uint64)
        epoch = g // np.uint64(self.num_sequences)
        i = g % np.uint64(self.num_sequences)
        s = (_epoch_perm(epoch, i, seed=self.seed, n=self.num_sequences,
                         pow2=self._pow2)
             if self.shuffle else i.astype(np.int64))
        out = np.empty((self.batch_size, self.seq_len), np.int32)
        shard_of = np.searchsorted(self._starts, s, side="right") - 1
        for r in range(self.batch_size):
            sh = int(shard_of[r])
            self._shards[sh].fetch(int(s[r] - self._starts[sh]),
                                   out=out[r])
        return out

    def iter_from(self, step: int = 0) -> Iterator[np.ndarray]:
        while True:
            yield self.batch_at(step)
            step += 1

    def close(self):
        for s in self._shards:
            s.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def pack_documents(docs: Sequence[np.ndarray], seq_len: int,
                   *, pad_id: int = 0,
                   restart_chunk_positions: bool = False):
    """Greedy first-fit packing of variable-length documents into fixed
    (rows, seq_len) batches — the data-side half of varlen attention
    (≙ the reference fmha's cu_seqlens packed QKV batches; the model side
    is ``segment_ids`` on the flash/ring attention kernels).

    Returns ``(tokens, segment_ids, positions)``, each (rows, seq_len)
    int32. ``segment_ids`` are unique per document within a row, ``-1`` on
    padding (never matches a real segment in the kernels' equality mask);
    ``positions`` restart at 0 per document (feed per-row RoPE tables).
    Documents longer than ``seq_len`` are split into ``seq_len`` chunks
    (each chunk its own segment); their positions continue within the doc
    (RoPE models — no table bound) unless ``restart_chunk_positions`` is
    set, which restarts every chunk at 0 (REQUIRED for learned-position
    models like GPT-2, whose position table would otherwise be indexed
    out of bounds and silently clamped).
    """
    if seq_len <= 0:
        # must precede the native branch: apex1_pack_plan's chunk loop
        # cannot advance at seq_len <= 0 (unbounded writes, not an error)
        raise ValueError(f"seq_len must be positive, got {seq_len}")
    docs = [np.ascontiguousarray(np.asarray(d).ravel(), np.int32)
            for d in docs]
    doc_lens = np.asarray([len(d) for d in docs], np.int64)
    doc_starts = np.zeros(len(docs) + 1, np.int64)
    np.cumsum(doc_lens, out=doc_starts[1:])
    flat = (np.concatenate(docs) if docs else np.zeros(0, np.int32))
    n_chunks = int(np.sum(-(-doc_lens // seq_len)))

    if _LIB is not None:
        # native plan (first-fit placement) + threaded fill
        starts = np.empty(n_chunks, np.int64)
        lens64 = np.empty(n_chunks, np.int64)
        rows64 = np.empty(n_chunks, np.int64)
        cols64 = np.empty(n_chunks, np.int64)
        segs32 = np.empty(n_chunks, np.int32)
        pos032 = np.empty(n_chunks, np.int32)
        n = _LIB.apex1_pack_plan(
            doc_lens.ctypes.data, doc_starts.ctypes.data, len(docs),
            seq_len, int(restart_chunk_positions), starts.ctypes.data,
            lens64.ctypes.data, rows64.ctypes.data, cols64.ctypes.data,
            segs32.ctypes.data, pos032.ctypes.data)
        tokens = np.empty((n, seq_len), np.int32)
        segs = np.empty((n, seq_len), np.int32)
        pos = np.empty((n, seq_len), np.int32)
        _LIB.apex1_pack_fill(
            flat.ctypes.data, starts.ctypes.data, lens64.ctypes.data,
            rows64.ctypes.data, cols64.ctypes.data, segs32.ctypes.data,
            pos032.ctypes.data, n_chunks, tokens.ctypes.data,
            segs.ctypes.data, pos.ctypes.data, n, seq_len, pad_id,
            _N_THREADS)
        return tokens, segs, pos

    # ---- NumPy fallback: identical first-fit policy in Python ----
    space: list[int] = []
    fill: list[int] = []       # next free column per row
    nseg: list[int] = []       # segments placed per row
    open_rows: list[int] = []  # bounded first-fit window: corpus-scale
    MAX_OPEN = 256             # packing stays O(chunks · MAX_OPEN)
    plan: list[tuple[int, int, int, int, int, int]] = []
    for di, doc in enumerate(docs):
        for lo in range(0, len(doc), seq_len):
            ln = min(seq_len, len(doc) - lo)
            for r in open_rows:
                if space[r] >= ln:
                    break
            else:
                r = len(space)
                space.append(seq_len)
                fill.append(0)
                nseg.append(0)
                if ln < seq_len:   # full rows never enter the window
                    open_rows.append(r)
                    if len(open_rows) > MAX_OPEN:
                        open_rows.pop(0)  # evict by age, stays bounded
            plan.append((int(doc_starts[di]) + lo, ln, r, fill[r],
                         nseg[r], 0 if restart_chunk_positions else lo))
            space[r] -= ln
            fill[r] += ln
            nseg[r] += 1
            if space[r] == 0 and r in open_rows:
                open_rows.remove(r)
    n = len(space)
    tokens = np.full((n, seq_len), pad_id, np.int32)
    segs = np.full((n, seq_len), -1, np.int32)
    pos = np.zeros((n, seq_len), np.int32)
    for start, ln, r, c, sid, pos0 in plan:
        tokens[r, c:c + ln] = flat[start:start + ln]
        segs[r, c:c + ln] = sid
        pos[r, c:c + ln] = np.arange(pos0, pos0 + ln)
    return tokens, segs, pos


def write_token_file(path: str, tokens: np.ndarray) -> None:
    """Write a flat token file `TokenDataset` can read (little-endian)."""
    arr = np.asarray(tokens)
    if arr.dtype.itemsize not in (2, 4):
        raise ValueError("token dtype must be 2 or 4 bytes")
    arr.astype(arr.dtype.newbyteorder("<")).tofile(path)


class PrefetchLoader:
    """Background-thread prefetcher — ``examples/imagenet ::
    data_prefetcher`` equivalent. Pulls batches from ``source`` on a worker
    thread, runs ``transform`` (e.g. `normalize_images` or `flatten`) off
    the critical path, and optionally ``device_put``s ahead so
    host→device transfer overlaps the current step (the reference's CUDA
    side-stream overlap, via JAX async dispatch)."""

    _DONE = object()

    def __init__(self, source: Iterable, *,
                 transform: Optional[Callable] = None,
                 device_put: bool = True, prefetch: int = 2):
        self.source = source
        self.transform = transform
        self.device_put = device_put
        self.prefetch = max(1, prefetch)

    def __iter__(self) -> Iterator:
        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        err: list[BaseException] = []
        stop = threading.Event()

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def work():
            try:
                import jax
                for batch in self.source:
                    if stop.is_set():
                        return
                    if self.transform is not None:
                        batch = self.transform(batch)
                    if self.device_put:
                        batch = jax.tree.map(jax.device_put, batch)
                    if not put(batch):
                        return
            except BaseException as e:  # propagate to consumer
                err.append(e)
            finally:
                put(self._DONE)

        t = threading.Thread(target=work, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is self._DONE:
                    if err:
                        raise err[0]
                    return
                yield item
        finally:
            # consumer stopped early (break/exception): unblock the worker
            # and wait until it is actually DEAD — callers (e.g. the
            # TokenDataset example) may tear down resources the worker
            # reads (an mmap) right after this returns, so a timed-out
            # join must not be swallowed
            stop.set()
            deadline = _time.monotonic() + 60.0
            while t.is_alive() and _time.monotonic() < deadline:
                while not q.empty():
                    try:
                        q.get_nowait()
                    except queue.Empty:
                        break
                t.join(timeout=0.1)
            if t.is_alive():
                # a source blocked in next() can never observe `stop`;
                # warn loudly instead of hanging teardown forever — the
                # caller must keep resources the worker reads alive
                import warnings
                warnings.warn(
                    "PrefetchLoader worker did not stop within 60s (source "
                    "blocked?); resources it reads must outlive it",
                    RuntimeWarning, stacklevel=2)


class RequestFeeder:
    """Background request-ingest thread for `apex1_tpu.serving`: pulls
    raw prompts from ``source`` (an iterable of anything — text lines,
    token lists), tokenizes them OFF the engine's critical path, and
    pushes them through ``submit`` (the engine/scheduler entry point),
    absorbing `Backpressure` with the scheduler docstring's promised
    429/retry contract: BOUNDED EXPONENTIAL BACKOFF with deterministic
    jitter (``resilience.retry.backoff_delays`` — base ``retry_wait_s``,
    doubling, capped at ``retry_cap_s``, jittered so a burst of rejected
    feeders doesn't re-slam the queue in lockstep) and a
    drop-after-deadline rule: once an item has spent ``deadline_s``
    total in retries it is shed (``dropped``), because an overloaded
    engine must shed load, not stretch tail latency unboundedly.

    A structured rejection's ``retry_after_s`` is the server's backoff
    hint and is honored as a FLOOR on the next sleep: the exponential
    schedule may wait longer, never shorter — a thousand feeders
    retrying "soon" against a server that said "50 ms" is exactly the
    re-slam the hint exists to prevent. The floored delay still counts
    against ``deadline_s``.

    ``tokenize(item) -> (tokens, kwargs)`` where kwargs go straight to
    ``submit(tokens, **kwargs)`` (``max_new_tokens`` etc.). Rejections
    that outlive ``retries``/``deadline_s`` land in ``dropped`` with the
    reason. ``counters`` tracks the aggregate: ``submitted``,
    ``retries`` (backoff sleeps taken), ``dropped_backpressure``,
    ``dropped_error`` — the feed-side metrics record.

    The worker only SUBMITS; stepping the engine stays with the caller
    (the engine is not thread-safe by design — one loop owns the
    device). Typical shape::

        feeder = RequestFeeder(prompts, tokenize, engine.submit)
        feeder.start()
        while not feeder.idle or engine.n_active or engine.scheduler.depth:
            engine.step()
        feeder.join()
    """

    def __init__(self, source: Iterable, tokenize: Callable,
                 submit: Callable, *, retries: int = 100,
                 retry_wait_s: float = 0.005,
                 retry_cap_s: float = 0.25,
                 deadline_s: Optional[float] = None,
                 jitter: float = 0.5, seed: int = 0):
        self.source = source
        self.tokenize = tokenize
        self.submit = submit
        self.retries = int(retries)
        self.retry_wait_s = float(retry_wait_s)
        self.retry_cap_s = float(retry_cap_s)
        self.deadline_s = deadline_s
        self.jitter = float(jitter)
        self.seed = int(seed)
        self.submitted: list = []
        self.dropped: list = []          # (item, reason)
        self.errors: list = []
        self.counters = {"submitted": 0, "retries": 0,
                         "dropped_backpressure": 0, "dropped_error": 0}
        self._thread: Optional[threading.Thread] = None
        self._done = threading.Event()

    @property
    def idle(self) -> bool:
        """True once the source is drained and every item dispatched."""
        return self._done.is_set()

    def start(self) -> "RequestFeeder":
        from apex1_tpu.resilience.retry import backoff_delays
        from apex1_tpu.serving.scheduler import (Backpressure,
                                                 new_request_id)

        def work():
            try:
                for n_item, item in enumerate(self.source):
                    # a PER-ITEM failure (tokenizer bug, contract
                    # ValueError from submit) drops THAT item and keeps
                    # feeding — one malformed request must not silently
                    # starve the rest of the stream (review finding)
                    try:
                        tokens, kw = self.tokenize(item)
                    except Exception as e:
                        self.dropped.append((item, f"tokenize: {e!r}"))
                        self.counters["dropped_error"] += 1
                        self.errors.append(e)
                        continue
                    # one id across every retry attempt: transient
                    # backpressure rejections then update ONE metrics
                    # record instead of minting a phantom rejected
                    # record per attempt (review finding)
                    kw.setdefault("req_id", new_request_id())
                    delays = backoff_delays(
                        self.retries, base_s=self.retry_wait_s,
                        cap_s=self.retry_cap_s, jitter=self.jitter,
                        seed=self.seed ^ n_item)
                    t0 = _time.monotonic()
                    while True:
                        try:
                            self.submitted.append(
                                self.submit(tokens, **kw))
                            self.counters["submitted"] += 1
                            break
                        except Backpressure as e:
                            d = next(delays, None)
                            if d is not None:
                                # server hint = the floor, not the value:
                                # back off MORE than asked, never less
                                floor = getattr(e, "retry_after_s", None)
                                if floor:
                                    d = max(d, float(floor))
                            waited = _time.monotonic() - t0
                            if d is None:
                                reason = f"{e.reason} (retries exhausted)"
                            elif (self.deadline_s is not None
                                  and waited + d > self.deadline_s):
                                reason = (f"{e.reason} (deadline "
                                          f"{self.deadline_s}s after "
                                          f"{waited:.3f}s)")
                            else:
                                self.counters["retries"] += 1
                                _time.sleep(d)
                                continue
                            self.dropped.append((item, reason))
                            self.counters["dropped_backpressure"] += 1
                            break
                        except Exception as e:
                            self.dropped.append((item, repr(e)))
                            self.counters["dropped_error"] += 1
                            self.errors.append(e)
                            break
            except BaseException as e:   # source iteration died —
                self.errors.append(e)    # surfaced via join()
            finally:
                self._done.set()

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()
        return self

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)
        if self.errors:
            raise self.errors[0]
