"""Continuous-batching inference engine over the chunk-decode spine.

`models.generate` runs ONE batch, assembled by the caller, start to
finish; the TPU idles while the host builds the next batch, and a long
request holds the whole batch hostage. This engine serves a STREAM:
requests join a fixed pool of KV slots the moment one frees, and leave
on EOS / length / deadline — the decode step never stops for them.

TPU-first shape discipline (PAPER.md: static shapes, one dispatch —
the serving corollary of the training thesis): the pool is a fixed
``(max_slots, max_len)`` cache pytree, and the whole engine compiles
EXACTLY TWO executables, traced once each for the life of the engine:

- **prefill** — one ``(1, prefill_chunk)`` chunk-decode forward against
  one slot's lane. Every prompt, of any length, is fed as right-padded
  fixed-width chunks at a traced ``cache_index`` (the chunk mode of
  `cached_attention` subsumes prefill — an empty cache at index 0 is
  its degenerate case), so admission never retraces. The slot id, the
  install-this-lane flag (zeros for a fresh request, a shared-prefix
  page for a sharer), and the real-token count are all traced operands.
- **decode** — one step for ALL slots: ONE cached forward at batch
  ``max_slots`` over the pool, ``cache_index`` a VECTOR, each row
  carrying its OWN traced cache index (rows are at different depths —
  that is the whole point), an idle lane the index -1. Where the
  kernels run, `cached_attention` hands each layer to ONE kernel over
  the pool (`ops.decode_attend`): it appends a live lane's K/V rows
  where they lie and reads the lane to its own horizon and no further,
  and an idle lane is neither read nor written — no copy of the pool,
  no leaf rewritten, no loop over slots (`tests/test_engine_aot.py`).
  Off the TPU the same step is the composite (`generate.cache_write`'s
  select and `cache_attend`), the parity gold. Only what is per row BY
  CONTRACT is vmapped: the counter-keyed sampling and the LoRA
  epilogue row. Retirement and admission change only ARRAY VALUES,
  never shapes. The step is one forward at the pool's batch, so a
  decoder whose rows INTERACT sees its neighbours here, as on the paged
  kernel path: a capacity-routed expert layer (`transformer.moe.router`,
  `MoEMLP`: a row's pair is dropped where the rows before it filled its
  expert) makes a lane's tokens depend on who shares the pool. The
  dropless layer (`transformer.moe.held_experts_mlp`, what
  `models.lfm2` is served with) keeps rows independent: every pair is
  computed whatever the batch, an idle lane and a padded prefill row
  are not routed at all, and a lane's tokens are those it gets alone
  (`tests/test_lfm2.py`).

With ``num_draft > 0`` the decode executable is replaced by **verify**
— same two-executable discipline, different second executable: the
same batch forward over ``(max_slots, num_draft + 1)`` chunks (a plain
decode step is its ``num_draft = 0`` case) that scores
the previous token plus K host-proposed draft tokens in ONE dispatch
and accepts the longest prefix matching the target's own counter-keyed
samples (see SPECULATIVE DECODE below).

``Engine.trace_counts`` is the compilation-count hook: the counter
increments inside each traced Python body, so a retrace — the thing
this design forbids — is observable as a count > 1 (`test_serving::
TestContinuousBatching::
test_staggered_join_leave_token_identical_two_executables`).

SAMPLING is counter-based and PER REQUEST: every request carries a
seed (explicit, or derived from its stable request id), and token i of
a request is sampled with ``fold_in(key(seed), i)`` — a pure function
of (params, prompt, seed), independent of batch composition, engine
step number, or which engine instance runs it. That is the serving
analogue of PR 6's bit-exact resume: a supervisor that loses a replica
mid-stream resubmits the request (same id, same seed) to a fresh
engine and the regenerated stream is token-identical to the lost one,
at ANY temperature — idempotent resubmission as a sampling property,
not a greedy-only accident.

RADIX PREFIX CACHE (``prefix_cache=True``, the default): admission
consults the pool's radix matcher (`kv_pool.RadixIndex`) with the
request's FULL prompt (explicit ``prefix=`` tuple, if any, simply
concatenated in front — the explicit API is a thin wrapper that also
pins the page's registration length), installs the longest registered
page, and prefills only the remainder. Requests WITHOUT an explicit
prefix auto-register a page at the chunk-aligned share point
``((len - 1) // prefill_chunk) * prefill_chunk`` — canonical lengths,
so requests that split prefix/prompt differently still converge on one
key. Token parity is untouched by a hit: chunked prefill computes the
same K/V whatever boundary it resumes from (fp32/toy exact; same bf16
near-tie caveat as chained `generate`). Near capacity (queue deeper
than free slots) admission becomes prefix-aware: within a QoS class,
requests that would HIT are dequeued first — a hit turns a slot over
sooner, which is the scarce resource under pressure.

SPECULATIVE DECODE (``num_draft=K``): each step, the host proposes K
tokens per active slot (`spec.ngram_propose` self-drafting by default;
``draft_propose=`` plugs in a small draft model) and ONE verify
dispatch scores all slots' chunks. Acceptance is EXACT-MATCH against
the target's counter-keyed stream (`generate.counter_sample`): draft j
is accepted iff it equals the token the engine would have sampled at
that output position anyway. The emitted stream is therefore
BIT-IDENTICAL to the non-speculative engine — and to solo `generate` —
at ANY temperature; drafts are pure latency hints, and the counter-seed
contract (resubmission idempotency, hedging, failover) survives
verbatim. What speculation changes is DISPATCH COUNT: ~(1 + accepted)
tokens land per verify instead of 1 per decode step — decode is
weight-streaming-bound on TPU, so fewer dispatches ≈ proportionally
fewer HBM weight streams. Accept rate is banked per request
(`RequestRecord.n_drafted/n_accepted`) and per class (the metrics
window), and the verify step reads back its per-slot accept counts —
the one host sync speculation's variable-rate emission costs.

ASYNC DISPATCH: the decode control vectors (token/index/active/seed/
output-position per slot) live on DEVICE and are patched in place at
join/leave boundaries, so the step chain is dispatch-only from the
host's side.
With ``eos_id=None`` retirement is purely length-based (known at
admission) and the engine NEVER reads a step's tokens back before
dispatching the next — per-step outputs accumulate in a device-side
log and are materialized once, at retirement. With an ``eos_id`` the
engine must observe every token, but not before it launches the next
steps: the loop runs AHEAD OF THE READ (`Engine._decode_step`). `step()`
launches a step, fed from device arrays, and only then reads the tokens
of the oldest launch in flight, whose device-to-host copy was started at
their own launch; host and device work at the same time, one thread,
JAX's asynchronous dispatch doing the overlapping. How many launches
stand behind the one that is read is the engine's DEPTH, and it is no
option: where the step in flight outlasts the launch of the next
(`packing.launch_is_hidden`, asked once at construction) the host waits
for the device whatever it does, and the depth is 1: launch n+1, read n.
Where the launch is not hidden, one launch in flight leaves the device
idle for the rest of every launch (two steps share one lap of launch +
loop + device step + a token's way to the host), and the depth is 2:
launch n+2, read n, three steps to the lap, and the gap between two
tokens falls to the longer of the device's step and the host's loop. A
call reads ONE launch's tokens, never two. A lane leaves the batch by
count at the launch of its last token and its request finishes where
those tokens are read; an ``eos`` is learnt ``depth`` steps late, and the
lane-steps already in flight for it are dropped on the host and harmless
on the device (the ordering argument is `_decode_step`'s docstring). A
token exists for the outside when the host holds its value. Speculative
mode reads each round back before the next (drafting needs the history;
accept counts gate retirement) and does not run ahead.

SPANS AND COUNTS: every phase of `step()` is an `obs.spine.span` — the
engine's one way of naming a region (``serving/step`` > ``expire``,
``admit`` > ``admit.alloc`` / ``prefill`` / ``admit.register`` /
``admit.first_read`` / ``admit.patch``, ``decode_step`` or
``verify_step``, ``read_tokens`` (with an ``eos_id``: of the launch
``depth`` before this step's), ``emit`` > ``retire``; the table is in
docs/observability.md). The spans of one request share its id, the two
in which the host waits for the device are marked ``wait``, and each
``serving/step`` carries what the step did (``admitted``, ``retired``,
``prefill_chunks``, ``prefill_tokens``, ``tokens_out``, ``n_active``,
``queue_depth``), ``ran_ahead``: the launches in flight, unread, when
its own was made (0 to ``depth``), ``overrun_lanes``: lane-steps launched
for a lane that had already sampled ``eos`` (up to ``depth`` an ``eos``),
``control_dispatches``: the
device programs it launched outside the two executables, and
``kv_blocks_read`` of
``kv_blocks_pool``: the blocks of K/V positions the step's attention
has to move, each live lane's up to its horizon, of those the pool
holds, with ``kv_fetch_ahead``: those of them that the kernel's queue
of fetches starts from an earlier lane's grid step (host arithmetic on
the slots' depths, all three). They are kept in memory,
always,
and lie in a profiler trace on the device's clock when one is taken.

OPERANDS: a launch costs the host by the array it is handed, whatever
the array's size, and most leaves of a decoder's tree are tiny. At
construction the small leaves are stacked by shape, dtype and sharding
(`serving.packing`), once; every executable is launched with the stacks
and the remaining leaves (the caller's own buffers: no matrix is
copied) and rebuilds the caller's tree at the head of its traced body.
``params`` stays the caller's tree, and an executable is still called
with it. The launch's span carries ``operands``, the arrays it hands
over. Cutting the stacks apart costs the device a little every step, so
a tree whose bytes alone take the device longer to stream than the
launch takes the host (`packing.launch_is_hidden`) is handed over as it
is: that launch lies under the step in flight. The same answer sets how
far the plain loop runs ahead (ASYNC DISPATCH).

RECURRENT STATE: a decoder's cache tree may hold, beside K/V, leaves
that hold no positions (`models.generate.granite_hybrid_decoder`: a
state-space layer's state and its convolution's last inputs). The pool
treats them like any leaf, slot on axis 0, and a fresh request's lane is
zeroed at admission, which is what a fresh state is. What differs: a
prefill chunk is right-padded, and a state advanced by pad tokens is
wrong for every later token, so ``prefill`` hands such a decoder
``n_real``; and a state is no list of positions, so a longer lane does
not hold a shorter prefix and a rejected draft cannot be rolled back:
``prefix_cache``, ``num_draft > 0`` and ``paged`` are refused at
construction. The step span of such an engine carries two more counts,
``state_lanes`` (the lanes whose state the launch updates) and
``state_bytes`` (what that moves: each of those lanes' recurrent leaves
read once and written once). The loop runs ahead as it does for any
decoder: an overrun lane-step, or two, advances a state that the next
admission zeroes, behind them in the device's order.

K/V LEAVES OF TWO LENGTHS: a decoder with sliding-window layers
(`models.generate.afmoe_decoder`) keeps, beside the leaves that hold every
position, RINGS: a sliding layer attends its last ``apply_fn.
sliding_window`` positions alone, so its leaf holds that many rows and one
launch's slack, position ``p`` in row ``p mod rows``
(`ops.decode_attend`, `generate.cached_attention`). The pool treats a ring
like any leaf, slot on axis 0; the engine reads the lengths off the tree
(`kv_leaf_rows`). What a ring has forgotten cannot be shared or rolled
back, so ``prefix_cache``, ``num_draft > 0`` and ``paged`` are refused at
construction, each by name, and so is a ``prefill_chunk`` longer than the
ring's slack (a chunk would overwrite rows its own first queries attend).
The step span's ``kv_blocks_read`` / ``kv_blocks_pool`` are then sums over
the attention LAYERS, each by its own leaf (a sliding layer reads the
blocks that hold its window, of those its ring has), and two counts stand
beside them: ``kv_blocks_read_window``, the sliding layers' part of the
first, and ``kv_layers``, the layers the sums run over. For a decoder
with one length all three stay what they were: blocks a lane, no layers.

EXPERTS: a decoder with sparse layers says so (``apply_fn.
moe_expert_slots``: the experts it holds, summed over its sparse layers)
and can be asked for a launch's routing counts. The plain step with an
``eos_id`` asks: the two counts ride behind the tokens in the ONE array
the host reads (no copy more, no synchronisation more; they arrive as
late as the tokens of their launch do), and the step span whose
`read_tokens` brought
them carries ``moe_rows`` (the (row, expert) pairs the launch computed
here), ``moe_experts_touched`` (the held experts with at least one, which
is what the launch had to stream) and ``moe_expert_slots``. A decoder
without experts is launched and read exactly as before.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex1_tpu.models.generate import (counter_sample, last_real_logits,
                                       sample_token)
from apex1_tpu.ops._common import use_pallas
from apex1_tpu.ops.decode_attend import DECODE_BLOCK, fetch_depth
from apex1_tpu.ops.paged_decode import (PagedCache, fused_sample,
                                        gather_pages, scatter_pages)
from apex1_tpu.resilience.retry import _mix32
from apex1_tpu.serving.kv_pool import KVPool, PagedKVPool
from apex1_tpu.serving.metrics import ServingMetrics
from apex1_tpu.serving.packing import Executable, PackedParams
from apex1_tpu.serving.scheduler import Backpressure, Request, Scheduler
from apex1_tpu.serving.spec import ngram_propose
from apex1_tpu.obs import spine
from apex1_tpu.obs.regions import region
from apex1_tpu.utils.observability import MetricsLogger


def derive_request_seed(engine_seed: int, req_id: int) -> int:
    """The per-request sampling seed when the caller supplies none:
    a deterministic avalanche of (engine seed, request id). Stable
    request ids (`scheduler.new_request_id`) therefore give stable
    seeds — the property replica failover's idempotent resubmission
    rides (same id on a fresh engine ⇒ bit-identical stream)."""
    return _mix32(int(engine_seed) ^ _mix32(int(req_id) + 0x5EED)) \
        & 0x7FFFFFFF


@dataclasses.dataclass
class EngineConfig:
    """Engine shape/sampling/admission knobs. Everything here is STATIC
    for the life of the engine (baked into the two executables); all
    per-request variation rides traced operands."""

    max_slots: int = 8           # concurrent requests (pool batch)
    max_len: int = 256           # cache positions per slot
    prefill_chunk: int = 16      # prompt tokens per prefill call
    temperature: float = 0.0     # 0 = greedy (engine-global; a per-
    top_k: Optional[int] = None  # request temperature would retrace)
    eos_id: Optional[int] = None
    pad_id: int = 0
    vocab_size: Optional[int] = None
    seed: int = 0                # base for derived PER-REQUEST seeds
                                 # (see derive_request_seed)
    max_queue: int = 64          # admission backpressure bound
    policy: str = "fifo"         # or "sjf" (see serving.scheduler)
    prefix_cache: bool = True    # radix cross-request prefix matching
    max_prefix_pages: int = 32   # LRU-by-last-hit page bound
    num_draft: int = 0           # >0: speculative decode, K drafts per
                                 # verify (the second executable becomes
                                 # the (1, K+1) chunk-verify)
    max_ngram: int = 3           # self-draft prompt-lookup n-gram cap
    cache_dtype: Optional[object] = None  # e.g. jnp.int8 — the KV pool's
    # steady-state capacity tier (half the bytes/slot ⇒ ~2x max_slots
    # for the same HBM; perf_model.kv_cache_bytes is the sizing model).
    # The Engine(cache_dtype=) kwarg still overrides (degraded-mode
    # restarts use it); None = the decoder's compute dtype.
    paged: bool = False          # route decode/verify through the paged
    # KV pool (`ops.paged_decode`): block-table page addressing, prefix
    # pages shared by REFERENCE (no copy-on-admit), the Pallas ragged
    # kernel + fused sampling epilogue on TPU. False keeps the dense
    # XLA-composed path — the parity reference (the paged CPU proxy is
    # pinned token-identical to it in tier-1).
    page_size: Optional[int] = None  # KV positions per page. None
    # resolves tuning-table winner > ceil8(prefill_chunk) heuristic;
    # the Pallas kernel path requires a multiple of 8 (sublane tiling)
    # and `check_paged_geometry` fails loudly otherwise.
    lora_rank: int = 0           # >0: multi-tenant LoRA adapter pages —
    # each slot carries a rank-length adapter block-table row and the
    # decode executables add the `ops.lora_epilogue` delta to the head
    # logits. tenant= on submit names the adapter (serving.lora);
    # requires Engine(lora_head=) — the model's (V, H) LM-head param.
    lora_max_adapters: int = 4   # adapter-page pool sizing (pages =
    #                              1 + max_adapters * rank)

    def __post_init__(self):
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if self.max_len < 2:
            raise ValueError("max_len must be >= 2")
        if self.num_draft < 0:
            raise ValueError(
                f"num_draft must be >= 0, got {self.num_draft}")
        if self.max_ngram < 1:
            raise ValueError(f"max_ngram must be >= 1, got {self.max_ngram}")
        if self.max_prefix_pages < 1:
            raise ValueError("max_prefix_pages must be >= 1")
        if self.page_size is not None and self.page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {self.page_size}")
        if self.lora_rank < 0:
            raise ValueError(
                f"lora_rank must be >= 0, got {self.lora_rank}")
        if self.lora_rank > 0 and self.lora_max_adapters < 1:
            raise ValueError(
                f"lora_max_adapters must be >= 1, "
                f"got {self.lora_max_adapters}")


@dataclasses.dataclass
class RequestResult:
    """Terminal outcome. ``tokens`` holds whatever was generated before
    the terminal event (full output for "done", a prefix for evictions
    and cancellations)."""

    req_id: int
    status: str                  # done | evicted | cancelled
    tokens: np.ndarray
    reason: str = ""


@dataclasses.dataclass
class _Slot:
    """Host-side state of one occupied pool lane."""

    req: Request
    first_tok: object            # device scalar (or int once read)
    start_step: int              # engine step its first DECODE lands at
    n_out: int = 1               # tokens emitted so far (first included)
    n_launched: int = 1          # tokens launched for: n_out, and one more
    #                              for each launch still unread
    in_batch: bool = False       # in the decode batch: joined, and has
    eos_seen: bool = False       #  not left it by count or by retiring
    depth: int = 0               # cache positions the lane holds
    produced: List[int] = dataclasses.field(default_factory=list)
    # speculative bookkeeping: the request's full known token history
    # (prefix + prompt + emitted — the self-draft corpus) and the
    # per-request accept-rate numerators the terminal event banks
    history: List[int] = dataclasses.field(default_factory=list)
    drafted: int = 0
    accepted: int = 0


def recurrent_lane_bytes(make_cache) -> int:
    """Bytes of the leaves of ONE lane of a decoder's cache tree that hold
    no positions (their shape does not follow ``max_len``): a recurrent
    state, as against K/V. 0 for a decoder that keeps K/V alone. Shapes
    only, nothing is allocated."""
    short, long = (jax.tree_util.tree_leaves(jax.eval_shape(
        lambda n=n: make_cache(1, n))) for n in (8, 16))
    return sum(a.size * a.dtype.itemsize for a, b in zip(short, long)
               if a.shape == b.shape)


def _k_leaves(make_cache, lane_len: int, **form) -> list:
    """Each attention layer's K leaf of ONE lane of ``lane_len`` positions
    (its shape and dtype: nothing is allocated), in tree order."""
    leaves = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: make_cache(1, lane_len, **form)))[0]
    return [x for path, x in leaves
            if path and getattr(path[-1], "key", None) == "k"]


def kv_leaf_rows(make_cache, lane_len: int) -> List[int]:
    """Rows of each attention layer's K leaf of ONE lane of ``lane_len``
    positions, in tree order: ``lane_len`` for a leaf that holds every
    position, fewer for a ring (a sliding-window layer's). Shapes only,
    nothing is allocated."""
    return [x.shape[1] for x in _k_leaves(make_cache, lane_len)]


def fetch_ahead(lanes: List[int], blocks: List[int], depth: int) -> int:
    """Of the blocks one `ops.decode_attend` call fetches (``blocks[i]``
    for the live lane ``lanes[i]``, in lane order), those whose fetch an
    EARLIER lane's grid step starts: the kernel keeps ``depth`` fetches
    of the call's one sequence in flight, so a lane's first ``depth - 1``
    blocks are started before its own step, unless it is lane 0 (whose
    step starts the queue)."""
    ahead = [min(n, depth - 1) for n in blocks]
    return sum(ahead) - (ahead[0] if lanes and lanes[0] == 0 else 0)


class Engine:
    """Continuous-batching engine over a ``(apply_fn, make_cache)``
    decoder pair (`models.generate.gpt2_decoder` / `llama_decoder`).

    Drive it with `submit` + `step`/`run`; finished requests appear in
    `results`. One `step()` = retire (deadline/cancel) → admit queued
    requests into free slots (chunked prefill) → one pooled decode (or
    speculative verify) step; with an ``eos_id`` the tokens a `step()`
    hands out are those of the launch BEFORE its own (`_decode_step`),
    so keep stepping while a request is unfinished (`run` does).
    ``metrics`` collects the full lifecycle
    (`ServingMetrics`). ``draft_propose(history, k) -> k ints`` plugs a
    custom draft source into speculative mode (default: n-gram
    prompt-lookup self-drafting, zero extra params).
    """

    def __init__(self, apply_fn: Callable, make_cache: Callable, params,
                 config: Optional[EngineConfig] = None, *,
                 metrics_logger: Optional[MetricsLogger] = None,
                 cache_dtype=None,
                 draft_propose: Optional[Callable] = None,
                 lora_head=None):
        self.cfg = cfg = config or EngineConfig()
        self.params = params
        self._apply_fn = apply_fn
        self._spec = cfg.num_draft > 0
        self._state_lane_bytes = recurrent_lane_bytes(make_cache)
        # a decoder with experts says how many it holds, summed over its
        # sparse layers, and can be asked for a launch's routing counts
        self._moe_slots = int(getattr(apply_fn, "moe_expert_slots", 0))
        if self._state_lane_bytes:
            missing = [why for asked, why in (
                (cfg.prefix_cache,
                 "prefix_cache=True (a prefix page needs a snapshot of the "
                 "state AT the share point; the lane is snapshotted after "
                 "the last chunk, and a later state does not hold an "
                 "earlier one)"),
                (cfg.num_draft > 0,
                 "num_draft > 0 (a rejected draft has advanced the state, "
                 "and a state is no list of positions to roll back)"),
                (cfg.paged,
                 "paged=True (the paged pool holds pages of positions and "
                 "has no place for a leaf without them)")) if asked]
            if missing:
                raise ValueError(
                    "this decoder's cache carries recurrent state, which "
                    "the engine cannot serve with " + "; ".join(missing))
        # multi-tenant LoRA (cfg.lora_rank > 0): the adapter-page store
        # rides beside the KV pool, and the executables recompute the
        # head matmul from the decoder's HIDDEN states (apply_fn must
        # accept return_hidden=True — llama_decoder does) so the paged
        # adapter delta fuses into the logits epilogue. lora_head is
        # the model's OWN (V, H) LM-head param (e.g. params["output"]);
        # the executable applies the model's exact einsum to it, so a
        # LoRA-off slot's logits are the model's logits verbatim.
        self._lora = self._lora_head = None
        if cfg.lora_rank > 0:
            if lora_head is None:
                raise ValueError(
                    "lora_rank > 0 requires lora_head= (the model's "
                    "(vocab, hidden) LM-head weight)")
            from apex1_tpu.serving.lora import LoraAdapterStore
            V, H = lora_head.shape
            self._lora = LoraAdapterStore(H, V, cfg.lora_rank,
                                          cfg.lora_max_adapters)
            self._lora_head = lora_head
        # the pool carries slack positions past the usable max_len: the
        # FINAL prefill chunk is right-padded to the full chunk width,
        # so its write can extend up to prefill_chunk-1 past the last
        # real token — without the slack, `dynamic_update_slice` would
        # CLAMP the start index and silently shift the whole chunk onto
        # earlier K/V (the same hazard generate()'s capacity check
        # guards). A speculative verify writes num_draft+1 entries at
        # the current index the same way, so the slack is the max of
        # the two write widths minus one. The pad/rejected K/V in the
        # slack is masked (never attended) and overwritten by later
        # writes; max_len itself stays the admission contract. A lane
        # is a whole number of the step kernel's blocks
        # (`ops.decode_attend`): 1151 positions are stored as 1152.
        slack = max(cfg.prefill_chunk, cfg.num_draft + 1) - 1
        lane_len = -(-(cfg.max_len + slack) // DECODE_BLOCK) * DECODE_BLOCK
        self._lane_blocks = lane_len // DECODE_BLOCK
        # leaves of two lengths: a sliding-window layer's is a ring
        self._kv_rows = kv_leaf_rows(make_cache, lane_len)
        rings = sorted({n for n in self._kv_rows if n < lane_len})
        self._window = (int(getattr(apply_fn, "sliding_window", 0))
                        if rings else 0)
        if rings:
            if not self._window:
                raise ValueError(
                    f"this decoder's cache has K/V leaves of {rings} rows "
                    f"beside {lane_len}: a ring, whose decoder names its "
                    f"window (apply_fn.sliding_window)")
            missing = [why for asked, why in (
                (cfg.prefix_cache,
                 "prefix_cache=True (a ring has forgotten the positions "
                 "before its window, which a sharer at a shorter length "
                 "would attend)"),
                (cfg.num_draft > 0,
                 "num_draft > 0 (a rejected draft's rows have overwritten "
                 "positions that the roll-back would attend again)"),
                (cfg.paged,
                 "paged=True (the paged pool holds pages of every "
                 "position and has no ring)"),
                (cfg.prefill_chunk > rings[0] - self._window,
                 f"prefill_chunk={cfg.prefill_chunk} (a ring of {rings[0]} "
                 f"rows under a window of {self._window} has "
                 f"{rings[0] - self._window} rows of slack: a longer "
                 f"chunk overwrites rows its own queries attend)"))
                if asked]
            if missing:
                raise ValueError(
                    "this decoder's cache holds sliding-window rings, "
                    "which the engine cannot serve with "
                    + "; ".join(missing))
        if cache_dtype is None:
            cache_dtype = cfg.cache_dtype    # kwarg (degraded-mode
        #                                      restarts) beats config
        self._paged = bool(cfg.paged)
        if self._paged:
            self.kv = PagedKVPool(
                make_cache, cfg.max_slots, lane_len,
                page_size=self._resolve_page_size(make_cache,
                                                  cache_dtype),
                dtype=cache_dtype, max_pages=cfg.max_prefix_pages)
            # device mirror of the host block tables, patched at
            # admission/retire boundaries only (like the control
            # vectors below) — the steady-state decode chain feeds it
            # back without host traffic. Freed rows reset to the trash
            # page so an inactive lane's masked-garbage scatter can
            # never land on a page a NEW request now owns.
            self._d_bt = jnp.zeros(
                (cfg.max_slots, self.kv.pages_per_lane), jnp.int32)
        else:
            self.kv = KVPool(make_cache, cfg.max_slots, lane_len,
                             dtype=cache_dtype,
                             max_pages=cfg.max_prefix_pages)
        self.scheduler = Scheduler(max_queue=cfg.max_queue,
                                   policy=cfg.policy)
        self.metrics = ServingMetrics(metrics_logger)
        self.results: Dict[int, RequestResult] = {}
        self.trace_counts = ({"prefill": 0, "verify": 0} if self._spec
                             else {"prefill": 0, "decode": 0})
        self._slots: List[Optional[_Slot]] = [None] * cfg.max_slots
        self._draft_propose = draft_propose or (
            lambda hist, k: ngram_propose(hist, k,
                                          max_ngram=cfg.max_ngram))
        # device-resident control vectors, patched in place at
        # join/leave boundaries — the steady-state step chain re-feeds
        # the previous step's outputs without ever touching the host.
        # seeds/pos drive the per-request counter-based sampling keys:
        # token i of a request is fold_in(key(seed), i), whatever slot,
        # step, or engine instance computes it
        self._d_toks = jnp.zeros((cfg.max_slots,), jnp.int32)
        self._d_idxs = jnp.zeros((cfg.max_slots,), jnp.int32)
        self._d_active = jnp.zeros((cfg.max_slots,), bool)
        self._d_seeds = jnp.zeros((cfg.max_slots,), jnp.int32)
        self._d_pos = jnp.zeros((cfg.max_slots,), jnp.int32)
        if self._lora is not None:
            # per-slot adapter block-table row + on-flag, patched at the
            # same join/leave boundaries as the control vectors. All-
            # zero rows name the zero page (exact 0.0 delta), so the
            # flag only guards the `logits + delta` add against -0.0
            # drift on adapterless rows — one executable either way.
            self._d_lora_bt = jnp.zeros(
                (cfg.max_slots, cfg.lora_rank), jnp.int32)
            self._d_lora_on = jnp.zeros((cfg.max_slots,), bool)
        self._n_active = 0
        # running totals a `serving/step` span reports its step's share
        # of; "control_dispatches" counts every device program launched
        # outside the two executables (`_patch`, lane snapshots, draft
        # uploads)
        self._tally = dict.fromkeys(
            ("admitted", "retired", "prefill_chunks", "prefill_tokens",
             "tokens_out", "control_dispatches", "kv_blocks_read",
             "kv_blocks_pool", "kv_fetch_ahead", "ran_ahead",
             "overrun_lanes"), 0)
        # the fetches the step kernel keeps in flight over this pool's
        # rows (their bytes alone: `ops.decode_attend.fetch_depth`)
        row = next(iter(_k_leaves(make_cache, lane_len, **(
            {} if cache_dtype is None else {"dtype": cache_dtype}))), None)
        self._fetch_depth = (2 if row is None else
                             fetch_depth(row.shape[-1], row.dtype))
        if self._state_lane_bytes:
            self._tally.update(state_lanes=0, state_bytes=0)
        if self._window:
            self._tally.update(kv_blocks_read_window=0, kv_layers=0)
            # what a launch's counts need of the tree, once: the sliding
            # layers, and the blocks the pool's K leaves hold
            self._ring_layers = sum(n < lane_len for n in self._kv_rows)
            self._pool_blocks = (cfg.max_slots * sum(self._kv_rows)
                                 // DECODE_BLOCK)
        # eos_id=None: retirement is length-based, so step tokens are
        # only READ at retirement — the log keeps each step's (N,)
        # output (device array until first fetch memoizes it as numpy).
        # Speculative mode always reads back (drafting needs history).
        self._defer = cfg.eos_id is None and not self._spec
        # the routing counts come with the tokens, where those are read
        # step by step: the dense pool's plain step with an eos_id
        self._moe_read = (bool(self._moe_slots) and not self._defer
                          and not self._spec and not self._paged
                          and self._lora is None)
        if self._moe_read:
            self._tally.update(moe_rows=0, moe_experts_touched=0,
                               moe_expert_slots=0)
        self._tok_log: Dict[int, object] = {}
        # with an eos_id the loop runs AHEAD of the read (`_decode_step`):
        # the launches whose tokens the host has not read yet, oldest
        # first, each ``(tokens on the device, {lane: its _Slot at that
        # launch})``
        self._inflight: List[tuple] = []
        self._step_no = 0
        # the mid-admission cancel window: `cancel` from an ingest
        # thread while `_admit` runs this request's prefill chain. The
        # lock serializes the flag handshake (check+add vs clear+read)
        # — without it a cancel that passed the _mid_admit check could
        # land its _cancel_mid entry just after _admit drained the set,
        # returning True for a cancel that never happens (review
        # finding)
        self._mid_admit: Optional[int] = None
        self._cancel_mid: set = set()
        self._admit_lock = threading.Lock()
        # prefix-aware admission probe memo, invalidated whenever the
        # page store changes (bounded by the queue: one bool per
        # queued request per store version)
        self._probe_cache: Dict[int, bool] = {}
        self._probe_cache_ver = -1
        self._sample_kw = dict(temperature=cfg.temperature,
                               top_k=cfg.top_k, vocab_size=cfg.vocab_size)
        self._build_executables()

    def _resolve_page_size(self, make_cache, cache_dtype) -> int:
        """Page-size precedence: explicit config > tuning-table winner
        (keyed on the decoder's padded head dim at the S=1 decode row
        class) > chunk-width heuristic (sublane-aligned, and one
        prefill chunk never spans more than two pages)."""
        cfg = self.cfg
        if cfg.page_size is not None:
            return int(cfg.page_size)
        from apex1_tpu import tuning
        kw = {} if cache_dtype is None else {"dtype": cache_dtype}
        probe = jax.tree_util.tree_leaves(
            make_cache(1, 1, page_form=True, **kw))[0]
        tuned = tuning.lookup(
            "paged_decode",
            {"Dp": tuning.padded_lanes(probe.shape[-1]), "Rq": 8},
            probe.dtype)
        if tuned is not None:
            return int(tuned["page_p"])
        return max(8, -(-cfg.prefill_chunk // 8) * 8)

    def _sync_bt(self, slot: int) -> None:
        """Push one slot's host block-table row to the device mirror —
        called wherever the host row changes (alloc, prefix acquire,
        free), never on the step path."""
        self._d_bt = self._patch(
            self._d_bt, slot,
            jnp.asarray(self.kv.block_tables[slot], jnp.int32))

    def _patch(self, vec, slot: int, value):
        """``vec`` with ``value`` at ``slot`` — the engine's ONE eager
        write to a device control vector: a device program of its own
        (its operand rides along), so it is counted, a step's
        ``control_dispatches``."""
        self._tally["control_dispatches"] += 1
        return vec.at[slot].set(value)

    # ---- the two executables -------------------------------------------

    def _model_calls(self):
        """``(forward, lora_row, score, accept)`` — the model call,
        the LoRA epilogue, and the step body that the dense executables
        and the off-TPU paged ones (the parity gold) both run, so that
        their token parity is structural.

        ``score`` is the whole of a decode or verify step over a dense
        cache tree (slot on axis 0): ONE batch-N chunk forward with a
        per-row cache index, -1 for a lane that is not ``active``
        (`cached_attention` appends a live row's K/V at its index and
        leaves an idle lane alone), then every row's
        canonical samples. Only what is per row BY CONTRACT is vmapped:
        the counter-keyed sampling (`counter_sample`) and the LoRA
        epilogue row. A plain decode step is the ``S = 1`` chunk."""
        cfg = self.cfg
        apply_fn = self._apply_fn
        lora = self._lora is not None
        head = self._lora_head
        sample_kw = self._sample_kw

        # LoRA epilogue leg (static — baked at build time like the
        # paged kernel_path): the forward returns HIDDEN states, the
        # body replays the model's exact head einsum, and the paged
        # adapter delta lands before sampling. `jnp.where(on, ...)`
        # rather than an unconditional add: the zero page makes an off
        # row's delta exactly 0.0, but `x + 0.0` can still flip -0.0
        # logits, and off rows must be BITWISE the base model's.
        def forward(params, tokens, cache, idx, moe_counts=False, **kw):
            """``(logits, hidden, cache)``; hidden is None without LoRA.
            With ``moe_counts`` (a decoder with experts, asked by the
            dense step alone) a fourth: the launch's routing counts."""
            if moe_counts:
                logits, cache, counts = apply_fn(params, tokens, cache, idx,
                                                 moe_counts=True, **kw)
                return logits, None, cache, counts
            if not lora:
                logits, cache = apply_fn(params, tokens, cache, idx, **kw)
                return logits, None, cache
            h, cache = apply_fn(params, tokens, cache, idx,
                                return_hidden=True, **kw)
            logits = jnp.einsum("bsh,vh->bsv", h, head.astype(h.dtype),
                                preferred_element_type=jnp.float32)
            return logits, h, cache

        def lora_row(logits, h, a_pg, b_pg, lrow, on):
            # one slot's (S, V) logits and (S, H) hidden rows
            from apex1_tpu.ops.lora_epilogue import _lora_delta_ref
            bt = jnp.broadcast_to(lrow[None, :],
                                  (h.shape[0], lrow.shape[0]))
            delta = _lora_delta_ref(h, a_pg, b_pg, bt)
            return jnp.where(on, logits + delta.astype(logits.dtype),
                             logits)

        def score(params, cache, chunks, idxs, active, seeds, pos,
                  a_pg=None, b_pg=None, lbt=None, lon=None, *,
                  moe_counts=False):
            steps = jnp.arange(chunks.shape[1], dtype=jnp.int32)
            logits, h, cache, *counts = forward(
                params, chunks, cache, jnp.where(active, idxs, -1),
                positions=idxs[:, None] + steps, chunk_decode=True,
                moe_counts=moe_counts)
            if lora:
                logits = jax.vmap(
                    lora_row, in_axes=(0, 0, None, None, 0, 0))(
                        logits, h, a_pg, b_pg, lbt, lon)
            # the target's CANONICAL stream at output positions
            # p..p+S-1, token i keyed fold_in(key(seed), i) — exact-match
            # acceptance means emitted tokens are these samples
            # verbatim, so speculation cannot perturb the (params,
            # prompt, seed) purity resubmission rides
            with region("head"):
                tgt = jax.vmap(lambda lg, seed, p: counter_sample(
                    lg, seed, p + steps, **sample_kw))(logits, seeds, pos)
            return (tgt, cache, *counts)

        def accept(tgt, drafts, active, idxs, pos):
            """Longest draft prefix equal to the target's own samples,
            per slot: ``(acc, nxt, idxs, pos)`` after the round."""
            acc = jnp.sum(jnp.cumprod(
                (tgt[:, :-1] == drafts).astype(jnp.int32), axis=1),
                axis=1)
            acc = jnp.where(active, acc, 0)
            adv = jnp.where(active, acc + 1, 0)
            nxt = jnp.where(
                active,
                jnp.take_along_axis(tgt, acc[:, None], 1)[:, 0],
                cfg.pad_id)
            return acc, nxt, idxs + adv, pos + adv

        return forward, lora_row, score, accept

    def _build_executables(self):
        if self._paged:
            return self._build_paged_executables()
        cfg = self.cfg
        C = cfg.prefill_chunk
        lora = self._lora is not None
        sample_kw = self._sample_kw
        forward, lora_row, score, accept = self._model_calls()
        recurrent = bool(self._state_lane_bytes)
        moe = self._moe_read
        # the traced bodies hold the counter, not the engine: an engine
        # is in no reference cycle, so its pool and weights are freed
        # with its last reference, without the cycle collector
        trace_counts = self.trace_counts

        def prefill(params, pool, slot, init_lane, install, tokens, idx,
                    n_real, seed, a_pg=None, b_pg=None, lbt=None,
                    lon=None):
            trace_counts["prefill"] += 1   # the compile-count hook
            lane = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, slot, 1, 0),
                pool)
            lane = jax.tree_util.tree_map(
                lambda cur, ini: jnp.where(install, ini, cur), lane,
                init_lane)
            positions = (jnp.asarray(idx, jnp.int32)
                         + jnp.arange(C, dtype=jnp.int32))[None]
            # K/V written for the chunk's pad tokens lies past the
            # horizon; a recurrent state is told which tokens are real
            logits, h, lane = forward(
                params, tokens, lane, idx, positions=positions,
                chunk_decode=True,
                **({"n_real": n_real} if recurrent else {}))
            pool = jax.tree_util.tree_map(
                lambda p, l: jax.lax.dynamic_update_slice_in_dim(
                    p, l.astype(p.dtype), slot, 0), pool, lane)
            lg = last_real_logits(logits, n_real[None])
            if lora:
                # the slot's adapter row, gathered at the same traced
                # index discipline as everything else in this body
                lrow = jax.lax.dynamic_slice_in_dim(lbt, slot, 1, 0)[0]
                on = jax.lax.dynamic_slice_in_dim(lon, slot, 1, 0)[0]
                lg = lora_row(lg, last_real_logits(h, n_real[None]),
                              a_pg, b_pg, lrow, on)
            # output token 0's counter-based key (re-seeding per draw
            # is the counter-PRNG contract — see ops.stochastic)
            with region("head"):
                key = jax.random.fold_in(jax.random.key(seed), 0)
                tok = sample_token(lg, key, **sample_kw)[0]
            return tok, pool

        def decode(params, pool, toks, idxs, active, seeds, pos,
                   *lora_args):
            trace_counts["decode"] += 1    # the compile-count hook
            tgt, pool, *counts = score(params, pool, toks[:, None], idxs,
                                       active, seeds, pos, *lora_args,
                                       moe_counts=moe)
            nxt = jnp.where(active, tgt[:, 0], cfg.pad_id)
            adv = active.astype(jnp.int32)
            # a decoder with experts: the launch's routing counts ride
            # behind the tokens, in the one array the host reads
            read = [jnp.concatenate([nxt, *counts])] if moe else []
            return (nxt, idxs + adv, pos + adv, *read, pool)

        def verify(params, pool, toks, idxs, active, seeds, pos,
                   drafts, *lora_args):
            trace_counts["verify"] += 1    # the compile-count hook
            tgt, pool = score(
                params, pool, jnp.concatenate([toks[:, None], drafts], 1),
                idxs, active, seeds, pos, *lora_args)
            return (tgt, *accept(tgt, drafts, active, idxs, pos), pool)

        # the pool is donated, on every backend (the CPU tests run the
        # same aliasing the chip does). "In place" for the step means:
        # each leaf's output IS its input buffer, and on the chip the
        # one kernel a layer that attends over the two leaves is also
        # the only instruction that writes them, a window of rows
        # around each live lane's new ones - no copy of a leaf, no leaf
        # rewritten, no loop over slots. `tests/test_engine_aot.py`
        # compiles all three for a v5e at the chat cell's shapes and
        # holds exactly that. Prefill moves one lane, not a leaf.
        self._jit_step(prefill, verify if self._spec else decode)

    def _jit_step(self, prefill, step):
        """The two executables from their traced bodies. Each is called
        with the caller's tree, as its body is, and LAUNCHED with the
        tree's packed operands (`serving.packing`: the small leaves
        stacked by shape, once, here; every other leaf the caller's own
        buffer), because a launch costs the host by the operand; where
        the device's step hides the launch anyway the tree is handed
        over as it is. The pool is donated. ``_n_operands`` is the
        arrays a call of each hands over, reckoned here: the count its
        span carries."""
        pool = len(jax.tree_util.tree_leaves(
            self.kv.pages if self._paged else self.kv.cache))
        # beside the parameters a step hands over the pool, the adapters,
        # the block table, the five control vectors and a verify's drafts
        step_others = (pool + len(self._lora_args()) + self._paged + 5
                       + self._spec)
        self._packed = packed = PackedParams(self.params, step_others)
        # the launches the plain loop keeps in flight behind the read
        # (`_decode_step`): where the step in flight outlasts the next
        # launch the host waits for the device anyway and a second buys
        # nothing; where it does not, a second takes the launch and a
        # token's way to the host out of the gap between two tokens
        self._depth = 1 if packed.layout.hidden else 2
        self._prefill = Executable(prefill, packed)
        if self._spec:
            self._verify = Executable(step, packed)
        else:
            self._decode = Executable(step, packed)
        n = packed.layout.n_operands
        # a prefill: the block table or a lane to install; slot, install
        # flag, tokens, index, real tokens, seed
        self._n_operands = {
            "prefill": n + pool + len(self._lora_args())
            + (1 + 5 if self._paged else pool + 6),
            "step": n + step_others}

    def _build_paged_executables(self):
        """The paged-mode executables. Two shapes of the same contract:

        - **off-TPU (the parity gold)**: gather each slot's dense lane
          from its pages, run the UNCHANGED reference body (`score` of
          `_model_calls`: the same batch-N forward, the same per-row
          sampling ops as the dense executables), scatter only the
          written window back.
          Every position the reference attends or writes is
          bit-identical to the dense pool's lane — garbage beyond a
          row's horizon is masked to an exact zero either way — so
          token streams match the dense engine BITWISE, at any
          temperature, by construction (pinned in
          ``tests/test_paged_decode.py``).
        - **TPU / forced-pallas**: thread :class:`PagedCache` entries
          through ONE batch-N forward — the model's attention routes to
          the `ops.paged_decode.paged_attend` kernel (block-table page
          streaming, fused int8 dequant, per-row ragged horizons) and
          sampling collapses into the `fused_sample` epilogue kernel,
          so one token id per slot is all that crosses back per step.
          The path is selected at BUILD time (``use_pallas()``), so a
          forced-impl test must construct the engine inside
          ``ops.force_impl("pallas")``.
        """
        cfg = self.cfg
        C = cfg.prefill_chunk
        K = cfg.num_draft
        L = self.kv.lane_len
        lora = self._lora is not None
        sample_kw = self._sample_kw
        tree_map = jax.tree_util.tree_map
        kernel_path = use_pallas()
        forward, lora_row, score, accept = self._model_calls()
        trace_counts = self.trace_counts   # not the engine: no cycle

        def lora_batch(logits, h, a_pg, b_pg, lbt, lon):
            # (N, V) logits + (N, H) hidden rows -> epilogue delta via
            # the scalar-prefetched page-gather kernel (composite gold
            # off-TPU); rows are independent, so mixed-tenant batches
            # stay bitwise equal to solo runs
            from apex1_tpu.ops.lora_epilogue import lora_delta
            delta = lora_delta(h, a_pg, b_pg, lbt)
            return jnp.where(lon[:, None],
                             logits + delta.astype(logits.dtype),
                             logits)

        def window(lane, start, width):
            # the (N, width, Hkv * D) rows the model just wrote at each
            # row's index — the only slice scatter-back needs
            pos = (start[:, None]
                   + jnp.arange(width, dtype=jnp.int32))[:, :, None]
            return jnp.take_along_axis(lane, pos, axis=1)

        def paged_cache(pages, bt):
            return {layer: PagedCache(entry["k"], entry["v"], bt, L)
                    for layer, entry in pages.items()}

        def unpack_cache(cache):
            return {layer: {"k": pc.k_pages, "v": pc.v_pages}
                    for layer, pc in cache.items()}

        def score_lanes(params, pages, bt, chunks, idxs, active, seeds,
                        pos, *lora_args):
            # the parity gold: dense lanes out of the pages, the dense
            # engine's own step body, the written window back.
            # Inactive rows (block-table = trash page) scatter their
            # masked garbage into page 0 — harmless, never attended,
            # never owned
            lanes = tree_map(lambda p: gather_pages(p, bt, L), pages)
            tgt, lanes = score(params, lanes, chunks, idxs, active,
                               seeds, pos, *lora_args)
            pages = tree_map(
                lambda pg, ln: scatter_pages(
                    pg, bt, window(ln, idxs, chunks.shape[1]), idxs),
                pages, lanes)
            return tgt, pages

        def prefill(params, pages, bt, slot, tokens, idx, n_real, seed,
                    a_pg=None, b_pg=None, lbt=None, lon=None):
            trace_counts["prefill"] += 1   # the compile-count hook
            bt_row = jax.lax.dynamic_slice_in_dim(bt, slot, 1, 0)
            positions = (jnp.asarray(idx, jnp.int32)
                         + jnp.arange(C, dtype=jnp.int32))[None]
            if kernel_path:
                logits, h, cache = forward(
                    params, tokens, paged_cache(pages, bt_row), idx,
                    positions=positions, chunk_decode=True)
                pages = unpack_cache(cache)
            else:
                lane = tree_map(lambda p: gather_pages(p, bt_row, L),
                                pages)
                logits, h, lane = forward(params, tokens, lane, idx,
                                          positions=positions,
                                          chunk_decode=True)
                idx_v = jnp.asarray(idx, jnp.int32)[None]
                pages = tree_map(
                    lambda pg, ln: scatter_pages(
                        pg, bt_row, window(ln, idx_v, C), idx_v),
                    pages, lane)
            # there is no install step: a prefix hit ARRIVES as shared
            # page ids in the block table (reference, not copy), and a
            # fresh slot's recycled-page garbage sits beyond the
            # attention horizon — exactly like the dense pool's masked
            # slack
            lg = last_real_logits(logits, n_real[None])
            if lora:
                lrow = jax.lax.dynamic_slice_in_dim(lbt, slot, 1, 0)[0]
                on = jax.lax.dynamic_slice_in_dim(lon, slot, 1, 0)[0]
                lg = lora_row(lg, last_real_logits(h, n_real[None]),
                              a_pg, b_pg, lrow, on)
            with region("head"):
                tok = fused_sample(lg, jnp.asarray(seed, jnp.int32)[None],
                                   jnp.zeros((1,), jnp.int32),
                                   **sample_kw)[0]
            return tok, pages

        def decode(params, pages, bt, toks, idxs, active, seeds, pos,
                   *lora_args):
            trace_counts["decode"] += 1    # the compile-count hook
            if kernel_path:
                logits, h, cache = forward(
                    params, toks[:, None], paged_cache(pages, bt), idxs,
                    positions=idxs[:, None])
                lg = logits[:, -1]
                if lora:
                    lg = lora_batch(lg, h[:, -1], *lora_args)
                pages = unpack_cache(cache)
                with region("head"):
                    nxt = fused_sample(lg, seeds, pos, **sample_kw)
            else:
                tgt, pages = score_lanes(params, pages, bt,
                                         toks[:, None], idxs, active,
                                         seeds, pos, *lora_args)
                nxt = tgt[:, 0]
            nxt = jnp.where(active, nxt, cfg.pad_id)
            adv = active.astype(jnp.int32)
            return nxt, idxs + adv, pos + adv, pages

        def verify(params, pages, bt, toks, idxs, active, seeds, pos,
                   drafts, *lora_args):
            trace_counts["verify"] += 1    # the compile-count hook
            chunks = jnp.concatenate([toks[:, None], drafts], 1)
            if kernel_path:
                positions = (idxs[:, None]
                             + jnp.arange(K + 1, dtype=jnp.int32)[None])
                logits, h, cache = forward(
                    params, chunks, paged_cache(pages, bt), idxs,
                    positions=positions, chunk_decode=True)
                if lora:
                    # flatten the (N, K+1) verify rows into the batch
                    # axis the paged delta kernel streams — each row
                    # repeats its slot's adapter block-table entry
                    a_pg, b_pg, lbt, lon = lora_args
                    logits = lora_batch(
                        logits.reshape(-1, logits.shape[-1]),
                        h.reshape(-1, h.shape[-1]), a_pg, b_pg,
                        jnp.repeat(lbt, K + 1, axis=0),
                        jnp.repeat(lon, K + 1, axis=0)
                    ).reshape(logits.shape)
                pages = unpack_cache(cache)
                posm = (pos[:, None]
                        + jnp.arange(K + 1, dtype=jnp.int32)[None])
                seedm = jnp.broadcast_to(seeds[:, None], posm.shape)
                V = logits.shape[-1]
                with region("head"):
                    tgt = fused_sample(
                        logits.reshape(-1, V), seedm.reshape(-1),
                        posm.reshape(-1),
                        **sample_kw).reshape(-1, K + 1)
            else:
                tgt, pages = score_lanes(params, pages, bt, chunks,
                                         idxs, active, seeds, pos,
                                         *lora_args)
            return (tgt, *accept(tgt, drafts, active, idxs, pos), pages)

        self._jit_step(prefill, verify if self._spec else decode)

    # ---- multi-tenant LoRA adapters -------------------------------------

    def register_adapter(self, tenant: str, A, B, *,
                         scale: float = 1.0):
        """Install ``tenant``'s LM-head adapter (``A`` (H, r), ``B``
        (r, V)); subsequent ``submit(tenant=...)`` requests decode
        through it. Two-phase page publish (`serving.lora`) — safe to
        call while the engine is serving."""
        if self._lora is None:
            raise RuntimeError(
                "register_adapter requires EngineConfig(lora_rank > 0)")
        return self._lora.register(tenant, A, B, scale=scale)

    def unregister_adapter(self, tenant: str) -> None:
        """Retire ``tenant``'s adapter. In-flight requests keep their
        pinned pages until retirement; new submits with this tenant
        decode adapterless (zero row)."""
        if self._lora is None:
            raise RuntimeError(
                "unregister_adapter requires "
                "EngineConfig(lora_rank > 0)")
        self._lora.unregister(tenant)

    def _lora_release(self, slot: int) -> None:
        """Unpin a slot's adapter pages and zero its device row (the
        LoRA analogue of the trash-page reset: the freed lane keeps
        computing, so its row must stop naming live adapter pages)."""
        if self._lora is None:
            return
        self._lora.release(slot)
        self._d_lora_bt = self._patch(
            self._d_lora_bt, slot,
            jnp.zeros((self.cfg.lora_rank,), jnp.int32))
        self._d_lora_on = self._patch(self._d_lora_on, slot, False)

    # ---- submission -----------------------------------------------------

    def submit(self, tokens, max_new_tokens: int, *, prefix=None,
               deadline: Optional[float] = None,
               req_id: Optional[int] = None,
               qos: str = "best_effort", tenant: Optional[str] = None,
               seed: Optional[int] = None) -> int:
        """Enqueue a request. Raises `Backpressure` when the queue is
        full and holds no weaker-class victim to shed (the caller's
        429, with ``retry_after_s``/``queue_depth`` attached) and
        `ValueError` when the request can NEVER fit (prefix + prompt +
        max_new_tokens - 1 > max_len — not backpressure, a contract
        violation). ``seed`` pins the request's sampling stream; None
        derives one from the request id (stable across resubmission)."""
        req = Request(tokens=tokens, max_new_tokens=max_new_tokens,
                      prefix=prefix, deadline=deadline, req_id=req_id,
                      qos=qos, tenant=tenant, seed=seed)
        if req.seed is None:
            req.seed = derive_request_seed(self.cfg.seed, req.req_id)
        if req.total_len > self.cfg.max_len:
            raise ValueError(
                f"request needs {req.total_len} cache positions but "
                f"slots hold max_len={self.cfg.max_len}")
        try:
            rid = self.scheduler.submit(req)
        except Backpressure as e:
            self.metrics.event(req.req_id, "queued",
                               n_prompt=req.tokens.size)
            self.metrics.event(req.req_id, "rejected", reason=e.reason)
            raise
        # a weaker-class request may have been shed to admit this one
        for victim in self.scheduler.drain_shed():
            self.metrics.incr("sheds")
            self._finish(victim.req_id, "evicted",
                         f"shed ({victim.qos})", [])
        self.metrics.event(rid, "queued", n_prompt=req.tokens.size)
        return rid

    def cancel(self, req_id: int) -> bool:
        """Cancel a queued OR running request. A running request is
        retired IMMEDIATELY: its KV slot and any refcounted
        shared-prefix page are released before this returns, not at
        the next step boundary — a frontend cancelling a hedge loser
        (or shedding load) must get the capacity back now, and an idle
        engine that is never stepped again must not leak the slot. A
        request whose ADMISSION is being built right now (an ingest
        thread racing the engine loop's prefill chain) is flagged and
        retired the moment the chain completes."""
        if self.scheduler.cancel(req_id):
            self._finish(req_id, "cancelled", "cancelled queued", [])
            return True
        with self._admit_lock:
            if req_id == self._mid_admit:
                self._cancel_mid.add(req_id)
                return True
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.req.req_id == req_id:
                self._retire(i, "cancelled", "cancelled running")
                return True
        return False

    # ---- the engine loop ------------------------------------------------

    def step(self) -> int:
        """One engine iteration: retire (deadline/cancel) → admit → one
        decode (or speculative verify) step over every occupied slot.
        Returns the number of lanes the step was launched for (0 = none:
        such a call hands out what the oldest launch in flight owes, and
        the engine is idle once it leaves none, see `_decode_step`)."""
        with spine.span("serving/step") as sp:
            before = dict(self._tally)
            with spine.span("serving/expire"):
                now = time.monotonic()
                for req in self.scheduler.expire(now):
                    self._finish(req.req_id, "evicted",
                                 "deadline (queued)", [])
                for i, slot in enumerate(self._slots):
                    if slot is None:
                        continue
                    if (slot.req.deadline is not None
                            and slot.req.deadline <= now):
                        self._retire(i, "evicted", "deadline")
            self._admit_all()
            n_active = self._n_active
            if self._spec:
                if n_active:
                    self._spec_step()
            elif n_active or self._inflight:
                self._decode_step()
            depth = self.scheduler.depth
            self.metrics.step_sample(n_active, self.cfg.max_slots, depth)
            sp.counts = {k: v - before[k] for k, v in self._tally.items()}
            sp.counts.update(n_active=n_active, queue_depth=depth)
        return n_active

    def _lora_args(self) -> tuple:
        """The adapter-page operands appended to every executable call
        when LoRA is enabled — page pools + per-slot block-table rows,
        all device-resident (the step path stays host-free)."""
        if self._lora is None:
            return ()
        return (self._lora.a_pages, self._lora.b_pages,
                self._d_lora_bt, self._d_lora_on)

    def _count_kv_blocks(self, width: int):
        """Tally what the step's attention has to move, in blocks of
        `DECODE_BLOCK` positions: each lane of the batch up to the
        horizon of its ``width`` new tokens, of the blocks the pool
        holds, and ``kv_fetch_ahead``: those of them whose fetch the
        kernel's queue starts from an earlier lane's grid step
        (`fetch_ahead`). Host arithmetic on the slots' depths, no device
        read. With rings in the pool, all are sums over the attention
        layers: a sliding layer reads the blocks that hold its window
        (what `ops.decode_attend` walks), of those its ring has."""
        lanes = [i for i, st in enumerate(self._slots)
                 if st is not None and st.in_batch]
        upto = [-(-(self._slots[i].depth + width) // DECODE_BLOCK)
                for i in lanes]
        ahead = fetch_ahead(lanes, upto, self._fetch_depth)
        if not self._window:
            self._tally["kv_blocks_pool"] += (self.cfg.max_slots
                                              * self._lane_blocks)
            self._tally["kv_blocks_read"] += sum(upto)
            self._tally["kv_fetch_ahead"] += ahead
            return
        held = [n - max(self._slots[i].depth - self._window + 1, 0)
                // DECODE_BLOCK for i, n in zip(lanes, upto)]
        in_window = self._ring_layers * sum(held)
        n_layers = len(self._kv_rows)
        self._tally["kv_blocks_pool"] += self._pool_blocks
        self._tally["kv_blocks_read"] += (
            (n_layers - self._ring_layers) * sum(upto) + in_window)
        self._tally["kv_blocks_read_window"] += in_window
        self._tally["kv_fetch_ahead"] += (
            (n_layers - self._ring_layers) * ahead + self._ring_layers
            * fetch_ahead(lanes, held, self._fetch_depth))
        self._tally["kv_layers"] += n_layers

    def _launch(self) -> tuple:
        """Dispatch the step executable for the lanes in the batch and
        feed its outputs to the next one on the device. Returns
        ``(tokens on the device, {lane: _Slot})``, the lanes being those
        the step runs for."""
        self._count_kv_blocks(1)
        lanes = {i: st for i, st in enumerate(self._slots)
                 if st is not None and st.in_batch}
        if self._state_lane_bytes:
            self._tally["state_lanes"] += len(lanes)
            self._tally["state_bytes"] += (2 * len(lanes)
                                           * self._state_lane_bytes)
        with spine.span("serving/decode_step",
                        operands=self._n_operands["step"]):
            if self._paged:
                nxt, idxs, pos, self.kv.pages = self._decode(
                    self.params, self.kv.pages, self._d_bt,
                    self._d_toks, self._d_idxs, self._d_active,
                    self._d_seeds, self._d_pos, *self._lora_args())
                read = nxt
            else:
                nxt, idxs, pos, *read, self.kv.cache = self._decode(
                    self.params, self.kv.cache, self._d_toks,
                    self._d_idxs, self._d_active, self._d_seeds,
                    self._d_pos, *self._lora_args())
                # what the host reads: the tokens, and behind them the
                # routing counts of a decoder with experts
                read = read[0] if read else nxt
            self._d_toks, self._d_idxs, self._d_pos = nxt, idxs, pos
            if not self._defer:
                # the tokens start for the host now, behind the step on
                # the device, and not when the host comes to ask
                read.copy_to_host_async()
                for i, st in lanes.items():
                    st.n_launched += 1
                    if st.n_launched >= st.req.max_new_tokens:
                        # leaves by COUNT, no token value needed: out of
                        # the batch before the next launch, so the lane
                        # never runs a step past its length. The request
                        # finishes where these tokens are read.
                        self._d_active = self._patch(self._d_active, i,
                                                     False)
                        st.in_batch = False
                        self._n_active -= 1
        for st in lanes.values():
            st.depth += 1
        self._step_no += 1
        return read, lanes

    def _decode_step(self):
        """One plain decode step. With an ``eos_id`` the loop runs AHEAD
        OF THE READ: this call launches a step and only then hands out
        the tokens of the OLDEST launch in flight, and only once
        ``_depth`` later launches stand behind it (1: launch n+1, read
        n; 2: launch n+2, read n). Their copy to the host was started
        at their own launch, so host and device work at the same time
        instead of in turns. A launch is fed from device arrays and
        needs nothing the host learns from the launches before it, but
        for this:

        A lane that sampled ``eos_id`` in step n has up to ``_depth``
        later steps in flight (OVERRUN lane-steps; a deadline or a
        cancel with launches in flight is the same case). The host drops
        those lane-steps' tokens (the lane's `_Slot` is no longer the
        one they were launched for, which holds for every launch in the
        queue) and their K/V rows land on the positions past the lane's
        end. Those rows harm nobody: every reader of the pool stops at
        its own horizon, an admission into the freed lane prefills from
        position 0 and patches the lane's index, token and output
        position, and the device runs its programs in the order they
        were launched, every step in flight before any later prefill
        into the lane (paged: before `_sync_bt` points the freed row at
        the trash page, and the row's own pages can have no new owner
        before that). The argument is the device's order, not the number
        of steps. They cannot run off the lane: a lane is launched for
        only while ``n_launched < max_new_tokens``, and `submit` holds
        the request's last position inside ``max_len``.

        What exists for the outside (``produced``, the ``token`` event,
        ``tokens_out``, a result) moves where the host READS a token,
        never where it is launched, and a call reads one launch's
        tokens, never two: a token's stamp is the return of the
        `step()` that read it, so a gap is one call long. A call with
        nothing to launch hands out what the oldest launch owes, and the
        engine is idle once such a call leaves nothing in flight."""
        if self._defer:
            nxt, lanes = self._launch()
            self._tok_log[self._step_no - 1] = nxt     # fetched at retire
            self._emit(lanes, None)
            return
        # a launch every lane of which has retired is dropped unread
        flight = self._inflight = [
            launch for launch in self._inflight
            if any(self._slots[i] is st for i, st in launch[1].items())]
        if self._n_active:
            self._tally["ran_ahead"] += len(flight)
            flight.append(self._launch())
            if len(flight) <= self._depth:
                return
        elif not flight:
            return
        toks, lanes = flight.pop(0)
        with spine.span("serving/read_tokens", wait=True):
            toks = np.asarray(toks)
        if self._moe_read:
            # of the launch whose tokens these are: as late as they
            rows, touched = toks[self.cfg.max_slots:]
            self._tally["moe_rows"] += int(rows)
            self._tally["moe_experts_touched"] += int(touched)
            self._tally["moe_expert_slots"] += self._moe_slots
        self._emit(lanes, toks)

    def _emit(self, lanes: dict, toks):
        """Hand out one launch's tokens, lane by lane, and retire what
        they finish. ``toks`` None: the values stay on the device (the
        deferred log) and only the counts move."""
        with spine.span("serving/emit"):
            for i, st in lanes.items():
                if self._slots[i] is not st:
                    continue        # retired with this step in flight
                st.n_out += 1
                self._tally["tokens_out"] += 1
                self.metrics.event(st.req.req_id, "token")
                if toks is not None:
                    tok = int(toks[i])
                    st.produced.append(tok)
                    st.history.append(tok)
                    if tok == self.cfg.eos_id:
                        st.eos_seen = True
                        self._tally["overrun_lanes"] += sum(
                            launch[1].get(i) is st
                            for launch in self._inflight)
                        self._retire(i, "done", "eos")
                        continue
                if st.n_out >= st.req.max_new_tokens:
                    self._retire(i, "done", "length")

    def _spec_step(self):
        """One draft → verify round for every occupied slot: the host
        proposes K tokens per slot from its own history, ONE verify
        dispatch scores all slots, and each slot emits its accepted
        prefix + the correction token (1..K+1 tokens per round). The
        per-slot accept counts gate retirement, so this path always
        reads the (small) verify outputs back."""
        cfg = self.cfg
        K = cfg.num_draft
        drafts = np.zeros((cfg.max_slots, K), np.int32)
        for i, st in enumerate(self._slots):
            if st is not None and st.in_batch:
                drafts[i] = np.asarray(
                    self._draft_propose(st.history, K),
                    np.int32).reshape(K)
        d_drafts = jnp.asarray(drafts)       # an upload of its own
        self._tally["control_dispatches"] += 1
        self._count_kv_blocks(K + 1)
        with spine.span("serving/verify_step",
                        operands=self._n_operands["step"]):
            if self._paged:
                tgt, acc, nxt, idxs, pos, self.kv.pages = self._verify(
                    self.params, self.kv.pages, self._d_bt,
                    self._d_toks, self._d_idxs, self._d_active,
                    self._d_seeds, self._d_pos, d_drafts,
                    *self._lora_args())
            else:
                tgt, acc, nxt, idxs, pos, self.kv.cache = self._verify(
                    self.params, self.kv.cache, self._d_toks,
                    self._d_idxs, self._d_active, self._d_seeds,
                    self._d_pos, d_drafts, *self._lora_args())
        self._d_toks, self._d_idxs, self._d_pos = nxt, idxs, pos
        with spine.span("serving/read_tokens", wait=True):
            tgt_np = np.asarray(tgt)
            acc_np = np.asarray(acc)
        self._step_no += 1
        with spine.span("serving/emit"):
            self._spec_emit(tgt_np, acc_np)

    def _spec_emit(self, tgt_np, acc_np):
        cfg = self.cfg
        K = cfg.num_draft
        for i, st in enumerate(self._slots):
            if st is None or not st.in_batch:
                continue
            a = int(acc_np[i])
            st.depth += a + 1                # as the device advanced it
            remaining = st.req.max_new_tokens - st.n_out
            emitted = [int(t) for t in tgt_np[i, :a + 1][:remaining]]
            # accept-rate accounting clamps to the EMISSION window:
            # only `remaining` draft positions could ever land, so a
            # truncated final round must not credit drafts past it —
            # uncapped counts systematically overstate draft quality
            # on short completions (review finding)
            d_used = min(K, remaining)
            a_used = min(a, d_used)
            st.drafted += d_used
            st.accepted += a_used
            self.metrics.incr("spec_drafted", d_used)
            self.metrics.incr("spec_accepted", a_used)
            done_reason = None
            n_emit = 0
            for t in emitted:
                st.produced.append(t)
                st.history.append(t)
                st.n_out += 1
                n_emit += 1
                if cfg.eos_id is not None and t == cfg.eos_id:
                    st.eos_seen = True
                    done_reason = "eos"
                    break
            self._tally["tokens_out"] += n_emit
            self.metrics.event(st.req.req_id, "token", n=n_emit)
            if done_reason is None and st.n_out >= st.req.max_new_tokens:
                done_reason = "length"
            if done_reason is not None:
                self._retire(i, "done", done_reason)

    def run(self, max_steps: Optional[int] = None) -> Dict[int,
                                                           RequestResult]:
        """Step until queue and slots drain (or ``max_steps``)."""
        steps = 0
        while self.scheduler.depth > 0 or any(
                s is not None for s in self._slots):
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return self.results

    # ---- admission ------------------------------------------------------

    def _admit_all(self):
        while self.kv.n_free > 0:
            prefer = None
            if (self.cfg.prefix_cache
                    and self.scheduler.depth > self.kv.n_free):
                # near capacity: slots are the scarce resource, and a
                # radix hit turns one over sooner — prefer hits WITHIN
                # a class (the scheduler never lets this cross the
                # QoS lattice)
                prefer = self._would_hit
            batch = self.scheduler.pop(1, prefer=prefer)
            if not batch:
                return
            self._admit(batch[0])

    def _full_prompt(self, req: Request) -> np.ndarray:
        if req.prefix:
            return np.concatenate([np.asarray(req.prefix, np.int32),
                                   req.tokens])
        return req.tokens

    def _would_hit(self, req: Request) -> bool:
        """Prefix-aware admission probe: would this queued request hit
        a registered page right now? A host-side radix walk — never a
        device op — and memoized per (request, page-store version):
        `pop(prefer=)` evaluates it for every queued request on every
        admission, so an uncached probe would cost O(depth x prompt)
        host work per freed slot while the queue stays deep (review
        finding)."""
        ver = self.kv.store_version
        if self._probe_cache_ver != ver:
            self._probe_cache_ver = ver
            self._probe_cache.clear()
        hit = self._probe_cache.get(req.req_id)
        if hit is None:
            full = self._full_prompt(req)
            hit = self.kv.match(full, int(full.size) - 1)[1] is not None
            if len(self._probe_cache) >= 2 * self.cfg.max_queue:
                # entries for long-departed requests only die on a
                # store-version bump; an all-hit steady state never
                # bumps, so cap the memo outright (a wholesale clear
                # just re-probes the <= max_queue live entries) —
                # review finding
                self._probe_cache.clear()
            self._probe_cache[req.req_id] = hit
        return hit

    def _admit(self, req: Request):
        rid = req.req_id
        spine.record_span("serving/queued", int(req.submitted_at * 1e9),
                          spine.monotonic_ns(), req=rid)
        with spine.span("serving/admit", req=rid):
            self._admit_one(req)

    def _admit_one(self, req: Request):
        cfg = self.cfg
        rid = req.req_id
        if (req.deadline is not None
                and req.deadline <= time.monotonic()):
            # expired between the step's expire() sweep and this
            # admission (e.g. while an earlier admission's prefill ran)
            # — evict before paying prefill or touching the pool
            self._finish(req.req_id, "evicted", "deadline (queued)", [])
            return
        with spine.span("serving/admit.alloc", req=rid):
            slot = self.kv.alloc()
            assert slot is not None
            if self._paged:
                # the freshly-owned page row must be on device before
                # any prefill chunk gathers/scatters through it
                self._sync_bt(slot)
            if self._lora is not None:
                # pin the tenant's adapter pages and patch the slot's
                # row BEFORE the prefill chain — token 0 already samples
                # through the fused epilogue. An unregistered (or None)
                # tenant gets the zero row: same executable, exact-zero
                # delta, flag off.
                lrow, lora_on = self._lora.acquire(req.tenant, slot)
                self._d_lora_bt = self._patch(
                    self._d_lora_bt, slot, jnp.asarray(lrow, jnp.int32))
                self._d_lora_on = self._patch(self._d_lora_on, slot,
                                              bool(lora_on))
            prefix = tuple(req.prefix) if req.prefix else ()
            full = self._full_prompt(req)
            key = page = None
            if cfg.prefix_cache:
                # cap at len-1: a full-prompt hit must still leave >= 1
                # real token to prefill (the logit the first token
                # samples from)
                key, page = self.kv.match(full, int(full.size) - 1)
                self.metrics.incr("prefix_lookups")
                if page is not None:
                    self.metrics.incr("prefix_hits")
                    self.metrics.incr("prefix_saved_tokens", page.length)
            elif prefix:
                # radix matching off: the PR-7 exact-tuple contract
                # still holds — a second sharer of the same explicit
                # prefix must reuse (not re-register: put_prefix would
                # raise) the page (review finding)
                page = self.kv.get_prefix(prefix)
                key = prefix if page is not None else None
            hit = page is not None
        self.metrics.event(
            req.req_id, "prefill",
            prefix_hit=(hit if cfg.prefix_cache else None),
            prefix_saved=(page.length if hit else 0))
        with self._admit_lock:
            self._mid_admit = req.req_id
        try:
            with spine.span("serving/prefill", req=rid,
                            operands=self._n_operands["prefill"]):
                if hit:
                    self.kv.acquire_prefix(key, slot)
                    if self._paged:
                        # the acquire REWIRED the slot's block table
                        # onto the shared pages — no lane copy exists
                        # to install, the pages themselves are the hit
                        self._sync_bt(slot)
                        install_lane, idx0 = None, page.length
                    else:
                        install_lane, idx0 = page.lane, page.length
                    if (prefix and idx0 < len(prefix)
                            and not self.kv.has_prefix(prefix)):
                        # partial hit below the caller's stated share
                        # point: pay the prefix remainder, then pin the
                        # explicit page at its stated length so later
                        # sharers hit in full
                        self._run_chunks(slot, full[idx0:len(prefix)],
                                         idx0, install_lane, req.seed)
                        self._register_page(slot, prefix, len(prefix))
                        install_lane, idx0 = None, len(prefix)
                    tok0 = self._run_chunks(slot, full[idx0:], idx0,
                                            install_lane, req.seed)
                elif prefix:
                    # first sharer pays: run the prefix's own chunks,
                    # snapshot the lane as the page, keep going
                    self._run_chunks(slot, full[:len(prefix)], 0,
                                     self.kv.zeros_lane, req.seed)
                    self._register_page(slot, prefix, len(prefix))
                    tok0 = self._run_chunks(slot, full[len(prefix):],
                                            len(prefix), None, req.seed)
                else:
                    tok0 = self._run_chunks(slot, full, 0,
                                            self.kv.zeros_lane, req.seed)
            if cfg.prefix_cache and not prefix:
                # auto-registration at the CHUNK-ALIGNED share point:
                # canonical lengths, so requests that split the same
                # prompt differently converge on one key. The last
                # token stays uncached (a future identical prompt must
                # still prefill >= 1 token).
                C = cfg.prefill_chunk
                lstar = ((int(full.size) - 1) // C) * C
                if lstar >= C and lstar > (page.length if hit else 0):
                    with spine.span("serving/admit.register", req=rid):
                        akey = tuple(int(t) for t in full[:lstar])
                        if not self.kv.has_prefix(akey):
                            self._register_page(slot, akey, lstar)
        except BaseException:
            # the first-sharer stranding window (ISSUE 15 satellite): a
            # prefill chain that dies mid-flight (chaos kill, XLA
            # error) must not leak the allocated slot or any acquired
            # page refs — free() releases both, fully-registered pages
            # stay (their snapshots completed), and the request's
            # verdict belongs to the caller's supervision (re-raise)
            self.kv.free(slot)
            if self._paged:
                self._sync_bt(slot)     # row back to the trash page
            self._lora_release(slot)
            with self._admit_lock:
                self._mid_admit = None
                self._cancel_mid.discard(req.req_id)
            raise
        self.metrics.event(req.req_id, "first_token")
        self._tally["admitted"] += 1
        self._tally["tokens_out"] += 1
        idx = int(full.size)
        st = _Slot(req=req, first_tok=tok0, start_step=self._step_no,
                   history=[int(t) for t in full], depth=idx)
        self._slots[slot] = st
        # close the mid-admission window only AFTER the slot is
        # published (a cancel arriving from here on routes to the
        # _slots scan), then drain any cancel that landed during the
        # chain under the handshake lock — clearing before publication
        # left a gap where a concurrent cancel found neither
        # _mid_admit nor _slots and returned a false False (review
        # finding)
        with self._admit_lock:
            self._mid_admit = None
            cancelled = req.req_id in self._cancel_mid
            self._cancel_mid.discard(req.req_id)
        first = None
        if not self._defer:
            with spine.span("serving/admit.first_read", req=rid,
                            wait=True):
                first = int(np.asarray(tok0))
            st.produced.append(first)
            st.history.append(first)
            st.first_tok = first
        if cancelled:
            # the cancel preceded any published result, so it wins
            # over an eos/length completion in this same admission —
            # the caller already holds cancel()'s True (review
            # finding: this used to lose to the eos retire and leak
            # the _cancel_mid entry)
            self._retire(slot, "cancelled", "cancelled running")
            return
        if (not self._defer and cfg.eos_id is not None
                and first == cfg.eos_id):
            st.eos_seen = True
            self._retire(slot, "done", "eos")
            return
        if req.max_new_tokens == 1:
            # finished at prefill: never occupies a decode step
            self._retire(slot, "done", "length")
            return
        # device-side boundary patch: the slot joins the decode batch
        # (pos=1: the next sampled token is the request's output #1 —
        # prefill already drew #0 from the same per-request stream)
        with spine.span("serving/admit.patch", req=rid):
            self._d_toks = self._patch(self._d_toks, slot,
                                       jnp.asarray(tok0, jnp.int32))
            self._d_idxs = self._patch(self._d_idxs, slot, idx)
            self._d_active = self._patch(self._d_active, slot, True)
            self._d_seeds = self._patch(self._d_seeds, slot,
                                        int(req.seed))
            self._d_pos = self._patch(self._d_pos, slot, 1)
        st.in_batch = True
        self._n_active += 1

    def _register_page(self, slot: int, pkey: tuple, length: int):
        """Snapshot ``slot``'s lane (which holds ``length`` completed
        positions) as a refcounted prefix page — put + acquire as one
        step, so no exception window can leave a registered page
        without its owner's ref. Paged mode registers by REFERENCE: the
        registry pins the slot's own pages (no device copy at all —
        copy-on-register is gone along with copy-on-admit); the stored
        length floors to a page multiple, so sub-page tails simply stay
        private and sharers re-prefill them."""
        if self._paged:
            if self.kv.register_prefix(slot, pkey, length) is not None:
                self.kv.acquire_prefix(pkey, slot)
            return
        # the snapshot must own its buffers: the pool is DONATED to the
        # next executable call, and on a one-slot pool x[0:1] is x itself
        lane = jax.tree_util.tree_map(
            lambda x: x[slot:slot + 1] if x.shape[0] > 1 else jnp.copy(x),
            self.kv.cache)
        self._tally["control_dispatches"] += len(
            jax.tree_util.tree_leaves(lane))
        self.kv.put_prefix(pkey, lane, length)
        self.kv.acquire_prefix(pkey, slot)

    def _run_chunks(self, slot: int, tokens: np.ndarray, idx0: int,
                    install_lane, seed: int):
        """Feed ``tokens`` through the prefill executable in fixed-width
        right-padded chunks starting at cache position ``idx0``.
        ``install_lane``: batch-1 pytree written over the slot's lane
        before the FIRST chunk (zeros, or a shared-prefix page); None
        continues on the lane as-is. Returns the (device) token sampled
        after the final chunk (drawn from the request's own counter
        stream at output position 0)."""
        C = self.cfg.prefill_chunk
        n = int(tokens.size)
        tok = None
        self._tally["prefill_chunks"] += math.ceil(n / C)
        self._tally["prefill_tokens"] += n
        for c in range(math.ceil(n / C)):
            seg = tokens[c * C:(c + 1) * C]
            buf = np.zeros((1, C), np.int32)
            buf[0, :seg.size] = seg
            if self._paged:
                # no install operand: prefix hits arrive as shared page
                # ids already synced into the device block table
                tok, self.kv.pages = self._prefill(
                    self.params, self.kv.pages, self._d_bt,
                    np.int32(slot), buf, np.int32(idx0 + c * C),
                    np.int32(seg.size), np.int32(seed),
                    *self._lora_args())
                continue
            install = np.bool_(c == 0 and install_lane is not None)
            lane_arg = (install_lane if install
                        else self.kv.zeros_lane)
            tok, self.kv.cache = self._prefill(
                self.params, self.kv.cache, np.int32(slot), lane_arg,
                install, buf, np.int32(idx0 + c * C),
                np.int32(seg.size), np.int32(seed),
                *self._lora_args())
        return tok

    # ---- retirement -----------------------------------------------------

    def _materialize(self, st: _Slot, slot_idx: int) -> List[int]:
        """Collect a deferred-mode slot's tokens from the step log (the
        only point the engine blocks on decode outputs)."""
        out = [int(np.asarray(st.first_tok))]
        for s in range(st.start_step,
                       st.start_step + max(st.n_out - 1, 0)):
            buf = self._tok_log[s]
            if not isinstance(buf, np.ndarray):     # memoize the fetch
                buf = np.asarray(buf)
                self._tok_log[s] = buf
            out.append(int(buf[slot_idx]))
        return out

    def _prune_log(self):
        if not self._tok_log:
            return
        live = [s.start_step for s in self._slots if s is not None]
        floor = min(live) if live else self._step_no
        for s in [s for s in self._tok_log if s < floor]:
            del self._tok_log[s]

    def _retire(self, slot_idx: int, status: str, reason: str):
        slot = self._slots[slot_idx]
        with spine.span("serving/retire", req=slot.req.req_id):
            self._slots[slot_idx] = None
            self._tally["retired"] += 1
            if self._defer:
                produced = self._materialize(slot, slot_idx)
                self._prune_log()
            else:
                produced = slot.produced
            if slot.in_batch:
                # boundary patch: drop the lane from the decode batch
                # (values only: the step hands an idle lane's index on
                # as -1, and the dense step's kernel then neither reads
                # nor writes it)
                self._d_active = self._patch(self._d_active, slot_idx,
                                             False)
                self._n_active -= 1
            self.kv.free(slot_idx)
            if self._paged:
                # the freed row now names the trash page — REQUIRED, not
                # hygiene: the retired lane keeps scattering its masked
                # garbage every step, and its old pages may be
                # reallocated (or live on as shared prefix pages)
                # immediately
                self._sync_bt(slot_idx)
            self._lora_release(slot_idx)
            spec = ({"n_drafted": slot.drafted,
                     "n_accepted": slot.accepted} if self._spec else {})
            self._finish(slot.req.req_id, status, reason, produced,
                         **spec)

    def _finish(self, req_id: int, status: str, reason: str,
                produced: List[int], **fields):
        if status == "evicted" and not reason.startswith("shed"):
            self.metrics.incr("evictions")  # sheds counted separately
        self.metrics.event(req_id, status, reason=reason,
                           n_generated=len(produced), **fields)
        self.results[req_id] = RequestResult(
            req_id=req_id, status=status,
            tokens=np.asarray(produced, np.int32), reason=reason)

    # ---- introspection --------------------------------------------------

    def pop_result(self, req_id: int) -> Optional[RequestResult]:
        """Remove and return a finished request's result — the
        long-running server's pressure valve (`results` is otherwise
        bounded only by the number of requests ever served; pair with
        `metrics.drain()`)."""
        return self.results.pop(req_id, None)

    @property
    def n_active(self) -> int:
        """Requests that hold a lane: in the decode batch, or out of it
        by count with their last tokens still to be read."""
        return sum(s is not None for s in self._slots)

    def slot_view(self) -> List[Optional[int]]:
        """req_id per slot (None = free) — the occupancy diagram."""
        return [None if s is None else s.req.req_id for s in self._slots]
