"""State-space (Mamba-2) mixing: the depthwise causal convolution, the
chunked state-space product of a prefill chunk, and the decode step that
updates each live lane's recurrent state where it lies in the pool.

One head's recurrence, with a scalar decay a head and one group of
``B``/``C`` shared by all heads (``S`` is ``P x N``: head width by state
width)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

- :func:`ssd_chunk` computes a run of tokens in chunks, taking and
  returning the state. With cumulative log-decays ``c_t = sum_{r<=t} dt_r
  A`` a chunk of Q tokens is ``Y = (L . C B^T)(dt . X) + exp(c_t) C_t
  S_prev`` with ``L[t, s] = exp(c_t - c_s)`` for ``s <= t``, and ``S_next =
  exp(c_Q) S_prev + sum_s exp(c_Q - c_s) dt_s x_s (x) B_s``. Plain
  ``jax.numpy`` einsums in float32 (the serving engine's prefill runs
  it as it stands: a kernel for it is a later change's).
- :func:`ssm_step` is one token a row. With a per-row index where the
  kernels run it is ONE Pallas call a layer, `apex1_ssm_step`: the state
  leaf, float32, stored ``(B, H / k, N, k * P)`` (`pack_state`: ``k``
  heads side by side on 128 lanes), is an aliased input/output that stays
  in HBM (``memory_space=ANY``, as `ops.decode_attend` holds its K/V
  leaves); a live lane's state is moved in and out by the kernel's own
  DMA, ``ROW_CHUNK`` rows of heads at a time, into one of two lane-sized
  buffers: while one live lane is updated, the next one's state is on its
  way in and the one before's on its way out; a lane with ``idx < 0`` (an
  idle slot) is neither read nor written. As XLA ops the same update is a
  select that rewrites the whole leaf (PERF.md, PRs 26 and 29, found that
  of K/V): that composite is what a CPU, a scalar index and the parity
  tests run.
- :func:`causal_conv` is the kernel-4 depthwise convolution in front of
  it, over the last ``K - 1`` inputs it keeps as its own state.

A right-padded chunk (``n_real`` real tokens, then padding) leaves both
states where the real tokens left them: a pad position's ``dt`` is 0, so
it neither decays the state nor adds to it, and the convolution's state
is cut at the last REAL input. K/V written for a pad token lies past the
horizon and harms nobody; a state advanced by one would be wrong for
every later token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (interpret_mode, kernel_call, out_struct,
                                   use_pallas)

#: rows of heads of one lane's stored state that one DMA moves (4 x 128 x
#: 128 float32 = 256 KiB at the published widths)
ROW_CHUNK = 4
_HIGHEST = jax.lax.Precision.HIGHEST


def causal_conv(x, w, b, conv_state, n_real=None):
    """Depthwise causal convolution over ``x`` (B, S, C) continuing from
    ``conv_state`` (B, R, C), the last ``R >= K - 1`` inputs: ``y_t = b +
    sum_k w[k] z[t - (K - 1) + k]`` over the inputs ``z`` so far (``w``
    (K, C); ``w[K - 1]`` meets the current input, as `torch.nn.Conv1d`
    with padding ``K - 1`` cut to S). Returns ``(y (B, S, C), new
    state)``: the last ``R`` inputs up to ``n_real - 1`` (``n_real`` a
    scalar, None for S: every input real), so a right-padded chunk keeps
    the last REAL inputs. A state of ``K - 1`` rows is all the next
    output needs; a family that keeps ``K`` (the kernel's whole width,
    as its published cache does) hands that in and gets it back."""
    S, K, R = x.shape[1], w.shape[0], conv_state.shape[1]
    xx = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    lead = R - (K - 1)
    y = sum(w[k].astype(x.dtype) * xx[:, lead + k:lead + k + S]
            for k in range(K))
    if b is not None:
        y = y + b.astype(x.dtype)
    start = S if n_real is None else n_real
    new = jax.lax.dynamic_slice_in_dim(xx, start, R, axis=1)
    return y, new.astype(conv_state.dtype)


def _chunk(x, dt, A, Bm, Cm, state):
    """One chunk of :func:`ssd_chunk`, float32: x (B, Q, H, P), dt
    (B, Q, H), Bm / Cm (B, Q, N), state (B, H, P, N)."""
    Q = x.shape[1]
    c = jnp.cumsum(dt * A, axis=1)                          # (B, Q, H) <= 0
    seg = c[:, :, None, :] - c[:, None, :, :]               # c_t - c_s
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    L = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    xdt = x * dt[..., None]
    cb = jnp.einsum("btn,bsn->bts", Cm, Bm)
    y = jnp.einsum("btsh,bshp->bthp", L * cb[..., None], xdt)
    # what the chunk inherits, and what it hands on: the state's own
    # products at full precision (a TPU's default rounds float32 operands
    # to bfloat16, and this sum is carried through every later token)
    y = y + jnp.einsum("bth,btn,bhpn->bthp", jnp.exp(c), Cm, state,
                       precision=_HIGHEST)
    tail = jnp.exp(c[:, -1:, :] - c)                        # exp(c_Q - c_s)
    new = (jnp.exp(c[:, -1])[:, :, None, None] * state
           + jnp.einsum("bsh,bshp,bsn->bhpn", tail, xdt, Bm,
                        precision=_HIGHEST))
    return y, new


def ssd_chunk(x, dt, A, B, C, D, state, n_real=None, *, chunk: int = 256):
    """The state-space product over a run of tokens, in chunks of
    ``chunk``: ``x`` (B, S, H, P), ``dt`` (B, S, H) after its softplus,
    ``A`` (H,) negative, ``B`` / ``C`` (B, S, N) one group for all heads,
    ``D`` (H,), ``state`` (B, H, P, N) float32. ``n_real`` (scalar, None
    for S): positions at or past it are padding and contribute nothing:
    their ``dt`` is 0. Returns ``(y (B, S, H, P) float32, state)``; the
    chunk width tiles the sums and changes no result."""
    S = x.shape[1]
    f32 = jnp.float32
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    A, D = A.astype(f32), D.astype(f32)
    if n_real is not None:
        dt = jnp.where(jnp.arange(S)[None, :, None] < n_real, dt, 0.0)
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:         # whole chunks: the tail is padding too (dt = 0)
        x, dt, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                       for a in (x, dt, B, C))
    n = (S + pad) // Q
    if n == 1:
        y, state = _chunk(x, dt, A, B, C, state.astype(f32))
    else:
        def body(st, xs):
            y, st = _chunk(*xs[:2], A, *xs[2:], st)
            return st, y

        chunks = tuple(a.reshape(a.shape[0], n, Q, *a.shape[2:]).swapaxes(
            0, 1) for a in (x, dt, B, C))
        state, y = jax.lax.scan(body, state.astype(f32), chunks)
        y = y.swapaxes(0, 1).reshape(x.shape)
    y = y[:, :S] + D[:, None] * x[:, :S]
    return y, state


def heads_a_row(heads: int, width: int) -> int:
    """Heads that share one row of 128 lanes in the stored state: a head
    of 64 would fill half a row, so two lie side by side."""
    k = max(1, 128 // width)
    return k if heads % k == 0 else 1


def pack_state(state):
    """(B, H, P, N), as the recurrence is written, to the form the pool
    stores and `ssm_step` updates: (B, H / k, N, k * P), ``k`` heads side
    by side on the lanes and the state width on the rows. Stored so, ``S
    C`` sums over rows (plain adds of whole registers) and a head's decay
    and ``dt x`` are rows spread over sublanes; with N on the lanes every
    eight head-rows would need a reduction across lanes of their own
    (PERF.md §6, PR 34: 49 % of the roofline, bound by those)."""
    B, H, P, N = state.shape
    k = heads_a_row(H, P)
    return state.reshape(B, H // k, k, P, N).transpose(
        0, 1, 4, 2, 3).reshape(B, H // k, N, k * P)


def unpack_state(packed, width: int):
    """The inverse of :func:`pack_state` for heads of ``width``."""
    B, G, N, KP = packed.shape
    k = KP // width
    return packed.reshape(B, G, N, k, width).transpose(
        0, 1, 3, 4, 2).reshape(B, G * k, width, N)


def _rows(x, dt, A, G):
    """A lane's decay and ``dt x`` as the stored state's rows: (B, G, k *
    P) each, ``k`` heads side by side."""
    rows = (x.shape[0], G, -1)
    da = jnp.broadcast_to(jnp.exp(dt * A)[:, :, None], x.shape)
    return da.reshape(rows), (dt[:, :, None] * x).reshape(rows)


def _ssm_step_composite(x, dt, A, B, C, D, state, idx):
    """The recurrence for one token a row as `jax.numpy`, with a select
    on the row: what the kernel is held to, and what runs off the TPU."""
    da, xdt = _rows(x, dt, A, state.shape[1])
    new = (da[:, :, None, :] * state
           + xdt[:, :, None, :] * B[:, None, :, None])
    y = jnp.sum(new * C[:, None, :, None], axis=2).reshape(x.shape)
    live = (idx >= 0).reshape(-1, 1, 1, 1)
    return y + D[:, None] * x, jnp.where(live, new, state)


def _ssm_step_kernel(idx_ref, ord_ref, nxt_ref, xdt_ref, da_ref, b_ref,
                     c_ref, s_in, y_ref, s_out, sbuf, rsem, wsem, *, gc):
    lane = pl.program_id(0)
    idx, o, nxt = idx_ref[lane], ord_ref[lane], nxt_ref[lane]
    G, N, KP = sbuf.shape[1:]
    n = G // gc
    q = jax.lax.rem(o, 2)

    @pl.when(idx < 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    def fetch(b, buf, c):
        rows = pl.ds(c * gc, gc)
        return pltpu.make_async_copy(s_in.at[b, rows], sbuf.at[buf, rows],
                                     rsem.at[buf, c])

    def store(b, buf, c):
        rows = pl.ds(c * gc, gc)
        return pltpu.make_async_copy(sbuf.at[buf, rows], s_out.at[b, rows],
                                     wsem.at[buf, c])

    @pl.when(idx >= 0)
    def _():
        # two lanes' states are in VMEM at a time: this lane's, fetched
        # while the live lane before it was updated, and the next live
        # lane's, fetched now, once the lane before has left its buffer
        @pl.when(o == 0)
        def _():
            for c in range(n):
                fetch(lane, q, c).start()

        @pl.when(o > 0)
        def _():
            for c in range(n):
                store(lane, 1 - q, c).wait()

        @pl.when(nxt >= 0)
        def _():
            for c in range(n):
                fetch(nxt, 1 - q, c).start()

        # B and C lie along the lanes as they come; the state wants them
        # down the rows: through the diagonal, then spread over the lanes
        diag = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))
        column = lambda ref: jnp.broadcast_to(jnp.sum(
            jnp.where(diag, ref[0], 0.0), axis=1, keepdims=True), (N, KP))
        bcol, ccol = column(b_ref), column(c_ref)
        for c in range(n):
            fetch(lane, q, c).wait()
            for g in range(c * gc, (c + 1) * gc):
                s = (da_ref[0, g:g + 1, :] * sbuf[q, g]
                     + xdt_ref[0, g:g + 1, :] * bcol)
                sbuf[q, g] = s
                y_ref[0, g:g + 1, :] = jnp.sum(s * ccol, axis=0,
                                               keepdims=True)
            store(lane, q, c).start()

        @pl.when(nxt < 0)
        def _():
            for c in range(n):
                store(lane, q, c).wait()


# a program's layers call this with the same shapes: jitted, they share one
# traced kernel and one lowering of it (PERF.md §6, PR 29, `setup_s`)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_step_pallas(x, dt, A, B, C, D, state, idx, *, interpret):
    Bsz, G, N, KP = state.shape
    gc = ROW_CHUNK if G % ROW_CHUNK == 0 else G
    da, xdt = _rows(x, dt, A, G)
    # the live lanes in order, for the kernel's hand-over from one to the
    # next: each one's place among them, and the lane that follows it
    live = idx >= 0
    lanes = jnp.arange(Bsz, dtype=jnp.int32)
    order = jnp.cumsum(live, dtype=jnp.int32) - 1
    after = jax.lax.cummin(jnp.where(live, lanes, Bsz), reverse=True)
    nxt = jnp.concatenate([after[1:], jnp.full((1,), Bsz, jnp.int32)])
    nxt = jnp.where(nxt < Bsz, nxt, -1)
    row = lambda a: pl.BlockSpec((1,) + a.shape[1:],
                                 lambda b, *_: (b, 0, 0),
                                 memory_space=pltpu.VMEM)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    Bv, Cv = B[:, None, :], C[:, None, :]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(Bsz,),
        in_specs=[row(xdt), row(da), row(Bv), row(Cv), any_spec],
        out_specs=[row(xdt), any_spec],
        scratch_shapes=[pltpu.VMEM((2, G, N, KP), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, G // gc)),
                        pltpu.SemaphoreType.DMA((2, G // gc))],
    )
    y, state = kernel_call(
        functools.partial(_ssm_step_kernel, gc=gc),
        name="ssm_step",
        grid_spec=grid_spec,
        out_shape=[out_struct(xdt.shape, jnp.float32, xdt, state),
                   out_struct(state.shape, state.dtype, state)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx, order, nxt, xdt, da, Bv, Cv, state)
    return y.reshape(x.shape) + D[:, None] * x, state


def ssm_step(x, dt, A, B, C, D, pool_state, idx):
    """One token a row: ``x`` (B, H, P), ``dt`` (B, H) after its
    softplus, ``A`` (H,), ``B`` / ``C`` (B, N), ``D`` (H,),
    ``pool_state`` float32 in its stored form (`pack_state`), ``idx`` a
    scalar or (B,): a row whose index is negative has nothing to do; its
    state is left as it is and its output is not meaningful. Returns ``(y
    (B, H, P) float32, pool_state)``. What the call can see chooses the
    path, as in `generate.cached_attention`: a rank-1 index where the
    kernels run (``use_pallas()``) is the kernel, which updates a donated
    state in place; a scalar index or a CPU takes the composite."""
    f32 = jnp.float32
    x, dt, A, B, C, D = (a.astype(f32) for a in (x, dt, A, B, C, D))
    idx = jnp.asarray(idx, jnp.int32)
    if idx.ndim == 1 and use_pallas() and pool_state.dtype == f32:
        return _ssm_step_pallas(x, dt, A, B, C, D, pool_state, idx,
                                interpret=interpret_mode())
    return _ssm_step_composite(x, dt, A, B, C, D, pool_state,
                               jnp.broadcast_to(idx, x.shape[:1]))
