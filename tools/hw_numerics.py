"""On-device Pallas-kernel numerics parity (VERDICT r2 Missing #2).

The 351-test suite proves kernel numerics in *interpret* mode on CPU and
`tools/aot_check.py` proves Mosaic *lowering* — this script closes the gap
in between: it runs each Pallas kernel through the real Mosaic compiler on
the attached TPU and compares against the XLA-composite gold (the same
gold the interpret-mode tests use, SURVEY §4.2.1 parity-vs-gold).

Small shapes, one compile per check, a hard watchdog, and a PASS/FAIL
line per check plus a final JSON summary line, so a run that dies midway
still leaves evidence. One process: it initialises the backend itself.

Run: python tools/hw_numerics.py [--timeout 900]
"""

import argparse
import json
import os
import signal
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


RESULTS = []
ONLY = None  # --only substring filter; None = run every check


class _Watchdog(BaseException):
    """Deadline signal. Derives from BaseException so check()'s broad
    ``except Exception`` (which must keep the sweep going on per-kernel
    failures) can NOT swallow it — a swallowed watchdog would leave the
    process sweeping past its caller's time limit."""


def _record(name, ok, detail, t0):
    print(f"{'OK  ' if ok else 'FAIL'} {name:<34s} {detail} "
          f"({time.time()-t0:.1f}s)", flush=True)
    RESULTS.append({"name": name, "ok": bool(ok), "detail": detail})


def check(name, fn, pallas_args, gold_args=None, tol=2e-2, grad_tol=5e-2,
          grad_argnums=None, reduce_for_grad=None):
    """Compare fn under force_impl('pallas') vs force_impl('xla').

    fn returns an array or tuple of arrays. If grad_argnums is set, also
    compare grads of sum(reduce_for_grad(fn(*args))) w.r.t. those args.
    """
    import jax
    import jax.numpy as jnp

    from apex1_tpu.ops import force_impl

    if ONLY is not None and not any(s in name for s in ONLY):
        return
    gold_args = gold_args if gold_args is not None else pallas_args
    t0 = time.time()
    try:
        # Impl choice must live INSIDE a per-impl closure: jitting one
        # shared function under two `force_impl` contexts lets JAX's
        # global pjit cache hand the second call the first call's
        # executable (observed once: the "xla" gold came back as the
        # Pallas kernel, relerr exactly 0.0 — a vacuous parity check).
        # Distinct function objects → distinct cache
        # entries; `force_impl` applies at trace time.
        def make_run(impl):
            def run(args):
                with force_impl(impl):
                    out = fn(*args)
                return out if isinstance(out, tuple) else (out,)
            return run

        got = jax.jit(make_run("pallas"))(pallas_args)
        got = [np.asarray(g, np.float32) for g in got]
        want = jax.jit(make_run("xla"))(gold_args)
        want = [np.asarray(w, np.float32) for w in want]
        errs = []
        for g, w in zip(got, want):
            denom = np.maximum(np.abs(w), 1.0)
            errs.append(float(np.max(np.abs(g - w) / denom)))
        ok = all(e <= tol for e in errs) and all(
            np.isfinite(g).all() for g in got)
        detail = f"fwd_relerr={max(errs):.2e} tol={tol:.0e}"

        if ok and grad_argnums is not None:
            red = reduce_for_grad or (
                lambda outs: sum(jnp.sum(o.astype(jnp.float32))
                                 for o in outs))

            def make_gfn(impl):
                run = make_run(impl)

                def scalar(*args):
                    return red(run(args))

                return jax.grad(scalar, argnums=grad_argnums)

            gp = jax.jit(make_gfn("pallas"))(*pallas_args)
            gx = jax.jit(make_gfn("xla"))(*gold_args)
            gerrs = []
            for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gx)):
                a = np.asarray(a, np.float32)
                b = np.asarray(b, np.float32)
                denom = np.maximum(np.abs(b), 1.0)
                gerrs.append(float(np.max(np.abs(a - b) / denom)))
            ok = all(e <= grad_tol for e in gerrs)
            detail += f" grad_relerr={max(gerrs):.2e} gtol={grad_tol:.0e}"
        _record(name, ok, detail, t0)
    except Exception as e:  # keep sweeping — partial evidence is the point
        _record(name, False, f"{type(e).__name__}: {e}", t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="smoke-test the harness on CPU (Pallas runs in "
                         "interpret mode — validates the script, not "
                         "Mosaic numerics)")
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings: run only checks "
                         "whose name contains one of them (e.g. "
                         "'bias,int8' = the checks added after the "
                         "round-3 hardware window)")
    args = ap.parse_args()
    global ONLY
    ONLY = args.only.split(",") if args.only else None

    import jax

    from apex1_tpu.testing import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()
    backend = jax.default_backend()
    if backend == "cpu" and not args.allow_cpu:
        print(json.dumps({"ok": False, "error": f"backend={backend}"}),
              flush=True)
        return 1

    def _alarm(signum, frame):
        raise _Watchdog("hw_numerics watchdog")

    signal.signal(signal.SIGALRM, _alarm)
    # a caller's `timeout` SIGTERMs the whole process; route it into the
    # same partial-summary path. (Neither handler can fire while blocked
    # inside a native compile — the per-check flushed PASS/FAIL lines
    # are the evidence that always survives.)
    signal.signal(signal.SIGTERM, _alarm)
    signal.alarm(int(args.timeout))
    timed_out = False
    try:
        _sweep(backend)
    except _Watchdog:
        timed_out = True  # partial RESULTS still get summarized
    signal.alarm(0)
    n_fail = sum(not r["ok"] for r in RESULTS)
    # an --only filter that matches nothing must not read as a pass
    ran_any = len(RESULTS) > 0
    print(json.dumps({
        "ok": n_fail == 0 and not timed_out and ran_any, "backend": backend,
        "timed_out": timed_out,
        "n_pass": len(RESULTS) - n_fail, "n_fail": n_fail,
        "failures": [r["name"] for r in RESULTS if not r["ok"]],
    }), flush=True)
    return 0 if (n_fail == 0 and not timed_out and ran_any) else 1


def _sweep(backend):
    import jax.numpy as jnp

    from apex1_tpu import ops

    rng = np.random.default_rng(0)

    def bf(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.bfloat16)

    # --- flash attention: fwd+bwd, causal / GQA / segments / offsets ---
    B, H, S, D = 2, 8, 512, 64
    q, k, v = bf(B, H, S, D), bf(B, H, S, D), bf(B, H, S, D)
    check("flash_fwd_bwd_causal",
          lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
          (q, k, v), grad_argnums=(0, 1, 2))
    # the gpt2m_train cell's own call (8 x 16 x 1024 x 64): the resident
    # loop with interior AND masked tiles at the heuristic's tile size
    qc, kc, vc = (bf(8, 16, 1024, D) for _ in range(3))
    check("flash_fwd_bwd_causal_cell",
          lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
          (qc, kc, vc), grad_argnums=(0, 1, 2))
    # the same call AS THE MODEL MAKES IT: the packed array in the rows
    # layout (PR 41); a head's two masked tiles lie on the diagonal and
    # run the DIAGONAL body (PR 51)
    check("fmha_rows_fwd_bwd_causal_cell",
          lambda x: ops.fmha(x, causal=True),
          (bf(8, 1024, 3, 16, D),), grad_argnums=(0,))
    _flash_diagonal_vs_masked(bf)
    kg, vg = bf(B, 2, S, D), bf(B, 2, S, D)
    check("flash_fwd_bwd_gqa",
          lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
          (q, kg, vg), grad_argnums=(0, 1, 2))
    segs = jnp.asarray(np.repeat(np.arange(4), S // 4)[None].repeat(B, 0),
                       jnp.int32)
    check("flash_fwd_bwd_segments",
          lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                              segment_ids=segs),
          (q, k, v), grad_argnums=(0, 1, 2))
    att_bias = jnp.asarray(rng.normal(size=(1, H, S, S)), jnp.float32)
    check("flash_fwd_bwd_bias",
          lambda q, k, v, b: ops.flash_attention(q, k, v, bias=b),
          (q, k, v, att_bias), grad_argnums=(0, 1, 2, 3))
    check("flash_fwd_ring_offset",
          lambda q, k, v: ops.flash_attention(
              q, k, v, causal=True, q_offset=S, k_offset=0,
              return_lse=True),
          (q, k, v))

    # --- layer norm / rms norm: bf16 x, fp32 scales ---
    R, Hn = 2048, 1024
    x = bf(R, Hn)
    g1 = jnp.asarray(rng.normal(size=(Hn,)), jnp.float32)
    b1 = jnp.asarray(rng.normal(size=(Hn,)), jnp.float32)
    check("layer_norm_fwd_bwd",
          lambda x, g, b: ops.layer_norm(x, g, b),
          (x, g1, b1), grad_argnums=(0, 1, 2))
    check("rms_norm_fwd_bwd",
          lambda x, g: ops.rms_norm(x, g),
          (x, g1), grad_argnums=(0, 1))

    # --- softmax (masked + causal) ---
    sc = bf(2, 4, 256, 256)
    mask = jnp.where(
        jnp.asarray(rng.random((2, 1, 256, 256)) < 0.2), ops.NEG_INF, 0.0
    ).astype(jnp.bfloat16)
    check("scaled_masked_softmax",
          lambda x, m: ops.scaled_masked_softmax(x, m, scale=0.5),
          (sc, mask), grad_argnums=(0,))
    check("causal_softmax",
          lambda x: ops.scaled_upper_triang_masked_softmax(x, scale=0.5),
          (sc,), grad_argnums=(0,))

    # --- xentropy: fp32 logits (production: fp32 logits from bf16 mm) ---
    T, V = 1024, 8192
    logits = jnp.asarray(rng.normal(size=(T, V)) * 2, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)
    labels = labels.at[::17].set(0)
    check("xentropy_fwd_bwd_smooth",
          lambda lg, lb: ops.softmax_cross_entropy_loss(
              lg, lb, smoothing=0.1, padding_idx=0),
          (logits, labels), tol=1e-3, grad_tol=1e-3, grad_argnums=(0,),
          reduce_for_grad=lambda outs: jnp.sum(outs[0]))

    # --- fused LM-head CE (linear_xent): bf16 x/W ---
    Tt, Hh, Vv = 512, 512, 16000
    xt = bf(Tt, Hh)
    wt = bf(Vv, Hh, scale=0.02)
    lb = jnp.asarray(rng.integers(0, Vv, (Tt,)), jnp.int32)
    check("linear_xent_fwd_bwd",
          lambda x, w, l: ops.linear_cross_entropy(x, w, l, smoothing=0.1),
          (xt, wt, lb), grad_argnums=(0, 1),
          reduce_for_grad=lambda outs: jnp.sum(outs[0]))

    # --- RoPE --- head_dim 256 so half=128 satisfies the Pallas kernel's
    # `half % 128 == 0` gate (rope.py:109); at the flash check's D=64 both
    # impls silently take the XLA composite and the parity is vacuous
    Dr = 256
    pos = jnp.arange(S)
    cos, sin = ops.rope_tables(pos, Dr)
    xr = bf(B, S, H, Dr)
    check("rope_half_split",
          lambda x: ops.apply_rotary_pos_emb(x, cos, sin),
          (xr,), grad_argnums=(0,))
    check("rope_interleaved",
          lambda x: ops.apply_rotary_pos_emb(x, cos, sin, interleaved=True),
          (xr,), grad_argnums=(0,))

    # --- int8 weight-only decode GEMM (added round 4; never yet run on
    # silicon) — decode-row x vs a head-sized weight; dequant in VMEM ---
    wq8, s8 = ops.quantize_int8(
        jnp.asarray(rng.normal(size=(2048, 1024)) * 0.05, jnp.float32))
    x8 = bf(8, 1024)
    check("int8_matmul_decode",
          lambda x: ops.int8_matmul(x, wq8, s8),
          (x8,))

    _dropout_mask_identity(rng)
    _decode_attend_queue()


#: the serving cells' step attention as the engine lays it out: (lanes, Hq,
#: Hkv, D, rows a lane, window). `gpt2m_serve_chat`, `granite4hm_serve_chat`,
#: `lfm2moe_serve_rollout`, and `trinitymini_serve_longctx`'s two kinds of
#: leaf (a sliding layer's ring, a global layer's whole lane)
DECODE_CELLS = {
    "gpt2m": (48, 16, 16, 64, 1152, None),
    "granite4hm": (48, 32, 8, 64, 1280, None),
    "lfm2moe": (96, 32, 8, 64, 2816, None),
    "trinity_ring": (16, 32, 4, 128, 2304, 2048),
    "trinity_global": (16, 32, 4, 128, 8960, None),
}


def decode_cell_depths(rng, B, L, S, window, blk=128):
    """Per-lane depths over one cell's geometry that sit where a queue of
    fetches that crosses lanes can go wrong: idle lanes between live ones
    (and first, and last), lanes of one block, lanes one row short of and
    one row past a block's edge, new rows astride an edge, the last row
    that fits; over a ring, lanes shallower than the window, at the wrap,
    and past three windows. The rest are drawn over the whole lane."""
    top = (4 * window if window else L) - S
    idx = rng.integers(0, top + 1, size=B)
    edge = blk * int(rng.integers(1, min(L, top) // blk))
    special = [0, blk - S, blk - 1, blk, edge - 1, edge, edge + 1,
               edge - S + 1 if S > 1 else edge - 2, top]
    if window:
        special += [window - 1, window, L - 1, L, L - S + 1 if S > 1 else L + 1,
                    3 * window + 77, int(rng.integers(3 * window, top + 1))]
    at = rng.permutation(B)
    for lane, v in zip(at, special):
        idx[lane] = max(min(int(v), top), 0)
    idle = rng.permutation(B)[:max(2, B // 6)]
    idx[idle] = -1
    idx[[0, B - 1]] = rng.choice([-1, 3, edge], size=2)
    return idx.astype(np.int32)


def _flash_diagonal_vs_masked(bf):
    """The flash kernels' DIAGONAL body THROUGH MOSAIC against the MASKED
    body on the same inputs (`ops.attention._diag_sub` answering 0: the
    program a call ran before PR 51), forward and every gradient, in both
    layouts: the training cell's packed call, and (B, H, S, D) operands
    under GQA with a ring shard's aligned offset. The two differ by exact
    zeros left out of sums (a reduction over fewer sublanes may round a
    last bit of float32 another way): held to a bfloat16 output step."""
    import jax
    import jax.numpy as jnp

    from apex1_tpu import ops
    from apex1_tpu.ops import attention, force_impl

    cases = {
        "rows_cell": (lambda x: ops.fmha(x, causal=True),
                      (bf(8, 1024, 3, 16, 64),)),
        "heads_gqa_offset": (
            lambda q, k, v: ops.flash_attention(
                q, k, v, causal=True, q_offset=1024, k_offset=1024),
            (bf(2, 8, 1024, 128), bf(2, 4, 1024, 128), bf(2, 4, 1024, 128))),
    }
    for case, (fn, args) in cases.items():
        name = f"flash_diagonal_vs_masked_{case}"
        if ONLY is not None and not any(s in name for s in ONLY):
            continue
        t0 = time.time()

        def run():
            with force_impl("pallas"):
                out, vjp = jax.vjp(fn, *args)
                return (out, *vjp(jnp.ones_like(out)))

        sub = attention._diag_sub
        try:
            got = [np.asarray(x, np.float32) for x in run()]
            attention._diag_sub = lambda *a, **k: 0
            jax.clear_caches()      # the launches are jitted
            want = [np.asarray(x, np.float32) for x in run()]
            errs = [float(np.max(np.abs(g - w) / np.maximum(np.abs(w), 1.0)))
                    for g, w in zip(got, want)]
            differ = sum(int(np.sum(g != w)) for g, w in zip(got, want))
            _record(name, max(errs) <= 2 ** -7 and all(
                np.isfinite(g).all() for g in got),
                f"sub={sub(512, 512, True)} max relerr (out, grads)="
                f"{max(errs):.2e} tol={2 ** -7:.1e}; {differ} of "
                f"{sum(g.size for g in got)} elements differ", t0)
        except Exception as e:  # keep sweeping
            _record(name, False, f"{type(e).__name__}: {e}", t0)
        finally:
            attention._diag_sub = sub
            jax.clear_caches()


def _decode_attend_queue(rounds=3, steps=12):
    """`ops.decode_attend`'s queue of fetches THROUGH MOSAIC at the four
    serving cells' own geometries: the attention and both pool leaves
    BITWISE against the two-buffer schedule it replaced (`run_on=False`
    of the same jitted call: the parent's kernel, op for op), and the
    attention against the composite within the tests' tolerance. Each
    round draws new depths and runs ``steps`` calls as the engine does,
    pools donated and every live lane one launch deeper each call, so a
    fetch that passes a write-back, a buffer refilled too early or a
    block fetched by another lane's arithmetic shows where it happens one
    time in ten. Interpret mode cannot stand in for this: there a copy is
    complete where it is started (docs/ops.md)."""
    import jax
    import jax.numpy as jnp

    from apex1_tpu.models.generate import cache_write
    from apex1_tpu.ops import decode_attend as da
    from apex1_tpu.ops._common import interpret_mode
    from apex1_tpu.ops.paged_decode import cache_attend

    for cell, (B, Hq, Hkv, D, L, window) in DECODE_CELLS.items():
        for S in (1, 5):
            name = f"decode_attend_{cell}_S{S}"
            if ONLY is not None and not any(s in name for s in ONLY):
                continue
            t0 = time.time()
            try:
                HD = Hkv * D
                depth = da.fetch_depth(HD, jnp.bfloat16, L)
                geometry = da.check_decode_geometry(L, HD, Hq * S, S,
                                                    jnp.bfloat16, window)
                kw = dict(scale=float(D ** -0.5), geometry=geometry,
                          interpret=interpret_mode(),
                          **({} if window is None else {"window": window}))

                def make(**schedule):
                    return jax.jit(lambda q, kn, vn, kp, vp, ix:
                                   da._decode_attend(q, kn, vn, kp, vp, ix,
                                                     **kw, **schedule),
                                   donate_argnums=(3, 4))

                queue, two = make(depth=depth), make(depth=2, run_on=False)
                same = jax.jit(lambda a, b: jnp.stack(
                    [jnp.array_equal(x, y) for x, y in zip(a, b)]))

                def composite(q, kn, vn, kp, vp, ix):
                    ring = {} if window is None else {"ring": True}
                    return cache_attend(
                        q, cache_write(kp, kn, ix, **ring),
                        cache_write(vp, vn, ix, **ring), ix,
                        **({} if window is None else {"window": window}))

                rng = np.random.default_rng(50 + S)
                calls, bad, err = 0, [], 0.0
                # tests/test_decode_attend.py's: an output step of bfloat16
                # against the composite, two over a ring
                tol = 2 ** -6 if window is None else 2 ** -5
                for r in range(rounds):
                    ks = jax.random.split(jax.random.key(100 * r + S), 5)
                    draw = lambda i, *shape: jax.random.normal(
                        ks[i], shape, jnp.bfloat16)
                    q, kn, vn = (draw(0, B, Hq, S, D), draw(1, B, Hkv, S, D),
                                 draw(2, B, Hkv, S, D))
                    kp, vp = draw(3, B, L, HD), draw(4, B, L, HD)
                    idx = decode_cell_depths(rng, B, L, S, window)
                    live = idx >= 0
                    want = np.asarray(jax.jit(composite)(
                        q, kn, vn, kp, vp, jnp.asarray(idx)), np.float32)
                    a, b = (kp, vp), (kp + 0, vp + 0)
                    for t in range(steps):
                        # a live lane one launch deeper each call; one
                        # that would pass its lane's end starts over
                        ix = idx + np.where(live, t * S, 0)
                        if window is None:
                            ix = np.where(ix > L - S, ix % S, ix)
                        ix = jnp.asarray(ix.astype(np.int32))
                        oa, *a = queue(q, kn, vn, *a, ix)
                        ob, *b = two(q, kn, vn, *b, ix)
                        ok3 = np.asarray(same((oa, *a), (ob, *b)))
                        calls += 1
                        if not ok3.all():
                            bad.append((r, t, ok3.tolist()))
                        if t == 0:
                            got = np.asarray(oa, np.float32)
                            err = max(err, float(np.abs(
                                got[live] - want[live]).max()))
                            if got[~live].any():
                                bad.append((r, "idle lane not zero"))
                _record(name, not bad and err <= tol,
                        f"depth={depth} {calls} calls bitwise vs two-buffer "
                        f"(out,k,v): {'all equal' if not bad else bad[:3]}; "
                        f"vs composite max|d|={err:.2e} tol={tol:.1e}",
                        t0)
            except Exception as e:
                _record(name, False, f"{type(e).__name__}: {e}", t0)


def _dropout_mask_identity(rng):
    """In-kernel dropout: the forward and both backward kernels must
    regenerate the SAME keep mask from (seed, salt, global tile offset).
    On the chip the mask comes from the hardware PRNG, so the XLA
    composite (hash mask) is no gold for it; instead the forward kernel
    itself is probed for its mask and a pure-jnp reference built on that
    mask supplies the gradients the backward kernels must match."""
    import jax
    import jax.numpy as jnp

    from apex1_tpu import ops
    from apex1_tpu.ops import force_impl

    if ONLY is not None and not any(s in "dropout_mask_identity"
                                    for s in ONLY):
        return
    p_drop, seed = 0.25, 1234

    # --- flash: 4x4 tiles of 128, two heads (distinct salts) ---
    t0 = time.time()
    try:
        B, H, S, D, blk = 1, 2, 512, 128, 128
        scale = D ** -0.5
        q, k, v, do = (jnp.asarray(rng.normal(size=(B, H, S, D)),
                                   jnp.bfloat16) for _ in range(4))

        def flash(q, k, v):
            with force_impl("pallas"):
                return ops.flash_attention(
                    q, k, v, causal=True, sm_scale=scale, block_q=blk,
                    block_k=blk, dropout_p=p_drop, dropout_seed=seed)

        # A = (P o M)/(1-p) column block c, read off the forward kernel
        # with V = the c-th column block of the identity
        eye = jnp.eye(S, dtype=jnp.bfloat16)
        cols = [jax.jit(flash)(q, k, jnp.broadcast_to(
            eye[:, c * D:(c + 1) * D], (B, H, S, D)))
            for c in range(S // D)]
        keep = jnp.concatenate(cols, axis=-1).astype(jnp.float32) > 0.0
        causal = jnp.tril(jnp.ones((S, S), bool))
        rate = float(jnp.sum(keep & causal) / (B * H * jnp.sum(causal)))

        def ref(q, k, v):
            sc = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
            pr = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
            pr = jnp.where(keep, pr / (1.0 - p_drop), 0.0)
            return jnp.einsum("bhqk,bhkd->bhqd", pr,
                              v.astype(jnp.float32))

        def grads(fn):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                        * do.astype(jnp.float32)),
                argnums=(0, 1, 2)))(q, k, v)

        errs = []
        for a, b in zip(grads(flash), grads(ref)):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            errs.append(float(np.max(np.abs(a - b)
                                     / np.maximum(np.abs(b), 1.0))))
        # a mask the backward drew differently moves O(1) entries; bf16
        # kernel arithmetic stays inside 5e-2
        ok = max(errs) <= 5e-2 and abs(rate - (1 - p_drop)) < 0.02
        _record("flash_dropout_mask_identity", ok,
                f"dq/dk/dv_relerr={errs[0]:.2e}/{errs[1]:.2e}/"
                f"{errs[2]:.2e} keep_rate={rate:.3f}", t0)
    except Exception as e:
        _record("flash_dropout_mask_identity", False,
                f"{type(e).__name__}: {e}", t0)

    # --- bias_dropout_add: forward and backward masks, bit for bit ---
    t0 = time.time()
    try:
        rows, hid = 4096, 1024          # several row blocks
        ones = jnp.ones((rows, hid), jnp.bfloat16)
        zeros = jnp.zeros((rows, hid), jnp.bfloat16)

        def bda(x):
            with force_impl("pallas"):
                return ops.fused_bias_dropout_add(x, zeros, p=p_drop,
                                                  seed=seed)

        fwd_keep = np.asarray(jax.jit(bda)(ones), np.float32) != 0.0
        bwd_keep = np.asarray(jax.jit(jax.grad(
            lambda x: jnp.sum(bda(x).astype(jnp.float32))))(ones),
            np.float32) != 0.0
        again = np.asarray(jax.jit(bda)(ones), np.float32) != 0.0
        rate = float(fwd_keep.mean())
        ok = (np.array_equal(fwd_keep, bwd_keep)
              and np.array_equal(fwd_keep, again)
              and abs(rate - (1 - p_drop)) < 0.02
              # row blocks must not repeat one another's stream
              and not np.array_equal(fwd_keep[:8], fwd_keep[-8:]))
        _record("bda_dropout_mask_identity", ok,
                f"fwd==bwd {np.array_equal(fwd_keep, bwd_keep)} "
                f"keep_rate={rate:.3f}", t0)
    except Exception as e:
        _record("bda_dropout_mask_identity", False,
                f"{type(e).__name__}: {e}", t0)


if __name__ == "__main__":
    sys.exit(main())
