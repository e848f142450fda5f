"""graftlint APX2xx suite — the kernel/collective analyzer.

The acceptance spine (ISSUE 11): both PR 9 review-round semaphore
races, re-introduced into fixture copies of the RDMA reduce-scatter
kernel, MUST be flagged with rule ids and line numbers; the shipped
kernel and every other pallas_call site in the repo MUST pass clean;
the n==1 hang check and the registry-shared VMEM model are each pinned
by a falsifiable negative test.

Fixtures run in memory through ``lint_sources(kernels=True)`` like the
APX1xx suite. The protocol fixtures are structural copies of
``ops/fused_collective._mrs_rdma_kernel`` — when that kernel's
protocol changes, change ``GOOD_KERNEL`` here in lockstep (the
repo-wide self-check will hold you to it).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from apex1_tpu.lint import lint_paths, lint_sources

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_lint(src, path="fix/mod.py", modname="fix.mod", **named):
    sources = {path: (modname, textwrap.dedent(src))}
    for p, (m, s) in named.items():
        sources[p] = (m, textwrap.dedent(s))
    return lint_sources(sources, kernels=True)


def codes(res, *, suppressed=False):
    pool = res.suppressed() if suppressed else res.unsuppressed()
    return {f.rule for f in pool}


def line_of(src, marker):
    for i, ln in enumerate(textwrap.dedent(src).splitlines(), 1):
        if marker in ln:
            return i
    raise AssertionError(f"marker {marker!r} not in fixture")


HEADER = """
import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import apex1_tpu
"""

# the protocol body shared by every RDMA fixture, parameterized by the
# slot-reuse block (where both PR 9 races lived) and the credit-signal
# placement
_RDMA_TEMPLATE = HEADER + """
def _kernel(x_ref, w_ref, o_ref, acc_buf, send_buf, send_sem,
            recv_sem, cap_sem, *, n, axis_name):
    t = pl.program_id(0)
    my = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my + 1, n)
    left = jax.lax.rem(my + n - 1, n)

    def dev(i):
        return (i,)

    @pl.when(t == 0)
    def _():
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=dev(left))
        pltpu.semaphore_signal(barrier, inc=1, device_id=dev(right))
        pltpu.semaphore_wait(barrier, 2)

    partial = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    slot = jax.lax.rem(t, 2)

    def send_desc(s):
        return pltpu.make_async_remote_copy(
            send_buf.at[s], acc_buf.at[s], send_sem.at[s],
            recv_sem.at[s], device_id=dev(right))

    @pl.when(t == 0)
    def _():
        send_buf[0] = partial

    @pl.when(t > 0)
    def _():
        prev = jax.lax.rem(t + 1, 2)
        pltpu.make_async_remote_copy(
            send_buf.at[prev], acc_buf.at[prev], send_sem.at[prev],
            recv_sem.at[prev], device_id=dev(right)).wait_recv()
%(consume)s
        @pl.when(t == n - 1)
        def _():
            o_ref[...] = ship

    @pl.when(t < n - 1)
    def _():
        send_desc(slot).start()

    @pl.when(t == n - 1)
    def _():
        send_desc(jax.lax.rem(t + 1, 2)).wait_send()

        @pl.when(n > 2)
        def _():
            send_desc(slot).wait_send()


def dispatch(x, w, axis_name="tp"):
    n = jax.lax.axis_size(axis_name)
%(guard)s
    return pl.pallas_call(
        functools.partial(_kernel, n=n, axis_name=axis_name),
        grid=(n,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0)),
                  pl.BlockSpec((128, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, 8, 128), jnp.float32),
            pltpu.VMEM((2, 8, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
    )(x, w)
"""

_GUARD = """\
    if n < 2:
        raise ValueError("ring of >= 2 devices required")
"""

# the SHIPPED protocol: read, credit only for reused slots, both waits
# before the slot-reuse write
_CONSUME_GOOD = """\
        ship = acc_buf[prev] + partial

        @pl.when(t < n - 2)
        def _():
            pltpu.semaphore_signal(cap_sem, inc=1, device_id=dev(left))

        @pl.when(t < n - 1)
        def _():
            @pl.when(t >= 2)
            def _():
                send_desc(slot).wait_send()
                pltpu.semaphore_wait(cap_sem, 1)
            send_buf[slot] = ship
"""

# PR 9 review round 1, verbatim shape: credit signalled for EVERY t>0
# (n-3 never consumed at n>=4) and the slot-reuse write lands BEFORE
# the send-wait/credit-wait that licenses it
_CONSUME_RACE1 = """\
        ship = acc_buf[prev] + partial
        pltpu.semaphore_signal(cap_sem, inc=1, device_id=dev(left))

        @pl.when(t < n - 1)
        def _():
            send_buf[slot] = ship      # RACE1: write before the waits

            @pl.when(t >= 2)
            def _():
                send_desc(slot).wait_send()
                pltpu.semaphore_wait(cap_sem, 1)
"""

# PR 9 review round 2, verbatim shape: the slot credit returns BEFORE
# acc_buf[prev] is read — an eager upstream overwrites the slot mid-read
_CONSUME_RACE2 = """\
        @pl.when(t < n - 2)
        def _():
            pltpu.semaphore_signal(cap_sem, inc=1, device_id=dev(left))

        ship = acc_buf[prev] + partial  # RACE2: read after credit

        @pl.when(t < n - 1)
        def _():
            @pl.when(t >= 2)
            def _():
                send_desc(slot).wait_send()
                pltpu.semaphore_wait(cap_sem, 1)
            send_buf[slot] = ship
"""


def _rdma_fixture(consume, guard=_GUARD):
    return _RDMA_TEMPLATE % {"consume": consume, "guard": guard}


GOOD_KERNEL = _rdma_fixture(_CONSUME_GOOD)
RACE1 = _rdma_fixture(_CONSUME_RACE1)
RACE2 = _rdma_fixture(_CONSUME_RACE2)
UNGUARDED = _rdma_fixture(
    _CONSUME_GOOD, guard="    del axis_name  # no ring-size guard\n")


def apx2(res, *, suppressed=False):
    return {f.rule for f in (res.suppressed() if suppressed
                             else res.unsuppressed())
            if f.rule.startswith("APX2")}


# ---------------------------------------------------------------------------
# the protocol micro-model-checker
# ---------------------------------------------------------------------------

class TestProtocolChecker:
    def test_good_kernel_clean(self):
        """The shipped protocol, verbatim as a fixture: no APX2xx
        findings at any ring size — the falsifiable negative for both
        race tests below."""
        res = run_lint(GOOD_KERNEL)
        assert not apx2(res), [f.render() for f in res.unsuppressed()]

    def test_race1_write_before_wait_flagged(self, monkeypatch):
        """PR 9 review round 1: the torn write is flagged AT ITS LINE
        (APX202) and the over-signalled credits as unpaired/undrained
        (APX201). Ring sizes capped at 4 here — the race first
        reproduces at n=4 and the un-flow-controlled fixture's n=5/6
        state spaces cost ~15s of tier-1 for no extra signal
        (test_kernel_rules_registered pins the default 1..6 sweep)."""
        import apex1_tpu.lint.kernels as K
        monkeypatch.setattr(K, "RING_SIZES", (1, 2, 3, 4))
        res = run_lint(RACE1)
        got = apx2(res)
        assert "APX202" in got and "APX201" in got, \
            [f.render() for f in res.unsuppressed()]
        wline = line_of(RACE1, "RACE1: write before the waits")
        torn = [f for f in res.unsuppressed() if f.rule == "APX202"
                and f.line == wline]
        assert torn, [f.render() for f in res.unsuppressed()]
        assert "still reading it" in torn[0].message

    def test_race2_signal_before_read_flagged(self):
        """PR 9 review round 2: the credit-before-read race is flagged
        at the read line as a schedule-dependent payload — and ONLY
        that (conservation and liveness are clean, exactly like the
        original bug)."""
        res = run_lint(RACE2)
        assert apx2(res) == {"APX202"}, \
            [f.render() for f in res.unsuppressed()]
        rline = line_of(RACE2, "RACE2: read after credit")
        bad = [f for f in res.unsuppressed() if f.rule == "APX202"]
        assert all(f.line == rline for f in bad)
        # ONE defect, one finding — ring sizes aggregate in the
        # message instead of multiplying near-identical findings
        assert len(bad) == 1, [f.render() for f in bad]
        # the race needs slot reuse: first reproducible ring size is 4
        assert "n=4,5,6" in bad[0].message

    def test_n1_hang_flagged_without_guard(self):
        """The n==1 never-started-DMA hang (PR 9 round 2): without a
        ring-size guard the kernel is flagged APX203 (hang) + APX204
        (missing guard)."""
        res = run_lint(UNGUARDED)
        got = apx2(res)
        assert "APX203" in got and "APX204" in got, \
            [f.render() for f in res.unsuppressed()]
        hang = [f for f in res.unsuppressed() if f.rule == "APX203"]
        assert any("n=1" in f.message for f in hang)

    def test_guard_licenses_n1_skip(self):
        """The falsifiable negative to the hang check: the SAME kernel
        with the `if n < 2: raise` guard loses both findings."""
        res = run_lint(GOOD_KERNEL)
        assert "APX203" not in codes(res)
        assert "APX204" not in codes(res)

    def test_nested_kernel_is_checked_not_its_wrapper(self):
        """Review fix: a protocol kernel DEFINED INSIDE its dispatch
        function must be the simulated subject — the wrapper (which
        `ast.walk` also sees the semaphore ops through) must get no
        bogus 'cannot be model-checked' finding, and a race in the
        nested kernel must still flag."""
        nested = HEADER + textwrap.dedent("""
        def dispatch(x, w, axis_name="tp"):
            n = jax.lax.axis_size(axis_name)
            if n < 2:
                raise ValueError("ring required")

            def _kern(x_ref, o_ref, acc_buf, send_sem, recv_sem, *,
                      n, axis_name):
                t = pl.program_id(0)
                d = pltpu.make_async_remote_copy(
                    acc_buf.at[0], acc_buf.at[0], send_sem.at[0],
                    recv_sem.at[0], device_id=1)

                @pl.when(t == 0)
                def _():
                    d.start()

                @pl.when(t == n - 1)
                def _():
                    o_ref[...] = acc_buf[0]   # read, but NO wait_recv
                    d.wait_send()

            return pl.pallas_call(
                functools.partial(_kern, n=n, axis_name=axis_name),
                grid=(n,))(x, w)
        """)
        res = run_lint(nested)
        msgs = [f for f in res.unsuppressed() if f.rule == "APX201"]
        assert not any("cannot be model-checked" in f.message
                       for f in msgs), [f.render() for f in msgs]
        # the un-waited recv_sem never drains; the unordered read races
        got = apx2(res)
        assert "APX201" in got, [f.render() for f in res.unsuppressed()]
        assert all("_kern" in f.message for f in msgs)

    def test_whole_ref_write_aliases_every_slot(self, monkeypatch):
        """Review fix: `send_buf[...] = ship` (whole-ref) must conflict
        with an in-flight DMA reading slot 1 — collapsing it to slot 0
        certified torn sends on slots 1+ as clean. The slot-indexed
        twin (GOOD_KERNEL) stays the falsifiable negative. Ring sizes
        capped at 4: the aliasing write de-flow-controls the fixture
        and the race already reproduces at n=3."""
        import apex1_tpu.lint.kernels as K
        monkeypatch.setattr(K, "RING_SIZES", (1, 2, 3, 4))
        aliased = GOOD_KERNEL.replace("send_buf[slot] = ship",
                                      "send_buf[...] = ship")
        res = run_lint(aliased)
        bad = [f for f in res.unsuppressed() if f.rule == "APX202"]
        assert any("still reading it" in f.message for f in bad), \
            [f.render() for f in res.unsuppressed()]

    def test_ordered_whole_ref_read_not_a_race(self):
        """Review fix: a whole-ref read AFTER both slots' recv waits is
        deterministic — per-slot payloads are distinct by design, and
        keying observations per slot must not read as a race."""
        src = HEADER + textwrap.dedent("""
        def _kern(x_ref, o_ref, sbuf, rbuf, send_sem, recv_sem, *, n,
                  axis_name):
            t = pl.program_id(0)

            def desc(s):
                return pltpu.make_async_remote_copy(
                    sbuf.at[s], rbuf.at[s], send_sem.at[s],
                    recv_sem.at[s], device_id=1)

            @pl.when(t == 0)
            def _():
                sbuf[0] = x_ref[...]
                sbuf[1] = x_ref[...]
                desc(0).start()
                desc(1).start()

            @pl.when(t == n - 1)
            def _():
                desc(0).wait_send()
                desc(1).wait_send()
                desc(0).wait_recv()
                desc(1).wait_recv()
                o_ref[...] = rbuf[...]

        def go(x, axis_name):
            n = jax.lax.axis_size(axis_name)
            if n < 2:
                raise ValueError
            return pl.pallas_call(
                functools.partial(_kern, n=n, axis_name=axis_name),
                grid=(n,))(x)
        """)
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX202"]
        assert not bad, [f.render() for f in bad]

    def test_kwonly_default_helper_is_modelable(self):
        """Review fix: a kw-only default on an in-kernel helper must
        bind like a positional default, not fall out of the fragment."""
        src = HEADER + textwrap.dedent("""
        def _kern(x_ref, o_ref, send_sem, *, n, axis_name):
            t = pl.program_id(0)

            def sig(*, amount=1):
                pltpu.semaphore_signal(send_sem, inc=amount,
                                       device_id=1)

            @pl.when(t == 0)
            def _():
                sig()

            @pl.when(t == n - 1)
            def _():
                pltpu.semaphore_wait(send_sem, 1)

        def go(x, axis_name):
            n = jax.lax.axis_size(axis_name)
            if n < 2:
                raise ValueError
            return pl.pallas_call(
                functools.partial(_kern, n=n, axis_name=axis_name),
                grid=(n,))(x)
        """)
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX201"
               and "cannot be model-checked" in f.message]
        assert not bad, [f.render() for f in bad]

    def test_unmodelable_kernel_flagged(self):
        src = """
            import functools
            import jax
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu
            import apex1_tpu

            def _kern(x_ref, o_ref, sem, *, n, axis_name):
                v = x_ref[...]

                @pl.when(v > 0)       # data-dependent predicate
                def _():
                    pltpu.semaphore_wait(sem, 1)

            def go(x, axis_name):
                n = jax.lax.axis_size(axis_name)
                if n < 2:
                    raise ValueError
                return pl.pallas_call(
                    functools.partial(_kern, n=n, axis_name=axis_name),
                    grid=(n,))(x)
        """
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX201"]
        assert bad and "cannot be model-checked" in bad[0].message

    def test_apx2xx_suppression_grammar(self):
        """The APX1xx suppression grammar covers the new family:
        slug or code, reason mandatory."""
        marked = UNGUARDED.replace(
            "    return pl.pallas_call(",
            "    return pl.pallas_call(  # graftlint: allow(ring-guard)"
            " -- fixture: single-host smoke only")
        res = run_lint(marked)
        assert "APX204" not in codes(res)
        sup = [f for f in res.suppressed() if f.rule == "APX204"]
        assert sup and sup[0].reason.startswith("fixture:")

    def test_shipped_rdma_kernel_verifies_clean(self):
        """THE must-pass case: the real ops/fused_collective.py —
        protocol model-checked at n=2..6 (n==1 skipped: its dispatch
        is ring-size-guarded), mesh + budget passes included."""
        from apex1_tpu.lint import lint_files
        res = lint_files(
            [os.path.join(REPO, "apex1_tpu", "ops",
                          "fused_collective.py")],
            root=REPO, kernels=True)
        bad = [f for f in res.unsuppressed()
               if f.rule.startswith("APX2")]
        assert not bad, [f.render() for f in bad]


# ---------------------------------------------------------------------------
# mesh/collective consistency
# ---------------------------------------------------------------------------

class TestMeshRules:
    def test_ppermute_bijection_positive(self):
        src = """
            import jax

            def bad_ring(x, axis_name):
                n = jax.lax.axis_size(axis_name)
                perm = [(i, (i * 0) % n) for i in range(n)]
                return jax.lax.ppermute(x, axis_name, perm)
        """
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX205"]
        assert bad and "duplicate destination" in bad[0].message

    def test_ppermute_ring_and_partial_clean(self):
        src = """
            import jax

            def ring(x, axis_name):
                n = jax.lax.axis_size(axis_name)
                perm = [(i, (i + 1) % n) for i in range(n)]
                return jax.lax.ppermute(x, axis_name, perm)

            def shift_no_wrap(x, axis_name):
                n = jax.lax.axis_size(axis_name)
                # partial permutations are legal (halo edge shifts)
                perm = [(i, i + 1) for i in range(n - 1)]
                return jax.lax.ppermute(x, axis_name, perm)
        """
        res = run_lint(src)
        assert "APX205" not in codes(res), \
            [f.render() for f in res.unsuppressed()]

    def test_ppermute_out_of_range(self):
        src = """
            import jax

            def off_by_one(x, axis_name):
                n = jax.lax.axis_size(axis_name)
                perm = [(i, i + 1) for i in range(n)]   # dst == n
                return jax.lax.ppermute(x, axis_name, perm)
        """
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX205"]
        assert bad and "outside" in bad[0].message

    def test_ppermute_unresolvable_is_skipped(self):
        src = """
            import jax

            def stages(x, axis_name, P):
                # P is a plain parameter, not the axis size: underclaim
                perm = [(i, (i + 1) % P) for i in range(P)]
                return jax.lax.ppermute(x, axis_name, perm)
        """
        assert "APX205" not in codes(run_lint(src))

    def test_axis_binding_positive_and_bound_literal(self):
        src = """
            import jax
            from jax.sharding import PartitionSpec as P

            def unbound(x):
                return jax.lax.psum(x, "nonexistent_axis")

            def bound(x):
                spec = P("tp")
                return jax.lax.psum(x, "tp")

            def contract(x, axis_name):
                return jax.lax.psum(x, axis_name)
        """
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX206"]
        assert len(bad) == 1 and "nonexistent_axis" in bad[0].message

    def test_exclusive_knob_def_without_guard(self):
        src = """
            def layer(x, overlap=False, fused=False):
                if fused:
                    return x * 2
                if overlap:
                    return x * 3
                return x
        """
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX207"]
        assert bad and "never raises" in bad[0].message

    def test_exclusive_knob_def_with_guard_clean(self):
        src = """
            def layer(x, overlap=False, fused=False):
                if overlap and fused:
                    raise ValueError("exclusive")
                return x
        """
        assert "APX207" not in codes(run_lint(src))

    def test_exclusive_knob_call_site(self):
        src = """
            def layer(x, overlap=False, fused=False):
                if overlap and fused:
                    raise ValueError("exclusive")
                return x

            def use(x, o):
                layer(x, overlap=True, fused=True)       # flagged
                layer(x, overlap=o, fused=False)         # fine
                layer(x, overlap=False, fused=True)      # fine
                layer(x, overlap=o, fused=True)          # fine: one
                #                         side is a runtime-guarded var
        """
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX207"]
        assert len(bad) == 1 and "mutually" in bad[0].message


# ---------------------------------------------------------------------------
# VMEM budget + kernel binding
# ---------------------------------------------------------------------------

_BUDGET_TEMPLATE = HEADER + """
def _k(x_ref, o_ref, acc):
    o_ref[...] = x_ref[...]

def go(x):
    return pl.pallas_call(
        _k,
        grid=(4,),
        in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((512, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((%(rows)s, 1024), jnp.float32)],
    )(x)
"""


class TestBudgetAndBinding:
    def test_vmem_over_budget_flagged(self):
        # 8192 x 1024 fp32 scratch = 32 MiB > the 16 MiB v5e budget
        res = run_lint(_BUDGET_TEMPLATE % {"rows": 8192})
        bad = [f for f in res.unsuppressed() if f.rule == "APX208"]
        assert bad and "planning budget" in bad[0].message

    def test_vmem_within_budget_clean(self):
        # the falsifiable negative: 512 x 1024 fp32 = 2 MiB fits
        res = run_lint(_BUDGET_TEMPLATE % {"rows": 512})
        assert "APX208" not in codes(res), \
            [f.render() for f in res.unsuppressed()]

    def test_arity_mismatch_flagged(self):
        src = HEADER + textwrap.dedent("""
            def _k(x_ref, o_ref):            # missing the scratch ref
                o_ref[...] = x_ref[...]

            def go(x):
                return pl.pallas_call(
                    _k,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct((32, 128),
                                                   jnp.float32),
                    scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
                )(x)
        """)
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX209"]
        assert bad and "arity" in bad[0].message

    def test_index_map_arity_flagged(self):
        src = HEADER + textwrap.dedent("""
            def _k(x_ref, o_ref):
                o_ref[...] = x_ref[...]

            def go(x):
                return pl.pallas_call(
                    _k,
                    grid=(4, 2),
                    in_specs=[pl.BlockSpec((8, 128),
                                           lambda i: (i, 0))],  # 1 != 2
                    out_specs=pl.BlockSpec((8, 128),
                                           lambda i, j: (i, j)),
                    out_shape=jax.ShapeDtypeStruct((32, 256),
                                                   jnp.float32),
                )(x)
        """)
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX209"]
        assert bad and "index_map" in bad[0].message

    def test_semaphore_used_as_buffer_flagged(self):
        src = HEADER + textwrap.dedent("""
            def _k(x_ref, o_ref, sem):
                sem[0] = x_ref[...]          # writing a semaphore

            def go(x):
                return pl.pallas_call(
                    _k,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct((32, 128),
                                                   jnp.float32),
                    scratch_shapes=[pltpu.SemaphoreType.REGULAR],
                )(x)
        """)
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX209"]
        assert bad and "data buffer" in bad[0].message

    def test_partial_bound_params_not_counted(self):
        """Review fix: functools.partial-bound params (kw AND leading
        positional) are consumed before Pallas binds refs — a standard
        idiom, not an arity mismatch."""
        src = HEADER + textwrap.dedent("""
        def _k(scale, x_ref, o_ref, gain=1.0):
            o_ref[...] = x_ref[...]

        def go(x):
            return pl.pallas_call(
                functools.partial(_k, 2.0, gain=3.0),
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((32, 128),
                                               jnp.float32),
            )(x)
        """)
        res = run_lint(src)
        bad = [f for f in res.unsuppressed() if f.rule == "APX209"]
        assert not bad, [f.render() for f in bad]

    def test_clean_wiring_no_findings(self):
        src = HEADER + textwrap.dedent("""
            def _k(x_ref, o_ref, acc):
                acc[0] = x_ref[...]
                o_ref[...] = acc[0]

            def go(x):
                return pl.pallas_call(
                    _k,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct((32, 128),
                                                   jnp.float32),
                    scratch_shapes=[pltpu.VMEM((2, 128), jnp.float32)],
                )(x)
        """)
        res = run_lint(src)
        assert not apx2(res), [f.render() for f in res.unsuppressed()]


# ---------------------------------------------------------------------------
# the ONE VMEM sizing model (satellite: dedup pinned bit-identical)
# ---------------------------------------------------------------------------

# frozen PRE-REFACTOR copies of tuning/registry.py's formulas (PR 3-9
# in-module versions). The shared apex1_tpu.vmem_model must reproduce
# them bit-for-bit — edit these only with a conscious re-gating.
_L, _D = 128, 2


def _orig_flash_grid(blocks, dims, es):
    bq, bk = blocks["block_q"], blocks["block_k"]
    dp = dims["Dp"]
    return (_D * es * (bq * dp + 2 * bk * dp) + _D * es * bq * dp
            + 4 * (bq * dp + 2 * bq * _L) + 2 * 4 * bq * bk)


# re-gated by PR 39 (the resident flash kernels): where the row's K and
# V (Sb keys) fit beside the dq kernel's tiles the frame is THAT one, the
# form `ops.attention` then takes; else the pre-refactor grid frame
def _orig_flash(blocks, dims, es, budget):
    bq, bk = blocks["block_q"], blocks["block_k"]
    dp = dims["Dp"]
    if "Sb" in dims:
        est = (_D * es * 2 * dims["Sb"] * dp + 3 * _D * es * bq * dp
               + 2 * (_D * 4 * 8 * bq + 4 * bq * _L) + 4 * bq * dp
               + 4 * 4 * bq * bk)
        if est <= budget:
            return True, est
    est = _orig_flash_grid(blocks, dims, es)
    return est <= budget, est


def _orig_row(n_passes):
    def check(blocks, dims, _es, budget):
        br = blocks["block_rows"]
        est = n_passes * _D * br * dims["lanes"] * 4
        return est <= budget, est
    return check


def _orig_linear_xent(blocks, dims, es, budget):
    bt, bv = blocks["block_t"], blocks["block_v"]
    hp = dims["Hp"]
    acc = 4 * (bt + bv) * hp
    est = (acc + _D * es * (bt + bv) * hp + 2 * 4 * bt * bv)
    return est <= budget and acc <= (budget // 4) * 3 // 4, est


def _orig_cm(blocks, dims, es, budget):
    bm, bn = blocks["block_m"], blocks["block_n"]
    kp = dims["Kp"]
    est = _D * es * (bm * kp + kp * bn) + _D * 4 * bm * bn
    return est <= budget, est


def _orig_agf(blocks, dims, es, budget):
    est = _orig_flash_grid(blocks, dims, es)
    bq, dp = blocks["block_q"], dims["Dp"]
    est += (_D * 4 * (bq * dp + bq * _L) + _D * 4 * bq * dp
            - _D * es * bq * dp)
    return est <= budget, est


def _orig_int8(blocks, dims, _es, budget):
    bn, bk = blocks["block_n"], blocks["block_k"]
    t = 1024
    est = (_D * (t * bk * 2 + bn * bk * 1 + bn * 4) + t * bn * 4)
    return est <= budget, est


# frozen as-landed copies of the PR 18 paged-decode formulas (no
# pre-refactor history — these pin the registry's gating against silent
# drift the same way; edit only with a conscious re-gating)
def _orig_paged(blocks, dims, es, budget):
    p = blocks["page_p"]
    dp, rq = dims["Dp"], dims["Rq"]
    est = (_D * es * 2 * p * dp + _D * 4 * rq * dp + _D * 4 * rq * dp
           + 4 * (rq * dp + 2 * rq * _L) + 2 * 4 * rq * p)
    return est <= budget, est


def _orig_fused_sample(blocks, dims, _es, budget):
    bv = blocks["block_v"]
    est = (_D * 4 * 8 * bv + 2 * _D * 4 * 8 * _L + 6 * 4 * 8 * bv)
    return est <= budget, est


# frozen as-landed copies of the PR 19 chunked-loss / fused-GLU / LoRA
# epilogue formulas (same no-silent-drift contract as _orig_paged)
def _orig_chunked_loss(blocks, dims, es, budget):
    cv = blocks["chunk_v"]
    hp = dims["Hp"]
    est = _D * 4 * 8 * cv + _D * es * 8 * hp + 4 * 8 * _L
    return est <= budget, est


def _orig_fused_swiglu(blocks, dims, es, budget):
    bt, bf = blocks["block_t"], blocks["block_f"]
    hp = dims["Hp"]
    est = (_D * es * (bt * hp + 2 * hp * bf) + _D * es * bt * bf
           + 2 * 4 * bt * bf)
    return est <= budget, est


def _orig_lora_epilogue(blocks, dims, es, budget):
    bv = blocks["block_v"]
    hp = dims["Hp"]
    est = (_D * es * 8 * hp + _D * es * 8 * bv + _D * es * 8 * hp
           + _D * es * 8 * bv + 4 * 8 * bv)
    return est <= budget, est


class TestVmemModelShared:
    _GRID = {
        "flash_attention": (_orig_flash,
                            [{"block_q": q, "block_k": k}
                             for q in (16, 128, 512)
                             for k in (16, 128, 512)],
                            [{"Dp": d, "Sb": 1024}
                             for d in (64, 128, 256)]),
        "fused_softmax": (_orig_row(3),
                          [{"block_rows": r}
                           for r in (8, 64, 512, 4096)],
                          [{"lanes": ln} for ln in (128, 512, 2048)]),
        "layer_norm": (_orig_row(5),
                       [{"block_rows": r} for r in (8, 512, 4096)],
                       [{"lanes": ln} for ln in (128, 2048)]),
        "rope": (_orig_row(6),
                 [{"block_rows": r} for r in (8, 512, 4096)],
                 [{"lanes": ln} for ln in (128, 2048)]),
        "xentropy": (_orig_row(2),
                     [{"block_rows": r} for r in (8, 512, 4096)],
                     [{"lanes": ln} for ln in (128, 2048)]),
        "bias_dropout_add": (_orig_row(4),
                             [{"block_rows": r} for r in (8, 4096)],
                             [{"lanes": ln} for ln in (128, 2048)]),
        "linear_xent": (_orig_linear_xent,
                        [{"block_t": t, "block_v": v}
                         for t in (16, 128, 512)
                         for v in (16, 256, 1024)],
                        [{"Hp": h} for h in (768, 4096)]),
        "fused_collective_matmul": (_orig_cm,
                                    [{"block_m": m, "block_n": n}
                                     for m in (16, 256, 1024)
                                     for n in (128, 512, 1024)],
                                    [{"Kp": k} for k in (128, 4096)]),
        "fused_ag_flash": (_orig_agf,
                           [{"block_q": q, "block_k": k}
                            for q in (16, 128, 512)
                            for k in (16, 512)],
                           [{"Dp": d, "Sb": 16384}
                            for d in (64, 128, 256)]),
        "int8_matmul": (_orig_int8,
                        [{"block_n": n, "block_k": k}
                         for n in (128, 256, 512)
                         for k in (128, 512, 1024)],
                        [{"N": 4096, "K": 4096}]),
        "paged_decode": (_orig_paged,
                         [{"page_p": p} for p in (8, 16, 64, 256, 2048)],
                         [{"Dp": d, "Rq": r}
                          for d in (128, 256)
                          for r in (8, 48, 512)]),
        "fused_sample": (_orig_fused_sample,
                         [{"block_v": v}
                          for v in (128, 1024, 25216, 50432, 1 << 20)],
                         [{"Vp": 50432}]),
        "chunked_loss": (_orig_chunked_loss,
                         [{"chunk_v": v}
                          for v in (128, 1024, 8192, 65536, 1 << 20)],
                         [{"Hp": h} for h in (128, 768, 4096, 8192)]),
        "fused_swiglu": (_orig_fused_swiglu,
                         [{"block_t": t, "block_f": f}
                          for t in (8, 128, 512)
                          for f in (128, 512, 2048)],
                         [{"Hp": h} for h in (128, 4096, 8192)]),
        "lora_epilogue": (_orig_lora_epilogue,
                          [{"block_v": v}
                           for v in (128, 2048, 50432, 1 << 20)],
                          [{"Hp": h, "Vp": 50432}
                           for h in (128, 4096, 8192)]),
    }

    def test_registry_gating_bit_identical(self):
        """THE dedup pin: every registry spec's check == the frozen
        pre-refactor formula, (ok, est) both, over a budget sweep that
        crosses every fits/doesn't boundary."""
        from apex1_tpu.tuning.registry import SPECS
        assert set(self._GRID) == set(SPECS)
        budgets = (2 * 2**20, 8 * 2**20, 16 * 2**20, 32 * 2**20)
        n_checked = 0
        for name, (orig, blocks_list, dims_list) in self._GRID.items():
            spec = SPECS[name]
            for blocks in blocks_list:
                for dims in dims_list:
                    for es in (1, 2, 4):
                        for budget in budgets:
                            assert spec.check(blocks, dims, es, budget) \
                                == orig(blocks, dims, es, budget), \
                                (name, blocks, dims, es, budget)
                            n_checked += 1
        assert n_checked > 1000   # the sweep is real, not vacuous

    def test_registry_checks_are_the_shared_objects(self):
        from apex1_tpu.tuning.registry import SPECS
        from apex1_tpu.vmem_model import CHECKS
        for name, spec in SPECS.items():
            assert spec.check is CHECKS[name], name

    @pytest.mark.parametrize("heads", [1, 2, 4])
    def test_flash_rows_layout_is_priced_by_the_deciding_functions(
            self, heads):
        """The ROWS layout's heads to a 128-lane block (PR 41) are a key of
        the SAME resident checks `ops.attention.flash_form` decides with:
        one head is the frozen frame bit for bit (the registry's
        `flash_check` included, which hands the key through), each head
        more costs its statistics, its zeroed operands and its live
        tiles, and the training cell's two heads of 64 fit a v5e where
        four at the same tiles do not."""
        from apex1_tpu.vmem_model import (budget_bytes, flash_check,
                                          flash_kv_row_check,
                                          flash_q_row_check)
        blocks = {"block_q": 512, "block_k": 512}
        v5e = budget_bytes("v5e")
        kv = {"Dp": 128, "Skp": 1024}
        qr = {"Dp": 128, "Sqp": 1024, "group": 1}
        got_kv = flash_kv_row_check(blocks, {**kv, "heads": heads}, 2, v5e)
        got_q = flash_q_row_check(blocks, {**qr, "heads": heads}, 2, v5e)
        assert flash_check(blocks, {"Dp": 128, "Sb": 1024, "heads": heads},
                           2, v5e) == (got_kv if got_kv[0] else
                                       flash_check(blocks, {"Dp": 128}, 2,
                                                   v5e))
        if heads == 1:
            assert got_kv == flash_kv_row_check(blocks, kv, 2, v5e) \
                == _orig_flash(blocks, {"Dp": 128, "Sb": 1024}, 2, v5e)
            assert got_q == flash_q_row_check(blocks, qr, 2, v5e)
            return
        less_kv = flash_kv_row_check(blocks, {**kv, "heads": heads - 1}, 2,
                                     v5e)
        less_q = flash_q_row_check(blocks, {**qr, "heads": heads - 1}, 2,
                                   v5e)
        assert got_kv[1] > less_kv[1] and got_q[1] > less_q[1]
        assert (got_kv[0], got_q[0]) == ((True, True) if heads == 2
                                         else (False, False))
        from apex1_tpu.ops.attention import flash_form
        form = flash_form(8 * heads, 8 * heads, 1024, 1024, 128 // heads,
                          packed=True)
        assert (form["layout"] == "rows") == (heads == 2)

    def test_rdma_rule_reproduces_gate_data_points(self):
        """The previously comment-only 16*chunk*N rule, now falsifiable:
        the aot gate's passing shape fits v5e with margin, the measured
        RESOURCE_EXHAUSTED shape does not."""
        from apex1_tpu.vmem_model import (budget_bytes, rdma_check,
                                          rdma_slot_bytes)
        assert rdma_slot_bytes(256, 512) == 16 * 256 * 512
        v5e = budget_bytes("v5e")
        ok, est = rdma_check(256, 1024, 512, 2, v5e)
        assert ok and est < v5e // 2          # "fits with margin"
        over, est2 = rdma_check(512, 1024, 1024, 2, v5e)
        assert not over and est2 > v5e

    def test_rdma_dispatch_enforces_budget(self):
        """matmul_reduce_scatter_rdma consumes the shared rule live: an
        over-budget shape raises the sizing ValueError, not a Mosaic
        RESOURCE_EXHAUSTED on silicon. (Checked through the sizing
        logic — off-TPU the entry raises NotImplementedError first, so
        drive the formula the dispatch calls.)"""
        from apex1_tpu.ops import fused_collective
        import inspect
        src = inspect.getsource(
            fused_collective.matmul_reduce_scatter_rdma)
        assert "rdma_check" in src and "raise ValueError" in src


# ---------------------------------------------------------------------------
# repo-wide self-check + CLI
# ---------------------------------------------------------------------------

class TestRepoKernelSelfCheck:
    def test_repo_kernels_clean(self):
        """The dogfood gate: the whole repo passes the APX2xx analyzer
        (every pallas_call site, the full shard_map surface), with any
        suppression carrying a reason."""
        res = lint_paths(["apex1_tpu", "tools", "examples"],
                         root=REPO, kernels=True)
        bad = res.unsuppressed()
        assert not bad, "unsuppressed findings:\n" + \
            "\n".join(f.render() for f in bad)
        for f in res.suppressed():
            assert f.reason and f.reason.strip(), f.render()

    def test_analyzer_actually_covers_the_repo(self):
        """Guard against a silently no-op analyzer: the site extractor
        must see the repo's pallas_call population and the protocol
        pass must model the RDMA kernel."""
        from apex1_tpu.lint import (collect_files, lint_files,
                                    module_name_for)
        from apex1_tpu.lint.project import build_project
        from apex1_tpu.lint.kernels.extract import (is_protocol_kernel,
                                                    pallas_sites)
        files = collect_files(["apex1_tpu"], root=REPO)
        named = {}
        for f in files:
            rel = os.path.relpath(f, REPO)
            named[rel] = (module_name_for(f, REPO),
                          open(f, encoding="utf-8").read())
        project = build_project(named)
        sites = pallas_sites(project)
        assert len(sites) >= 20, len(sites)
        protocol = [i for i in project.functions.values()
                    if is_protocol_kernel(project, i)
                    and i.name == "_mrs_rdma_kernel"]
        assert protocol, "the RDMA kernel fell out of the protocol scan"
        with_kernel = [s for s in sites if s.kernel is not None]
        assert len(with_kernel) >= 15, len(with_kernel)

    def test_kernel_rules_registered(self):
        from apex1_tpu.lint.kernels import KERNEL_RULES, RING_SIZES
        from apex1_tpu.lint.core import RULE_SLUGS
        assert [r.code for r in KERNEL_RULES] == [
            "APX201", "APX202", "APX203", "APX204", "APX205",
            "APX206", "APX207", "APX208", "APX209"]
        for r in KERNEL_RULES:
            assert RULE_SLUGS[r.code] == r.slug
        # the default sweep is the full 1..6 contract (the race tests
        # above cap it locally for wall-time only)
        assert RING_SIZES == (1, 2, 3, 4, 5, 6)

    def test_baseline_banked_with_kernel_family(self):
        path = os.path.join(REPO, "perf_results", "lint_baseline.json")
        doc = json.load(open(path))
        assert doc["ok"] is True
        assert doc["counts"]["unsuppressed"] == 0
        assert "APX201" in doc["rules"], \
            "re-bank with `python tools/lint.py --kernels --json`"


class TestCliKernels:
    def _run(self, *args, env_extra=None):
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               **(env_extra or {})}
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "lint.py"),
             *args],
            capture_output=True, text=True, cwd=REPO, env=env)

    def test_kernels_flag_finds_fixture_races(self, tmp_path):
        d = tmp_path / "apex1_tpu"
        d.mkdir()
        (d / "race.py").write_text(RACE2)
        p = self._run("--kernels", str(d))
        assert p.returncode == 1, p.stdout + p.stderr
        assert "APX202" in p.stdout

    def test_kernels_flag_clean_without_fixture(self, tmp_path):
        d = tmp_path / "apex1_tpu"
        d.mkdir()
        (d / "ok.py").write_text(GOOD_KERNEL)
        p = self._run("--kernels", str(d))
        assert p.returncode == 0, p.stdout + p.stderr

    def test_list_rules_includes_family(self):
        p = self._run("--list-rules")
        assert p.returncode == 0
        for code in ("APX201", "APX205", "APX208"):
            assert code in p.stdout

    def test_cli_kernels_path_is_jax_free(self, tmp_path):
        """The check_all step's cold-start contract: the --kernels CLI
        never imports jax (stub parents for apex1_tpu and
        apex1_tpu.core). Poison jax on the path — the analyzer must
        still run and still find the fixture race."""
        poison = tmp_path / "site"
        poison.mkdir()
        (poison / "jax.py").write_text(
            "raise ImportError('poisoned: the lint CLI must stay "
            "jax-free')\n")
        d = tmp_path / "apex1_tpu"
        d.mkdir()
        (d / "race.py").write_text(RACE2)
        p = self._run(
            "--kernels", str(d),
            env_extra={"PYTHONPATH": str(poison)})
        assert p.returncode == 1, p.stdout + p.stderr
        assert "poisoned" not in p.stderr
        assert "APX202" in p.stdout


# ---------------------------------------------------------------------------
# paged-decode block-table publish: the file-based golden/bug pair
# ---------------------------------------------------------------------------

FIXDIR = os.path.join(REPO, "tests", "fixtures", "kernels")


def _load_fixture(name):
    with open(os.path.join(FIXDIR, name)) as fh:
        return fh.read()


class TestPagedBtPublishFixtures:
    """ISSUE 18's protocol pair: the double-buffered block-table
    publish loop behind the paged KV pool, as on-disk fixtures under
    tests/fixtures/kernels/ (the golden and bug halves diff as ONE
    moved statement). Ring sizes are capped at 3-4: local-DMA devices
    never interact, so n=5/6 multiply per-device delivery timings into
    the state cap without adding schedules (the torn read first
    reproduces at n=3)."""

    def test_golden_publish_clean(self, monkeypatch):
        import apex1_tpu.lint.kernels as K
        monkeypatch.setattr(K, "RING_SIZES", (1, 2, 3, 4))
        src = _load_fixture("paged_bt_publish_golden.py")
        res = run_lint(src)
        assert not apx2(res), [f.render() for f in res.unsuppressed()]

    def test_torn_block_table_read_flagged(self, monkeypatch):
        import apex1_tpu.lint.kernels as K
        monkeypatch.setattr(K, "RING_SIZES", (1, 2, 3))
        src = _load_fixture("paged_bt_publish_torn_bt_bug.py")
        res = run_lint(src)
        assert apx2(res) == {"APX202"}, \
            [f.render() for f in res.unsuppressed()]
        wline = line_of(src, "BUG: torn block-table read")
        torn = [f for f in res.unsuppressed() if f.rule == "APX202"]
        assert len(torn) == 1, [f.render() for f in torn]
        assert torn[0].line == wline
        assert "still reading it" in torn[0].message

    def test_pair_differs_by_one_moved_statement(self):
        """The pair's contract: identical protocols modulo the write
        placement — so the flagged defect IS the moved line, not an
        unrelated drift between the files."""
        def code_lines(name):
            body = _load_fixture(name).split('"""', 2)[2]
            lines = [ln.split("#")[0].rstrip()
                     for ln in body.splitlines()]
            return [ln for ln in lines if ln.strip()]

        g = code_lines("paged_bt_publish_golden.py")
        b = code_lines("paged_bt_publish_torn_bt_bug.py")
        assert sorted(g) == sorted(b)
        assert g != b


class TestLoraPagePublishFixtures:
    """ISSUE 19's protocol pair: the double-buffered adapter-page
    publish loop behind the multi-tenant LoRA store
    (serving.lora.LoraAdapterStore.register phase 1), as on-disk
    fixtures under tests/fixtures/kernels/. Same race class as the
    block-table pair — a staging slot rewritten while the publish DMA
    from two steps ago is still reading it — but the torn payload here
    is adapter weights, not page indices: a decode step whose LoRA
    block-table row already names the page gathers a half-updated
    adapter. The golden/bug halves diff as ONE moved statement."""

    def test_golden_publish_clean(self, monkeypatch):
        import apex1_tpu.lint.kernels as K
        monkeypatch.setattr(K, "RING_SIZES", (1, 2, 3, 4))
        src = _load_fixture("lora_page_publish_golden.py")
        res = run_lint(src)
        assert not apx2(res), [f.render() for f in res.unsuppressed()]

    def test_torn_page_publish_flagged(self, monkeypatch):
        import apex1_tpu.lint.kernels as K
        monkeypatch.setattr(K, "RING_SIZES", (1, 2, 3))
        src = _load_fixture("lora_page_publish_torn_page_bug.py")
        res = run_lint(src)
        assert apx2(res) == {"APX202"}, \
            [f.render() for f in res.unsuppressed()]
        wline = line_of(src, "BUG: torn adapter-page publish")
        torn = [f for f in res.unsuppressed() if f.rule == "APX202"]
        assert len(torn) == 1, [f.render() for f in torn]
        assert torn[0].line == wline
        assert "still reading it" in torn[0].message

    def test_pair_differs_by_one_moved_statement(self):
        """The pair's contract: identical protocols modulo the write
        placement — so the flagged defect IS the moved line, not an
        unrelated drift between the files."""
        def code_lines(name):
            body = _load_fixture(name).split('"""', 2)[2]
            lines = [ln.split("#")[0].rstrip()
                     for ln in body.splitlines()]
            return [ln for ln in lines if ln.strip()]

        g = code_lines("lora_page_publish_golden.py")
        b = code_lines("lora_page_publish_torn_page_bug.py")
        assert sorted(g) == sorted(b)
        assert g != b
