"""graftlint suite: per rule family a positive fixture (the hazard is
found), a negative fixture (the clean idiom is NOT flagged), and a
suppressed fixture (the allow() grammar covers it, reason mandatory) —
plus the repo-wide self-check that makes the linter a tier-1 gate: the
installed ``apex1_tpu`` package must lint clean.

Fixtures are linted in memory through ``lint_sources`` — no tmpdir, no
subprocess — so the whole suite runs in well under a second. The CLI
surface (exit codes, --json, --changed plumbing) is covered at the
bottom via the real ``tools/lint.py``.
"""

import json
import os
import subprocess
import sys
import textwrap


from apex1_tpu.lint import (RULES, canonical_rule, lint_paths,
                            lint_sources)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_lint(src, path="fix/mod.py", modname="fix.mod"):
    return lint_sources({path: (modname, textwrap.dedent(src))})


def codes(res, *, suppressed=False):
    pool = res.suppressed() if suppressed else res.unsuppressed()
    return {f.rule for f in pool}


# ---------------------------------------------------------------------------
# APX101 host-sync
# ---------------------------------------------------------------------------

HOST_POS = """
    import jax
    import numpy as np

    @jax.jit
    def step(x):
        y = np.asarray(x)           # sync 1
        jax.device_get(y)           # sync 2
        return y.item()             # sync 3

    def helper(x):                  # hot only via the call below
        return x.tolist()

    @jax.jit
    def outer(x):
        return helper(x)
"""

HOST_NEG = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def step(x):
        return jnp.sum(x * 2)

    def host_loop(step_fn, xs):
        # host code may sync freely: not jit-reachable
        out = [np.asarray(step_fn(x)) for x in xs]
        return [o.item() for o in out]

    def callback_target(x):
        return np.asarray(x)        # runs host-side by construction

    @jax.jit
    def with_callback(x):
        jax.debug.callback(callback_target, x)
        return x
"""

HOST_SUP = """
    import jax
    import numpy as np

    @jax.jit
    def step(x):
        y = np.asarray(x)  # graftlint: allow(APX101) -- warmup-only path, measured free
        return y
"""


class TestHostSync:
    def test_positive(self):
        res = run_lint(HOST_POS)
        bad = [f for f in res.unsuppressed() if f.rule == "APX101"]
        assert len(bad) == 4, [f.render() for f in res.findings]
        # propagation: the helper called from a jit body is flagged too
        assert any("helper" in f.message for f in bad)

    def test_negative(self):
        res = run_lint(HOST_NEG)
        assert "APX101" not in codes(res), \
            [f.render() for f in res.unsuppressed()]

    def test_suppressed(self):
        res = run_lint(HOST_SUP)
        assert "APX101" not in codes(res)
        sup = [f for f in res.suppressed() if f.rule == "APX101"]
        assert len(sup) == 1
        assert sup[0].reason == "warmup-only path, measured free"


# ---------------------------------------------------------------------------
# APX102 retrace
# ---------------------------------------------------------------------------

RETRACE_POS = """
    import time
    import functools
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(5,))
    def bad_static(x, y):
        return x + y

    @functools.partial(jax.jit, static_argnames=("missing",))
    def bad_staticname(x, mode="a"):
        return x

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def bad_default(x, cfg={"a": 1}):
        return x

    @jax.jit
    def clocky(x):
        t = time.time()
        s = jnp.sum(x)
        if s > 0:
            return x
        lab = f"sum was {s}"
        return x * t
"""

RETRACE_NEG = """
    import functools
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1,))
    def ok_static(x, mode):
        if mode == "double":        # static python value: branch is fine
            return x * 2
        return x

    @jax.jit
    def ok_body(x, n_heads):
        n = jax.lax.axis_size("dp")     # static int at trace time
        if n > 1:
            x = jax.lax.psum(x, "dp")
        s = jnp.sum(x)
        if x.shape[0] > 2:              # shapes are static
            x = x[:2]
        if n_heads is not None:         # identity check is static
            x = x * n_heads
        # traced value used the right way:
        x = jnp.where(s > 0, x, -x)
        assert x.ndim >= 1, f"rank collapsed: {x.shape}"
        return x

    @jax.jit
    def ok_raise(x):
        s = jnp.sum(x)
        if x.shape[0] == 0:
            raise ValueError(f"empty input {x.shape}")
        return s
"""

RETRACE_SUP = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def warmup_probe(x):
        s = jnp.sum(x)
        if s > 0:  # graftlint: allow(retrace) -- eager-only probe, never jitted in prod
            return x
        return -x
"""


class TestRetrace:
    def test_positive(self):
        res = run_lint(RETRACE_POS)
        msgs = [f.message for f in res.unsuppressed()
                if f.rule == "APX102"]
        assert any("out of range" in m for m in msgs), msgs
        assert any("does not name a parameter" in m for m in msgs), msgs
        assert any("mutable default" in m for m in msgs), msgs
        assert any("time.time" in m for m in msgs), msgs
        assert any("python if on traced value 's'" in m
                   for m in msgs), msgs
        assert any("f-string" in m for m in msgs), msgs

    def test_negative(self):
        res = run_lint(RETRACE_NEG)
        assert "APX102" not in codes(res), \
            [f.render() for f in res.unsuppressed()]

    def test_suppressed(self):
        res = run_lint(RETRACE_SUP)
        assert "APX102" not in codes(res)
        assert codes(res, suppressed=True) == {"APX102"}


# ---------------------------------------------------------------------------
# APX103 prng-reuse
# ---------------------------------------------------------------------------

PRNG_POS = """
    import jax

    def double_draw(key):
        a = jax.random.normal(key, (2,))
        b = jax.random.uniform(key, (2,))
        return a + b

    def split_after_draw(key):
        a = jax.random.normal(key, (2,))
        k1, k2 = jax.random.split(key)      # splitting a used key
        return a, k1, k2

    def loop_reuse(key, n):
        tot = 0.0
        for _ in range(n):
            tot = tot + jax.random.normal(key)
        return tot
"""

PRNG_NEG = """
    import jax

    def chained(key):
        key, sub = jax.random.split(key)
        a = jax.random.normal(sub, (2,))
        key, sub = jax.random.split(key)
        b = jax.random.uniform(sub, (2,))
        return a + b

    def folded(key, n):
        tot = 0.0
        for i in range(n):
            sub = jax.random.fold_in(key, i)    # sanctioned base-key use
            tot = tot + jax.random.normal(sub)
        return tot

    def fanned(key, n):
        keys = jax.random.split(key, n)
        return jax.vmap(lambda k: jax.random.normal(k, (2,)))(keys)

    def branch_draw(key, flag):
        # one draw per path: never two draws from one key on ANY path
        if flag:
            return jax.random.normal(key)
        return jax.random.uniform(key)
"""

PRNG_SUP = """
    import jax

    def identical_masks(key):
        a = jax.random.bernoulli(key, 0.5, (4,))
        b = jax.random.bernoulli(key, 0.5, (4,))  # graftlint: allow(prng-reuse) -- tied masks are the contract here
        return a, b
"""

# pltpu.prng_seed consumes int32 COUNTER SEEDS, not keys: re-seeding in
# the forward kernel and again in the backward's mask recompute is the
# in-kernel stochasticity contract (ops.stochastic), not key reuse —
# even when the seed variable is key-NAMED. Deriving the seed with ONE
# jax.random.randint draw at the call site is the sanctioned idiom.
PRNG_KERNEL_NEG = """
    import jax
    from jax.experimental.pallas import tpu as pltpu

    def kernel_reseed(seed_rng, o_ref):
        pltpu.prng_seed(seed_rng, 0)        # fwd tile stream
        a = pltpu.prng_random_bits((8, 128))
        pltpu.prng_seed(seed_rng, 1)        # bwd recompute: NOT reuse
        b = pltpu.prng_random_bits((8, 128))
        o_ref[...] = a ^ b

    def call_site(rng, fwd, bwd, x):
        seed = jax.random.randint(rng, (), 0, 2**31 - 1)  # one draw
        y = fwd(x, seed)         # the int32 seed is reused freely by
        dx = bwd(x, seed)        # the fwd and bwd kernels — not a key
        return y, dx
"""

# the exemption must NOT leak: a real key double-drawn around kernel
# PRNG calls is still flagged
PRNG_KERNEL_POS = """
    import jax
    from jax.experimental.pallas import tpu as pltpu

    def mixed(key):
        a = jax.random.normal(key, (2,))
        pltpu.prng_seed(key, 0)             # exempt — not a consumption
        b = jax.random.uniform(key, (2,))   # second REAL draw: flagged
        return a, b
"""


class TestPrngReuse:
    def test_positive(self):
        res = run_lint(PRNG_POS)
        bad = [f for f in res.unsuppressed() if f.rule == "APX103"]
        assert len(bad) == 3, [f.render() for f in res.findings]
        assert any("loop-carried" in f.message for f in bad)

    def test_negative(self):
        res = run_lint(PRNG_NEG)
        assert "APX103" not in codes(res), \
            [f.render() for f in res.unsuppressed()]

    def test_suppressed(self):
        res = run_lint(PRNG_SUP)
        assert "APX103" not in codes(res)
        sup = res.suppressed()
        assert len(sup) == 1 and "tied masks" in sup[0].reason

    def test_kernel_prng_seed_is_not_key_reuse(self):
        res = run_lint(PRNG_KERNEL_NEG)
        assert "APX103" not in codes(res), \
            [f.render() for f in res.unsuppressed()]

    def test_kernel_prng_exemption_does_not_leak(self):
        res = run_lint(PRNG_KERNEL_POS)
        bad = [f for f in res.unsuppressed() if f.rule == "APX103"]
        assert len(bad) == 1, [f.render() for f in res.findings]
        assert "jax.random.uniform" in bad[0].message


# ---------------------------------------------------------------------------
# APX104 donation
# ---------------------------------------------------------------------------

DON_POS = """
    import jax

    def make(f):
        g = jax.jit(f, donate_argnums=(0,))

        def run(state, x):
            new_state = g(state, x)
            stale = state + 1          # read after donation
            return new_state, stale
        return run
"""

DON_NEG = """
    import jax

    def make(f):
        g = jax.jit(f, donate_argnums=(0,))

        def run(state, x):
            state = g(state, x)        # rebind revives the name
            return state + 1

        def run_tuple(state, x):
            state, aux = g(state, x), x * 2
            return state, aux
        return run, run_tuple

    class Engine:
        def __init__(self, f):
            self._step = jax.jit(f, donate_argnums=(1,))

        def step(self, params, pool, tok):
            # the engine idiom: donate + rebind in ONE statement
            tok, pool = self._step(params, pool, tok)
            return tok, pool
"""

DON_SUP = """
    import jax

    def make(f):
        g = jax.jit(f, donate_argnums=(0,))

        def run(state, x):
            out = g(state, x)
            probe = state  # graftlint: allow(donation) -- CPU-only debug harness, no donation there
            return out, probe
        return run
"""


class TestDonation:
    def test_positive(self):
        res = run_lint(DON_POS)
        bad = [f for f in res.unsuppressed() if f.rule == "APX104"]
        assert len(bad) == 1, [f.render() for f in res.findings]
        assert "'state'" in bad[0].message

    def test_negative(self):
        res = run_lint(DON_NEG)
        assert "APX104" not in codes(res), \
            [f.render() for f in res.unsuppressed()]

    def test_suppressed(self):
        res = run_lint(DON_SUP)
        assert "APX104" not in codes(res)
        assert codes(res, suppressed=True) == {"APX104"}


# ---------------------------------------------------------------------------
# suppression grammar
# ---------------------------------------------------------------------------

class TestSuppressionGrammar:
    def test_reason_is_mandatory(self):
        src = """
            import jax
            import numpy as np

            @jax.jit
            def step(x):
                return np.asarray(x)  # graftlint: allow(APX101)
        """
        res = run_lint(src)
        assert codes(res) == {"APX000", "APX101"}, \
            [f.render() for f in res.unsuppressed()]

    def test_unknown_rule_is_flagged(self):
        src = "x = 1  # graftlint: allow(APX999) -- whatever\n"
        res = run_lint(src)
        assert codes(res) == {"APX000"}

    def test_standalone_comment_covers_next_line(self):
        src = """
            import jax
            import numpy as np

            @jax.jit
            def step(x):
                # graftlint: allow(host-sync) -- covers the line below
                y = np.asarray(x)
                return y
        """
        res = run_lint(src)
        assert "APX101" not in codes(res)
        assert codes(res, suppressed=True) == {"APX101"}

    def test_multi_rule_allow(self):
        src = """
            import time
            import jax
            import numpy as np

            @jax.jit
            def step(x):
                return np.asarray(x) * time.time()  # graftlint: allow(APX101, APX102) -- fixture
        """
        res = run_lint(src)
        assert not res.unsuppressed(), \
            [f.render() for f in res.unsuppressed()]
        assert codes(res, suppressed=True) == {"APX101", "APX102"}

    def test_suppression_is_rule_specific(self):
        src = """
            import time
            import jax
            import numpy as np

            @jax.jit
            def step(x):
                return np.asarray(x) * time.time()  # graftlint: allow(APX101) -- only the sync
        """
        res = run_lint(src)
        assert codes(res) == {"APX102"}

    def test_marker_requires_reason(self):
        src = """
            def f(x):  # graftlint: hot
                return x
        """
        res = run_lint(src)
        assert codes(res) == {"APX000"}

    def test_standalone_directive_skips_comment_lines(self):
        # a multi-line marker comment must bind to the next CODE line
        # (the def), not the next comment line — the amp train_step
        # marker regression
        src = """
            import numpy as np

            # graftlint: hot -- first line of the marker comment,
            # which continues onto a second comment line
            def traced_by_contract(x):
                return np.asarray(x)
        """
        res = run_lint(src)
        assert codes(res) == {"APX101"}, \
            [f.render() for f in res.findings]

    def test_detached_marker_is_a_finding(self):
        # a marker binding to no function would silently change gate
        # coverage: fail loudly instead
        src = """
            x = 1
            # graftlint: hot -- nothing below is a def
            y = 2
        """
        res = run_lint(src)
        assert codes(res) == {"APX000"}
        assert any("detached" in f.message for f in res.unsuppressed())

    def test_marker_binds_to_innermost_function(self):
        # when a nested def is the enclosing function's first
        # statement both spans contain the def line; only the nested
        # function is the marker's subject — the enclosing factory may
        # do host work freely
        src = """
            import numpy as np

            def make(cfg):
                # graftlint: hot -- returned for the caller to jit
                def step(x):
                    return x
                host_probe = np.asarray(cfg).item()
                return step, host_probe
        """
        res = run_lint(src)
        assert not res.unsuppressed(), \
            [f.render() for f in res.unsuppressed()]

    def test_hot_marker_forces_reachability(self):
        src = """
            import numpy as np

            # graftlint: hot -- returned for the caller to jit
            def traced_by_contract(x):
                return np.asarray(x)
        """
        res = run_lint(src)
        assert codes(res) == {"APX101"}

    def test_cold_marker_severs_reachability(self):
        src = """
            import jax
            import numpy as np

            # graftlint: cold -- only ever run under pure_callback
            def host_side(x):
                return np.asarray(x)

            @jax.jit
            def step(x):
                return host_side(x)
        """
        res = run_lint(src)
        assert "APX101" not in codes(res)

    def test_canonical_rule_names(self):
        assert canonical_rule("APX103") == "APX103"
        assert canonical_rule("prng-reuse") == "APX103"
        assert canonical_rule("HOST-SYNC") == "APX101"  # case-blind
        assert canonical_rule("apx101") == "APX101"
        assert canonical_rule("nope") is None

    def test_syntax_error_is_reported_not_crashed(self):
        res = run_lint("def f(:\n")
        assert codes(res) == {"APX001"}


# ---------------------------------------------------------------------------
# the gate: repo-wide self-check (tier-1)
# ---------------------------------------------------------------------------

class TestRepoSelfCheck:
    def test_repo_self_check(self):
        """The installed apex1_tpu package (plus tools/ and examples/)
        lints clean: zero unsuppressed findings, and every suppression
        that exists carries a reason. THIS test is what makes graftlint
        a gate — a hazard introduced anywhere in the package fails
        tier-1, not just check_all."""
        res = lint_paths(["apex1_tpu", "tools", "examples"], root=REPO)
        bad = res.unsuppressed()
        assert not bad, "unsuppressed graftlint findings:\n" + \
            "\n".join(f.render() for f in bad)
        for f in res.suppressed():
            assert f.reason and f.reason.strip(), f.render()

    def test_rules_registered(self):
        assert [r.code for r in RULES] == [
            "APX101", "APX102", "APX103", "APX104"]


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

class TestCli:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "lint.py"),
             *args],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})

    def test_clean_repo_exits_zero_and_json(self):
        p = self._run("--json", "apex1_tpu/lint")
        assert p.returncode == 0, p.stdout + p.stderr
        doc = json.loads(p.stdout)
        assert doc["ok"] is True
        assert set(doc["rules"]) == {"APX101", "APX102", "APX103",
                                     "APX104"}

    def test_every_rule_positive_exits_nonzero(self, tmp_path):
        """One subprocess over a directory holding every rule family's
        positive fixture: the CLI must exit 1 and report all four
        codes. (One spawn, not four — each CLI start pays the jax
        import; the per-rule finding behavior is covered in-memory
        above.)"""
        d = tmp_path / "tools"
        d.mkdir()
        for name, fixture in [("host.py", HOST_POS),
                              ("retrace.py", RETRACE_POS),
                              ("prng.py", PRNG_POS),
                              ("don.py", DON_POS)]:
            (d / name).write_text(textwrap.dedent(fixture))
        p = self._run(str(d))
        assert p.returncode == 1, p.stdout + p.stderr
        for rule in ("APX101", "APX102", "APX103", "APX104"):
            assert rule in p.stdout, (rule, p.stdout)

    def test_nonexistent_path_fails_closed(self):
        # a typoed path in a CI job must not read as a passing gate
        p = self._run("apex1_tpu/no_such_dir_xyz")
        assert p.returncode == 2, p.stdout + p.stderr
        assert "no such path" in p.stderr

    def test_baseline_is_banked_and_clean(self):
        path = os.path.join(REPO, "perf_results", "lint_baseline.json")
        assert os.path.exists(path), \
            "perf_results/lint_baseline.json missing (bank it with " \
            "`python tools/lint.py --kernels --json > " \
            "perf_results/lint_baseline.json`)"
        doc = json.load(open(path))
        assert doc["ok"] is True
        assert doc["counts"]["unsuppressed"] == 0
