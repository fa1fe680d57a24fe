"""graftlint: the analyzer gates itself (tier-1 self-gate) and every rule
is exercised on a positive (flagging) and negative (clean) snippet.

The snippets are synthetic distillations of the bug each rule encodes —
the PR-1 thread deadlock, the gloo divergent-collective hang, key reuse,
host sync in fit loops, jit retracing, tracer branches, and swallowed
exceptions around collectives (see docs/design.md, "Concurrency & SPMD
contract").
"""

import json
import os
import textwrap

import pytest

from dask_ml_tpu.analysis import (
    RULES,
    all_rules,
    lint_paths,
    lint_source,
    main,
    per_rule_counts,
    render_json,
    render_text,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "dask_ml_tpu")
BASELINE = os.path.join(REPO, "tools", "graftlint_baseline.json")


def lint(src, **kw):
    return lint_source(textwrap.dedent(src), **kw)


def active(findings):
    return [f for f in findings if not f.suppressed]


def rule_ids(findings):
    return sorted({f.rule for f in findings})


@pytest.fixture(scope="module")
def pkg_lint(tmp_path_factory):
    """ONE full-package lint shared by every gate test (through the
    whole-project cache, so repeat calls inside the module are free)."""
    cache = str(tmp_path_factory.mktemp("graftlint") / "cache.json")
    findings, errors = lint_paths([PKG], cache=cache)
    return findings, errors


# ---------------------------------------------------------------------------
# the tier-1 self-gate: the library must lint clean
# ---------------------------------------------------------------------------

class TestPackageGate:
    def test_package_has_zero_unsuppressed_findings(self, pkg_lint):
        findings, errors = pkg_lint
        assert not errors, errors
        bad = active(findings)
        assert not bad, "\n".join(f.render() for f in bad)

    def test_every_suppression_carries_a_justification(self, pkg_lint):
        # bad-suppression findings are themselves active findings, so the
        # gate above covers this — but assert directly so a regression in
        # THAT wiring is also caught
        findings, _ = pkg_lint
        for f in findings:
            if f.suppressed:
                assert f.justification, f.render()

    def test_no_unused_suppressions(self, pkg_lint):
        # the zero-active gate covers this too (unused-suppression
        # findings are active), but assert by name: every justified
        # suppression in the library must still be EARNING its keep
        findings, _ = pkg_lint
        assert not [f for f in findings if f.rule == "unused-suppression"]

    def test_committed_baseline_matches(self, pkg_lint):
        # the ratchet's committed snapshot must match reality exactly:
        # no new findings, no stale entries (refresh via
        # `tools/lint.sh --rebaseline` after intentional changes)
        from dask_ml_tpu.analysis import baseline as bl

        findings, _ = pkg_lint
        snap = bl.load(BASELINE)
        delta = bl.compare(snap, findings, bl.baseline_root([PKG]))
        assert not delta["new"], [f.render() for f in delta["new"]]
        assert not delta["fixed"], delta["fixed"]

    def test_cli_gate_exit_zero(self, capsys):
        assert main([PKG]) == 0
        out = capsys.readouterr().out
        assert "0 finding(s)" in out

    def test_cli_ratchet_gate_exit_zero(self, capsys):
        assert main([PKG, "--baseline", BASELINE]) == 0
        out = capsys.readouterr().out
        assert "0 new, 0 stale" in out


# ---------------------------------------------------------------------------
# per-rule positive / negative snippets
# ---------------------------------------------------------------------------

class TestThreadDispatch:
    def test_flags_unguarded_pool(self):
        findings = lint("""
            from concurrent.futures import ThreadPoolExecutor

            def fan_out(run, tasks):
                with ThreadPoolExecutor(max_workers=4) as pool:
                    return list(pool.map(run, tasks))
        """)
        assert rule_ids(active(findings)) == ["thread-dispatch"]

    def test_flags_bare_thread(self):
        findings = lint("""
            import threading

            def go(fn):
                t = threading.Thread(target=fn)
                t.start()
        """)
        assert rule_ids(active(findings)) == ["thread-dispatch"]

    def test_guarded_pool_is_clean(self):
        findings = lint("""
            from concurrent.futures import ThreadPoolExecutor

            def fan_out(est, run, tasks):
                n_workers = 4
                if _uses_device_estimator(est):
                    n_workers = 1
                with ThreadPoolExecutor(max_workers=n_workers) as pool:
                    return list(pool.map(run, tasks))
        """)
        assert not active(findings)


class TestDivergentCollective:
    def test_flags_process_index_guard(self):
        findings = lint("""
            import jax

            def maybe_sync(x):
                if jax.process_index() == 0:
                    return jax.lax.psum(x, "data")
                return x
        """)
        assert rule_ids(active(findings)) == ["divergent-collective"]

    def test_flags_wall_clock_guard(self):
        findings = lint("""
            import time
            from jax.experimental import multihost_utils

            def heartbeat(flag, deadline):
                while time.monotonic() < deadline:
                    flag = multihost_utils.process_allgather(flag)
                return flag
        """)
        assert rule_ids(active(findings)) == ["divergent-collective"]

    def test_uniform_condition_is_clean(self):
        findings = lint("""
            import jax

            def sync(x, every_process_same_flag):
                if every_process_same_flag:
                    return jax.lax.psum(x, "data")
                return x
        """)
        assert not active(findings)

    def test_collective_outside_branch_is_clean(self):
        findings = lint("""
            import jax

            def sync(x):
                y = jax.lax.psum(x, "data")
                if jax.process_index() == 0:
                    log(y)
                return y
        """)
        assert not active(findings)


class TestKeyReuse:
    def test_flags_double_sample(self):
        findings = lint("""
            import jax

            def sample(key):
                a = jax.random.normal(key, (3,))
                b = jax.random.uniform(key, (3,))
                return a + b
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["key-reuse"]
        assert "already consumed" in fs[0].message

    def test_flags_double_split(self):
        findings = lint("""
            import jax

            def children(key):
                a = jax.random.split(key)
                b = jax.random.split(key)
                return a, b
        """)
        assert rule_ids(active(findings)) == ["key-reuse"]

    def test_flags_loop_carried_reuse(self):
        findings = lint("""
            import jax

            def draws(key, n):
                out = []
                for _ in range(n):
                    out.append(jax.random.normal(key, (3,)))
                return out
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["key-reuse"]
        assert "loop iteration" in fs[0].message

    def test_split_chain_is_clean(self):
        findings = lint("""
            import jax

            def sample(key):
                key, k1 = jax.random.split(key)
                a = jax.random.normal(k1, (3,))
                key, k2 = jax.random.split(key)
                b = jax.random.uniform(k2, (3,))
                return a + b
        """)
        assert not active(findings)

    def test_loop_with_resplit_is_clean(self):
        findings = lint("""
            import jax

            def draws(key, n):
                out = []
                for _ in range(n):
                    key, sub = jax.random.split(key)
                    out.append(jax.random.normal(sub, (3,)))
                return out
        """)
        assert not active(findings)

    def test_fold_in_is_exempt(self):
        findings = lint("""
            import jax

            def per_shard(key, n):
                return [jax.random.fold_in(key, i) for i in range(n)]
        """)
        assert not active(findings)

    def test_rebind_in_both_branches_is_clean(self):
        # a key refreshed on EVERY surviving path is fresh afterwards
        findings = lint("""
            import jax

            def sample(key, cond):
                a = jax.random.normal(key, (3,))
                if cond:
                    key = jax.random.PRNGKey(0)
                else:
                    key = jax.random.PRNGKey(1)
                b = jax.random.uniform(key, (3,))
                return a + b
        """)
        assert not active(findings)

    def test_rebind_in_one_branch_still_flags(self):
        # ...but refreshed on only ONE path is still a reuse on the other
        findings = lint("""
            import jax

            def sample(key, cond):
                a = jax.random.normal(key, (3,))
                if cond:
                    key = jax.random.PRNGKey(0)
                b = jax.random.uniform(key, (3,))
                return a + b
        """)
        assert rule_ids(active(findings)) == ["key-reuse"]

    def test_host_rng_modules_are_exempt(self):
        # stdlib random / np.random have no key argument: a repeated
        # first-arg Name there is data, not key reuse
        findings = lint("""
            import random
            import numpy as np

            def pick(xs):
                a = random.choice(xs)
                b = random.choice(xs)
                n = np.random.choice(xs)
                m = np.random.choice(xs)
                return a, b, n, m
        """)
        assert not active(findings)

    def test_exclusive_return_branches_are_clean(self):
        # the k_means init ladder: `if mode == a: return sample(key)`
        # followed by another use — exclusive via return, not a reuse
        findings = lint("""
            import jax

            def init(key, mode):
                if mode == "random":
                    return jax.random.normal(key, (3,))
                if mode == "choice":
                    return jax.random.choice(key, 10, (3,))
                raise ValueError(mode)
        """)
        assert not active(findings)


class TestHostSyncLoop:
    def test_flags_float_in_fit_loop(self):
        findings = lint("""
            def fit(self, X):
                for _ in range(10):
                    loss = step(X)
                    if float(loss) < 1e-3:
                        break
                return self
        """)
        assert rule_ids(active(findings)) == ["host-sync-loop"]

    def test_flags_item_and_asarray(self):
        findings = lint("""
            import numpy as np

            def fit_loop(state, xs):
                for x in xs:
                    state = step(state, x)
                    history.append(state.loss.item())
                    snap = np.asarray(state.w)
                return state
        """)
        assert len(active(findings)) == 2

    def test_boundary_sync_outside_loop_is_clean(self):
        findings = lint("""
            def fit(self, X):
                for _ in range(10):
                    loss = step(X)
                return float(loss)
        """)
        assert not active(findings)

    def test_non_fit_function_is_clean(self):
        findings = lint("""
            def render(self, rows):
                for r in rows:
                    print(float(r))
        """)
        assert not active(findings)

    def test_device_reduction_wrapped_sync_is_flagged(self):
        # the canonical convergence check: float(jnp.max(shift)) is a
        # per-iteration device sync — a dotted jnp/np reduction must not
        # read as host-side (only the BARE builtins do)
        findings = lint("""
            import jax.numpy as jnp

            def fit(self, X, tol):
                for _ in range(10):
                    shift = step(X)
                    if float(jnp.max(shift)) < tol:
                        break
                return self
        """)
        assert rule_ids(active(findings)) == ["host-sync-loop"]

    def test_shape_touch_is_clean(self):
        findings = lint("""
            def fit(self, X):
                for _ in range(10):
                    n = float(X.shape[0])
                return n
        """)
        assert not active(findings)


class TestJitInLoop:
    def test_flags_jit_in_loop(self):
        findings = lint("""
            import jax

            def train(xs):
                out = []
                for x in xs:
                    f = jax.jit(lambda v: v * 2)
                    out.append(f(x))
                return out
        """)
        assert rule_ids(active(findings)) == ["jit-in-loop"]

    def test_flags_partial_jit_in_loop(self):
        findings = lint("""
            import jax
            from functools import partial

            def train(xs):
                while xs:
                    step = partial(jax.jit, static_argnums=0)(make_step())
                    xs = step(xs)
        """)
        assert rule_ids(active(findings)) == ["jit-in-loop"]

    def test_hoisted_jit_is_clean(self):
        findings = lint("""
            import jax

            def train(xs):
                f = jax.jit(lambda v: v * 2)
                return [f(x) for x in xs]
        """)
        assert not active(findings)


class TestTracerBranch:
    def test_flags_branch_on_traced_arg(self):
        findings = lint("""
            import jax

            @jax.jit
            def absval(x):
                if x > 0:
                    return x
                return -x
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["tracer-branch"]
        assert "absval" in fs[0].message

    def test_static_argnames_is_clean(self):
        findings = lint("""
            import jax
            from functools import partial

            @partial(jax.jit, static_argnames=("mode",))
            def step(x, mode):
                if mode == "fast":
                    return x * 2
                return x
        """)
        assert not active(findings)

    def test_shape_and_none_checks_are_clean(self):
        findings = lint("""
            import jax

            @jax.jit
            def norm(x, w):
                if w is None:
                    return x
                if x.ndim == 2:
                    return x * w
                return x
        """)
        assert not active(findings)

    def test_undecorated_function_is_clean(self):
        findings = lint("""
            def absval(x):
                if x > 0:
                    return x
                return -x
        """)
        assert not active(findings)


class TestSwallowedCollective:
    def test_flags_broad_except(self):
        findings = lint("""
            import jax

            def agree(x):
                try:
                    return jax.lax.psum(x, "data")
                except Exception:
                    return x
        """)
        assert rule_ids(active(findings)) == ["swallowed-collective"]

    def test_flags_bare_except(self):
        findings = lint("""
            from jax.experimental import multihost_utils

            def agree(flag):
                try:
                    return multihost_utils.process_allgather(flag)
                except:
                    return flag
        """)
        assert rule_ids(active(findings)) == ["swallowed-collective"]

    def test_reraise_is_clean(self):
        findings = lint("""
            import jax

            def agree(x):
                try:
                    return jax.lax.psum(x, "data")
                except Exception:
                    log_failure()
                    raise
        """)
        assert not active(findings)

    def test_narrow_except_is_clean(self):
        findings = lint("""
            import jax

            def agree(x):
                try:
                    return jax.lax.psum(x, "data")
                except ValueError:
                    return x
        """)
        assert not active(findings)

    def test_no_collective_in_try_is_clean(self):
        findings = lint("""
            def host_only(path):
                try:
                    return open(path).read()
                except Exception:
                    return None
        """)
        assert not active(findings)


# ---------------------------------------------------------------------------
# v2 rules: stage-purity, unbounded-retry, checkpoint-schema-drift,
# undocumented-knob — pos+neg snippet per rule
# ---------------------------------------------------------------------------

class TestStagePurity:
    def test_flags_dispatch_in_pf_stage_reachable_helper(self):
        # the acceptance drill: inject a device program into a helper a
        # _pf_stage implementation reaches — the chain must be flagged
        findings = lint("""
            import numpy as np
            import jax.numpy as jnp

            class Est:
                def _prep(self, X):
                    x = np.asarray(X, np.float32)
                    return jnp.dot(jnp.asarray(x), jnp.asarray(x).T)

                def _pf_stage(self, X, y=None, **kwargs):
                    if kwargs:
                        return None
                    return self._prep(X)
        """)
        fs = [f for f in active(findings) if f.rule == "stage-purity"]
        assert fs, rule_ids(findings)
        assert "_pf_stage" in fs[0].message and "_prep" in fs[0].message

    def test_flags_collective_and_consume(self):
        findings = lint("""
            import jax

            class Est:
                def _pf_stage(self, X, y=None):
                    flag = jax.lax.psum(1, "data")
                    return self._pf_consume(X)
        """)
        ids = [f.rule for f in active(findings)]
        assert ids.count("stage-purity") == 2

    def test_transfer_only_stage_is_clean(self):
        # the real contract: host parse + jnp.asarray puts are LEGAL on
        # the worker thread (design.md §8: a put is not a program)
        findings = lint("""
            import numpy as np
            import jax.numpy as jnp

            class Est:
                def _host_pad(self, X):
                    x = np.asarray(X, np.float32)
                    return np.concatenate([x, np.zeros_like(x)])

                def _pf_stage(self, X, y=None, **kwargs):
                    if kwargs or isinstance(X, jnp.ndarray):
                        return None
                    return jnp.asarray(self._host_pad(X))
        """)
        assert not active(findings)

    def test_device_cast_flagged_host_cast_clean(self):
        findings = lint("""
            import numpy as np
            import jax.numpy as jnp

            class Bad:
                def _pf_stage(self, X, y=None):
                    return X.astype(jnp.float32)

            class Good:
                def _pf_stage(self, X, y=None):
                    return jnp.asarray(X.astype(np.float32))
        """)
        fs = [f for f in active(findings) if f.rule == "stage-purity"]
        assert len(fs) == 1
        assert fs[0].line == 7  # the jnp cast, not the np one


class TestUnboundedRetry:
    def test_flags_nonliteral_budget_without_deadline(self):
        findings = lint("""
            from dask_ml_tpu.resilience.retry import retry

            def pull(fetch, retries):
                return retry(fetch, retries=int(retries), backoff=0.1)
        """)
        assert rule_ids(active(findings)) == ["unbounded-retry"]

    def test_deadline_bounds_it(self):
        findings = lint("""
            from dask_ml_tpu.resilience.retry import retry

            def pull(fetch, retries):
                return retry(fetch, retries=int(retries), deadline=120.0)
        """)
        assert not active(findings)

    def test_literal_budget_is_clean(self):
        findings = lint("""
            from dask_ml_tpu.resilience.retry import retry

            def pull(fetch, lockstep):
                a = retry(fetch)                       # default budget
                b = retry(fetch, retries=5)            # literal
                c = retry(fetch, retries=0 if lockstep else 1)  # both literal
                return a, b, c
        """)
        assert not active(findings)

    def test_deadline_none_does_not_count(self):
        findings = lint("""
            from dask_ml_tpu.resilience.retry import retry

            def pull(fetch, n):
                return retry(fetch, retries=n, deadline=None)
        """)
        assert rule_ids(active(findings)) == ["unbounded-retry"]

    def test_unrelated_retry_suffixes_ignored(self):
        findings = lint("""
            def note(stats):
                stats.record_retry("tag")
        """)
        assert not active(findings)

    def test_shared_fault_budget_bounds_it(self):
        """PR 9: a non-None budget= (the per-fit shared FaultBudget,
        design.md §13) attempt-bounds the loop like a Deadline does."""
        findings = lint("""
            from dask_ml_tpu.resilience.retry import retry

            def pull(fetch, retries, budget):
                return retry(fetch, retries=int(retries), budget=budget)
        """)
        assert not active(findings)

    def test_budget_none_does_not_count(self):
        findings = lint("""
            from dask_ml_tpu.resilience.retry import retry

            def pull(fetch, n):
                return retry(fetch, retries=n, budget=None)
        """)
        assert rule_ids(active(findings)) == ["unbounded-retry"]


class TestSwallowedFault:
    """PR 9 satellite: the static twin of the chaos drill suite's
    'every fault is observable' contract — a try/except around a
    FaultPlan-registered call site whose handler neither raises nor
    calls anything erases a fault from the books."""

    def _pkg(self, tmp_path, handler_body):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "sites.py").write_text(textwrap.dedent("""
            def maybe_fault(point):
                pass

            def read_block(path):
                maybe_fault("ingest")
                return path
        """))
        (pkg / "caller.py").write_text(textwrap.dedent(f"""
            from .sites import read_block

            def pull(path):
                try:
                    return read_block(path)
                except Exception:
                    {handler_body}
        """))
        return str(pkg)

    def test_silent_swallow_around_fault_site_flagged(self, tmp_path):
        findings, errors = lint_paths(
            [self._pkg(tmp_path, "return None")])
        assert not errors
        assert "swallowed-fault" in rule_ids(active(findings))

    def test_transitive_reach_through_helper_flagged(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "deep.py").write_text(textwrap.dedent("""
            def maybe_fault(point):
                pass

            def inner():
                maybe_fault("collective")

            def outer():
                return inner()

            def pull():
                try:
                    outer()
                except Exception:
                    pass
        """))
        findings, _ = lint_paths([str(pkg)])
        assert "swallowed-fault" in rule_ids(active(findings))

    def test_logging_handler_is_clean(self, tmp_path):
        findings, _ = lint_paths(
            [self._pkg(tmp_path, "logger.warning('fault dropped')")])
        assert "swallowed-fault" not in rule_ids(active(findings))

    def test_reraise_handler_is_clean(self, tmp_path):
        findings, _ = lint_paths([self._pkg(tmp_path, "raise")])
        assert "swallowed-fault" not in rule_ids(active(findings))

    def test_swallow_around_plain_call_is_clean(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "plain.py").write_text(textwrap.dedent("""
            def host_only(x):
                return x + 1

            def pull(x):
                try:
                    return host_only(x)
                except Exception:
                    return None
        """))
        findings, _ = lint_paths([str(pkg)])
        assert "swallowed-fault" not in rule_ids(active(findings))


class TestBlessedCompileThread:
    """PR-6 stage-purity extension: a Thread constructed with a literal
    name in ``_spmd.BLESSED_COMPILE_THREADS`` may COMPILE off the main
    thread (the ROADMAP [compile] compile-ahead worker); it still may
    not fetch, rendezvous, or run a dispatch surface — and ``_pf_stage``
    workers stay forbidden from compiling entirely."""

    def test_blessed_thread_compiling_is_clean(self):
        findings = lint("""
            import threading
            import jax

            def _warm_cache():
                jax.jit(lambda v: v).lower(1.0).compile()

            t = threading.Thread(
                target=_warm_cache, name="dask-ml-tpu-compile-ahead")
        """)
        assert not active(findings), rule_ids(active(findings))

    def test_blessed_thread_fetch_is_flagged(self):
        findings = lint("""
            import threading
            from dask_ml_tpu.core.sharded import unshard

            def _leak(x):
                return unshard(x)

            t = threading.Thread(
                target=_leak, name="dask-ml-tpu-compile-ahead")
        """)
        fs = [f for f in active(findings) if f.rule == "stage-purity"]
        assert fs and "blessed" in fs[0].message

    def test_blessed_thread_collective_is_flagged(self):
        findings = lint("""
            import threading
            import jax

            def _run():
                jax.lax.psum(1, "i")

            t = threading.Thread(
                target=_run, name="dask-ml-tpu-compile-ahead")
        """)
        assert "stage-purity" in rule_ids(active(findings))

    def test_unblessed_name_still_flags_thread_dispatch(self):
        findings = lint("""
            import threading
            import jax

            def _warm_cache():
                jax.jit(lambda v: v)(1.0)

            t = threading.Thread(
                target=_warm_cache, name="some-random-worker")
        """)
        assert rule_ids(active(findings)) == ["thread-dispatch"]

    def test_computed_name_is_not_blessed(self):
        # only a string LITERAL blesses: a computed name is unprovable
        findings = lint("""
            import threading
            import jax

            NAME = "dask-ml-tpu-compile-ahead"

            def _warm_cache():
                jax.jit(lambda v: v)(1.0)

            t = threading.Thread(target=_warm_cache, name=NAME)
        """)
        assert "thread-dispatch" in rule_ids(active(findings))

    def test_pf_stage_still_forbidden_from_compiling(self):
        # the blessing must NOT leak to staging workers: a _pf_stage
        # that compiles keeps flagging regardless of thread names
        findings = lint("""
            import jax

            class Est:
                def _pf_stage(self, X, y=None, **kwargs):
                    return jax.jit(lambda v: v)(X)
        """)
        assert "stage-purity" in rule_ids(active(findings))


class TestHostOnlyThreadNames:
    """PR-10 graftscope extension: a Thread constructed with a literal
    name in ``_spmd.HOST_ONLY_THREAD_NAMES`` (the readiness sampler,
    the metrics endpoint) is DECLARED host-only — the declaration lets
    thread-dispatch accept a target it cannot resolve (the stdlib
    ``serve_forever`` loop), because graftsan's dispatch detector holds
    that name to the contract at runtime.  A target that provably
    reaches device work still flags: the declaration forgives opacity,
    never evidence."""

    def test_unresolvable_target_with_host_only_name_is_clean(self):
        # the obs/serve.py shape: the submitted callable is a method on
        # a stdlib object the index cannot see into
        findings = lint("""
            import threading
            from http.server import HTTPServer

            def serve(server: HTTPServer):
                t = threading.Thread(
                    target=server.serve_forever, daemon=True,
                    name="dask-ml-tpu-metrics")
                t.start()
        """)
        assert "thread-dispatch" not in rule_ids(active(findings))

    def test_unresolvable_target_without_the_name_still_flags(self):
        findings = lint("""
            import threading
            from http.server import HTTPServer

            def serve(server: HTTPServer):
                t = threading.Thread(
                    target=server.serve_forever, daemon=True,
                    name="some-random-worker")
                t.start()
        """)
        assert "thread-dispatch" in rule_ids(active(findings))

    def test_provable_device_work_flags_despite_the_name(self):
        # the declaration must never beat evidence: a host-only-named
        # thread whose target provably dispatches is a contract
        # violation the static rule can see — flag it
        findings = lint("""
            import threading
            import jax

            def _rogue():
                jax.jit(lambda v: v)(1.0)

            t = threading.Thread(
                target=_rogue, name="dask-ml-tpu-scope")
        """)
        assert "thread-dispatch" in rule_ids(active(findings))

    def test_computed_host_only_name_does_not_declare(self):
        findings = lint("""
            import threading
            from http.server import HTTPServer

            NAME = "dask-ml-tpu-metrics"

            def serve(server: HTTPServer):
                t = threading.Thread(
                    target=server.serve_forever, name=NAME)
                t.start()
        """)
        assert "thread-dispatch" in rule_ids(active(findings))

    def test_host_only_is_not_blessed_to_compile(self):
        # HOST_ONLY and BLESSED_COMPILE are disjoint privileges: the
        # sampler/endpoint names must not inherit the compile-ahead
        # thread's compile allowance
        from dask_ml_tpu.analysis.rules._spmd import (
            BLESSED_COMPILE_THREADS, HOST_ONLY_THREAD_NAMES)

        assert not (BLESSED_COMPILE_THREADS & HOST_ONLY_THREAD_NAMES)


class TestJitOutsideCache:
    """PR-8: streamed-step jax.jit wraps must route through programs/
    (scope: reachable from partial_fit/_pf_stage/_pf_consume/
    _step_block; whole-array fit solvers are out of scope)."""

    def test_flags_decorated_step_on_stream_path(self):
        findings = lint("""
            import jax

            @jax.jit
            def _step(x):
                return x + 1

            class Est:
                def partial_fit(self, X):
                    return _step(X)
        """)
        fs = [f for f in active(findings) if f.rule == "jit-outside-cache"]
        assert fs and "cached_program" in fs[0].message

    def test_flags_wrap_at_assignment_through_helper_chain(self):
        # this repo's idiom: partial(jax.jit, ...)(fn), reached via
        # _pf_consume -> self._step_block -> the wrapped name
        findings = lint("""
            import jax
            from functools import partial

            def step(state, x):
                return state

            _jitted_step = partial(jax.jit, donate_argnames=("state",))(step)

            class Est:
                def _pf_consume(self, staged):
                    return self._step_block(staged)

                def _step_block(self, staged):
                    return _jitted_step(self._state, staged)
        """)
        assert "jit-outside-cache" in rule_ids(active(findings))

    def test_flags_bare_jit_import(self):
        findings = lint("""
            from jax import jit

            @jit
            def _moments(x):
                return x

            class Est:
                def partial_fit(self, X):
                    return _moments(X)
        """)
        assert "jit-outside-cache" in rule_ids(active(findings))

    def test_foreign_jit_clean(self):
        findings = lint("""
            from numba import jit

            @jit
            def _step(x):
                return x

            class Est:
                def partial_fit(self, X):
                    return _step(X)
        """)
        assert "jit-outside-cache" not in rule_ids(active(findings))

    def test_fit_only_solver_out_of_scope(self):
        # whole-array fit programs compile once per dataset shape — the
        # streaming recompile tax does not apply, so no finding
        findings = lint("""
            import jax

            @jax.jit
            def _solve(x):
                return x

            class Est:
                def fit(self, X):
                    return _solve(X)
        """)
        assert "jit-outside-cache" not in rule_ids(active(findings))

    def test_jit_not_on_stream_path_clean(self):
        findings = lint("""
            import jax

            @jax.jit
            def _other(x):
                return x

            class Est:
                def partial_fit(self, X):
                    return X
        """)
        assert "jit-outside-cache" not in rule_ids(active(findings))

    def test_cached_program_idiom_clean(self):
        findings = lint("""
            from dask_ml_tpu import programs

            def step(x):
                return x * 2

            _step = programs.cached_program(step, name="m.step")

            class Est:
                def partial_fit(self, X):
                    return _step(X)
        """)
        assert "jit-outside-cache" not in rule_ids(active(findings))

    def test_suppression_lives_only_in_cache_internals(self):
        """The one sanctioned suppression is programs/cache.py's own
        wrap; it must exist (and match, or it becomes an active
        unused-suppression finding)."""
        path = os.path.join(PKG, "programs", "cache.py")
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        assert "disable=jit-outside-cache" in src
        findings = lint_source(src, path=path)
        sup = [f for f in findings if f.rule == "jit-outside-cache"]
        assert sup and all(f.suppressed for f in sup)
        assert "unused-suppression" not in rule_ids(active(findings))


class TestRecompileRisk:
    """PR-6: the static twin of graftsan's compile sanitizer."""

    def test_flags_traced_param_in_reshape(self):
        findings = lint("""
            import jax

            @jax.jit
            def f(x, n):
                return x.reshape(n, -1)
        """)
        fs = [f for f in active(findings) if f.rule == "recompile-risk"]
        assert fs and "n" in fs[0].message and "static_argnames" in \
            fs[0].message

    def test_flags_partial_applied_idiom_with_propagation(self):
        # this repo's module-level wrap: partial(jax.jit, ...)(fn), and
        # the taint flows through a local arithmetic assignment
        findings = lint("""
            import jax
            import jax.numpy as jnp
            from functools import partial

            def step(state, n):
                m = n * 2
                return state + jnp.zeros(m)

            _jitted = partial(jax.jit, donate_argnames=("state",))(step)
        """)
        assert "recompile-risk" in rule_ids(active(findings))

    def test_flags_jit_call_form(self):
        findings = lint("""
            import jax
            import jax.numpy as jnp

            def g(x, k):
                return jnp.arange(k) + x

            wrapped = jax.jit(g)
        """)
        assert "recompile-risk" in rule_ids(active(findings))

    def test_static_argnames_is_clean(self):
        findings = lint("""
            import jax
            from functools import partial

            @partial(jax.jit, static_argnames=("n",))
            def f(x, n):
                return x.reshape(n, -1)
        """)
        assert not active(findings)

    def test_shape_touch_is_shielded(self):
        findings = lint("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                b = x.shape[0]
                return jnp.zeros(b) + x.reshape(x.shape[0], -1)
        """)
        assert not active(findings)

    def test_helper_call_result_does_not_taint(self):
        # a call's result is unknowable (usually a static shape helper):
        # treating it as tainted would flag every _pdim-style helper
        findings = lint("""
            import jax
            import jax.numpy as jnp

            def _pdim(x):
                return x.shape[1]

            @jax.jit
            def f(x):
                d = _pdim(x)
                return jnp.zeros(d)
        """)
        assert not active(findings)

    def test_data_arg_of_reshape_function_form_is_not_shape(self):
        findings = lint("""
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return jnp.reshape(x, (2, -1))
        """)
        assert not active(findings)

    def test_nonstandard_module_alias_resolves_as_function_form(self):
        # import-table resolution, not a hardcoded alias list: `jn` must
        # read as jax.numpy, so arg 0 is the DATA, not a shape position
        findings = lint("""
            import jax
            import jax.numpy as jn

            @jax.jit
            def f(x):
                return jn.reshape(x, (2, -1))
        """)
        assert not active(findings)


class TestCheckpointSchemaDrift:
    def test_flags_consumed_key_never_written(self):
        findings = lint("""
            class KM:
                def fit(self, X):
                    ckpt = self.fit_checkpoint
                    snap = ckpt.load_if_matches(self)
                    if snap is not None:
                        it, state = snap
                        centers = state["centres"]
                    for i in range(10):
                        centers = step(X)
                        ckpt.save(self, {"centers": centers}, i)
                    return self
        """)
        fs = [f for f in active(findings)
              if f.rule == "checkpoint-schema-drift"]
        assert len(fs) == 1
        assert "centres" in fs[0].message and "centers" in fs[0].message

    def test_flags_written_key_never_consumed(self):
        findings = lint("""
            class KM:
                def fit(self, X):
                    ckpt = self.fit_checkpoint
                    snap = ckpt.load_if_matches(self)
                    if snap is not None:
                        it, state = snap
                        centers = state["centers"]
                    for i in range(10):
                        centers, counts = step(X)
                        ckpt.save(self, {"centers": centers,
                                         "counts": counts}, i)
                    return self
        """)
        fs = [f for f in active(findings)
              if f.rule == "checkpoint-schema-drift"]
        assert len(fs) == 1 and "'counts'" in fs[0].message

    def test_matching_schema_is_clean(self):
        findings = lint("""
            class KM:
                def fit(self, X):
                    ckpt = self.fit_checkpoint
                    snap = ckpt.load_if_matches(self)
                    if snap is not None:
                        it, state = snap
                        centers = state["centers"]
                        counts = state["counts"]
                    for i in range(10):
                        centers, counts = step(X)
                        state = {"centers": centers, "counts": counts}
                        ckpt.save(self, state, i)
                        check_preemption(ckpt, self, state, i)
                    return self
        """)
        assert not active(findings)

    def test_state_through_local_helper_function(self):
        # the _sgd shape: the snapshot dict is built by a nested helper
        findings = lint("""
            def fit(est, X):
                ckpt = getattr(est, "fit_checkpoint", None)
                def _snapshot_state():
                    return {"state": est._state, "best": est._best}
                snap = ckpt.load_if_matches(est)
                if snap is not None:
                    epoch0, st = snap
                    est._state = st["state"]
                    est._best = st["best"]
                for e in range(10):
                    ckpt.save(est, _snapshot_state(), e)
        """)
        assert not active(findings)

    def test_wildcard_write_skips_module(self):
        # unresolvable snapshot (dict comprehension): wildcard, NOT clean
        # evidence and NOT a finding either
        findings = lint("""
            class IPCA:
                def _fit_state(self):
                    return {a: getattr(self, a) for a in self._ATTRS}

                def fit(self, X):
                    ckpt = self.fit_checkpoint
                    snap = ckpt.load_if_matches(self)
                    if snap is not None:
                        it, state = snap
                        anything = state["whatever"]
                    ckpt.save(self, self._fit_state(), 1)
        """)
        assert not active(findings)

    def test_np_save_is_not_checkpoint_traffic(self):
        findings = lint("""
            import numpy as np

            def dump(path, arr, meta):
                np.save(path, arr)
        """)
        assert not active(findings)


class TestUndocumentedKnob:
    def _tree(self, tmp_path, documented, read_name, via_constant=False):
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "api.md").write_text(
            f"| `{documented}` | int | a knob | — |\n"
            f"`DASK_ML_TPU_TEST_*` harness knobs\n"
        )
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        if via_constant:
            body = (f'KNOB = "{read_name}"\n'
                    f'import os\n'
                    f'def depth():\n'
                    f'    return int(os.environ.get(KNOB, "2"))\n')
        else:
            body = (f'import os\n'
                    f'def depth():\n'
                    f'    return int(os.environ.get("{read_name}", "2"))\n')
        (pkg / "mod.py").write_text(body)
        return str(pkg)

    def test_flags_undocumented_read(self, tmp_path):
        pkg = self._tree(tmp_path, "DASK_ML_TPU_DEPTH",
                         "DASK_ML_TPU_SECRET")
        findings, errors = lint_paths([pkg])
        assert not errors
        fs = [f for f in active(findings) if f.rule == "undocumented-knob"]
        assert len(fs) == 1 and "DASK_ML_TPU_SECRET" in fs[0].message

    def test_documented_read_is_clean(self, tmp_path):
        pkg = self._tree(tmp_path, "DASK_ML_TPU_DEPTH", "DASK_ML_TPU_DEPTH")
        findings, _ = lint_paths([pkg])
        assert not active(findings)

    def test_name_resolved_through_module_constant(self, tmp_path):
        # the pipeline/core.py shape: DEPTH_ENV = "DASK_ML_TPU_..." then
        # os.environ.get(DEPTH_ENV)
        pkg = self._tree(tmp_path, "DASK_ML_TPU_DEPTH",
                         "DASK_ML_TPU_HIDDEN", via_constant=True)
        findings, _ = lint_paths([pkg])
        fs = [f for f in active(findings) if f.rule == "undocumented-knob"]
        assert len(fs) == 1 and "DASK_ML_TPU_HIDDEN" in fs[0].message

    def test_wildcard_prefix_allows(self, tmp_path):
        pkg = self._tree(tmp_path, "DASK_ML_TPU_DEPTH",
                         "DASK_ML_TPU_TEST_SEED")
        findings, _ = lint_paths([pkg])
        assert not active(findings)

    def test_env_write_is_not_a_read(self, tmp_path):
        # propagating a knob into a spawned worker's env is a WRITE —
        # the _multihost_worker pattern — and must not flag
        pkg = self._tree(tmp_path, "DASK_ML_TPU_DEPTH", "DASK_ML_TPU_DEPTH")
        (tmp_path / "pkg" / "spawn.py").write_text(
            'import os\n'
            'def child_env():\n'
            '    env = dict(os.environ)\n'
            '    os.environ["DASK_ML_TPU_UNLISTED"] = "1"\n'
            '    return env\n')
        findings, _ = lint_paths([pkg])
        assert not [f for f in active(findings)
                    if f.rule == "undocumented-knob"]

    def test_no_api_md_in_reach_is_silent(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            'import os\nV = os.environ.get("DASK_ML_TPU_ANYTHING")\n')
        findings, _ = lint_paths([str(pkg)])
        assert not active(findings)


# ---------------------------------------------------------------------------
# interprocedural upgrades of the v1 rules
# ---------------------------------------------------------------------------

class TestInterproceduralThreadDispatch:
    def test_host_only_target_is_clean_without_guard(self):
        # the _multihost_worker drain shape: resolvable target, pipe
        # reads only — v1 forced a suppression here, v2 proves it clean
        findings = lint("""
            import threading

            def run_all(procs):
                outs = [None] * len(procs)

                def drain(i, p):
                    outs[i], _ = p.communicate(timeout=60)

                threads = [threading.Thread(target=drain, args=(i, p))
                           for i, p in enumerate(procs)]
                for t in threads:
                    t.start()
        """)
        assert not active(findings)

    def test_target_reaching_device_work_is_flagged(self):
        findings = lint("""
            import threading
            import jax.numpy as jnp

            def go(xs):
                def work():
                    return jnp.dot(xs, xs.T)

                threading.Thread(target=work).start()
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["thread-dispatch"]
        assert "work" in fs[0].message

    def test_dynamic_callable_target_still_flags(self):
        # the pipeline worker shape: the staged callable is a parameter —
        # nothing can be proven, the (justified) suppression stays
        findings = lint("""
            import threading

            def staged_iter(src, stage):
                def work():
                    return stage(next(src))

                threading.Thread(target=work).start()
        """)
        assert rule_ids(active(findings)) == ["thread-dispatch"]

    def test_pool_with_host_only_submit_is_clean(self):
        findings = lint("""
            from concurrent.futures import ThreadPoolExecutor

            def hash_all(chunks):
                def hash_chunk(c):
                    return hash(tuple(c))

                with ThreadPoolExecutor(max_workers=4) as pool:
                    return list(pool.map(hash_chunk, chunks))
        """)
        assert not active(findings)

    def test_second_device_target_not_masked_by_first_host_target(self):
        # regression: resolving thread targets went through an
        # id()-keyed memo with a transient synthesized Call node —
        # after GC the next target could inherit the PREVIOUS target's
        # resolution, judging a device-dispatching thread host-only
        findings = lint("""
            import threading
            import jax.numpy as jnp

            def host_work():
                return sum(range(10))

            def device_work(xs):
                return jnp.dot(xs, xs.T)

            def go(xs):
                t1 = threading.Thread(target=host_work)
                t2 = threading.Thread(target=device_work)
                t3 = threading.Thread(target=host_work)
                for t in (t1, t2, t3):
                    t.start()
        """)
        fs = [f for f in active(findings) if f.rule == "thread-dispatch"]
        assert len(fs) == 1
        assert "device_work" in fs[0].message

    def test_pool_submitting_partial_fit_is_flagged(self):
        findings = lint("""
            from concurrent.futures import ThreadPoolExecutor

            def train(model, blocks):
                def unit(b):
                    return model.partial_fit(b)

                with ThreadPoolExecutor(max_workers=4) as pool:
                    return list(pool.map(unit, blocks))
        """)
        assert rule_ids(active(findings)) == ["thread-dispatch"]

    def test_unmodelable_callee_shape_is_not_proven_host_only(self):
        # a registry-dispatched callable (subscript call) in the target:
        # nothing can be proven about it, so the Thread must still flag
        findings = lint("""
            import threading

            _CALLBACKS = []

            def worker():
                _CALLBACKS[0]()

            def go():
                threading.Thread(target=worker).start()
        """)
        assert rule_ids(active(findings)) == ["thread-dispatch"]

    def test_with_bound_pool_after_earlier_binding_stays_clean(self):
        # regression: ast.withitem has no lineno, so the with-pool's
        # submit used to bind to the EARLIER assignment, leaving the
        # with-pool "no submitted work visible" — a false positive
        findings = lint("""
            from concurrent.futures import ThreadPoolExecutor

            def run(chunks):
                def host(c):
                    return hash(c)

                pool = ThreadPoolExecutor(2)
                pool.submit(host, chunks[0])
                pool.shutdown()
                with ThreadPoolExecutor(2) as pool:
                    pool.submit(host, chunks[1])
        """)
        assert not active(findings)

    def test_unindexed_own_package_callee_is_not_proven_host_only(
            self, tmp_path):
        # single-FILE lint: the target calls into a sibling module of
        # the same package that is NOT in this lint's scope — the body
        # exists but cannot be seen, so the Thread must still flag
        pkg = tmp_path / "mypkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "ops.py").write_text(
            "import jax.numpy as jnp\n"
            "def device_helper(x):\n    return jnp.sum(x)\n")
        (pkg / "runner.py").write_text(
            "import threading\n"
            "from .ops import device_helper\n"
            "def go(x):\n"
            "    def work():\n"
            "        return device_helper(x)\n"
            "    threading.Thread(target=work).start()\n")
        # partial scope (runner only): unprovable → flags
        findings, _ = lint_paths([str(pkg / "runner.py")])
        assert "thread-dispatch" in rule_ids(active(findings))
        # full scope: resolvable, genuinely device-reaching → still flags
        findings_full, _ = lint_paths([str(pkg)])
        assert "thread-dispatch" in rule_ids(active(findings_full))

    def test_rebound_pool_variable_judged_per_binding(self):
        # two pools under one name: each constructor is judged on ITS
        # binding's submissions only (def-use chains, not a name match)
        findings = lint("""
            from concurrent.futures import ThreadPoolExecutor
            import jax.numpy as jnp

            def run(xs):
                def host(c):
                    return hash(c)

                def dev(c):
                    return jnp.sum(c)

                pool = ThreadPoolExecutor(2)
                pool.submit(host, xs)
                pool = ThreadPoolExecutor(2)
                pool.submit(dev, xs)
        """)
        fs = [f for f in active(findings) if f.rule == "thread-dispatch"]
        assert len(fs) == 1
        assert "dev" in fs[0].message


class TestInterproceduralDivergentCollective:
    def test_collective_through_helper_under_divergent_guard(self):
        findings = lint("""
            import jax
            from jax.experimental import multihost_utils

            def agree(flag):
                return multihost_utils.process_allgather(flag)

            def maybe(flag):
                if jax.process_index() == 0:
                    return agree(flag)
                return flag
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["divergent-collective"]
        assert "agree()" in fs[0].message

    def test_helper_without_collective_is_clean(self):
        findings = lint("""
            import jax

            def log_it(flag):
                print(flag)

            def maybe(flag):
                if jax.process_index() == 0:
                    log_it(flag)
                return flag
        """)
        assert not active(findings)


class TestInterproceduralKeyReuse:
    def test_helper_consuming_key_counts_as_use(self):
        findings = lint("""
            import jax

            def init_centers(X, key):
                return jax.random.choice(key, X.shape[0], (3,))

            def fit(X, key):
                c = init_centers(X, key)
                noise = jax.random.normal(key, (3,))
                return c + noise
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["key-reuse"]
        assert "init_centers" in fs[0].message

    def test_exclusive_helper_branches_are_clean(self):
        # the k_means _init_centers ladder, incl. a `with` body return
        findings = lint("""
            import jax

            def init_scalable(X, key):
                return jax.random.choice(key, X.shape[0], (3,))

            def init(X, key, mode, timer):
                if mode == "scalable":
                    with timer():
                        return init_scalable(X, key)
                if mode == "random":
                    return jax.random.choice(key, X.shape[0], (3,))
                key, sub = jax.random.split(key)
                return jax.random.normal(sub, (3,))
        """)
        assert not active(findings)

    def test_transitive_helper_consumption(self):
        findings = lint("""
            import jax

            def inner(k):
                return jax.random.normal(k, (3,))

            def outer(key):
                return inner(key)

            def fit(key):
                a = outer(key)
                b = outer(key)
                return a + b
        """)
        assert rule_ids(active(findings)) == ["key-reuse"]

    def test_helper_taking_fresh_subkeys_is_clean(self):
        findings = lint("""
            import jax

            def draw(k):
                return jax.random.normal(k, (3,))

            def fit(key, n):
                out = []
                for _ in range(n):
                    key, sub = jax.random.split(key)
                    out.append(draw(sub))
                return out
        """)
        assert not active(findings)


# ---------------------------------------------------------------------------
# unused suppressions
# ---------------------------------------------------------------------------

class TestUnusedSuppressions:
    def test_stale_suppression_is_flagged(self):
        findings = lint("""
            import jax

            def sample(key):
                k1, k2 = jax.random.split(key)
                a = jax.random.normal(k1, (3,))  # graftlint: disable=key-reuse -- left over from an old refactor
                return a
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["unused-suppression"]
        assert "key-reuse" in fs[0].message

    def test_used_suppression_is_not_flagged(self):
        findings = lint(TestSuppressions.SRC)
        assert not active(findings)

    def test_unused_not_reported_on_partial_runs(self):
        # --select runs a subset: the unselected rules' suppressions are
        # legitimately unmatched and must not be called stale
        src = """
            import jax

            def fit(self, key, xs):
                for x in xs:
                    print(float(step(x)))  # graftlint: disable=host-sync-loop -- boundary sync
        """
        assert not active(lint(src, select=["key-reuse"]))
        # ...but the full run DOES judge them (here the suppression is
        # used, so still clean)
        assert not active(lint(src))

    def test_unused_disable_all_cannot_hide_itself(self):
        findings = lint("""
            x = 1  # graftlint: disable=all -- nothing here ever flags
        """)
        assert rule_ids(active(findings)) == ["unused-suppression"]


# ---------------------------------------------------------------------------
# baseline ratchet
# ---------------------------------------------------------------------------

class TestBaseline:
    SRC_V1 = textwrap.dedent("""
        import jax

        def sample(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))  # graftlint: disable=key-reuse -- intentional correlated draws
            return a + b
    """)

    def _write_pkg(self, tmp_path, src):
        mod = tmp_path / "mod.py"
        mod.write_text(src)
        return str(tmp_path)

    def test_round_trip_and_clean_compare(self, tmp_path):
        from dask_ml_tpu.analysis import baseline as bl

        pkg = self._write_pkg(tmp_path, self.SRC_V1)
        findings, errors = lint_paths([pkg])
        root = bl.baseline_root([pkg])
        payload = bl.emit(findings, errors, root)
        path = tmp_path / "baseline.json"
        bl.write(str(path), payload)
        delta = bl.compare(bl.load(str(path)), findings, root)
        assert not delta["new"] and not delta["fixed"]

    def test_new_finding_detected(self, tmp_path):
        from dask_ml_tpu.analysis import baseline as bl

        pkg = self._write_pkg(tmp_path, self.SRC_V1)
        findings, errors = lint_paths([pkg])
        root = bl.baseline_root([pkg])
        snap = bl.emit(findings, errors, root)
        # add a second violation
        self._write_pkg(tmp_path, self.SRC_V1 + textwrap.dedent("""
            def more(key):
                c = jax.random.normal(key, (3,))
                d = jax.random.normal(key, (3,))
                return c + d
        """))
        findings2, _ = lint_paths([pkg])
        delta = bl.compare(snap, findings2, root)
        assert len(delta["new"]) == 1 and delta["new"][0].rule == "key-reuse"
        assert not delta["fixed"]

    def test_fixed_finding_reported_stale(self, tmp_path):
        from dask_ml_tpu.analysis import baseline as bl

        pkg = self._write_pkg(tmp_path, self.SRC_V1)
        findings, errors = lint_paths([pkg])
        root = bl.baseline_root([pkg])
        snap = bl.emit(findings, errors, root)
        self._write_pkg(tmp_path, "x = 1\n")
        findings2, _ = lint_paths([pkg])
        delta = bl.compare(snap, findings2, root)
        assert not delta["new"]
        assert {e["rule"] for e in delta["fixed"]} == {"key-reuse"}

    def test_fingerprint_survives_line_drift(self, tmp_path):
        # code inserted ABOVE the finding must not churn the baseline
        from dask_ml_tpu.analysis import baseline as bl

        pkg = self._write_pkg(tmp_path, self.SRC_V1)
        findings, errors = lint_paths([pkg])
        root = bl.baseline_root([pkg])
        snap = bl.emit(findings, errors, root)
        self._write_pkg(tmp_path, "# a new header comment\nVERSION = 1\n"
                        + self.SRC_V1)
        findings2, _ = lint_paths([pkg])
        delta = bl.compare(snap, findings2, root)
        assert not delta["new"] and not delta["fixed"]


# ---------------------------------------------------------------------------
# suppression mechanics
# ---------------------------------------------------------------------------

class TestSuppressions:
    SRC = """
        import jax

        def sample(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))  # graftlint: disable=key-reuse -- correlated draws are intentional here
            return a + b
    """

    def test_inline_suppression(self):
        findings = lint(self.SRC)
        assert not active(findings)
        sup = [f for f in findings if f.suppressed]
        assert len(sup) == 1
        assert sup[0].justification == "correlated draws are intentional here"

    def test_suppression_on_line_above(self):
        findings = lint("""
            import jax

            def sample(key):
                a = jax.random.normal(key, (3,))
                # graftlint: disable=key-reuse -- intentional
                b = jax.random.uniform(key, (3,))
                return a + b
        """)
        assert not active(findings)

    def test_disable_all(self):
        findings = lint("""
            import jax

            def sample(key):
                a = jax.random.normal(key, (3,))
                b = jax.random.uniform(key, (3,))  # graftlint: disable=all -- test fixture
                return a + b
        """)
        assert not active(findings)

    def test_bare_suppression_is_a_finding(self):
        findings = lint("""
            import jax

            def sample(key):
                a = jax.random.normal(key, (3,))
                b = jax.random.uniform(key, (3,))  # graftlint: disable=key-reuse
                return a + b
        """)
        assert "bad-suppression" in rule_ids(active(findings))

    def test_unknown_rule_id_is_a_finding(self):
        findings = lint("""
            x = 1  # graftlint: disable=no-such-rule -- whatever
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["bad-suppression"]
        assert "no-such-rule" in fs[0].message

    def test_inline_suppression_does_not_bleed_to_next_line(self):
        # an INLINE disable covers its own statement only; the next
        # line's unjustified violation must still fail the gate
        findings = lint("""
            import jax

            def sample(key, key2):
                a = jax.random.normal(key, (3,))
                c = jax.random.normal(key2, (3,))
                b = jax.random.uniform(key, (3,))  # graftlint: disable=key-reuse -- intentional
                d = jax.random.uniform(key2, (3,))
                return a + b + c + d
        """)
        assert rule_ids(active(findings)) == ["key-reuse"]

    def test_wrong_rule_id_does_not_suppress(self):
        findings = lint("""
            import jax

            def sample(key):
                a = jax.random.normal(key, (3,))
                b = jax.random.uniform(key, (3,))  # graftlint: disable=jit-in-loop -- wrong id
                return a + b
        """)
        assert "key-reuse" in rule_ids(active(findings))


# ---------------------------------------------------------------------------
# framework: registry, reporters, CLI
# ---------------------------------------------------------------------------

class TestFramework:
    def test_registry_has_all_rules(self):
        all_rules()  # force registration
        assert set(RULES) == {
            "thread-dispatch", "divergent-collective", "key-reuse",
            "host-sync-loop", "jit-in-loop", "tracer-branch",
            "swallowed-collective",
            # v2: project-wide contracts
            "stage-purity", "unbounded-retry", "checkpoint-schema-drift",
            "undocumented-knob",
            # PR 6: the static twin of graftsan's compile sanitizer
            "recompile-risk",
            # PR 8: streamed-step jits must route through programs/
            "jit-outside-cache",
            # PR 9: the static twin of the chaos drill suite
            "swallowed-fault",
            # ISSUE 12: every cached program makes a donation decision
            "donation-miss",
            # ISSUE 17 (graftlock): lock-order + shared-state ownership
            "lock-order-cycle", "unguarded-shared-state",
            "lock-held-across-dispatch",
            # ISSUE 20 (graftcontract): stringly-typed contract closure
            "contract-orphan-producer", "contract-dead-consumer",
            "contract-roster-drift", "contract-baseline-drift",
            "contract-undocumented-metric",
        }

    def test_select_unknown_rule_raises(self):
        with pytest.raises(KeyError):
            all_rules(["no-such-rule"])

    def test_select_filters(self):
        src = """
            import jax

            def fit(self, key, xs):
                for x in xs:
                    v = jax.random.normal(key, (3,))
                    print(float(v))
        """
        both = lint(src)
        assert set(rule_ids(active(both))) == {"key-reuse", "host-sync-loop"}
        only = lint(src, select=["key-reuse"])
        assert rule_ids(active(only)) == ["key-reuse"]

    def test_json_reporter(self):
        findings = lint("""
            import jax

            def sample(key):
                a = jax.random.normal(key, (3,))
                b = jax.random.uniform(key, (3,))
                return a + b
        """)
        payload = json.loads(render_json(findings))
        assert payload["version"] == 2
        assert payload["counts"]["key-reuse"]["active"] == 1
        assert payload["findings"][0]["rule"] == "key-reuse"
        assert "key-reuse" in payload["rules"]

    def test_text_reporter_counts_line(self):
        out = render_text([], [])
        assert "0 finding(s)" in out

    def test_per_rule_counts(self):
        findings = lint(TestSuppressions.SRC)
        counts = per_rule_counts(findings)
        assert counts["key-reuse"] == {"active": 0, "suppressed": 1}

    def test_bare_string_path_accepted(self):
        # a bare str must lint the path, not iterate its characters
        findings_str, errors_str = lint_paths(PKG)
        findings_list, errors_list = lint_paths([PKG])
        assert not errors_str
        assert len(findings_str) == len(findings_list)
        assert findings_str  # the 13 justified suppressions, at least

    def test_missing_path_is_an_error_not_a_clean_pass(self):
        findings, errors = lint_paths(["/no/such/dir/anywhere"])
        assert not findings
        assert errors and "no such file" in errors[0]

    def test_syntax_error_is_reported_not_skipped(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("def broken(:\n")
        findings, errors = lint_paths([str(bad)])
        assert errors and "syntax error" in errors[0]


class TestCLI:
    def test_exit_one_on_findings(self, tmp_path, capsys):
        f = tmp_path / "mod.py"
        f.write_text(textwrap.dedent("""
            import jax

            def sample(key):
                a = jax.random.normal(key, (3,))
                b = jax.random.uniform(key, (3,))
                return a + b
        """))
        assert main([str(f)]) == 1
        assert "key-reuse" in capsys.readouterr().out

    def test_exit_zero_on_clean(self, tmp_path, capsys):
        f = tmp_path / "mod.py"
        f.write_text("x = 1\n")
        assert main([str(f)]) == 0

    def test_exit_two_on_missing_path(self, capsys):
        assert main(["/no/such/dir/anywhere"]) == 2

    def test_exit_two_on_bad_select(self, tmp_path, capsys):
        f = tmp_path / "mod.py"
        f.write_text("x = 1\n")
        assert main([str(f), "--select", "bogus"]) == 2

    def test_json_format(self, tmp_path, capsys):
        f = tmp_path / "mod.py"
        f.write_text("x = 1\n")
        assert main([str(f), "--format", "json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["findings"] == []

    def test_list_rules(self, capsys):
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        assert "key-reuse" in out and "thread-dispatch" in out


class TestDiagnosticsLintReport:
    def test_lint_report_shape(self):
        from dask_ml_tpu import diagnostics

        report = diagnostics.lint_report()
        assert report["active"] == 0, report
        assert report["errors"] == []
        assert report["suppressed"] >= 1  # the library's justified debt
        for rule, c in report["counts"].items():
            assert set(c) == {"active", "suppressed"}
            assert rule in RULES

    def test_lint_report_explicit_paths(self, tmp_path):
        from dask_ml_tpu import diagnostics

        f = tmp_path / "mod.py"
        f.write_text(textwrap.dedent("""
            import jax

            def sample(key):
                a = jax.random.normal(key, (3,))
                b = jax.random.uniform(key, (3,))
                return a + b
        """))
        report = diagnostics.lint_report([str(tmp_path)])
        assert report["active"] == 1
        assert report["counts"]["key-reuse"]["active"] == 1


class TestDonationMiss:
    """ISSUE-12: every cached_program call must make its donation
    decision — donate_argnames wired, or an inline justified
    suppression naming why nothing aliases (the gemm-output-smaller
    class)."""

    def test_flags_cached_program_without_donation(self):
        findings = lint("""
            from dask_ml_tpu import programs as _programs

            def step(state, x):
                return state

            _step = _programs.cached_program(step, name="m.step")
        """)
        fs = [f for f in active(findings) if f.rule == "donation-miss"]
        assert fs and "donate_argnames" in fs[0].message

    def test_explicit_empty_tuple_still_flags(self):
        # an empty donate_argnames=() is "no donation" without the
        # reviewable justification a suppression carries
        findings = lint("""
            from dask_ml_tpu import programs as _programs

            def step(state, x):
                return state

            _step = _programs.cached_program(
                step, name="m.step", donate_argnames=())
        """)
        assert "donation-miss" in rule_ids(active(findings))

    def test_wired_donation_is_clean(self):
        findings = lint("""
            from dask_ml_tpu import programs as _programs

            def step(state, x):
                return state

            _step = _programs.cached_program(
                step, name="m.step", donate_argnames=("state",))
        """)
        assert "donation-miss" not in rule_ids(active(findings))

    def test_justified_suppression_is_honored(self):
        findings = lint("""
            from dask_ml_tpu import programs as _programs

            def loss(state, x):
                return 0.0

            # graftlint: disable=donation-miss -- scalar output, nothing aliases
            _loss = _programs.cached_program(loss, name="m.loss")
        """)
        fs = [f for f in findings if f.rule == "donation-miss"]
        assert fs and all(f.suppressed for f in fs)

    def test_direct_class_form_flags_too(self):
        findings = lint("""
            from dask_ml_tpu.programs.cache import CachedProgram

            def step(state, x):
                return state

            _step = CachedProgram(step, name="m.step")
        """)
        assert "donation-miss" in rule_ids(active(findings))

    def test_foreign_same_name_helper_does_not_match(self):
        findings = lint("""
            from mylib import cached_program

            def step(state, x):
                return state

            _step = cached_program(step, name="m.step")
        """)
        assert "donation-miss" not in rule_ids(active(findings))
