"""graftlint v2 engine: the project-wide machinery UNDER the rules.

The rules' pos/neg snippets live in test_graftlint.py; this file pins
the engine itself — module indexing and import resolution (aliased,
relative, from-imports), call-graph resolution (lexical nesting,
methods, super(), cycles), def-use chains, the whole-project cache, the
CLI's exit-code contract (findings=1 vs crash/bad-args=2), and the
timing budget that keeps the tier-1 gate negligible."""

import ast
import json
import os
import textwrap

import pytest

from dask_ml_tpu.analysis import Context, lint_paths, main
from dask_ml_tpu.analysis import cache as glcache
from dask_ml_tpu.analysis import dataflow
from dask_ml_tpu.analysis.graph import Project, module_name_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "dask_ml_tpu")


def ctx_of(src, path="<string>"):
    return Context(textwrap.dedent(src), path)


def project_of(*srcs_paths):
    return Project([ctx_of(s, p) for s, p in srcs_paths])


def first_call(ctx, name):
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Attribute):
                got = node.func.attr
            elif isinstance(node.func, ast.Name):
                got = node.func.id
            else:
                continue
            if got == name:
                return node
    raise AssertionError(f"no call to {name}")


# ---------------------------------------------------------------------------
# module naming + import resolution
# ---------------------------------------------------------------------------

class TestModuleIndex:
    def test_module_name_walks_packages(self, tmp_path):
        d = tmp_path / "pkg" / "sub"
        d.mkdir(parents=True)
        (tmp_path / "pkg" / "__init__.py").write_text("")
        (d / "__init__.py").write_text("")
        (d / "mod.py").write_text("")
        assert module_name_for(str(d / "mod.py")) == "pkg.sub.mod"
        assert module_name_for(str(d / "__init__.py")) == "pkg.sub"

    def test_module_name_outside_package(self, tmp_path):
        p = tmp_path / "script.py"
        p.write_text("")
        assert module_name_for(str(p)) == "script"

    def test_aliased_and_from_imports(self):
        ctx = ctx_of("""
            import jax.numpy as jnp
            import os
            from concurrent.futures import ThreadPoolExecutor as TPE
            from functools import partial
        """)
        mod = Project([ctx]).modules[0]
        assert mod.imports["jnp"] == "jax.numpy"
        assert mod.imports["os"] == "os"
        assert mod.imports["TPE"] == "concurrent.futures.ThreadPoolExecutor"
        assert mod.expand_alias("jnp.asarray") == "jax.numpy.asarray"
        assert mod.expand_alias("partial") == "functools.partial"

    def test_relative_imports_resolve(self, tmp_path):
        pkg = tmp_path / "pkg"
        (pkg / "sub").mkdir(parents=True)
        (pkg / "__init__.py").write_text("")
        (pkg / "util.py").write_text("def helper():\n    return 1\n")
        (pkg / "sub" / "__init__.py").write_text("")
        (pkg / "sub" / "mod.py").write_text(
            "from ..util import helper as h\n"
            "from .. import util\n"
            "def go():\n    return h() + util.helper()\n"
        )
        ctxs = []
        for p in [pkg / "util.py", pkg / "sub" / "mod.py"]:
            ctxs.append(Context(p.read_text(), str(p)))
        project = Project(ctxs)
        mod = project.by_name["pkg.sub.mod"]
        assert mod.imports["h"] == "pkg.util.helper"
        assert mod.imports["util"] == "pkg.util"
        # both call forms resolve to the same indexed function
        r1 = project.resolve_call(mod, first_call(mod.ctx, "h"))
        r2 = project.resolve_call(mod, first_call(mod.ctx, "helper"))
        assert r1.kind == "function" and r1.target.qualname == \
            "pkg.util.helper"
        assert r2.kind == "function" and r2.target is r1.target

    def test_module_level_str_constants_indexed(self):
        ctx = ctx_of('DEPTH_ENV = "DASK_ML_TPU_PREFETCH_DEPTH"\nX = 3\n')
        mod = Project([ctx]).modules[0]
        assert mod.str_constants == {
            "DEPTH_ENV": "DASK_ML_TPU_PREFETCH_DEPTH"}


# ---------------------------------------------------------------------------
# call resolution
# ---------------------------------------------------------------------------

class TestCallResolution:
    SRC = """
        import math

        def outer(cb):
            def inner():
                return helper()
            return inner() + cb() + math.sqrt(2) + len("x") + mystery()

        def helper():
            return 1

        class Base:
            def shared(self):
                return 1

        class Est(Base):
            def shared(self):
                return 2

            def run(self):
                return self.shared() + super().shared() + self.ghost()
    """

    @pytest.fixture()
    def proj(self):
        ctx = ctx_of(self.SRC)
        return Project([ctx]), ctx

    def _resolve(self, proj, ctx, name):
        project = proj
        return project.resolve_call(project.modules[0],
                                    first_call(ctx, name))

    def test_kinds(self, proj):
        project, ctx = proj
        assert self._resolve(project, ctx, "inner").kind == "function"
        assert self._resolve(project, ctx, "helper").kind == "function"
        assert self._resolve(project, ctx, "cb").kind == "dynamic"
        assert self._resolve(project, ctx, "sqrt").kind == "external"
        assert self._resolve(project, ctx, "len").kind == "builtin"
        assert self._resolve(project, ctx, "mystery").kind == "unknown"

    def test_self_method_resolves_to_override(self, proj):
        project, ctx = proj
        res = self._resolve(project, ctx, "shared")
        assert res.kind == "function" and res.bound
        assert res.target.qualname.endswith("Est.shared")

    def test_super_resolves_to_base(self, proj):
        project, ctx = proj
        calls = [n for n in ast.walk(ctx.tree)
                 if isinstance(n, ast.Call)
                 and isinstance(n.func, ast.Attribute)
                 and n.func.attr == "shared"]
        supers = [c for c in calls if isinstance(c.func.value, ast.Call)]
        res = project.resolve_call(project.modules[0], supers[0])
        assert res.kind == "function"
        assert res.target.qualname.endswith("Base.shared")

    def test_unknown_self_method_is_method_kind(self, proj):
        project, ctx = proj
        assert self._resolve(project, ctx, "ghost").kind == "method"

    def test_reachable_handles_cycles(self):
        ctx = ctx_of("""
            def a():
                return b()

            def b():
                return a()
        """)
        project = Project([ctx])
        mod = project.modules[0]
        names = [fn.name for fn, _ in
                 project.reachable(mod.functions["a"])]
        assert names == ["a", "b"]  # terminates, each visited once

    def test_reaches_collective_through_chain_and_cycle(self):
        ctx = ctx_of("""
            import jax

            def leaf(x):
                return jax.lax.psum(x, "data")

            def mid(x):
                return leaf(x)

            def loopy(x):
                return loopy(x) + mid(x)

            def clean(x):
                return x + 1
        """)
        project = Project([ctx])
        mod = project.modules[0]
        assert project.reaches_collective(mod.functions["mid"])
        assert project.reaches_collective(mod.functions["loopy"])
        assert not project.reaches_collective(mod.functions["clean"])

    def test_key_consuming_params_transitive(self):
        ctx = ctx_of("""
            import jax

            def inner(k):
                return jax.random.normal(k, (3,))

            def outer(data, key):
                return inner(key)

            def fresh(key):
                key, sub = jax.random.split(key)
                return sub
        """)
        project = Project([ctx])
        mod = project.modules[0]
        assert project.key_consuming_params(mod.functions["inner"]) == \
            frozenset({"k"})
        assert project.key_consuming_params(mod.functions["outer"]) == \
            frozenset({"key"})
        # `fresh` consumes its key too (split consumes) — the CALLER's
        # protection is rebinding, which the rule models separately
        assert "key" in project.key_consuming_params(mod.functions["fresh"])


# ---------------------------------------------------------------------------
# dataflow
# ---------------------------------------------------------------------------

class TestDefUse:
    def test_chains_attribute_uses_to_nearest_def(self):
        fn = ast.parse(textwrap.dedent("""
            def f(a):
                x = 1
                y = x + a
                x = 2
                z = x + y
                return z
        """)).body[0]
        du = dataflow.def_use(fn)
        xs = du.defs["x"]
        assert len(xs) == 2
        # first def of x used once (line `y = x + a`), second once
        assert [len(uses) for (_n, _v, uses) in xs] == [1, 1]
        assert len(du.uses_of("a")) == 1
        assert [v.value for v in du.values_of("x")] == [1, 2]

    def test_attribution_is_by_line_not_collection_order(self):
        # BFS collects the top-level line-5 def BEFORE the nested
        # line-3 def; the use on line 6 must still bind to line 5
        fn = ast.parse(textwrap.dedent("""
            def f(c, other):
                if c:
                    pool = make_a()
                pool = other
                return pool.submit
        """)).body[0]
        du = dataflow.def_use(fn)
        entries = du.defs["pool"]
        by_line = {getattr(n, "lineno", 0): uses
                   for (n, _v, uses) in entries}
        assert [len(u) for u in (by_line[4], by_line[5])] == [0, 1]

    def test_unpack_and_with_and_walrus_defs(self):
        fn = ast.parse(textwrap.dedent("""
            def f(snap, mk):
                it, state = snap
                with mk() as fh:
                    data = fh.read()
                if (n := len(data)) > 0:
                    return state, n
        """)).body[0]
        du = dataflow.def_use(fn)
        assert "state" in du.defs and "it" in du.defs
        assert du.unpack_sources("state")  # tuple-unpack recorded
        assert "fh" in du.defs and "n" in du.defs

    def test_nested_function_bodies_excluded(self):
        fn = ast.parse(textwrap.dedent("""
            def f():
                x = 1
                def g():
                    return x
                return g
        """)).body[0]
        du = dataflow.def_use(fn)
        assert du.uses_of("x") == []  # the closure use is g's business

    def test_resolve_dict_keys_through_name_and_call(self):
        ctx = ctx_of("""
            def make():
                return {"a": 1, "b": 2}

            def f():
                d = {"x": 1}
                d = {"y": 2}
                e = make()
                return d, e
        """)
        project = Project([ctx])
        mod = project.modules[0]
        fn = mod.functions["f"].node
        du = dataflow.DefUse(fn)
        ret = [n for n in ast.walk(fn) if isinstance(n, ast.Return)][0]
        d_expr, e_expr = ret.value.elts
        assert dataflow.resolve_dict_keys(d_expr, du, mod, project) == \
            frozenset({"x", "y"})  # union over reassignments
        assert dataflow.resolve_dict_keys(e_expr, du, mod, project) == \
            frozenset({"a", "b"})

    def test_resolve_dict_keys_wildcards(self):
        ctx = ctx_of("""
            def make(ks):
                return {k: 1 for k in ks}

            def f(ks):
                return make(ks)
        """)
        project = Project([ctx])
        mod = project.modules[0]
        fn = mod.functions["f"].node
        ret = [n for n in ast.walk(fn) if isinstance(n, ast.Return)][0]
        assert dataflow.resolve_dict_keys(
            ret.value, dataflow.DefUse(fn), mod, project) is None

    def test_resolve_str_constant_local_and_module(self):
        ctx = ctx_of("""
            KNOB = "DASK_ML_TPU_A"

            def f():
                local = "DASK_ML_TPU_B"
                return local, KNOB
        """)
        mod = Project([ctx]).modules[0]
        fn = mod.functions["f"].node
        du = dataflow.DefUse(fn)
        ret = [n for n in ast.walk(fn) if isinstance(n, ast.Return)][0]
        local_name, knob_name = ret.value.elts
        assert dataflow.resolve_str_constant(local_name, du, mod) == \
            "DASK_ML_TPU_B"
        assert dataflow.resolve_str_constant(knob_name, du, mod) == \
            "DASK_ML_TPU_A"


# ---------------------------------------------------------------------------
# the whole-project cache
# ---------------------------------------------------------------------------

class TestLintCache:
    SRC = """
        import jax

        def sample(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
    """

    def test_warm_hit_and_invalidation(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(textwrap.dedent(self.SRC))
        cache = str(tmp_path / "cache.json")
        f1, e1 = lint_paths([str(tmp_path)], cache=cache)
        assert os.path.exists(cache)
        f2, e2 = lint_paths([str(tmp_path)], cache=cache)
        assert [f.render() for f in f2] == [f.render() for f in f1]
        # an edit anywhere invalidates the whole entry
        mod.write_text("x = 1\n")
        f3, _ = lint_paths([str(tmp_path)], cache=cache)
        assert f3 == []

    def test_select_keys_the_digest(self, tmp_path):
        (tmp_path / "mod.py").write_text(textwrap.dedent(self.SRC))
        cache = str(tmp_path / "cache.json")
        full, _ = lint_paths([str(tmp_path)], cache=cache)
        only, _ = lint_paths([str(tmp_path)], select=["host-sync-loop"],
                             cache=cache)
        assert full and not only  # the select run must not reuse full's

    def test_corrupt_cache_is_a_miss_not_a_crash(self, tmp_path):
        (tmp_path / "mod.py").write_text(textwrap.dedent(self.SRC))
        cache = tmp_path / "cache.json"
        cache.write_text("{not json")
        findings, errors = lint_paths([str(tmp_path)], cache=str(cache))
        assert findings and not errors

    def test_cwd_keys_the_digest(self, tmp_path, monkeypatch):
        # findings carry as-given (often cwd-relative) paths: a cache
        # entry warmed from one cwd must not serve another cwd's run
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(textwrap.dedent(self.SRC))
        cache = str(tmp_path / "cache.json")
        monkeypatch.chdir(tmp_path)
        f1, _ = lint_paths(["pkg"], cache=cache)
        monkeypatch.chdir(pkg)
        f2, _ = lint_paths([str(pkg)], cache=cache)
        assert f1 and f2
        # the second run must NOT have inherited the first run's
        # relative path strings
        assert all(os.path.exists(f.path) or os.path.isabs(f.path)
                   for f in f2), [f.path for f in f2]

    def test_env_knob_disables(self, tmp_path, monkeypatch):
        monkeypatch.setenv(glcache.CACHE_ENV, "")
        assert glcache.resolve_cache_path(True, [str(tmp_path)]) is None
        monkeypatch.setenv(glcache.CACHE_ENV, str(tmp_path / "c.json"))
        assert glcache.resolve_cache_path(True, [str(tmp_path)]) == \
            str(tmp_path / "c.json")

    def test_syntax_errors_cached_missing_paths_not(self, tmp_path):
        (tmp_path / "bad.py").write_text("def broken(:\n")
        cache = str(tmp_path / "cache.json")
        _, e1 = lint_paths([str(tmp_path)], cache=cache)
        _, e2 = lint_paths([str(tmp_path)], cache=cache)
        assert e1 == e2 and any("syntax error" in e for e in e1)
        _, e3 = lint_paths([str(tmp_path), "/no/such/dir"], cache=cache)
        assert any("no such file" in e for e in e3)


class TestTimingBudget:
    def test_warm_run_of_the_package_parses_nothing(self, tmp_path,
                                                    monkeypatch):
        # what keeps the tier-1 gate negligible is that an unchanged
        # tree is not analysed twice: the warm run of the whole package
        # is a digest hit that hands back the stored findings and
        # parses no file.  (It used to assert cold < 10 s and warm
        # < 2 s; beside five other workers a clock is not a finding.)
        from dask_ml_tpu.analysis import core

        cache = str(tmp_path / "cache.json")
        findings, errors = lint_paths([PKG], cache=cache)
        assert not errors and os.path.exists(cache)
        parsed = []
        parse = core.Context
        monkeypatch.setattr(
            core, "Context",
            lambda src, path: parsed.append(path) or parse(src, path))
        findings2, errors2 = lint_paths([PKG], cache=cache)
        assert parsed == [] and not errors2
        assert [f.render() for f in findings2] == \
            [f.render() for f in findings]
        # the spy is live: a run without the cache parses what it lints
        one = tmp_path / "one.py"
        one.write_text("x = 1\n")
        lint_paths([str(one)], cache=None)
        assert parsed == [str(one)]


# ---------------------------------------------------------------------------
# CLI exit-code contract: findings=1, crash/bad-args=2
# ---------------------------------------------------------------------------

class TestCliExitCodes:
    def test_findings_exit_one_crash_exit_two(self, tmp_path, capsys,
                                              monkeypatch):
        mod = tmp_path / "mod.py"
        mod.write_text(textwrap.dedent(TestLintCache.SRC))
        assert main([str(mod), "--no-cache"]) == 1
        capsys.readouterr()

        # an analyzer crash must NOT masquerade as a findings verdict
        from dask_ml_tpu.analysis import cli

        def boom(*a, **k):
            raise RuntimeError("engine exploded")

        monkeypatch.setattr(cli, "lint_paths", boom)
        assert cli.main([str(mod), "--no-cache"]) == 2
        err = capsys.readouterr().err
        assert "analyzer crash" in err and "engine exploded" in err

    def test_bad_args_exit_two(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text("x = 1\n")
        assert main([str(mod), "--select", "bogus"]) == 2
        assert main(["/no/such/path/at/all"]) == 2
        assert main([str(mod), "--baseline",
                     str(tmp_path / "missing.json")]) == 2

    def test_baseline_ratchet_flow(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text(textwrap.dedent("""
            import jax

            def sample(key):
                a = jax.random.normal(key, (3,))
                b = jax.random.uniform(key, (3,))  # graftlint: disable=key-reuse -- intentional
                return a + b
        """))
        base = str(tmp_path / "base.json")
        assert main([str(tmp_path), "--write-baseline", base,
                     "--no-cache"]) == 0
        capsys.readouterr()
        # unchanged tree: ratchet passes
        assert main([str(tmp_path), "--baseline", base, "--no-cache"]) == 0
        capsys.readouterr()
        # a NEW suppressed finding still fails the ratchet
        mod.write_text(mod.read_text() + textwrap.dedent("""
            def more(key2):
                c = jax.random.normal(key2, (3,))
                d = jax.random.normal(key2, (3,))  # graftlint: disable=key-reuse -- smuggled debt
                return c + d
        """))
        assert main([str(tmp_path), "--baseline", base, "--no-cache"]) == 1
        out = capsys.readouterr().out
        assert "1 new" in out and "new vs baseline" in out
        # fixing EVERYTHING leaves the baseline stale: also a failure
        mod.write_text("x = 1\n")
        assert main([str(tmp_path), "--baseline", base, "--no-cache"]) == 1
        out = capsys.readouterr().out
        assert "stale" in out and "rebaseline" in out

    def test_scope_mismatch_is_exit_two_not_mass_churn(self, tmp_path,
                                                       capsys):
        # a --select subset (or a different target root) compared
        # against a full-run baseline must refuse loudly, not report
        # every entry stale
        mod = tmp_path / "mod.py"
        mod.write_text(textwrap.dedent(TestLintCache.SRC))
        base = str(tmp_path / "base.json")
        assert main([str(tmp_path), "--write-baseline", base,
                     "--no-cache"]) == 0
        capsys.readouterr()
        assert main([str(tmp_path), "--baseline", base, "--select",
                     "key-reuse", "--no-cache"]) == 2
        assert "different rule set" in capsys.readouterr().err

        from dask_ml_tpu.analysis import baseline as bl

        other = tmp_path / "elsewhere"
        other.mkdir()
        (other / "mod.py").write_text("x = 1\n")
        snap = bl.load(base)
        with pytest.raises(ValueError, match="target root"):
            bl.compare(snap, [], str(other), rules=None)

    def test_new_rule_drift_ratchets_instead_of_refusing(self, tmp_path):
        # registering a NEW rule later must flow through the normal
        # ratchet (new findings → exit 1 → rebaseline), not read as a
        # scope error — only an explicit --select is refused
        from dask_ml_tpu.analysis import baseline as bl

        (tmp_path / "mod.py").write_text("x = 1\n")
        findings, errors = lint_paths([str(tmp_path)])
        root = bl.baseline_root([str(tmp_path)])
        snap = bl.emit(findings, errors, root,
                       rules=["only-the-old-rules"])
        delta = bl.compare(snap, findings, root, rules=None)  # full run
        assert delta == {"new": [], "fixed": []}
        with pytest.raises(ValueError, match="different rule set"):
            bl.compare(snap, findings, root, rules=["key-reuse"])

    def test_write_baseline_wins_over_baseline_flag(self, tmp_path,
                                                    capsys):
        # bootstrap: both flags, no snapshot on disk yet — must WRITE,
        # not die trying to read
        (tmp_path / "mod.py").write_text("x = 1\n")
        base = str(tmp_path / "base.json")
        assert main([str(tmp_path), "--write-baseline", base,
                     "--baseline", base, "--no-cache"]) == 0
        assert os.path.exists(base)

    def test_json_carries_baseline_block(self, tmp_path, capsys):
        mod = tmp_path / "mod.py"
        mod.write_text("x = 1\n")
        base = str(tmp_path / "base.json")
        assert main([str(tmp_path), "--write-baseline", base,
                     "--no-cache"]) == 0
        capsys.readouterr()
        mod.write_text(textwrap.dedent(TestLintCache.SRC))
        assert main([str(tmp_path), "--baseline", base, "--format",
                     "json", "--no-cache"]) == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["baseline"]["new"][0]["rule"] == "key-reuse"
        assert payload["baseline"]["stale"] == []


# ---------------------------------------------------------------------------
# diagnostics.lint_report: per-rule new/fixed deltas vs baseline
# ---------------------------------------------------------------------------

class TestLintReportDeltas:
    def test_package_report_against_committed_baseline(self):
        from dask_ml_tpu import diagnostics

        report = diagnostics.lint_report()
        assert report["active"] == 0, report
        assert report["baseline"] is not None
        assert report["baseline"]["new"] == 0
        assert report["baseline"]["fixed"] == 0

    def test_explicit_baseline_deltas(self, tmp_path):
        from dask_ml_tpu import diagnostics
        from dask_ml_tpu.analysis import baseline as bl

        mod = tmp_path / "mod.py"
        mod.write_text("x = 1\n")
        findings, errors = lint_paths([str(tmp_path)])
        base = tmp_path / "base.json"
        bl.write(str(base), bl.emit(findings, errors,
                                    bl.baseline_root([str(tmp_path)])))
        mod.write_text(textwrap.dedent(TestLintCache.SRC))
        report = diagnostics.lint_report([str(tmp_path)],
                                         baseline=str(base))
        assert report["active"] == 1
        assert report["baseline"]["new"] == 1
        assert report["baseline"]["per_rule"]["key-reuse"]["new"] == 1

    def test_no_baseline_block_when_none(self, tmp_path):
        from dask_ml_tpu import diagnostics

        (tmp_path / "mod.py").write_text("x = 1\n")
        report = diagnostics.lint_report([str(tmp_path)], baseline=None)
        assert report["baseline"] is None
