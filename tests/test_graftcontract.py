"""graftcontract: whole-program stringly-typed contract drift analysis
(design.md §23) gates itself.

Every contract family gets a positive (drifting) and a negative (clean)
snippet; the package-level closure proofs pin the PR-19 RETRYABLE
reason set and the PR-17 POLICY verdict keys closed (producer set ==
consumer set); and the seeded-drift self-test holds both ends — the
sighted gate exits 0, either ``DASK_ML_TPU_CONTRACT_INJECT`` drift
exits 1, a typo'd mode exits 2 (a drift detector that cannot fail can
never gate)."""

import json
import os
import textwrap

import pytest

from dask_ml_tpu.analysis import lint_paths, lint_source, main
from dask_ml_tpu.analysis import baseline as bl
from dask_ml_tpu.analysis import cache as lint_cache
from dask_ml_tpu.analysis import contracts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "dask_ml_tpu")
CONTRACT_BASELINE = os.path.join(REPO, "tools", "contract_baseline.json")

CONTRACT_RULES = (
    "contract-orphan-producer",
    "contract-dead-consumer",
    "contract-roster-drift",
    "contract-baseline-drift",
    "contract-undocumented-metric",
)
SEL = ",".join(CONTRACT_RULES)


# a path under a root that does not exist: find_api_md's walk-up must
# not escape into the REAL repo's docs/ and tools/ (lint_source's
# default "<string>" resolves against cwd, which during pytest IS the
# repo — snippets would silently check against the live contracts)
SNIPPET = os.path.join(os.sep, "graftcontract-snippet", "pkg", "mod.py")


def lint(src):
    return lint_source(textwrap.dedent(src), path=SNIPPET,
                       select=CONTRACT_RULES)


def active(findings):
    return [f for f in findings if not f.suppressed]


def rule_ids(findings):
    return sorted({f.rule for f in findings})


@pytest.fixture(scope="module")
def pkg_model():
    """ONE whole-package contract model shared by the closure proofs."""
    from dask_ml_tpu.analysis.core import Context, all_rules, iter_py_files
    from dask_ml_tpu.analysis.graph import Project

    all_rules()
    ctxs = []
    for path in iter_py_files([PKG]):
        with open(path, encoding="utf-8") as fh:
            ctxs.append(Context(fh.read(), path))
    return contracts.model_for(Project(ctxs))


@pytest.fixture(scope="module")
def pkg_contract_lint(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("graftcontract") / "cache.json")
    return lint_paths([PKG], select=CONTRACT_RULES, cache=cache)


# ---------------------------------------------------------------------------
# the tier-1 self-gate + closure proofs on the real package
# ---------------------------------------------------------------------------

class TestPackageContractGate:
    def test_package_has_zero_unsuppressed_contract_findings(
            self, pkg_contract_lint):
        findings, errors = pkg_contract_lint
        assert not errors, errors
        bad = active(findings)
        assert not bad, "\n".join(f.render() for f in bad)

    def test_committed_contract_baseline_matches(self, pkg_contract_lint):
        findings, _ = pkg_contract_lint
        snap = bl.load(CONTRACT_BASELINE)
        delta = bl.compare(snap, findings, bl.baseline_root([PKG]),
                           rules=sorted(CONTRACT_RULES))
        assert not delta["new"], [f.render() for f in delta["new"]]
        assert not delta["fixed"], delta["fixed"]

    def test_cli_contract_gate_exit_zero(self, capsys):
        assert main([PKG, "--select", SEL,
                     "--baseline", CONTRACT_BASELINE]) == 0
        assert "0 new, 0 stale" in capsys.readouterr().out

    def test_retryable_reason_set_is_closed(self, pkg_model):
        # PR-19's routing contract, proven both ways: every produced
        # RequestRejected reason is classified, and every roster entry
        # is producible — no dropped-request default, no dead entry
        produced = pkg_model.produced_reasons()
        classified = pkg_model.classified_reasons()
        assert produced, "extraction found no reason producers"
        assert produced == classified, (
            f"orphans: {produced - classified}, "
            f"dead: {classified - produced}")

    def test_retryable_reason_set_exact(self, pkg_model):
        # the full vocabulary, pinned: growing it is deliberate (add
        # the producer AND the roster entry AND update this set)
        assert pkg_model.classified_reasons() == {
            "queue_full", "draining", "serve_down", "shutdown",
            "unknown_model", "bad_input", "oversize", "deadline",
            "brownout"}

    def test_policy_verdict_keys_are_closed(self, pkg_model):
        # PR-17's autopilot contract: every POLICY key names a verdict
        # class graftpath can produce and a plane that exists
        classes = {s.value for s in pkg_model.verdict_classes}
        assert classes, "extraction found no BOTTLENECK_CLASSES"
        for (plane, cls), _site in pkg_model.policy_keys:
            assert cls in classes, (plane, cls)
            assert plane in ("fit", "search", "serve"), plane

    def test_every_injection_point_is_wired(self, pkg_model):
        wired = {s.value for s in pkg_model.fault_sites}
        for site in pkg_model.injection_roster:
            assert site.value in wired, site.value

    def test_every_produced_metric_family_documented(self, pkg_model):
        text = pkg_model.api_md_text()
        assert text is not None
        missing = {s.value for s in pkg_model.metric_literals
                   if s.value not in text}
        assert not missing, missing


# ---------------------------------------------------------------------------
# seeded drift: the detector must be able to fail the very gate CI runs
# ---------------------------------------------------------------------------

class TestSeededDrift:
    def test_sighted_gate_exits_zero(self, monkeypatch):
        monkeypatch.delenv(contracts.CONTRACT_INJECT_ENV, raising=False)
        assert main([PKG, "--select", SEL,
                     "--baseline", CONTRACT_BASELINE]) == 0

    def test_orphan_reason_drift_exits_one(self, monkeypatch, capsys):
        monkeypatch.setenv(contracts.CONTRACT_INJECT_ENV, "orphan-reason")
        assert main([PKG, "--select", SEL,
                     "--baseline", CONTRACT_BASELINE]) == 1
        out = capsys.readouterr().out
        assert "seeded drift" in out and "contract-orphan-producer" in out

    def test_dead_policy_drift_exits_one(self, monkeypatch, capsys):
        monkeypatch.setenv(contracts.CONTRACT_INJECT_ENV, "dead-policy")
        assert main([PKG, "--select", SEL,
                     "--baseline", CONTRACT_BASELINE]) == 1
        out = capsys.readouterr().out
        assert "seeded drift" in out and "contract-dead-consumer" in out

    def test_typo_mode_exits_two(self, monkeypatch):
        # graftlock's strict-parse convention: a misspelled injection
        # must crash the analyzer (2), never read as a lint verdict
        monkeypatch.setenv(contracts.CONTRACT_INJECT_ENV, "orfan-reason")
        assert main([PKG, "--select", SEL, "--no-cache"]) == 2

    def test_inject_is_inert_without_a_contract(self, monkeypatch):
        # guard check: a snippet with no rosters has nothing to drift —
        # the injection must not fabricate findings out of thin air
        monkeypatch.setenv(contracts.CONTRACT_INJECT_ENV, "orphan-reason")
        assert not active(lint("x = 1\n"))


# ---------------------------------------------------------------------------
# rejection-reason family
# ---------------------------------------------------------------------------

class TestRejectionReasons:
    CLEAN = """
        class RequestRejected(Exception):
            def __init__(self, reason, detail=""):
                self.reason = reason

        _RETRYABLE = ("queue_full",)
        _NON_RETRYABLE = ("bad_input",)

        def submit(full, bad):
            if full:
                raise RequestRejected("queue_full", "shed")
            if bad:
                raise RequestRejected("bad_input", "nan rows")
    """

    def test_clean_closed_set(self):
        assert not active(lint(self.CLEAN))

    def test_orphan_reason_flagged(self):
        findings = lint(self.CLEAN + """
        def worse():
            raise RequestRejected("mystery", "who classifies this?")
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["contract-orphan-producer"]
        assert "mystery" in fs[0].message

    def test_dead_roster_entry_flagged(self):
        findings = lint(self.CLEAN.replace(
            '_RETRYABLE = ("queue_full",)',
            '_RETRYABLE = ("queue_full", "draining")'))
        fs = active(findings)
        assert rule_ids(fs) == ["contract-dead-consumer"]
        assert "draining" in fs[0].message

    def test_helper_producers_recognized(self):
        # reject(req, reason, ...) and self._fleet_reject(reason, ...)
        # are reason positions too (arg index differs per callable)
        findings = lint("""
            _RETRYABLE = ("queue_full",)

            def reject(req, reason, detail):
                pass

            class Fleet:
                def _fleet_reject(self, reason, detail):
                    pass

                def shed(self, req):
                    reject(req, "queue_full", "full")
                    self._fleet_reject("overheat", "thermals")
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["contract-orphan-producer"]
        assert "overheat" in fs[0].message

    def test_no_roster_means_no_contract(self):
        # without a _RETRYABLE roster in scope there is nothing to
        # classify against — vendored subsets must not light up
        findings = lint("""
            class RequestRejected(Exception):
                pass

            def submit():
                raise RequestRejected("anything_goes", "no roster here")
        """)
        assert not active(findings)


# ---------------------------------------------------------------------------
# verdict-class / POLICY family
# ---------------------------------------------------------------------------

class TestVerdictPolicy:
    CLEAN = """
        BOTTLENECK_CLASSES = ("unknown", "device-bound", "parse-bound")

        POLICY = {
            ("fit", "parse-bound"): ("data_readers", "up"),
            ("serve", "device-bound"): ("serve_max_batch", "up"),
        }
    """

    def test_clean_policy(self):
        assert not active(lint(self.CLEAN))

    def test_unreachable_policy_key_flagged(self):
        findings = lint(self.CLEAN.replace(
            '("serve", "device-bound")', '("serve", "zebra-bound")'))
        fs = active(findings)
        assert rule_ids(fs) == ["contract-dead-consumer"]
        assert "zebra-bound" in fs[0].message and "POLICY" in fs[0].message


# ---------------------------------------------------------------------------
# metric-family / flight-event family
# ---------------------------------------------------------------------------

class TestMetricFamilies:
    CLEAN = """
        def tick(reg, obs):
            reg.counter("pipeline.blocks", "ok").inc()
            reg.family("pipeline.blocks")
            obs.event("pipeline.fault", label="x")
    """

    def test_clean_produced_and_read(self):
        assert not active(lint(self.CLEAN))

    def test_dead_family_read_flagged(self):
        findings = lint(self.CLEAN + """
        def stale(reg):
            return reg.family("pipeline.gone")
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["contract-dead-consumer"]
        assert "pipeline.gone" in fs[0].message

    def test_event_off_metric_namespace_flagged(self):
        findings = lint(self.CLEAN + """
        def shout(obs):
            obs.event("zebra.fault", label="orphan layer")
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["contract-orphan-producer"]
        assert "zebra.fault" in fs[0].message

    def test_fstring_pattern_producer_matches_consumer(self):
        # serve/runtime.py's f"serve.req_{leg}_s" shape: the consumer
        # of a concrete expansion must resolve against the pattern
        findings = lint("""
            def split(reg, leg):
                reg.histogram(f"serve.req_{leg}_s").observe(0.1)
                reg.counter("serve.requests").inc()

            def read(reg):
                return reg.family("serve.req_queue_s")
        """)
        assert not active(findings)


# ---------------------------------------------------------------------------
# injection-point family
# ---------------------------------------------------------------------------

class TestInjectionPoints:
    CLEAN = """
        INJECTION_POINTS = ("step", "stage")

        def run(maybe_fault):
            maybe_fault("step")
            maybe_fault("stage")
    """

    def test_clean_roster(self):
        assert not active(lint(self.CLEAN))

    def test_unrostered_fault_site_flagged(self):
        findings = lint(self.CLEAN + """
        def sneak(maybe_fault):
            maybe_fault("rogue-point")
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["contract-orphan-producer"]
        assert "rogue-point" in fs[0].message

    def test_unwired_roster_entry_flagged(self):
        findings = lint(self.CLEAN.replace(
            '("step", "stage")', '("step", "stage", "prefetch")'))
        fs = active(findings)
        assert rule_ids(fs) == ["contract-dead-consumer"]
        assert "prefetch" in fs[0].message


# ---------------------------------------------------------------------------
# thread-name / lock-name roster family
# ---------------------------------------------------------------------------

class TestThreadLockRosters:
    CLEAN = """
        import threading

        KNOWN_THREAD_NAMES = frozenset({"dask-ml-tpu-serve"})

        def start(fn):
            t = threading.Thread(target=fn, name="dask-ml-tpu-serve")
            return t
    """

    def test_clean_rostered_thread(self):
        assert not active(lint(self.CLEAN))

    def test_off_roster_package_thread_flagged(self):
        findings = lint(self.CLEAN + """
        def sneak(fn):
            return threading.Thread(target=fn, name="dask-ml-tpu-rogue")
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["contract-roster-drift"]
        assert "dask-ml-tpu-rogue" in fs[0].message

    def test_unprefixed_thread_is_not_package_namespace(self):
        findings = lint(self.CLEAN + """
        def client(fn):
            return threading.Thread(target=fn, name="client-traffic")
        """)
        assert not active(findings)

    def test_rostered_but_never_constructed_flagged(self):
        findings = lint(self.CLEAN.replace(
            '{"dask-ml-tpu-serve"}',
            '{"dask-ml-tpu-serve", "dask-ml-tpu-ghost"}'))
        fs = active(findings)
        assert rule_ids(fs) == ["contract-roster-drift"]
        assert "dask-ml-tpu-ghost" in fs[0].message

    def test_lock_contract_key_without_lock_flagged(self):
        findings = lint("""
            LOCK_THREAD_CONTRACTS = {
                "serve.server": ("serve-loop",),
                "gone.lock": ("nobody",),
            }

            def build(make_lock):
                return make_lock("serve.server")
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["contract-roster-drift"]
        assert "gone.lock" in fs[0].message

    def test_lock_contract_keys_all_produced_is_clean(self):
        findings = lint("""
            LOCK_THREAD_CONTRACTS = {"serve.server": ("serve-loop",)}

            def build(make_lock):
                return make_lock("serve.server")
        """)
        assert not active(findings)


# ---------------------------------------------------------------------------
# knob-name family
# ---------------------------------------------------------------------------

class TestKnobNames:
    CLEAN = """
        class Knob:
            def __init__(self, name, env, kind, default, lo, hi):
                self.name = name

        KNOBS = {k.name: k for k in (
            Knob("prefetch_depth", "DASK_ML_TPU_PREFETCH_DEPTH",
                 int, 2, 0, 64),
        )}

        def read(registry):
            return registry.override_or("prefetch_depth", 2)
    """

    def test_clean_declared_knob(self):
        assert not active(lint(self.CLEAN))

    def test_undeclared_knob_reference_flagged(self):
        findings = lint(self.CLEAN + """
        def poke(registry):
            registry.set_knob("ghost_knob", 9)
        """)
        fs = active(findings)
        assert rule_ids(fs) == ["contract-dead-consumer"]
        assert "ghost_knob" in fs[0].message


# ---------------------------------------------------------------------------
# committed-baseline pin family (tools/*_baseline.json)
# ---------------------------------------------------------------------------

class TestCommittedBaselinePins:
    def _tree(self, tmp_path, drill=None, lock=None):
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "api.md").write_text(
            "| `pipeline.blocks` | counter | — | blocks |\n")
        tools = tmp_path / "tools"
        tools.mkdir()
        if drill is not None:
            (tools / "drill_baseline.json").write_text(json.dumps(drill))
        if lock is not None:
            (tools / "lock_baseline.json").write_text(json.dumps(lock))
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        return pkg

    def _lint(self, pkg):
        return lint_paths([str(pkg)], select=CONTRACT_RULES)[0]

    def test_drill_point_drift_flagged(self, tmp_path):
        pkg = self._tree(tmp_path,
                         drill={"drills": {"d": {"point": "gone-point"}}})
        (pkg / "mod.py").write_text(
            'INJECTION_POINTS = ("step",)\n'
            'def run(maybe_fault):\n'
            '    maybe_fault("step")\n')
        fs = active(self._lint(pkg))
        assert rule_ids(fs) == ["contract-baseline-drift"]
        assert "gone-point" in fs[0].message

    def test_lock_edge_drift_flagged(self, tmp_path):
        pkg = self._tree(tmp_path,
                         lock={"edges": ["serve.server -> gone.lock"]})
        (pkg / "mod.py").write_text(
            'LOCK_THREAD_CONTRACTS = {"serve.server": ("serve-loop",)}\n'
            'def build(make_lock):\n'
            '    return make_lock("serve.server")\n')
        fs = active(self._lint(pkg))
        assert rule_ids(fs) == ["contract-baseline-drift"]
        assert "gone.lock" in fs[0].message

    def test_no_committed_baseline_is_silent(self, tmp_path):
        pkg = self._tree(tmp_path)
        (pkg / "mod.py").write_text(
            'BOTTLENECK_CLASSES = ("unknown", "device-bound")\n')
        assert not active(self._lint(pkg))


# ---------------------------------------------------------------------------
# docs family: contract-undocumented-metric
# ---------------------------------------------------------------------------

class TestUndocumentedMetric:
    def _tree(self, tmp_path, documented, produced):
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "api.md").write_text(
            f"| `{documented}` | counter | — | a family |\n")
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            f'def tick(reg):\n'
            f'    reg.counter("{produced}", "t").inc()\n')
        return str(pkg)

    def test_documented_family_is_clean(self, tmp_path):
        pkg = self._tree(tmp_path, "pipeline.blocks", "pipeline.blocks")
        findings, _ = lint_paths([pkg], select=CONTRACT_RULES)
        assert not active(findings)

    def test_undocumented_family_flagged(self, tmp_path):
        pkg = self._tree(tmp_path, "pipeline.blocks", "pipeline.secret")
        findings, _ = lint_paths([pkg], select=CONTRACT_RULES)
        fs = active(findings)
        assert rule_ids(fs) == ["contract-undocumented-metric"]
        assert "pipeline.secret" in fs[0].message

    def test_no_api_md_in_reach_is_silent(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            'def tick(reg):\n'
            '    reg.counter("pipeline.secret", "t").inc()\n')
        findings, _ = lint_paths([str(pkg)], select=CONTRACT_RULES)
        assert not active(findings)


# ---------------------------------------------------------------------------
# ratchet mechanics: round-trip / new / stale / wrong-root refusal
# ---------------------------------------------------------------------------

class TestContractRatchet:
    DRIFTED = textwrap.dedent("""
        _RETRYABLE = ("queue_full",)

        class RequestRejected(Exception):
            pass

        def submit(full):
            if full:
                raise RequestRejected("queue_full", "shed")
            raise RequestRejected("mystery", "unclassified")
    """)

    def _pkg(self, tmp_path, src):
        (tmp_path / "mod.py").write_text(src)
        return str(tmp_path)

    def test_round_trip_and_clean_compare(self, tmp_path):
        pkg = self._pkg(tmp_path, self.DRIFTED)
        findings, errors = lint_paths([pkg], select=CONTRACT_RULES)
        assert rule_ids(active(findings)) == ["contract-orphan-producer"]
        root = bl.baseline_root([pkg])
        path = tmp_path / "contract_baseline.json"
        bl.write(str(path), bl.emit(findings, errors, root,
                                    rules=sorted(CONTRACT_RULES)))
        delta = bl.compare(bl.load(str(path)), findings, root,
                           rules=sorted(CONTRACT_RULES))
        assert not delta["new"] and not delta["fixed"]

    def test_new_drift_detected(self, tmp_path):
        pkg = self._pkg(tmp_path, self.DRIFTED)
        findings, errors = lint_paths([pkg], select=CONTRACT_RULES)
        root = bl.baseline_root([pkg])
        snap = bl.emit(findings, errors, root)
        self._pkg(tmp_path, self.DRIFTED + textwrap.dedent("""
            def worse():
                raise RequestRejected("second_mystery", "more drift")
        """))
        findings2, _ = lint_paths([pkg], select=CONTRACT_RULES)
        delta = bl.compare(snap, findings2, root)
        assert len(delta["new"]) == 1
        assert delta["new"][0].rule == "contract-orphan-producer"

    def test_fixed_drift_reported_stale(self, tmp_path):
        pkg = self._pkg(tmp_path, self.DRIFTED)
        findings, errors = lint_paths([pkg], select=CONTRACT_RULES)
        root = bl.baseline_root([pkg])
        snap = bl.emit(findings, errors, root)
        self._pkg(tmp_path, self.DRIFTED.replace(
            '_RETRYABLE = ("queue_full",)',
            '_RETRYABLE = ("queue_full", "mystery")'))
        findings2, _ = lint_paths([pkg], select=CONTRACT_RULES)
        delta = bl.compare(snap, findings2, root)
        assert not delta["new"]
        assert {e["rule"] for e in delta["fixed"]} == \
            {"contract-orphan-producer"}

    def test_wrong_root_refused(self, tmp_path):
        pkg_a = tmp_path / "repo_a"
        pkg_a.mkdir()
        pkg_b = tmp_path / "repo_b"
        pkg_b.mkdir()
        (pkg_a / "mod.py").write_text(self.DRIFTED)
        (pkg_b / "mod.py").write_text(self.DRIFTED)
        findings, errors = lint_paths([str(pkg_a)], select=CONTRACT_RULES)
        snap = bl.emit(findings, errors, bl.baseline_root([str(pkg_a)]))
        with pytest.raises(ValueError):
            bl.compare(snap, findings, bl.baseline_root([str(pkg_b)]))

    def test_cli_wrong_root_exits_two(self, tmp_path, capsys):
        pkg_a = tmp_path / "repo_a"
        pkg_a.mkdir()
        pkg_b = tmp_path / "repo_b"
        pkg_b.mkdir()
        (pkg_a / "mod.py").write_text(self.DRIFTED)
        (pkg_b / "mod.py").write_text(self.DRIFTED)
        path = str(tmp_path / "bl.json")
        assert main([str(pkg_a), "--select", SEL,
                     "--write-baseline", path]) == 0
        assert main([str(pkg_b), "--select", SEL,
                     "--baseline", path]) == 2
        capsys.readouterr()

    def test_cli_exit_zero_and_one(self, tmp_path, capsys):
        pkg = self._pkg(tmp_path, self.DRIFTED)
        assert main([pkg, "--select", SEL]) == 1
        self._pkg(tmp_path, self.DRIFTED.replace(
            '_RETRYABLE = ("queue_full",)',
            '_RETRYABLE = ("queue_full", "mystery")'))
        assert main([pkg, "--select", SEL]) == 0
        capsys.readouterr()


# ---------------------------------------------------------------------------
# reporter schema for the contract rules
# ---------------------------------------------------------------------------

class TestContractReporters:
    def test_text_reporter(self, tmp_path, capsys):
        (tmp_path / "mod.py").write_text(TestContractRatchet.DRIFTED)
        assert main([str(tmp_path), "--select", SEL]) == 1
        out = capsys.readouterr().out
        assert "[contract-orphan-producer]" in out
        assert "mystery" in out
        assert "graftlint: 1 finding(s)" in out

    def test_json_reporter_schema(self, tmp_path, capsys):
        (tmp_path / "mod.py").write_text(TestContractRatchet.DRIFTED)
        assert main([str(tmp_path), "--select", SEL,
                     "--format", "json"]) == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["version"] == 2
        assert payload["counts"]["contract-orphan-producer"] == {
            "active": 1, "suppressed": 0}
        [f] = payload["findings"]
        assert f["rule"] == "contract-orphan-producer"
        assert set(f) >= {"rule", "path", "line", "col", "message",
                          "suppressed", "justification"}
        assert f["line"] > 0 and not f["suppressed"]
        assert not payload["errors"]
        # the rules block is the full registry (id -> summary): every
        # contract rule must be registered and self-describing
        for rule in CONTRACT_RULES:
            assert payload["rules"][rule]

    def test_json_reporter_ratchet_block(self, tmp_path, capsys):
        # ACTIVE findings still exit 1 even when baselined — the gate
        # demands zero active; the ratchet exists for the suppressed
        # tail — but the delta block itself must read clean
        (tmp_path / "mod.py").write_text(TestContractRatchet.DRIFTED)
        path = str(tmp_path / "bl.json")
        assert main([str(tmp_path), "--select", SEL,
                     "--write-baseline", path]) == 0
        capsys.readouterr()
        assert main([str(tmp_path), "--select", SEL, "--baseline", path,
                     "--format", "json"]) == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["baseline"] == {"new": [], "stale": []}


# ---------------------------------------------------------------------------
# cache digest: analyzer identity + inject knob + committed ratchets
# ---------------------------------------------------------------------------

class TestCacheDigest:
    def _sources(self, tmp_path):
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "api.md").write_text("knobs\n")
        (tmp_path / "tools").mkdir()
        (tmp_path / "tools" / "drill_baseline.json").write_text(
            '{"drills": {}}')
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        mod = pkg / "mod.py"
        mod.write_text("x = 1\n")
        return [(str(mod), mod.read_text())]

    def test_inject_env_keys_the_digest(self, tmp_path, monkeypatch):
        src = self._sources(tmp_path)
        monkeypatch.delenv(contracts.CONTRACT_INJECT_ENV, raising=False)
        d0 = lint_cache.project_digest(src)
        monkeypatch.setenv(contracts.CONTRACT_INJECT_ENV, "orphan-reason")
        d1 = lint_cache.project_digest(src)
        monkeypatch.setenv(contracts.CONTRACT_INJECT_ENV, "dead-policy")
        d2 = lint_cache.project_digest(src)
        assert len({d0, d1, d2}) == 3

    def test_committed_ratchet_keys_the_digest(self, tmp_path):
        src = self._sources(tmp_path)
        d0 = lint_cache.project_digest(src)
        (tmp_path / "tools" / "drill_baseline.json").write_text(
            '{"drills": {"d": {}}}')
        assert lint_cache.project_digest(src) != d0

    def test_analyzer_sources_key_the_digest(self, tmp_path, monkeypatch):
        # adding OR editing a rule module must invalidate the warm
        # cache even when the linted tree is unchanged — point the
        # analyzer-identity walk at a scratch package and mutate it
        src = self._sources(tmp_path)
        fake = tmp_path / "analysis"
        (fake / "rules").mkdir(parents=True)
        (fake / "rules" / "a.py").write_text("A = 1\n")
        monkeypatch.setattr(lint_cache, "__file__",
                            str(fake / "cache.py"))
        d0 = lint_cache.project_digest(src)
        (fake / "rules" / "a.py").write_text("A = 2\n")
        d1 = lint_cache.project_digest(src)
        (fake / "rules" / "b.py").write_text("B = 1\n")
        d2 = lint_cache.project_digest(src)
        assert len({d0, d1, d2}) == 3

    def test_warm_cache_does_not_mask_injection(self, tmp_path,
                                                monkeypatch):
        # the end-to-end regression this PR hit: a sighted run warms
        # the cache, then an injected run MUST NOT read its findings
        (tmp_path / "mod.py").write_text(TestContractRatchet.DRIFTED.replace(
            '_RETRYABLE = ("queue_full",)',
            '_RETRYABLE = ("queue_full", "mystery")'))
        cache = str(tmp_path / "cache.json")
        monkeypatch.delenv(contracts.CONTRACT_INJECT_ENV, raising=False)
        findings, _ = lint_paths([str(tmp_path)], select=CONTRACT_RULES,
                                 cache=cache)
        assert not active(findings)
        monkeypatch.setenv(contracts.CONTRACT_INJECT_ENV, "orphan-reason")
        findings2, _ = lint_paths([str(tmp_path)], select=CONTRACT_RULES,
                                  cache=cache)
        assert rule_ids(active(findings2)) == ["contract-orphan-producer"]


# ---------------------------------------------------------------------------
# satellite: Knob(...) declarations and _env_number resolution are
# knob-read sites for undocumented-knob
# ---------------------------------------------------------------------------

class TestKnobRegistryReads:
    def _tree(self, tmp_path, documented, body):
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "api.md").write_text(
            f"| `{documented}` | int | a knob | — |\n")
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(textwrap.dedent(body))
        return str(pkg)

    KNOB_DECL = """
        class Knob:
            def __init__(self, name, env, kind, default, lo, hi):
                self.name = name

        K = Knob("depth", "{env}", int, 2, 0, 64)
    """

    ENV_NUMBER = """
        import os

        def _env_number(env, cast, default):
            return cast(os.environ.get(env, default))

        def depth():
            return _env_number("{env}", int, 2)
    """

    def test_knob_declaration_is_a_read_site(self, tmp_path):
        pkg = self._tree(tmp_path, "DASK_ML_TPU_DEPTH",
                         self.KNOB_DECL.format(env="DASK_ML_TPU_SECRET"))
        findings, _ = lint_paths([pkg], select=["undocumented-knob"])
        fs = active(findings)
        assert rule_ids(fs) == ["undocumented-knob"]
        assert "DASK_ML_TPU_SECRET" in fs[0].message

    def test_documented_knob_declaration_is_clean(self, tmp_path):
        pkg = self._tree(tmp_path, "DASK_ML_TPU_DEPTH",
                         self.KNOB_DECL.format(env="DASK_ML_TPU_DEPTH"))
        findings, _ = lint_paths([pkg], select=["undocumented-knob"])
        assert not active(findings)

    def test_env_number_is_a_read_site(self, tmp_path):
        pkg = self._tree(tmp_path, "DASK_ML_TPU_DEPTH",
                         self.ENV_NUMBER.format(env="DASK_ML_TPU_HIDDEN"))
        findings, _ = lint_paths([pkg], select=["undocumented-knob"])
        fs = active(findings)
        assert rule_ids(fs) == ["undocumented-knob"]
        assert "DASK_ML_TPU_HIDDEN" in fs[0].message

    def test_documented_env_number_is_clean(self, tmp_path):
        pkg = self._tree(tmp_path, "DASK_ML_TPU_DEPTH",
                         self.ENV_NUMBER.format(env="DASK_ML_TPU_DEPTH"))
        findings, _ = lint_paths([pkg], select=["undocumented-knob"])
        assert not active(findings)


# ---------------------------------------------------------------------------
# regression pins for the real drift this PR fixed
# ---------------------------------------------------------------------------

class TestFixedDriftStaysFixed:
    def test_non_retryable_roster_exists_and_is_load_bearing(self):
        from dask_ml_tpu.serve import fleet

        assert set(fleet._NON_RETRYABLE) == {
            "bad_input", "oversize", "deadline", "brownout"}
        assert not set(fleet._RETRYABLE) & set(fleet._NON_RETRYABLE)

    def test_rogue_writer_thread_stays_suppressed_not_rostered(self):
        # the sanitize drill thread must stay OFF the roster (rostering
        # it would blind the runtime check it exists to prove) and stay
        # suppressed rather than deleted
        from dask_ml_tpu.analysis.rules import _spmd

        assert "dask-ml-tpu-rogue-writer" not in _spmd.KNOWN_THREAD_NAMES
        with open(os.path.join(PKG, "sanitize", "locks.py"),
                  encoding="utf-8") as fh:
            src = fh.read()
        assert "disable=contract-roster-drift" in src
