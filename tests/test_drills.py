"""Chaos drill suite: the tier-1 recovery gate (docs/design.md §13).

One full suite run is shared by the gate assertions (the drills are
the expensive part — each is a real streamed fit with an injected
fault); the ratchet compares against the COMMITTED
``tools/drill_baseline.json`` exactly as CI does via
``tools/lint.sh --drills``.
"""

import copy
import json
import os

import pytest

from dask_ml_tpu.resilience import drills
from dask_ml_tpu.resilience.testing import INJECTION_POINTS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRILL_BASELINE = os.path.join(REPO, "tools", "drill_baseline.json")


# ---------------------------------------------------------------------------
# the gate: one full run, ratcheted against the committed snapshot
# ---------------------------------------------------------------------------

class TestDrillGate:
    @pytest.fixture(scope="class")
    def suite(self):
        return drills.run_suite()

    def test_every_drill_recovers_with_matching_model(self, suite):
        """The acceptance criterion: every registered fault point, at
        prefetch depth 0 AND 2, recovers and lands on the unfaulted
        twin's model."""
        for name, m in sorted(suite.items()):
            assert not m.get("error"), f"{name}: {m.get('error')}"
            assert m["recovered"], f"{name}: recovery path broken"
            assert m["model_match"], (
                f"{name}: recovered model diverged from the unfaulted "
                f"twin (max_rel_diff={m['max_rel_diff']})")

    def test_every_injection_point_has_a_drill(self, suite):
        covered = {m["point"] for m in suite.values()}
        assert set(INJECTION_POINTS) <= covered

    def test_thread_death_drills_clean_under_armed_sanitizer(self, suite):
        """Prefetch-worker crash and compile-ahead crash recover with
        ZERO steady-state compile/dispatch violations — recovery may
        not smuggle work past graftsan."""
        for name in ("prefetch_crash_sgd_d0", "prefetch_crash_sgd_d2",
                     "ahead_crash_sgd_d0", "ahead_crash_sgd_d2"):
            assert suite[name]["steady_violations"] == 0, name
        # and at depth 2 the faults actually fired (not vacuous)
        assert suite["prefetch_crash_sgd_d2"]["faults_injected"] == 1
        assert suite["ahead_crash_sgd_d2"]["faults_injected"] == 1

    def test_degraded_skip_recorded_exactly_once(self, suite):
        for depth in (0, 2):
            m = suite[f"stage_skip_ipca_d{depth}"]
            assert m["degraded_skips"] == 1

    def test_committed_baseline_matches(self, suite):
        """The ratchet gate: clean against the COMMITTED snapshot —
        new/stale drills, broken recovery, retry counts above the
        ceilings all fail."""
        snap = drills.load_baseline(DRILL_BASELINE)
        delta = drills.compare(snap, suite)
        assert drills.is_clean(delta), delta


class TestTracingLeftAsFound:
    @pytest.mark.parametrize("armed", [True, False])
    def test_exporter_drill_leaves_recording_as_it_found_it(self, armed):
        """The drill arms its own JSONL sink and closes it; a caller
        that was recording still is afterwards (it used to end
        disarmed, and every later span tree of the process with it),
        and one that was not is not switched on."""
        from dask_ml_tpu import obs

        was = obs.enabled()
        (obs.enable if armed else obs.disable)()
        try:
            m = drills.run_drill("exporter_enospc_mbk_d0")
            assert not m.get("error") and m["recovered"], m
            assert obs.enabled() is armed
        finally:
            (obs.enable if was else obs.disable)()


# ---------------------------------------------------------------------------
# ratchet semantics (pure-python, no fits)
# ---------------------------------------------------------------------------

def _clean_metrics(point="ingest", **over):
    m = {"point": point, "depth": 0, "recovered": True,
         "model_match": True, "max_rel_diff": 0.0, "retries": 1,
         "faults_injected": 1, "degraded_skips": 0,
         "steady_violations": 0}
    m.update(over)
    return m


def _full_results():
    return {f"d_{p}": _clean_metrics(point=p) for p in INJECTION_POINTS}


class TestCompare:
    def test_clean_round_trip(self):
        results = _full_results()
        snap = {"drills": copy.deepcopy(results)}
        assert drills.is_clean(drills.compare(snap, results))

    def test_new_drill_fails(self):
        results = _full_results()
        snap = {"drills": copy.deepcopy(results)}
        results["d_extra"] = _clean_metrics()
        delta = drills.compare(snap, results)
        assert delta["new"] == ["d_extra"]

    def test_stale_entry_fails(self):
        results = _full_results()
        snap = {"drills": copy.deepcopy(results)}
        snap["drills"]["d_gone"] = _clean_metrics()
        delta = drills.compare(snap, results)
        assert delta["stale"] == ["d_gone"]

    def test_uncovered_point_fails(self):
        results = _full_results()
        del results["d_ingest"]
        snap = {"drills": copy.deepcopy(results)}
        delta = drills.compare(snap, results)
        assert any("'ingest'" in line for line in delta["uncovered"])

    def test_retry_ceiling_regression_fails(self):
        results = _full_results()
        snap = {"drills": copy.deepcopy(results)}
        results["d_ingest"]["retries"] = 5
        delta = drills.compare(snap, results)
        assert any("retries 5 > baseline 1" in line
                   for line in delta["regressions"])

    def test_broken_recovery_is_a_hard_violation(self):
        results = _full_results()
        snap = {"drills": copy.deepcopy(results)}
        results["d_step"]["recovered"] = False
        delta = drills.compare(snap, results)
        assert any("recovered" in line for line in delta["violations"])

    def test_snapshot_cannot_grandfather_broken_recovery(self):
        results = _full_results()
        snap = {"drills": copy.deepcopy(results)}
        snap["drills"]["d_step"]["model_match"] = False
        delta = drills.compare(snap, results)
        assert any("grandfather" in line for line in delta["violations"])

    def test_steady_violation_is_hard_zero(self):
        results = _full_results()
        snap = {"drills": copy.deepcopy(results)}
        results["d_compile-ahead"]["steady_violations"] = 1
        delta = drills.compare(snap, results)
        assert any("steady_violations" in line
                   for line in delta["violations"])

    def test_partial_subset_skips_stale_and_coverage(self):
        results = {"d_ingest": _clean_metrics()}
        snap = {"drills": _full_results()}
        delta = drills.compare(snap, results, partial=True)
        assert drills.is_clean(delta)

    def test_errored_drill_is_a_violation(self):
        results = _full_results()
        snap = {"drills": copy.deepcopy(results)}
        results["d_stage"]["error"] = "RuntimeError: boom"
        delta = drills.compare(snap, results)
        assert any("errored" in line for line in delta["violations"])


class TestBaselineStore:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "b.json")
        drills.write_baseline(path, drills.emit_baseline(_full_results()))
        snap = drills.load_baseline(path)
        assert snap["tool"] == "graftdrill"
        assert set(snap["drills"]) == set(_full_results())

    def test_newer_version_refused(self, tmp_path):
        path = tmp_path / "b.json"
        path.write_text(json.dumps({"version": 99, "drills": {}}))
        with pytest.raises(ValueError, match="newer"):
            drills.load_baseline(str(path))

    def test_malformed_refused(self, tmp_path):
        path = tmp_path / "b.json"
        path.write_text(json.dumps({"version": 1}))
        with pytest.raises(ValueError, match="malformed"):
            drills.load_baseline(str(path))

    def test_committed_baseline_carries_no_violations(self):
        """A committed snapshot may never grandfather a broken recovery
        path — checked standalone so a bad hand-edit fails even before
        the suite runs."""
        snap = drills.load_baseline(DRILL_BASELINE)
        delta = drills.compare(snap, {k: dict(v) for k, v in
                                      snap["drills"].items()})
        assert not delta["violations"], delta["violations"]
        assert not delta["uncovered"], delta["uncovered"]


class TestCLI:
    def test_partial_write_baseline_refused(self, tmp_path, capsys):
        rc = drills.main(["--write-baseline", str(tmp_path / "b.json"),
                          "--drills", "ingest_retry_sgd_d0"])
        assert rc == 2
        assert not (tmp_path / "b.json").exists()

    def test_unknown_drill_exits_two(self, capsys):
        rc = drills.main(["--drills", "no_such_drill"])
        assert rc == 2

    def test_unreadable_baseline_exits_two(self, tmp_path, capsys,
                                           monkeypatch):
        monkeypatch.setattr(drills, "run_suite",
                            lambda names=None: _full_results())
        rc = drills.main(["--baseline", str(tmp_path / "missing.json")])
        assert rc == 2

    def test_violating_run_never_writes_baseline(self, tmp_path, capsys,
                                                 monkeypatch):
        bad = _full_results()
        bad["d_step"]["recovered"] = False
        monkeypatch.setattr(drills, "run_suite",
                            lambda names=None: bad)
        path = tmp_path / "b.json"
        rc = drills.main(["--write-baseline", str(path)])
        assert rc == 1
        assert not path.exists()

    def test_clean_run_round_trips_and_gates(self, tmp_path, capsys,
                                             monkeypatch):
        monkeypatch.setattr(drills, "run_suite",
                            lambda names=None: _full_results())
        path = str(tmp_path / "b.json")
        assert drills.main(["--write-baseline", path]) == 0
        assert drills.main(["--baseline", path]) == 0

    def test_list_drills(self, capsys):
        assert drills.main(["--list-drills"]) == 0
        out = capsys.readouterr().out
        assert "ingest_retry_sgd_d0" in out
        assert "ahead_crash_sgd_d2" in out
