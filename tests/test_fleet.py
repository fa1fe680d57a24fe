"""graftfleet: replicated serving with a health-aware router
(dask_ml_tpu/serve/fleet.py + router.py, design.md §22).

Covers the PR 19 acceptance criteria: consistent placement (hot
replication, cold rendezvous partitioning under per-replica budgets
with counted spill), readiness-gated routing (a warming replica never
sees traffic), budgeted retry with full-jitter backoff, tail hedging
(first-response-wins with the loser's spend counted), replica death →
budgeted respawn while survivors absorb, brownout shedding by priority
class when the fleet budget is gone (never blackout), rolling deploys
behind the drain barrier with the autopilot held, the per-replica
graftpath verdicts, and the seeded-fault self-test's exit contract
(sighted 0 / blind 1).  The chaos-drill versions of these scenarios
ratchet in resilience/drills.py; this file owns the unit-level policy
checks that need no baseline.
"""

import threading
import time

import numpy as np
import pytest

from dask_ml_tpu.control import pilot as _pilot
from dask_ml_tpu.linear_model import SGDClassifier
from dask_ml_tpu.obs.metrics import registry as _registry
from dask_ml_tpu.resilience.elastic import FaultBudget
from dask_ml_tpu.serve import (
    RequestRejected,
    Router,
    ServeFleet,
    full_jitter_backoff,
    rendezvous,
)
from dask_ml_tpu.serve import config as _cfg


def _fitted_clf(seed=0, d=8, n=512):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32)
    clf = SGDClassifier(random_state=seed)
    clf.partial_fit(X, y, classes=np.arange(2))
    return clf, X


def _mini_fleet(n=2, **kw):
    kw.setdefault("window_s", 0.0)
    kw.setdefault("hedge_ms", 0.0)
    kw.setdefault("budget", FaultBudget(16, 60.0, name="t_fleet"))
    return ServeFleet(replicas=n, label="t_fleet", **kw)


class _FakeRep:
    def __init__(self, index, ready=True, qsize=0):
        self.index = index
        self._ready = ready
        self._qsize = qsize

    def ready(self):
        return self._ready

    def qsize(self):
        return self._qsize


class TestRouterPolicy:
    def test_rendezvous_is_consistent_under_membership_change(self):
        ids = [0, 1, 2, 3]
        ranked = rendezvous("some-model", ids, k=4)
        assert sorted(ranked) == ids
        # removing a NON-chosen replica must not move the model
        loser = ranked[-1]
        assert rendezvous("some-model", [i for i in ids if i != loser],
                          k=1) == ranked[:1]
        # same name, same ids → same answer every time
        assert rendezvous("some-model", ids, k=2) == ranked[:2]

    def test_hot_replicates_cold_partitions(self):
        reps = [_FakeRep(i) for i in range(3)]
        r = Router(reps)
        assert r.place("hot-model", hot=True) == (0, 1, 2)
        cold = r.place("cold-model")
        assert len(cold) == 1
        # idempotent re-place: deploys refresh in place, never migrate
        assert r.place("cold-model") == cold

    def test_cold_placement_respects_budget_and_counts_spill(self):
        reps = [_FakeRep(i) for i in range(2)]
        r = Router(reps, budget_bytes=100)
        spill0 = _registry().counter("fleet.placement_spill").value
        first = r.place("model-a", nbytes=80)
        second = r.place("model-b", nbytes=80)
        # the second cold model cannot share the first's replica budget
        assert first != second
        assert _registry().counter("fleet.placement_spill").value == spill0
        third = r.place("model-c", nbytes=80)  # fits nowhere: spills
        assert len(third) == 1
        assert _registry().counter(
            "fleet.placement_spill").value == spill0 + 1

    def test_candidates_gate_on_readiness_and_partition(self):
        reps = [_FakeRep(0, qsize=5), _FakeRep(1, qsize=1),
                _FakeRep(2, ready=False)]
        r = Router(reps)
        r.place("m", hot=True)
        # warming replica excluded; least-loaded first
        assert [c.index for c in r.candidates("m")] == [1, 0]
        r.partition(0, duration_s=30.0)
        assert [c.index for c in r.candidates("m")] == [1]
        assert r.is_partitioned(0) is True
        r._partition_until[0] = 0.0  # force-expire: heals, re-admits
        assert r.is_partitioned(0) is False
        assert [c.index for c in r.candidates("m")] == [1, 0]

    def test_blind_router_skips_every_gate(self):
        reps = [_FakeRep(0, ready=False, qsize=9), _FakeRep(1)]
        r = Router(reps, blind=True)
        r.place("m", hot=True)
        r.partition(0, duration_s=30.0)
        # raw placement order: no readiness, no partition, no reorder
        assert [c.index for c in r.candidates("m")] == [0, 1]

    def test_full_jitter_backoff_bounds(self):
        import random

        rng = random.Random(7)
        for attempt in range(8):
            cap = min(0.25, 0.01 * 2 ** attempt)
            for _ in range(20):
                d = full_jitter_backoff(attempt, rng=rng)
                assert 0.0 <= d <= cap


class TestFleetServing:
    def test_fleet_predictions_match_direct(self):
        clf, X = _fitted_clf()
        with _mini_fleet(2) as fleet:
            assert fleet.load("m", clf, hot=True) == (0, 1)
            for rows in (1, 3, 16):
                np.testing.assert_array_equal(
                    fleet.predict("m", X[:rows]),
                    np.asarray(clf.predict(X[:rows])))

    def test_unknown_model_and_priorities(self):
        clf, X = _fitted_clf()
        with _mini_fleet(2) as fleet:
            fleet.load("m", clf)
            with pytest.raises(RequestRejected) as ei:
                fleet.submit("nope", X[:1])
            assert ei.value.reason == "unknown_model"
            with pytest.raises(ValueError):
                fleet.submit("m", X[:1], priority="vip")

    def test_replica_death_respawns_within_budget(self):
        clf, X = _fitted_clf()
        reg = _registry()
        respawn0 = reg.counter("fleet.respawn").value
        with _mini_fleet(2, replica_fault_attempts=0) as fleet:
            fleet.load("m", clf, hot=True)
            fleet.predict("m", X[:1])
            victim = fleet._replicas[0]
            victim.server.kill()
            fleet.predict("m", X[:1])  # tick the victim's loop awake
            for _ in range(500):
                if victim.state() == "dead":
                    break
                time.sleep(0.01)
            # survivors absorb while the routing sweep respawns
            for i in range(4):
                np.testing.assert_array_equal(
                    fleet.predict("m", X[i:i + 2], timeout=30.0),
                    np.asarray(clf.predict(X[i:i + 2])))
            assert reg.counter("fleet.respawn").value >= respawn0 + 1
            # the fresh slot warms and re-enters the candidate set
            for _ in range(1000):
                if len(fleet._router.candidates("m")) == 2:
                    break
                time.sleep(0.01)
            assert len(fleet._router.candidates("m")) == 2

    def test_hedge_beats_a_stalled_replica(self):
        clf, X = _fitted_clf()
        reg = _registry()
        won0 = reg.counter("fleet.hedge", "won").value
        launched0 = reg.counter("fleet.hedge", "launched").value
        with _mini_fleet(2, hedge_ms=20.0) as fleet:
            fleet.load("m", clf, hot=True)
            fleet.predict("m", X[:1])  # warm both paths
            slow = fleet._router.candidates("m")[0]
            slow.server._test_dispatch_delay_s = 0.4
            got = fleet.predict("m", X[:4], timeout=30.0)
            slow.server._test_dispatch_delay_s = 0.0
            np.testing.assert_array_equal(
                got, np.asarray(clf.predict(X[:4])))
            assert reg.counter("fleet.hedge",
                               "launched").value >= launched0 + 1
            # the hedge's answer was the one delivered: it beat the
            # straggler (the counter says so; no clock is asked)
            assert reg.counter("fleet.hedge", "won").value >= won0 + 1

    def test_brownout_sheds_lowest_class_first_and_clears(self):
        clf, X = _fitted_clf()
        with _mini_fleet(2, replica_fault_attempts=0,
                         budget=FaultBudget(0, 60.0,
                                            name="t_brownout")) as fleet:
            fleet.load("m", clf, hot=True)
            fleet.predict("m", X[:1])
            victim = fleet._replicas[0]
            victim.server.kill()
            try:  # traffic, so that the victim's loop cycles and dies
                fleet.predict("m", X[:1])
            except RequestRejected:
                pass  # it died holding this one: no budget to replay on
            for _ in range(500):
                if victim.state() == "dead":
                    break
                time.sleep(0.01)
            # the respawn attempt hits the exhausted FLEET budget →
            # brownout: low sheds, high keeps serving on the survivor
            np.testing.assert_array_equal(
                fleet.predict("m", X[:2], priority="high"),
                np.asarray(clf.predict(X[:2])))
            assert fleet._shed_level >= 1
            with pytest.raises(RequestRejected) as ei:
                fleet.submit("m", X[:1], priority="low")
            assert ei.value.reason == "brownout"
            assert _registry().family(
                "fleet.rejected").get("brownout", 0) >= 1
            # manual recovery (a fresh slot outside the dead budget):
            # all replicas ready again → the next submit clears shed
            from dask_ml_tpu.serve.fleet import Replica
            # close the corpse first: a replaced-but-unclosed server
            # would leak its dead supervised unit + not-ready probe
            # into the process-global healthz/readyz books
            fleet._replicas[0].server.close(timeout=1.0)
            fleet._replicas[0] = Replica(0, fleet._spawn_server(0))
            fleet._replicas[0].server.load("m", clf)
            fleet._router._replicas[0] = fleet._replicas[0]
            fleet.predict("m", X[:1], priority="high")
            assert fleet._shed_level == 0
            fleet.predict("m", X[:1], priority="low")  # re-admitted

    def test_slo_miss_counted_per_model(self):
        clf, X = _fitted_clf()
        reg = _registry()
        with _mini_fleet(2) as fleet:
            fleet.load("m", clf, hot=True, slo_ms=0.0001)
            miss0 = reg.counter("fleet.slo_miss", "m").value
            fleet.predict("m", X[:4])
            assert reg.counter("fleet.slo_miss", "m").value >= miss0 + 1


class TestRollingDeploy:
    def test_refresh_under_traffic_rejections_confined_to_draining(self):
        clf_a, X = _fitted_clf(seed=0)
        clf_b, _ = _fitted_clf(seed=3)
        twin_a = np.asarray(clf_a.predict(X[:8]))
        twin_b = np.asarray(clf_b.predict(X[:8]))
        reg = _registry()
        reject0 = dict(reg.family("serve.rejected"))
        stop = threading.Event()
        served, holds_seen = [], []

        with _mini_fleet(2, retries=3) as fleet:
            fleet.load("m", clf_a, hot=True)
            # what the controller is held by, read at every replica's
            # drain barrier (not from the traffic thread, which a busy
            # box may not schedule once inside the walk)
            for rep in fleet._replicas:
                def _drain(*a, real=rep.server.drain, **k):
                    holds_seen.extend(_pilot.active_holds())
                    return real(*a, **k)

                rep.server.drain = _drain

            def _traffic():
                while not stop.is_set():
                    try:
                        served.append(np.asarray(
                            fleet.predict("m", X[:8], timeout=30.0)))
                    except BaseException as exc:  # noqa: BLE001
                        served.append(exc)

            t = threading.Thread(target=_traffic, name="t_deploy_tfc")
            t.start()
            try:
                out = fleet.rolling_refresh("m", clf_b, timeout=30.0)
            finally:
                stop.set()
                t.join(timeout=30.0)
            assert not t.is_alive()
            assert set(out) == {"r0", "r1"}
            assert all(v["ready"] for v in out.values())
            # the controller was held for the whole walk
            assert holds_seen.count("fleet_drain") == 2  # at each drain
            assert not _pilot.active_holds()  # and released after
            # every served answer is EXACTLY old or new — never a blend
            for r in served:
                assert isinstance(r, np.ndarray), r
                assert (np.array_equal(r, twin_a)
                        or np.array_equal(r, twin_b))
            # fleet-level replay confined any rejection to `draining`
            delta = {k: v - reject0.get(k, 0)
                     for k, v in reg.family("serve.rejected").items()
                     if v - reject0.get(k, 0)}
            assert set(delta) <= {"draining"}
            np.testing.assert_array_equal(
                fleet.predict("m", X[:8]), twin_b)

    def test_refresh_unplaced_model_raises(self):
        with _mini_fleet(2) as fleet:
            with pytest.raises(KeyError):
                fleet.rolling_refresh("ghost", object())


class TestWarmupAndObservability:
    def test_warm_from_drives_per_host_shards(self, tmp_path):
        from dask_ml_tpu import data as _data

        clf, X = _fitted_clf(d=4)
        rng = np.random.RandomState(5)
        Xd = rng.normal(size=(512, 4)).astype(np.float32)
        yd = (Xd[:, 0] > 0).astype(np.int32)
        _data.write_dataset(str(tmp_path), Xd, yd, shards=4,
                            block_rows=256)
        with _mini_fleet(2) as fleet:
            fleet.load("m", clf, hot=True)
            warmed = fleet.warm_from(str(tmp_path), rows=16)
            assert warmed.get("r0/m") == 16
            assert warmed.get("r1/m") == 16

    def test_report_aggregates_replica_scrapes(self):
        clf, X = _fitted_clf()
        with _mini_fleet(2) as fleet:
            fleet.load("m", clf, hot=True)
            fleet.predict("m", X[:2])
            rep = fleet.report()
            assert set(rep["replicas"]) == {"r0", "r1"}
            assert all(r["state"] == "ready"
                       for r in rep["replicas"].values())
            assert rep["router"]["placement"] == {"m": [0, 1]}
            assert any(k.startswith("fleet.replica_state")
                       for k in rep["metrics"])
            assert rep["priorities"] == ["low", "normal", "high"]

    def test_per_replica_critical_verdicts(self):
        from dask_ml_tpu.obs.critical import serve_critical

        clf, X = _fitted_clf()
        reg = _registry()
        reg.reset(prefix="serve.req_")
        reg.reset(prefix="serve.request_s")
        with _mini_fleet(2) as fleet:
            fleet.load("m", clf, hot=True)
            for i in range(8):
                fleet.predict("m", X[i:i + 2])
            tagged = [serve_critical(tag=f"r{i}", publish=False)
                      for i in range(2)]
            assert any(v is not None for v in tagged)
            for v in tagged:
                if v is not None:
                    assert v["plane"].startswith("serve:r")
                    assert v["requests"] >= 1
            # an unknown tag is silence, not an invented story
            assert serve_critical(tag="r9", publish=False) is None


class TestSelfTestContract:
    def test_sighted_exits_zero(self, monkeypatch):
        from dask_ml_tpu.serve import fleet as fleet_mod

        monkeypatch.delenv(_cfg.FLEET_INJECT_ENV, raising=False)
        assert fleet_mod.self_test(verbose=False) == 0

    def test_blind_router_exits_one(self, monkeypatch):
        from dask_ml_tpu.serve import fleet as fleet_mod

        monkeypatch.setenv(_cfg.FLEET_INJECT_ENV, "replica-kill")
        assert fleet_mod.self_test(verbose=False) == 1


class TestFleetKnobs:
    def test_strict_parse_rejects_typos(self, monkeypatch):
        monkeypatch.setenv(_cfg.FLEET_REPLICAS_ENV, "two")
        with pytest.raises(ValueError):
            _cfg.resolve_fleet_replicas()
        monkeypatch.delenv(_cfg.FLEET_REPLICAS_ENV)
        monkeypatch.setenv(_cfg.FLEET_INJECT_ENV, "replica-maim")
        with pytest.raises(ValueError):
            _cfg.resolve_fleet_inject()

    def test_priorities_parse_and_validate(self, monkeypatch):
        monkeypatch.setenv(_cfg.FLEET_PRIORITIES_ENV, "bulk, rt")
        assert _cfg.resolve_fleet_priorities() == ("bulk", "rt")
        monkeypatch.setenv(_cfg.FLEET_PRIORITIES_ENV, "a,a")
        with pytest.raises(ValueError):
            _cfg.resolve_fleet_priorities()

    def test_explicit_args_pin_over_env(self, monkeypatch):
        monkeypatch.setenv(_cfg.FLEET_REPLICAS_ENV, "7")
        assert _cfg.resolve_fleet_replicas(3) == 3
        assert _cfg.resolve_fleet_replicas() == 7
        assert _cfg.resolve_hedge_s(250.0) == pytest.approx(0.25)
        assert _cfg.resolve_fleet_retries(0) == 0


class TestRejectClassification:
    """Every rejection reason lands in the flight recorder with an
    explicit retry classification — 'unclassified' is the graftcontract
    drift signal (contract-orphan-producer), never a shipped state."""

    def _last_reject(self):
        from dask_ml_tpu.obs import flight

        evs = [e for e in flight.tail() if e["name"] == "fleet.reject"]
        assert evs, "no fleet.reject flight event recorded"
        return evs[-1]["attrs"]

    def test_retryable_reason_tags_retryable(self):
        from dask_ml_tpu.serve import fleet as fleet_mod

        with _mini_fleet(1) as fleet:
            fleet._count_reject("queue_full", "m")
            assert self._last_reject()["retry"] == "retryable"
            for reason in fleet_mod._RETRYABLE:
                fleet._count_reject(reason, "m")
                assert self._last_reject() == {
                    "model": "m", "reason": reason, "retry": "retryable"}

    def test_terminal_reason_tags_terminal(self):
        from dask_ml_tpu.serve import fleet as fleet_mod

        with _mini_fleet(1) as fleet:
            for reason in fleet_mod._NON_RETRYABLE:
                fleet._count_reject(reason, "m")
                assert self._last_reject() == {
                    "model": "m", "reason": reason, "retry": "terminal"}

    def test_unknown_reason_is_loud_not_defaulted(self):
        # an unrostered reason must scream 'unclassified' in the books
        # (and graftcontract rejects it at lint time before it ships)
        with _mini_fleet(1) as fleet:
            fleet._count_reject("mystery", "m")
            assert self._last_reject()["retry"] == "unclassified"

    def test_real_rejection_carries_classification(self):
        clf, X = _fitted_clf()
        with _mini_fleet(1) as fleet:
            fleet.load("m", clf)
            with pytest.raises(RequestRejected) as ei:
                fleet.predict("nope", X[:1])
            assert ei.value.reason == "unknown_model"
            attrs = self._last_reject()
            assert attrs["reason"] == "unknown_model"
            assert attrs["retry"] == "retryable"
