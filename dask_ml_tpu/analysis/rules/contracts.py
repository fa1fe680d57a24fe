"""graftcontract rules: producer/consumer drift across every stringly-
typed plane contract (design.md §23).

All five rules share one :class:`~..contracts.ContractModel` per lint
(extraction walks each module once).  Each check arms only when BOTH
sides of its family exist in the linted project: a snippet with no
``_RETRYABLE`` roster has no reason contract to drift from, so rules
stay silent rather than flagging every string in sight — the same
posture ``undocumented-knob`` takes when no docs/api.md is in reach.

The seeded-drift self-test rides these rules (not a parallel code
path): ``DASK_ML_TPU_CONTRACT_INJECT=orphan-reason`` re-classifies one
REAL producer site's reason as unknown inside the orphan rule, and
``=dead-policy`` appends one unreachable key to the REAL policy table
inside the dead-consumer rule — so the gate invocation CI trusts is the
one proven able to fail (``tools/lint.sh`` runs both on its default
path, same posture as graftlock's ``--inject-*``)."""

from __future__ import annotations

from ..core import Rule, register
from .. import contracts as _c

#: baseline-drift checks: committed tools/<stem>_baseline.json file →
#: which contract family pins its keys
_DRILL_STEM, _LOCK_STEM = "drill", "lock"


def _finding(rule, site: _c.Site, message: str):
    return site.mod.ctx.finding(rule.id, site.node, message)


def _first_per_value(sites):
    """One site per distinct value (the first in path/line order) — a
    family produced at ten call sites needs one fix, not ten findings."""
    seen: set = set()
    for s in sites:
        if s.value not in seen:
            seen.add(s.value)
            yield s


@register
class ContractOrphanProducerRule(Rule):
    id = "contract-orphan-producer"
    project_wide = True
    summary = (
        "string produced into a contract-typed position that no "
        "consumer classifies — a rejection reason outside the "
        "retryable/terminal rosters is a dropped request, a fault "
        "point outside INJECTION_POINTS is an undrilled failure mode"
    )

    def run_project(self, project):
        model = _c.model_for(project)
        inject = _c.resolve_inject()
        # rejection reasons: every produced reason must be classified
        # by the retryable OR the declared non-retryable roster
        if model.retryable:
            classified = model.classified_reasons()
            for site in _first_per_value(model.reason_producers):
                if site.value not in classified:
                    yield _finding(
                        self, site,
                        f"rejection reason {site.value!r} is produced "
                        f"here but classified by neither _RETRYABLE "
                        f"nor _NON_RETRYABLE — the fleet router would "
                        f"treat it as terminal by accident; add it to "
                        f"a roster (serve/fleet.py) so the retry "
                        f"semantics are a decision, not a default",
                    )
            if inject == "orphan-reason" and model.reason_producers:
                site = model.reason_producers[0]
                yield _finding(
                    self, site,
                    f"seeded drift ({_c.CONTRACT_INJECT_ENV}="
                    f"orphan-reason): reason {site.value!r} treated as "
                    f"unclassified — the self-test proving this "
                    f"detector can fail the gate",
                )
        # injection points: a maybe_fault() literal off the roster is a
        # fault path the chaos suite will never drill
        if model.injection_roster:
            roster = {s.value for s in model.injection_roster}
            for site in model.fault_sites:
                if site.value not in roster:
                    yield _finding(
                        self, site,
                        f"injection point {site.value!r} is wired here "
                        f"but absent from INJECTION_POINTS "
                        f"(resilience/testing.py) — no drill will ever "
                        f"cover it; register it (every entry there "
                        f"must have a recovery drill)",
                    )
        # flight events: an event name claims a <layer>. namespace some
        # registry family must own (the obs spine's naming contract)
        if model.metric_literals:
            layers = model.metric_layers()
            for site in _first_per_value(model.event_producers):
                layer = site.value.split(".", 1)[0]
                if layer not in layers:
                    yield _finding(
                        self, site,
                        f"flight event {site.value!r} claims metric "
                        f"namespace {layer + '.'!r} that no registry "
                        f"family is produced under — events and "
                        f"metrics share the <layer>.<what> namespace "
                        f"so dashboards can join them; use an "
                        f"established layer or add the family",
                    )


@register
class ContractDeadConsumerRule(Rule):
    id = "contract-dead-consumer"
    project_wide = True
    summary = (
        "classifier/roster entry no producer can ever send — a POLICY "
        "key off the verdict enum silently freezes the autopilot, a "
        "RETRYABLE reason nothing raises is dead retry logic"
    )

    def run_project(self, project):
        model = _c.model_for(project)
        inject = _c.resolve_inject()
        # roster entries must be producible
        if model.reason_producers:
            produced = model.produced_reasons()
            for roster, label in ((model.retryable, "_RETRYABLE"),
                                  (model.non_retryable,
                                   "_NON_RETRYABLE")):
                for site in roster:
                    if site.value not in produced:
                        yield _finding(
                            self, site,
                            f"{label} classifies reason {site.value!r} "
                            f"that no producer site raises — dead "
                            f"classification (or the producer renamed "
                            f"its string and this entry silently "
                            f"stopped matching)",
                        )
        # POLICY keys must use producible verdict classes
        if model.verdict_classes:
            classes = {s.value for s in model.verdict_classes}
            for (plane, cls), site in model.policy_keys:
                if cls not in classes:
                    yield _finding(
                        self, site,
                        f"POLICY key ({plane!r}, {cls!r}) names a "
                        f"verdict class outside BOTTLENECK_CLASSES "
                        f"(obs/critical.py) — graftpath can never "
                        f"produce it, so this policy entry is "
                        f"unreachable and its plane silently freezes",
                    )
            if inject == "dead-policy" and model.policy_keys:
                _key, site = model.policy_keys[0]
                yield _finding(
                    self, site,
                    f"seeded drift ({_c.CONTRACT_INJECT_ENV}="
                    f"dead-policy): POLICY key ('fit', "
                    f"'__injected__') treated as present — the "
                    f"self-test proving this detector can fail the "
                    f"gate",
                )
        # metric lookups must name produced families
        if model.metric_literals:
            for site in model.metric_consumers:
                if not model.produces_metric(site.value):
                    yield _finding(
                        self, site,
                        f"metric family {site.value!r} is read here "
                        f"but no registry.counter/gauge/histogram "
                        f"site produces it — the lookup returns empty "
                        f"books forever (a renamed family leaves its "
                        f"consumers reading zeros, not failing)",
                    )
        # knob references must name declared knobs
        if model.knob_declared:
            declared = model.declared_knobs()
            for site in model.knob_consumers:
                if site.value not in declared:
                    yield _finding(
                        self, site,
                        f"knob {site.value!r} is referenced here but "
                        f"not declared in control/knobs.KNOBS — the "
                        f"strict registry raises KeyError at runtime "
                        f"(or an override/observe lands in a knob "
                        f"nobody reads)",
                    )
        # every injection point must be wired somewhere
        if model.fault_sites:
            wired = {s.value for s in model.fault_sites}
            for site in model.injection_roster:
                if site.value not in wired:
                    yield _finding(
                        self, site,
                        f"INJECTION_POINTS entry {site.value!r} has no "
                        f"maybe_fault() site — the chaos suite drills "
                        f"a point the runtime never reaches",
                    )


@register
class ContractRosterDriftRule(Rule):
    id = "contract-roster-drift"
    project_wide = True
    summary = (
        "package-namespace thread/lock name constructed off the "
        "_spmd.py rosters (or rostered but never constructed) — the "
        "static twin of graftlock's runtime roster check: an unknown "
        "dask-ml-tpu-* thread is a plane that skipped review"
    )

    def run_project(self, project):
        model = _c.model_for(project)
        if model.thread_roster:
            roster = model.rostered_threads()
            constructed = set()
            for site in model.thread_names:
                if not site.value.startswith(_c.THREAD_PREFIX):
                    continue  # client/test threads own their names
                constructed.add(site.value)
                if site.value not in roster:
                    yield _finding(
                        self, site,
                        f"thread name {site.value!r} claims the "
                        f"package namespace but is absent from the "
                        f"_spmd.py roster (KNOWN_THREAD_NAMES) — the "
                        f"roster is closed: declare the plane's "
                        f"compile/dispatch contract there or rename "
                        f"the thread out of {_c.THREAD_PREFIX!r}*",
                    )
            if constructed:
                # roster files declare names; constructions elsewhere
                # realize them — skip the check when the lint scope has
                # the roster but no constructors (vendored subsets)
                for site in _first_per_value(model.thread_roster):
                    if site.value not in constructed:
                        yield _finding(
                            self, site,
                            f"rostered thread name {site.value!r} is "
                            f"never constructed — a stale roster "
                            f"entry (or its constructor renamed the "
                            f"literal and the contract silently "
                            f"detached)",
                        )
        if model.lock_names:
            produced = model.produced_locks()
            for site in model.lock_contract_keys:
                if site.value not in produced:
                    yield _finding(
                        self, site,
                        f"LOCK_THREAD_CONTRACTS key {site.value!r} "
                        f"matches no make_lock/make_rlock/"
                        f"make_condition literal — the runtime "
                        f"monitor enforces a contract on a lock that "
                        f"no longer exists under that name",
                    )


@register
class ContractBaselineDriftRule(Rule):
    id = "contract-baseline-drift"
    project_wide = True
    summary = (
        "committed tools/*_baseline.json pins a contract string the "
        "code no longer produces (injection point, lock name) — the "
        "ratchet would compare against a "
        "family that can never recur"
    )

    def run_project(self, project):
        model = _c.model_for(project)
        drill = model.committed_baseline(_DRILL_STEM)
        if drill and model.injection_roster:
            points = {s.value for s in model.injection_roster}
            anchor = model.injection_roster[0]
            for dname, dr in sorted(drill.get("drills", {}).items()):
                pt = dr.get("point")
                if pt is not None and pt not in points:
                    yield _finding(
                        self, anchor,
                        f"drill baseline entry {dname!r} pins "
                        f"injection point {pt!r} which "
                        f"INJECTION_POINTS no longer registers — the "
                        f"chaos ratchet gates a fault path that "
                        f"cannot fire",
                    )
        lock = model.committed_baseline(_LOCK_STEM)
        if lock and model.lock_contract_keys and model.lock_names:
            produced = model.produced_locks()
            anchor = model.lock_contract_keys[0]
            for edge in sorted(lock.get("edges", ())):
                for lname in str(edge).split(" -> "):
                    if lname and lname not in produced:
                        yield _finding(
                            self, anchor,
                            f"lock baseline edge {edge!r} names lock "
                            f"{lname!r} which no make_lock literal "
                            f"produces — the deadlock ratchet pins an "
                            f"ordering over a lock that no longer "
                            f"exists",
                        )


@register
class ContractUndocumentedMetricRule(Rule):
    id = "contract-undocumented-metric"
    project_wide = True
    summary = (
        "registry family exported on /metrics but missing from "
        "docs/api.md — the metric twin of undocumented-knob: a family "
        "dashboards cannot discover and SLOs cannot audit"
    )

    def run_project(self, project):
        model = _c.model_for(project)
        text = model.api_md_text()
        if text is None:
            return  # no docs in reach: nothing to check against
        for site in _first_per_value(model.metric_literals):
            if site.value not in text:
                yield _finding(
                    self, site,
                    f"metric family {site.value!r} is produced here "
                    f"but never mentioned in docs/api.md — document "
                    f"it in the metrics-families table (layer, kind, "
                    f"tag, what it measures) so the /metrics surface "
                    f"stays discoverable and auditable",
                )
