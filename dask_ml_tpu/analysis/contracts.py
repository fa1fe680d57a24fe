"""graftcontract: the whole-program stringly-typed contract model.

Nineteen PRs of planes coordinate almost entirely through STRING
contracts: ``RequestRejected(reason=...)`` strings the fleet router
classifies as retryable, graftpath verdict classes keyed into the
autopilot POLICY table, registry metric families scraped via
``/metrics``, injection-point names drilled by
the chaos ratchet, thread/lock names rostered in ``rules/_spmd.py``,
knob names resolved through ``control/knobs.KNOBS``.  Nothing *ran*
when one side drifted: a renamed reason silently turns a retryable
rejection into a dropped request; a renamed verdict class silently
freezes the autopilot.  This module mechanizes those contracts the way
``undocumented-knob`` mechanizes env knobs — extract every PRODUCER
site (a string literal flowing into a contract-typed position) and
every CONSUMER site (a roster, a classifier table, a committed
baseline, a docs table) per family, and let ``rules/contracts.py``
report the difference.

Families (the design.md §23 table, one row per entry here):

* **rejection-reason** — produced by ``RequestRejected(reason, ...)``,
  ``reject(req, reason, ...)``, ``_fleet_reject(reason, ...)`` /
  ``_reject_submit(reason, ...)``; consumed by the ``_RETRYABLE`` /
  ``_NON_RETRYABLE`` rosters (serve/fleet.py).
* **verdict-class** — declared by ``BOTTLENECK_CLASSES``
  (obs/critical.py); consumed by the ``POLICY`` table keys
  (control/pilot.py).
* **metric-family** — produced by ``registry.counter/gauge/histogram
  (name, ...)`` (literal or f-string prefix); consumed by
  ``registry.family(name)`` lookups, ``_PROGRESS_FAMILIES``, and the
  docs/api.md metrics table.
* **flight-event** — produced by ``obs.event(name, ...)``; an event
  name claims a ``<layer>.`` namespace some metric family must own.
* **injection-point** — produced by ``maybe_fault(point)`` sites;
  consumed by the ``INJECTION_POINTS`` roster (resilience/testing.py)
  and the drill baseline's per-drill ``point`` entries.
* **thread/lock-roster** — produced by ``Thread(name=...)`` /
  ``make_lock(name)`` constructions; consumed by the ``_spmd.py``
  rosters (``KNOWN_THREAD_NAMES``, ``LOCK_THREAD_CONTRACTS``) and the
  lock baseline's edge set.
* **knob-name** — declared by ``Knob(name, env, ...)``; consumed by
  ``knobs.set_knob/override/override_or/observe/knob(name)``.

Pure ``ast`` like the rest of the engine — never imports the package
under analysis.  Extraction is conservative: a reason/name the
dataflow half cannot prove to be a string (a pass-through variable,
``e.reason`` re-raises) is NOT a producer site — it forwards someone
else's literal, which is extracted where it was born.

Seeded-drift self-test (``tools/lint.sh`` posture: a blind detector can
never gate): ``DASK_ML_TPU_CONTRACT_INJECT=orphan-reason`` makes the
orphan-producer rule treat one REAL producer site's reason as
unclassified, ``=dead-policy`` makes the dead-consumer rule see one
extra POLICY key no producer can send — either must turn a clean gate
run into exit 1 through the very invocation CI trusts.
"""

from __future__ import annotations

import ast
import json
import os
import re

from .core import Context, dotted_name
from .dataflow import resolve_str_constant
from .graph import ModuleInfo, Project, find_api_md

__all__ = [
    "CONTRACT_INJECT_ENV",
    "INJECT_MODES",
    "ContractModel",
    "Site",
    "model_for",
    "resolve_inject",
]

#: seeded-drift self-test knob (``tools/lint.sh`` convention, same
#: posture as DASK_ML_TPU_LOCK_INJECT / DASK_ML_TPU_FLEET_INJECT):
#: ``orphan-reason`` seeds an unclassified rejection reason at a real
#: producer site, ``dead-policy`` seeds an unreachable POLICY key at
#: the real table — the contract gate must exit 1 under either.
CONTRACT_INJECT_ENV = "DASK_ML_TPU_CONTRACT_INJECT"

INJECT_MODES = ("orphan-reason", "dead-policy")


def resolve_inject() -> str | None:
    """The armed seeded-drift mode, or None.  Strict parse: an unknown
    value raises (analyzer exit 2 — a typo'd self-test knob must never
    read as a clean gate)."""
    raw = os.environ.get(CONTRACT_INJECT_ENV, "").strip()
    if not raw:
        return None
    if raw not in INJECT_MODES:
        raise ValueError(
            f"{CONTRACT_INJECT_ENV} must be one of "
            f"{'|'.join(INJECT_MODES)}, got {raw!r}")
    return raw


class Site:
    """One extracted contract string and where it lives."""

    __slots__ = ("mod", "node", "value")

    def __init__(self, mod: ModuleInfo, node: ast.AST, value: str):
        self.mod = mod
        self.node = node
        self.value = value

    def __repr__(self):
        return f"Site({self.value!r}, {self.mod.path}:{self.node.lineno})"


def _sort_key(site: Site):
    return (site.mod.path, site.node.lineno,
            getattr(site.node, "col_offset", 0), site.value)


#: registry-family shape: ``<layer>.<what>[_<unit>]`` — anything else a
#: ``.counter(...)`` receives is some other API's counter, not ours
_FAMILY_RE = re.compile(r"^[a-z][a-z0-9_]*\.[a-z0-9_.]+$")

#: rejection-reason producer callables → which argument is the reason
#: (``reject(req, reason, detail)`` offsets by one)
_REASON_CALLS = {"RequestRejected": 0, "_fleet_reject": 0,
                 "_reject_submit": 0, "reject": 1}

_METRIC_CTORS = frozenset({"counter", "gauge", "histogram"})
_LOCK_CTORS = frozenset({"make_lock", "make_rlock", "make_condition"})
_FAULT_CALLS = frozenset({"maybe_fault", "_maybe_fault"})
_KNOB_CONSUMERS = frozenset({
    "knob", "set_knob", "override", "override_or", "observe",
    "clear_override",
})
_THREAD_ROSTER_NAMES = frozenset({
    "BLESSED_COMPILE_THREADS", "BLESSED_DISPATCH_THREADS",
    "HOST_ONLY_THREAD_NAMES", "KNOWN_THREAD_NAMES",
})
#: the package thread namespace: a constructed name claiming it must be
#: on the roster (names outside the prefix are client/test threads)
THREAD_PREFIX = "dask-ml-tpu-"


def _collect_strs(expr: ast.AST, mod: ModuleInfo,
                  env: dict) -> set | None:
    """Every string constant a roster expression evaluates to — through
    set/tuple/list literals, ``frozenset(...)``/``set(...)`` calls,
    ``|`` unions, and Names bound to earlier rosters or module string
    constants.  None = not provably a string collection."""
    if isinstance(expr, ast.Constant):
        return {expr.value} if isinstance(expr.value, str) else None
    if isinstance(expr, (ast.Set, ast.Tuple, ast.List)):
        out: set = set()
        for elt in expr.elts:
            sub = _collect_strs(elt, mod, env)
            if sub is None:
                return None
            out |= sub
        return out
    if isinstance(expr, ast.Call):
        fn = dotted_name(expr.func) or ""
        if fn.rpartition(".")[2] in ("frozenset", "set", "tuple") \
                and len(expr.args) == 1:
            return _collect_strs(expr.args[0], mod, env)
        return None
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.BitOr):
        left = _collect_strs(expr.left, mod, env)
        right = _collect_strs(expr.right, mod, env)
        if left is None or right is None:
            return None
        return left | right
    if isinstance(expr, ast.Name):
        if expr.id in env:
            return set(env[expr.id])
        const = mod.str_constants.get(expr.id)
        return {const} if const is not None else None
    return None


class ContractModel:
    """Every producer and consumer site, extracted once per lint."""

    def __init__(self, project: Project):
        self.project = project
        # producers
        self.reason_producers: list[Site] = []
        self.metric_literals: list[Site] = []
        self.metric_patterns: list[tuple[str, str, Site]] = []
        self.event_producers: list[Site] = []
        self.fault_sites: list[Site] = []
        self.thread_names: list[Site] = []
        self.lock_names: list[Site] = []
        self.knob_declared: list[Site] = []     # value = knob name
        self.knob_envs: list[Site] = []         # value = env spelling
        # consumers / rosters
        self.retryable: list[Site] = []
        self.non_retryable: list[Site] = []
        self.verdict_classes: list[Site] = []
        self.policy_keys: list[tuple[tuple[str, str], Site]] = []
        self.metric_consumers: list[Site] = []
        self.injection_roster: list[Site] = []
        self.thread_roster: list[Site] = []
        self.lock_contract_keys: list[Site] = []
        self.knob_consumers: list[Site] = []
        for mod in project.modules:
            self._extract_module(mod)
        for lst in (
            self.reason_producers, self.metric_literals,
            self.event_producers, self.fault_sites, self.thread_names,
            self.lock_names, self.knob_declared, self.knob_envs,
            self.retryable, self.non_retryable, self.verdict_classes,
            self.metric_consumers, self.injection_roster,
            self.thread_roster, self.lock_contract_keys,
            self.knob_consumers,
        ):
            lst.sort(key=_sort_key)
        self._api_md_text: str | None | bool = False

    # -- extraction ------------------------------------------------------
    def _extract_module(self, mod: ModuleInfo) -> None:
        roster_env: dict[str, set] = {}
        for stmt in mod.ctx.tree.body:
            self._extract_toplevel(mod, stmt, roster_env)
        for node in ast.walk(mod.ctx.tree):
            if isinstance(node, ast.Call):
                self._extract_call(mod, node)

    def _extract_toplevel(self, mod: ModuleInfo, stmt: ast.stmt,
                          roster_env: dict) -> None:
        """Module-level roster/classifier declarations."""
        if isinstance(stmt, ast.AnnAssign):
            targets, value = [stmt.target], stmt.value
        elif isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
            targets, value = stmt.targets, stmt.value
        else:
            return
        target = targets[0]
        if not isinstance(target, ast.Name) or value is None:
            return
        name = target.id
        if name in ("_RETRYABLE", "_NON_RETRYABLE", "RETRYABLE",
                    "NON_RETRYABLE"):
            dest = self.retryable if "NON" not in name \
                else self.non_retryable
            for v in _collect_strs(value, mod, roster_env) or ():
                dest.append(Site(mod, stmt, v))
        elif name == "BOTTLENECK_CLASSES":
            for v in _collect_strs(value, mod, roster_env) or ():
                self.verdict_classes.append(Site(mod, stmt, v))
        elif name == "POLICY" and isinstance(value, ast.Dict):
            for k in value.keys:
                if isinstance(k, ast.Tuple) and len(k.elts) == 2 and \
                        all(isinstance(e, ast.Constant) and
                            isinstance(e.value, str) for e in k.elts):
                    key = (k.elts[0].value, k.elts[1].value)
                    self.policy_keys.append((key, Site(mod, k, key[1])))
        elif name == "_PROGRESS_FAMILIES":
            for v in _collect_strs(value, mod, roster_env) or ():
                self.metric_consumers.append(Site(mod, stmt, v))
        elif name == "INJECTION_POINTS":
            for v in _collect_strs(value, mod, roster_env) or ():
                self.injection_roster.append(Site(mod, stmt, v))
        elif name in _THREAD_ROSTER_NAMES:
            vals = _collect_strs(value, mod, roster_env)
            if vals is not None:
                roster_env[name] = vals
                for v in vals:
                    self.thread_roster.append(Site(mod, stmt, v))
        elif name == "LOCK_THREAD_CONTRACTS" and \
                isinstance(value, ast.Dict):
            for k in value.keys:
                if isinstance(k, ast.Constant) and \
                        isinstance(k.value, str):
                    self.lock_contract_keys.append(
                        Site(mod, stmt, k.value))

    def _arg(self, call: ast.Call, pos: int, kw: str | None = None):
        if len(call.args) > pos:
            return call.args[pos]
        if kw is not None:
            for k in call.keywords:
                if k.arg == kw:
                    return k.value
        return None

    def _str_arg(self, mod: ModuleInfo, node: ast.AST | None) -> str | None:
        if node is None:
            return None
        return resolve_str_constant(node, None, mod)

    def _extract_call(self, mod: ModuleInfo, call: ast.Call) -> None:
        name = dotted_name(call.func)
        if name is None and isinstance(call.func, ast.Attribute):
            # `_registry().counter(...)` hangs the contract method off a
            # Call, which dotted_name cannot render — the attribute name
            # alone still identifies the position
            name = call.func.attr
        if name is None:
            return
        last = name.rpartition(".")[2]
        # rejection reasons
        if last in _REASON_CALLS:
            pos = _REASON_CALLS[last]
            reason = self._str_arg(
                mod, self._arg(call, pos, "reason"))
            if reason is not None:
                self.reason_producers.append(Site(mod, call, reason))
            return
        # metric families
        if last in _METRIC_CTORS and call.args:
            arg = call.args[0]
            lit = self._str_arg(mod, arg)
            if lit is not None:
                if _FAMILY_RE.match(lit):
                    self.metric_literals.append(Site(mod, call, lit))
            elif isinstance(arg, ast.JoinedStr):
                prefix, suffix = _fstring_affixes(arg)
                if prefix or suffix:
                    self.metric_patterns.append(
                        (prefix, suffix, Site(mod, call,
                                              f"{prefix}*{suffix}")))
            return
        # flight events
        if last == "event" and call.args:
            lit = self._str_arg(mod, call.args[0])
            if lit is not None and _FAMILY_RE.match(lit):
                self.event_producers.append(Site(mod, call, lit))
            return
        # metric consumers
        if last == "family" and call.args:
            lit = self._str_arg(mod, call.args[0])
            if lit is not None and _FAMILY_RE.match(lit):
                self.metric_consumers.append(Site(mod, call, lit))
            return
        # injection points
        if last in _FAULT_CALLS and call.args:
            lit = self._str_arg(mod, call.args[0])
            if lit is not None:
                self.fault_sites.append(Site(mod, call, lit))
            return
        # threads
        if last == "Thread":
            tname = self._str_arg(mod, self._arg(call, 99, "name"))
            if tname is not None:
                self.thread_names.append(Site(mod, call, tname))
            return
        # locks
        if last in _LOCK_CTORS and call.args:
            lit = self._str_arg(mod, call.args[0])
            if lit is not None:
                self.lock_names.append(Site(mod, call, lit))
            return
        # knob declarations / consumers
        if last == "Knob" and len(call.args) >= 2:
            kname = self._str_arg(mod, call.args[0])
            kenv = self._str_arg(mod, call.args[1])
            if kname is not None:
                self.knob_declared.append(Site(mod, call, kname))
            if kenv is not None:
                self.knob_envs.append(Site(mod, call, kenv))
            return
        if last in _KNOB_CONSUMERS and call.args:
            # histogram.observe(value) and friends take numbers — a
            # non-string first arg simply fails to resolve and is
            # skipped, exactly right
            lit = self._str_arg(mod, call.args[0])
            if lit is not None:
                self.knob_consumers.append(Site(mod, call, lit))
            return

    # -- derived sets ----------------------------------------------------
    def produced_reasons(self) -> set:
        return {s.value for s in self.reason_producers}

    def classified_reasons(self) -> set:
        return ({s.value for s in self.retryable}
                | {s.value for s in self.non_retryable})

    def produced_metrics(self) -> set:
        return {s.value for s in self.metric_literals}

    def metric_layers(self) -> set:
        return {s.value.split(".", 1)[0] for s in self.metric_literals}

    def produces_metric(self, name: str) -> bool:
        """Does any producer site (literal or f-string pattern) emit
        this family name?"""
        if name in self.produced_metrics():
            return True
        return any(
            name.startswith(prefix) and name.endswith(suffix)
            and len(name) > len(prefix) + len(suffix)
            for prefix, suffix, _site in self.metric_patterns
        )

    def declared_knobs(self) -> set:
        return {s.value for s in self.knob_declared}

    def produced_locks(self) -> set:
        return {s.value for s in self.lock_names}

    def rostered_threads(self) -> set:
        return {s.value for s in self.thread_roster}

    def roster_files(self) -> set:
        return {s.mod.path for s in self.thread_roster}

    # -- external inputs -------------------------------------------------
    def repo_root(self) -> str | None:
        """The checkout root (the directory holding ``docs/api.md``) —
        where the committed ``tools/*_baseline.json`` ratchets live."""
        api = find_api_md(m.path for m in self.project.modules)
        return None if api is None \
            else os.path.dirname(os.path.dirname(api))

    def api_md_text(self) -> str | None:
        """The raw docs/api.md text (metric families must appear in
        it), or None when no docs are in reach (snippet linting)."""
        if self._api_md_text is not False:
            return self._api_md_text
        self._api_md_text = None
        path = find_api_md(m.path for m in self.project.modules)
        if path is not None:
            try:
                with open(path, encoding="utf-8") as fh:
                    self._api_md_text = fh.read()
            except OSError:
                pass
        return self._api_md_text

    def committed_baseline(self, stem: str) -> dict | None:
        """``tools/<stem>_baseline.json`` parsed, or None when absent/
        unreadable (snippet linting, partial checkouts)."""
        root = self.repo_root()
        if root is None:
            return None
        path = os.path.join(root, "tools", f"{stem}_baseline.json")
        try:
            with open(path, encoding="utf-8") as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None


def _fstring_affixes(node: ast.JoinedStr) -> tuple[str, str]:
    """Constant prefix/suffix of an f-string — ``f"serve.req_{leg}_s"``
    → ``("serve.req_", "_s")``.  A family produced through an f-string
    is an OPEN set; consumers match by affix."""
    prefix = ""
    if node.values and isinstance(node.values[0], ast.Constant):
        prefix = str(node.values[0].value)
    suffix = ""
    if len(node.values) > 1 and isinstance(node.values[-1], ast.Constant):
        suffix = str(node.values[-1].value)
    return prefix, suffix


def model_for(project: Project) -> ContractModel:
    """The memoized per-lint contract model (extraction walks every
    module once; five rules share the result)."""
    model = getattr(project, "_contract_model", None)
    if model is None:
        model = ContractModel(project)
        project._contract_model = model
    return model


def single_module_project(source: str, path: str = "<string>") -> Project:
    """A one-module project for direct model tests."""
    return Project([Context(source, path)])
