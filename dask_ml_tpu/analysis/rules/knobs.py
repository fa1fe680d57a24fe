"""Env-knob documentation honesty: every ``DASK_ML_TPU_*`` read in the
package must appear in docs/api.md's knob table.

The knob table is the repo's contract about which environment variables
exist, what values they take, and what evidence backs their defaults —
an env read the table does not mention is a knob users cannot discover
and benches cannot audit.  The rule collects every env read
(``os.environ.get``/``[]``, ``os.getenv``, the shared ``env_choice``
and ``_env_number`` helpers, and ``Knob(name, env, ...)`` registry
declarations) whose name is a ``DASK_ML_TPU_``-prefixed string — literal or a
resolvable constant like ``DEPTH_ENV`` — and checks it against the
table (wildcard rows like ``DASK_ML_TPU_TEST_*`` allow prefixes).

When no ``docs/api.md`` is reachable above the linted tree (snippet
linting, vendored subsets) the rule stays silent rather than flagging
everything."""

from __future__ import annotations

import ast

from ..core import Context, Rule, dotted_name, register
from .. import dataflow

_PREFIX = "DASK_ML_TPU_"


def _env_read_name_node(node: ast.AST):
    """The AST node holding the env-var name for a recognized env read,
    else None."""
    if isinstance(node, ast.Call):
        name = dotted_name(node.func) or ""
        head, _, last = name.rpartition(".")
        if last == "get" and "environ" in head and node.args:
            return node.args[0]
        if last == "getenv" and node.args:
            return node.args[0]
        if last == "env_choice" and node.args:
            return node.args[0]
        if last == "Knob" and len(node.args) >= 2:
            # control/knobs.py declarations: Knob(name, env, kind, ...)
            # resolve the env at registry build time — a declared knob
            # is a read site even before any plane polls it
            return node.args[1]
        if last == "_env_number" and node.args:
            # serve/config.py's shared strict-parse resolver: the env
            # name is its first argument, the environ.get happens once
            # inside the helper
            return node.args[0]
        return None
    if isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
        # Load context only: `os.environ["X"] = v` is a WRITE (knob
        # propagation into a spawned worker), not an undocumented read
        base = dotted_name(node.value) or ""
        if "environ" in base:
            return node.slice
    return None


@register
class UndocumentedKnobRule(Rule):
    id = "undocumented-knob"
    summary = (
        "DASK_ML_TPU_* environment read not listed in docs/api.md's "
        "knob table — an undiscoverable knob with unaudited defaults"
    )

    def run(self, ctx: Context):
        project = getattr(ctx, "project", None)
        if project is None:
            return
        docs = project.documented_knobs()
        if docs is None:
            return  # no knob table in reach: nothing to check against
        exact, prefixes = docs
        mod = project.module_for(ctx)
        du_cache: dict = {}
        for node in ast.walk(ctx.tree):
            name_node = _env_read_name_node(node)
            if name_node is None:
                continue
            fn = ctx.enclosing_function(node)
            du = None
            if fn is not None:
                du = du_cache.get(id(fn))
                if du is None:
                    du = du_cache[id(fn)] = dataflow.DefUse(fn)
            knob = dataflow.resolve_str_constant(name_node, du, mod)
            if knob is None or not knob.startswith(_PREFIX):
                continue
            if knob in exact or any(knob.startswith(p) for p in prefixes):
                continue
            yield ctx.finding(
                self.id, node,
                f"environment knob {knob!r} is read here but absent "
                f"from docs/api.md's knob table: document its values, "
                f"default, and evidence (or fold it into an existing "
                f"knob) — undocumented knobs cannot be discovered or "
                f"audited",
            )