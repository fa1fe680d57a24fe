"""Whole-project lint cache: the tier-1 gate runs graftlint on every
pytest invocation, and the v2 engine does strictly more work than v1 —
so an unchanged tree must not pay for it twice.

The cache is one JSON file holding the findings of ONE project digest:
a hash over every source file's content plus the engine version, the
analyzer's OWN sources (so adding/removing/editing a rule module
invalidates it), the selected rule set, the contract seeded-drift env
knob, the committed ``tools/*_baseline.json`` ratchets, and the knob
table ``docs/api.md`` (which the ``undocumented-knob`` and contract
rules read).  Interprocedural findings depend on
*other* modules' sources, so there is deliberately no per-file caching —
any edit anywhere invalidates the whole entry, and a warm hit skips
parsing and analysis entirely (hashing ~100 files costs milliseconds).

Default location: a per-user file under the system temp dir, keyed on
the target paths — override with ``DASK_ML_TPU_LINT_CACHE=<path>``
(documented in docs/api.md's knob table; the knob rule keeps that
honest)."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile

from .core import Finding

__all__ = [
    "CACHE_ENV",
    "ENGINE_VERSION",
    "atomic_write_json",
    "default_cache_path",
    "load",
    "project_digest",
    "resolve_cache_path",
    "store",
]


def atomic_write_json(path: str, payload, *, best_effort: bool = False,
                      **dump_kw) -> None:
    """tmp + ``os.replace`` JSON write shared by the cache and the
    baseline: a crash mid-write can never corrupt the existing file,
    and a failed write never leaves a stray ``.tmp`` behind.  With
    ``best_effort`` the OSError is swallowed (the cache is an
    optimization, never a gate); without it, it propagates (a baseline
    the user asked to write MUST exist afterwards)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(payload, fh, **dump_kw)
            if dump_kw.get("indent") is not None:
                fh.write("\n")
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        if not best_effort:
            raise

#: bump on ANY behavior change in the engine or rules: a stale cache
#: must never serve findings a newer analyzer would not produce
ENGINE_VERSION = 3

#: policy knob: lint-cache file location ('' / '0' disables caching)
CACHE_ENV = "DASK_ML_TPU_LINT_CACHE"


def default_cache_path(paths) -> str:
    key = hashlib.sha1(
        "\x00".join(sorted(os.path.abspath(p) for p in paths)).encode()
    ).hexdigest()[:12]
    uid = os.getuid() if hasattr(os, "getuid") else 0
    return os.path.join(tempfile.gettempdir(),
                        f"graftlint-cache-{uid}-{key}.json")


def resolve_cache_path(cache, paths) -> str | None:
    """None (no caching), an explicit path, or True → the env knob /
    default location."""
    if cache is None or cache is False:
        return None
    if cache is True:
        env = os.environ.get(CACHE_ENV)
        if env is not None:
            env = env.strip()
            if env in ("", "0"):
                return None
            return env
        return default_cache_path(paths)
    return str(cache)


def _analyzer_identity(h) -> None:
    """Fold the ANALYZER itself into the digest: every ``.py`` under
    this package (engine + every registered rule module).  Editing a
    rule's logic, or adding/removing a rule module, must invalidate the
    warm cache even when the linted tree and the rule-ID list are
    unchanged — the version constant alone only helps when someone
    remembers to bump it."""
    pkg_dir = os.path.dirname(os.path.abspath(__file__))
    for dirpath, dirnames, filenames in os.walk(pkg_dir):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                with open(path, "rb") as fh:
                    h.update(b"\x00analyzer\x00")
                    h.update(os.path.relpath(path, pkg_dir).encode())
                    h.update(b"\x00")
                    h.update(fh.read())
            except OSError:
                pass


def project_digest(sources, select=None) -> str:
    """Digest of the whole analysis input: engine version, analyzer
    sources (active rule registry included), rule selection, every
    (path, content) pair, the contract seeded-drift knob, the committed
    baselines the contract-baseline-drift rule reads, and the knob
    table the undocumented-knob rule cross-references."""
    from .core import RULES
    from .graph import find_api_md

    h = hashlib.sha1()
    h.update(f"graftlint-engine-{ENGINE_VERSION}".encode())
    _analyzer_identity(h)
    rule_ids = sorted(RULES) if select is None else sorted(select)
    h.update(("rules:" + ",".join(rule_ids)).encode())
    # seeded contract drift changes findings without touching any file:
    # the injected and sighted runs need distinct (but each still warm)
    # cache entries, or lint.sh's default-path self-test reads stale
    # sighted findings and the detector looks blind
    from .contracts import CONTRACT_INJECT_ENV
    h.update(("inject:"
              + os.environ.get(CONTRACT_INJECT_ENV, "")).encode())
    # findings carry paths AS GIVEN (often cwd-relative): a hit from a
    # different cwd would serve paths that resolve to nowhere and break
    # baseline fingerprints, so the invoking cwd is part of the key
    h.update(("cwd:" + os.getcwd()).encode())
    for path, src in sorted(sources):
        h.update(b"\x00file\x00")
        h.update(os.path.abspath(path).encode())
        h.update(b"\x00")
        h.update(src.encode("utf-8", "replace"))
    api_md = find_api_md([p for p, _ in sources])
    if api_md is not None:
        try:
            with open(api_md, encoding="utf-8") as fh:
                h.update(b"\x00api.md\x00" + fh.read().encode())
        except OSError:
            pass
        # the contract-baseline-drift rule reads the committed ratchet
        # files next to the docs root; rebaselining must invalidate
        root = os.path.dirname(os.path.dirname(api_md))
        for stem in ("drill", "lock"):
            bl = os.path.join(root, "tools", f"{stem}_baseline.json")
            try:
                with open(bl, "rb") as fh:
                    h.update(b"\x00baseline\x00" + stem.encode()
                             + b"\x00" + fh.read())
            except OSError:
                h.update(b"\x00baseline\x00" + stem.encode()
                         + b"\x00absent")
    return h.hexdigest()


def load(cache_path: str, digest: str):
    """(findings, errors) on a digest match, else None.  Any read or
    decode failure is a miss — the cache is best-effort, never a gate."""
    try:
        with open(cache_path, encoding="utf-8") as fh:
            payload = json.load(fh)
    except (OSError, ValueError):
        return None
    if payload.get("digest") != digest:
        return None
    try:
        findings = [Finding(**d) for d in payload["findings"]]
        errors = [str(e) for e in payload["errors"]]
    except (KeyError, TypeError):
        return None
    return findings, errors


def store(cache_path: str, digest: str, findings, errors) -> None:
    payload = {
        "digest": digest,
        "engine_version": ENGINE_VERSION,
        "findings": [dataclasses.asdict(f) for f in findings],
        "errors": list(errors),
    }
    atomic_write_json(cache_path, payload, best_effort=True)
