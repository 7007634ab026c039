"""Event-vocabulary contract checker (ISSUE 20).

The observability surface is stringly-typed: ``sink.event("repin", ...)``
on one side, ``if ev["event"] == "repin"`` in a report section or smoke
check on the other, and nothing ties the two names together — the PR 16
review round found a dashboard reading a name nothing wrote.
``obs/vocabulary.py`` is the contract: every structured event, trace
instant, and telemetry series name is declared there with its intended
consumers.  This rule parses that registry STATICALLY (no import of the
linted tree), collects every emit site across the whole tree, and flags:

- **emitted-but-unregistered** — an emit site whose name literal is not in
  the vocabulary (at the emit site);
- **consumed-but-never-emitted** — a registered name that a declared
  consumer file actually references but no emit site produces (the typo /
  dead-producer class; at the vocabulary entry);
- **registered-but-never-emitted** — a registered name with no emit sites
  and no consumer references: stale vocabulary (at the entry);
- a declared consumer path that is not a scanned file (at the entry).

Emit sites are calls whose attribute is ``event`` / ``instant`` /
``counter`` / ``gauge`` / ``histogram`` (or an ``emit``/``_emit_event``
helper) with a string-literal first argument; and, as attribute calls only
(``trace.phase(...)``, not a script's own ``phase()`` helper), ``phase`` /
``record_phase``: the set-up record of obs/trace.py (ISSUE 34).  Dynamic names
(``sink.event(name, ...)``) are invisible to the rule and should be
funnelled through a registered prefix helper or suppressed with rationale.
"""

from __future__ import annotations

import ast

from batchai_retinanet_horovod_coco_tpu.analysis.engine import (
    Finding,
    PACKAGE_NAME,
    ProjectContext,
    register_project,
)

RULE = "event-vocabulary"

VOCABULARY_RELPATH = f"{PACKAGE_NAME}/obs/vocabulary.py"

#: call-attribute → emit kind
_EMIT_ATTRS = {
    "event": "event",
    "instant": "instant",
    "counter": "series",
    "gauge": "series",
    "histogram": "series",
    "emit": "event",
    "_emit_event": "event",
    "emit_event": "event",
}

#: emit kinds that count only as ``<object>.<attr>(...)`` calls
_ATTRIBUTE_ONLY = {
    "phase": "phase",
    "record_phase": "phase",
}

#: files whose string literals are never emit sites: the registry itself
#: and the analysis engine/rules (they talk ABOUT names).
_EXCLUDED_PREFIXES = (
    f"{PACKAGE_NAME}/obs/vocabulary.py",
    f"{PACKAGE_NAME}/analysis/",
)


def _parse_vocabulary(source: str, tree: ast.AST) -> dict[str, dict]:
    """Extract the VOCABULARY dict literal without importing the module."""
    out: dict[str, dict] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign):
            targets = [node.target]
        else:
            continue
        if not any(isinstance(t, ast.Name) and t.id == "VOCABULARY"
                   for t in targets):
            continue
        if not isinstance(node.value, ast.Dict):
            continue
        for k, v in zip(node.value.keys, node.value.values):
            if not (isinstance(k, ast.Constant)
                    and isinstance(k.value, str)):
                continue
            entry = {"line": k.lineno, "kinds": (), "consumers": ()}
            if isinstance(v, ast.Dict):
                for ek, ev in zip(v.keys, v.values):
                    if not (isinstance(ek, ast.Constant)
                            and ek.value in ("kinds", "consumers")):
                        continue
                    vals = []
                    if isinstance(ev, (ast.Tuple, ast.List)):
                        vals = [e.value for e in ev.elts
                                if isinstance(e, ast.Constant)
                                and isinstance(e.value, str)]
                    entry[ek.value] = tuple(vals)
            out[k.value] = entry
    return out


def _emit_sites(pctx: ProjectContext):
    """Every ``(name, kind, relpath, line)`` emit site in the tree."""
    for ctx in pctx.contexts:
        rel = ctx.relpath.replace("\\", "/")
        if any(rel.startswith(p) for p in _EXCLUDED_PREFIXES):
            continue
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute):
                attr = node.func.attr
                kind = _EMIT_ATTRS.get(attr) or _ATTRIBUTE_ONLY.get(attr)
            elif isinstance(node.func, ast.Name):
                kind = _EMIT_ATTRS.get(node.func.id)
            else:
                continue
            if kind is None:
                continue
            if not (node.args and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            yield node.args[0].value, kind, ctx.relpath, node.lineno


@register_project(
    RULE,
    "every structured event / trace instant / telemetry series name must "
    "be declared in obs/vocabulary.py; orphan consumers and stale entries "
    "are flagged")
def check(pctx: ProjectContext) -> list[Finding]:
    vocab_ctx = pctx.by_path.get(VOCABULARY_RELPATH)
    if vocab_ctx is None:
        return []  # fixture trees without a vocabulary: nothing to check
    vocab = _parse_vocabulary(vocab_ctx.source, vocab_ctx.tree)

    emits: dict[str, list[tuple[str, str, int]]] = {}
    findings: list[Finding] = []
    n_sites = 0
    for name, kind, relpath, line in _emit_sites(pctx):
        n_sites += 1
        emits.setdefault(name, []).append((kind, relpath, line))
        if name not in vocab:
            ctx = pctx.by_path[relpath]
            findings.append(Finding(
                rule=RULE, path=relpath, line=line,
                message=f"emitted-but-unregistered {kind} name {name!r}: "
                        f"declare it in obs/vocabulary.py with its "
                        f"intended consumers",
                snippet=ctx.snippet(line)))
    pctx.count(RULE, n_sites)
    pctx.exports["event_names_emitted"] = sorted(emits)

    for name, entry in sorted(vocab.items()):
        consumed_in: list[str] = []
        for consumer in entry["consumers"]:
            cctx = pctx.by_path.get(consumer)
            if cctx is None:
                findings.append(Finding(
                    rule=RULE, path=VOCABULARY_RELPATH,
                    line=entry["line"],
                    message=f"vocabulary entry {name!r} declares consumer "
                            f"{consumer!r} which is not a scanned file",
                    snippet=vocab_ctx.snippet(entry["line"])))
                continue
            if _references(cctx.tree, name):
                consumed_in.append(consumer)
        if name in emits:
            continue
        if consumed_in:
            findings.append(Finding(
                rule=RULE, path=VOCABULARY_RELPATH, line=entry["line"],
                message=f"consumed-but-never-emitted: {name!r} is read by "
                        f"{', '.join(consumed_in)} but nothing in the "
                        f"tree emits it",
                snippet=vocab_ctx.snippet(entry["line"])))
        else:
            findings.append(Finding(
                rule=RULE, path=VOCABULARY_RELPATH, line=entry["line"],
                message=f"registered-but-never-emitted: {name!r} has no "
                        f"emit site and no consumer reference — stale "
                        f"vocabulary entry",
                snippet=vocab_ctx.snippet(entry["line"])))
    return findings


def _references(tree: ast.AST, name: str) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and node.value == name:
            return True
    return False
