"""atomic-artifacts: package artifact writes must commit via rename.

The durability subsystem's restore path (utils/checkpoint.py) SCANS
directories and trusts what it finds; so do the export loader, the lint
baseline, and the obs trace merger.  A plain
``open(path, "w")`` publishes the file name BEFORE the bytes: a reader
racing the write — or a process SIGKILLed mid-write, the exact fault
``scripts/chaos.py`` injects — observes a truncated artifact that either
crashes the consumer or silently loads as garbage.  The invariant: every
write-truncate ``open`` in the package commits through tmp-then-rename —
either the ``utils.atomicio`` helpers (``atomic_write_json`` & co.) or an
inline ``os.replace``/``os.rename`` in the same function.

Rule: an ``open(..., "w"/"wb"/...)`` call (any truncating/creating mode:
'w' or 'x'; append 'a' and read 'r' are exempt) inside the package is a
finding unless its nearest enclosing function (module scope for
top-level writes) also calls ``os.replace``/``os.rename`` or an
``atomic_write_*`` helper.  Genuinely append-only sinks and write-once
private temp files suppress with ``# lint: atomic-artifacts: <why>``.

Scope: package only (``ctx.in_package``) — top-level bench/driver
scripts own their artifacts' lifecycles and are audited by review, not
this lexical pass.
"""

from __future__ import annotations

import ast

from batchai_retinanet_horovod_coco_tpu.analysis.engine import (
    FileContext,
    Finding,
    register,
)
from batchai_retinanet_horovod_coco_tpu.analysis.rules.common import dotted

NAME = "atomic-artifacts"

_RENAMES = frozenset({"os.replace", "os.rename"})
_FUNCS = (ast.FunctionDef, ast.AsyncFunctionDef)


def _mode_literal(call: ast.Call) -> str | None:
    """The literal mode of an ``open`` call (positional or keyword);
    None when absent or not a string literal (dynamic modes are not
    inspectable — out of scope for a lexical pass)."""
    mode: ast.expr | None = None
    if len(call.args) >= 2:
        mode = call.args[1]
    for kw in call.keywords:
        if kw.arg == "mode":
            mode = kw.value
    if mode is None:
        return None
    if isinstance(mode, ast.Constant) and isinstance(mode.value, str):
        return mode.value
    return None


def _scope_nodes(scope: ast.AST):
    """All nodes of one function scope (module = the top scope), NOT
    descending into nested function definitions — the nearest enclosing
    function owns its writes."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        node = stack.pop()
        if isinstance(node, _FUNCS + (ast.Lambda,)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _nested_defs(scope: ast.AST):
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        node = stack.pop()
        if isinstance(node, _FUNCS):
            yield node
            continue
        stack.extend(ast.iter_child_nodes(node))


def _sanctions(fn: ast.AST) -> bool:
    """Does this function commit via rename (or the atomicio helpers)?
    Nested helpers count — defining ``_commit()`` with the replace inside
    and calling it is the same pattern, one indirection deeper."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        path = dotted(node.func)
        if path in _RENAMES:
            return True
        name = path.rsplit(".", 1)[-1] if path else None
        if name is not None and name.startswith("atomic_write"):
            return True
    return False


@register(NAME, "write-truncate open() in the package must commit via "
                "tmp-then-rename (utils.atomicio or os.replace)")
def check(ctx: FileContext) -> list[Finding]:
    if not ctx.in_package:
        return []
    out: list[Finding] = []

    def scan(scope: ast.AST) -> None:
        sanctioned: bool | None = None  # computed lazily, once per scope
        for node in _scope_nodes(scope):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "open"
            ):
                continue
            mode = _mode_literal(node)
            if mode is None or not any(c in mode for c in ("w", "x")):
                continue
            ctx.count(NAME)
            if sanctioned is None:
                sanctioned = _sanctions(scope)
            if sanctioned:
                continue
            out.append(
                ctx.finding(
                    NAME, node.lineno,
                    "write-truncate open() with no rename commit in this "
                    "function: a reader (or a kill mid-write) sees a torn "
                    "artifact — write via utils.atomicio.atomic_write_* "
                    "or tmp + os.replace; append-only sinks suppress "
                    "with '# lint: atomic-artifacts: <why>'",
                )
            )
        for fn in _nested_defs(scope):
            scan(fn)

    scan(ctx.tree)
    return out
