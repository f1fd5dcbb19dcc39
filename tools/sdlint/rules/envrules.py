"""Environment-knob hygiene.

SD021  env-knob-catalog-drift: every ``SD_*`` environment knob read in
       the analyzed tree must have a catalog row in the docs (and every
       catalog row must name a knob that is still read) — the SD020
       metric-catalog discipline, applied to the other operator
       surface. The knob count grew past a dozen across six PRs with
       no single place an operator could enumerate them; an
       uncataloged knob is invisible, a stale row documents a lie.

Detection keys off this repo's idioms for reading environment:
``os.environ.get("SD_…")`` / ``os.getenv("SD_…")`` /
``os.environ["SD_…"]`` / ``"SD_…" in os.environ`` /
``environ.setdefault("SD_…", …)``. Only literal names count — a
computed env-var name is unauditable and has never appeared in this
tree.

The catalog (default ``docs/telemetry.md``, override with
``SDLINT_KNOB_CATALOG`` for fixtures) is a markdown table whose first
cell backticks the knob name. A row whose SECOND cell is ``script``
documents a knob read by a repo-root script *outside* the linted
package (``bench_scale.py``) — those stay cataloged for operators
without tripping the stale-row check, since the analyzer never parses
them.
"""

from __future__ import annotations

import ast
import os as _os
import re as _re
from pathlib import Path
from typing import Iterator

from ..core import FileContext, Finding, ProjectContext, dotted_name, rule

#: env override so fixture tests can point the rule at a temp catalog
_CATALOG_ENV = "SDLINT_KNOB_CATALOG"
_CATALOG_DEFAULT = "docs/telemetry.md"

#: a catalog row: first cell backticks the knob; the optional second
#: cell ``script`` marks a repo-root-script knob (exempt from the
#: stale-row check — the analyzer never sees those files)
_KNOB_ROW = _re.compile(r"^\|\s*`(SD_[A-Z0-9_]+)`\s*\|\s*([^|]*)\|")

_KNOB_NAME = _re.compile(r"^SD_[A-Z0-9_]+$")

#: dotted callee tails whose first literal-string argument is an
#: env-var name (plus bare/attributed ``getenv``)
_ENV_GETTER_TAILS = ("environ.get", "environ.setdefault", "environ.pop")


def _is_env_getter(callee: str) -> bool:
    if callee.rsplit(".", 1)[-1] == "getenv":
        return True
    return any(callee == t or callee.endswith("." + t)
               for t in _ENV_GETTER_TAILS)


def _catalog_path() -> Path:
    return Path(_os.environ.get(_CATALOG_ENV, _CATALOG_DEFAULT))


def _catalog_rows(path: Path) -> list[tuple[str, str, int, str]]:
    """(knob, scope-cell, 1-based line, raw line) per catalog row."""
    out: list[tuple[str, str, int, str]] = []
    try:
        lines = path.read_text(encoding="utf-8").splitlines()
    except OSError:
        return []
    for i, line in enumerate(lines, start=1):
        m = _KNOB_ROW.match(line.strip())
        if m:
            out.append((m.group(1), m.group(2).strip().lower(), i, line))
    return out


def _literal_knob(node: ast.AST,
                  consts: dict[str, str] | None = None) -> str | None:
    """The knob name an expression denotes: a literal ``"SD_*"``
    string, or a module-level constant bound to one (the
    ``ENV_VAR = "SD_JAX_PROFILE"`` idiom in telemetry/profiler.py)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str) \
            and _KNOB_NAME.match(node.value):
        return node.value
    if consts and isinstance(node, ast.Name):
        return consts.get(node.id)
    return None


def _module_constants(tree: ast.Module) -> dict[str, str]:
    """Module-level ``NAME = "SD_*"`` bindings (simple, single-target
    assignments only — anything fancier is unauditable)."""
    out: dict[str, str] = {}
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and isinstance(stmt.value, ast.Constant) \
                and isinstance(stmt.value.value, str) \
                and _KNOB_NAME.match(stmt.value.value):
            out[stmt.targets[0].id] = stmt.value.value
    return out


def _read_knobs(project: ProjectContext) \
        -> dict[str, tuple[FileContext, ast.AST]]:
    """Every ``SD_*`` name read from the environment in the analyzed
    tree, keyed to its first read site."""
    out: dict[str, tuple[FileContext, ast.AST]] = {}

    for ctx in project.files:
        consts = _module_constants(ctx.tree)
        for node in ast.walk(ctx.tree):
            name: str | None = None
            if isinstance(node, ast.Call) and node.args:
                callee = dotted_name(node.func) or ""
                if _is_env_getter(callee):
                    name = _literal_knob(node.args[0], consts)
            elif isinstance(node, ast.Subscript):
                base = dotted_name(node.value) or ""
                if base == "environ" or base.endswith(".environ"):
                    name = _literal_knob(node.slice, consts)
            elif isinstance(node, ast.Compare) and len(node.ops) == 1 \
                    and isinstance(node.ops[0], (ast.In, ast.NotIn)):
                base = dotted_name(node.comparators[0]) or ""
                if base == "environ" or base.endswith(".environ"):
                    name = _literal_knob(node.left, consts)
            if name is not None:
                out.setdefault(name, (ctx, node))
    return out


@rule(
    "SD021",
    "env-knob-catalog-drift",
    "every SD_* env knob read in the tree needs a docs catalog row, and "
    "every non-script catalog row must name a knob still read somewhere "
    "— an uncataloged knob is invisible to operators, a stale row "
    "documents a lie (the SD020 discipline for the other operator "
    "surface)",
    project=True,
)
def check_env_knob_catalog(project: ProjectContext) -> Iterator[Finding]:
    read = _read_knobs(project)
    if not read:
        return  # fixture trees reading no knobs have nothing to drift
    path = _catalog_path()
    rows = _catalog_rows(path)
    if not rows:
        ctx, node = next(iter(read.values()))
        yield ctx.finding(
            "SD021",
            node,
            f"SD_* env knobs are read here but the catalog "
            f"({path.as_posix()}) is missing or has no `SD_*` table rows "
            f"— document every knob (name, default, effect)",
        )
        return
    cataloged = {name for name, _, _, _ in rows}
    for name, (ctx, node) in sorted(read.items()):
        if name not in cataloged:
            yield ctx.finding(
                "SD021",
                node,
                f"env knob `{name}` has no catalog row in "
                f"{path.as_posix()} — add one (name, default, effect)",
            )
    for name, scope, line_no, raw in rows:
        if scope == "script":
            # documented repo-root-script knob (bench_scale.py lives
            # outside the analyzed package) — cataloged on purpose
            continue
        if name not in read:
            snippet = " ".join(raw.split())[:160]
            yield Finding(
                "SD021",
                path.as_posix(),
                line_no,
                0,
                f"catalog row for `{name}` names a knob no longer read "
                f"anywhere in the tree — delete the stale row (or mark "
                f"its scope cell `script` if a repo-root script reads it)",
                snippet,
            )
