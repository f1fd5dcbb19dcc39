"""Incremental analysis: the ``--changed`` fast path.

A cold whole-tree run costs seconds — fine for CI, too slow for the
editor loop. This module keeps a manifest under ``.sdlint_cache/``
mapping every analyzed file to its content hash, its findings, and its
outgoing import edges.

A warm run splits the rule set by the declared :attr:`core.Rule.scope`:

- **file** rules (verdict depends only on the file itself) re-run only
  over the **dirty closure** — the changed files expanded over the
  import graph in both directions: reverse edges (``callers_of``; a
  caller's composed summary folds the changed callee in) and forward
  edges (a changed caller seeds execution contexts into its callees).
  Findings for files outside the closure are spliced from the manifest.
- **closure** rules (SD023/SD024/SD026 — influence travels call edges,
  and a cross-file call rides an import of the callee's module, so the
  import graph covers them at file granularity) re-run over the closure
  as a sub-project. Context sets and effect summaries computed on a
  sub-project are *subsets* of the full-tree ones, so a sub-project run
  can only miss findings (a cross-boundary race pairs two files with no
  import path between them), never invent them — warm findings are
  FP-free; the cold CI run (``make lint``) remains authoritative for
  the misses.
- **tree** rules (a policy map in serve/policy.py, the knob catalog,
  the full caller set) re-run over the whole project on every changed
  run — scoping any of their context out flips verdicts, as the first
  cut of this cache demonstrated with 111 spurious SD015 findings.

Warm runs parse lazily: hashing reads bytes only, so a no-change run
splices every finding without parsing or running anything, and a
changed run parses just the dirty closure (plus the whole tree when
tree-scope rules are selected).

Two consequences of the FN-only contract are deliberate: a baselined
closure-rule finding whose influence seed lives outside the closure can
transiently vanish from a warm run (the CLI therefore suppresses
stale-baseline warnings on warm runs, and the baseline hygiene commands
refuse ``--changed``; the next cold run restores the authoritative
picture), and the closure of a widely-imported hub module approaches
the whole tree — a hub edit costs near-cold, a leaf edit re-analyzes a
handful of files, and the no-change run is near-free.

Invalidation is content-addressed twice over: each file by the hash of
its bytes, and the whole manifest by a *salt* hashing the linter's own
sources plus the selected rule set — editing sdlint itself, or linting
with a different ``--rules``, discards the cache wholesale.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
from dataclasses import dataclass, field
from pathlib import Path

from .core import (
    RULES,
    FileContext,
    Finding,
    ProjectContext,
    analyze_project,
    iter_python_files,
)

CACHE_DIR = ".sdlint_cache"
MANIFEST_VERSION = 2

_FINDING_FIELDS = ("rule", "path", "line", "col", "message", "snippet",
                   "ordinal")


def _sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:20]


def linter_salt(rule_ids=None) -> str:
    """Hash of the linter's own sources + the selected rule set: any
    edit to sdlint (or a different --rules) invalidates the cache."""
    h = hashlib.sha256()
    pkg = Path(__file__).resolve().parent
    for f in sorted(pkg.rglob("*.py")):
        h.update(f.relative_to(pkg).as_posix().encode())
        h.update(f.read_bytes())
    h.update(repr(sorted(set(rule_ids)) if rule_ids else None).encode())
    return h.hexdigest()[:20]


def _scope_of(rule_id: str) -> str:
    r = RULES.get(rule_id)
    return r.scope if r is not None else "tree"


def _import_edges(rel: str, tree: ast.AST, files: set[str]) -> list[str]:
    """Outgoing import edges of one parsed file, resolved against the
    analyzed file set (same dotted-name mapping CallGraph uses; the
    leading-slash probes cover trees analyzed by absolute path)."""

    def module_for(dotted: str) -> str | None:
        base = dotted.replace(".", "/")
        for cand in (f"{base}.py", f"{base}/__init__.py",
                     f"/{base}.py", f"/{base}/__init__.py"):
            if cand in files and cand != rel:
                return cand
        return None

    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                m = module_for(alias.name)
                if m is not None:
                    out.add(m)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                parts = rel.split("/")[:-1]
                for _ in range(node.level - 1):
                    if parts:
                        parts.pop()
                dotted = ".".join(
                    ["/".join(parts).replace("/", "."), node.module or ""]
                ).strip(".")
            else:
                dotted = node.module or ""
            m = module_for(dotted) if dotted else None
            if m is not None:
                out.add(m)
            for alias in node.names:  # `from pkg import submodule`
                if dotted:
                    sub = module_for(f"{dotted}.{alias.name}")
                    if sub is not None:
                        out.add(sub)
    return sorted(out)


def _reach(start: set[str], edges: dict[str, set[str]]) -> set[str]:
    seen = set(start)
    frontier = list(start)
    while frontier:
        nxt = frontier.pop()
        for other in edges.get(nxt, ()):
            if other not in seen:
                seen.add(other)
                frontier.append(other)
    return seen


def _closure(dirty: set[str], deps: dict[str, list[str]]) -> set[str]:
    """Files whose closure-rule findings a change in ``dirty`` can
    reach: transitive *importers* (their composed summaries fold the
    changed callee in — the ``callers_of`` direction) plus transitive
    *imports* (a changed caller seeds execution contexts downstream).
    The two directions are walked separately — chaining them through
    hub modules (everything imports telemetry; telemetry is imported by
    everything) would pull in the whole tree."""
    forward: dict[str, set[str]] = {}
    reverse: dict[str, set[str]] = {}
    for src, targets in deps.items():
        for dst in targets:
            forward.setdefault(src, set()).add(dst)
            reverse.setdefault(dst, set()).add(src)
    return _reach(dirty, forward) | _reach(dirty, reverse)


@dataclass
class CacheStats:
    """What a cached run actually did — surfaced by the CLI and
    asserted on by the cache-layer tests."""

    cold: bool
    changed: list[str] = field(default_factory=list)
    analyzed: list[str] = field(default_factory=list)
    reused: int = 0
    #: whether the tree-scope project rules ran over the full project
    #: (any changed warm run; never on a no-change warm run)
    tree_pass: bool = False

    def describe(self) -> str:
        if self.cold:
            return (f"cold run: analyzed all {len(self.analyzed)} files, "
                    f"cache primed")
        if not self.changed:
            return (f"warm run: nothing changed, reused all "
                    f"{self.reused} files")
        out = (f"warm run: re-analyzed {len(self.analyzed)} files "
               f"(closure of {len(self.changed)} changed)")
        if self.tree_pass:
            out += " + tree-scope rules project-wide"
        return out + f", reused {self.reused}"


def _load_manifest(path: Path, salt: str) -> dict | None:
    try:
        doc = json.loads(path.read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError):
        return None
    if doc.get("version") != MANIFEST_VERSION or doc.get("salt") != salt:
        return None
    if not isinstance(doc.get("files"), dict):
        return None
    return doc


def _write_manifest(cache_dir: Path, doc: dict) -> None:
    cache_dir.mkdir(parents=True, exist_ok=True)
    ignore = cache_dir / ".gitignore"
    if not ignore.exists():
        ignore.write_text("*\n")
    tmp = cache_dir / "manifest.json.tmp"
    tmp.write_text(json.dumps(doc, sort_keys=True), encoding="utf-8")
    os.replace(tmp, cache_dir / "manifest.json")


def _thaw(entries: list[dict]) -> list[Finding]:
    return [Finding(**{k: d[k] for k in _FINDING_FIELDS}) for d in entries]


def _parse_subset(
    sources: dict[str, str], subset
) -> tuple[ProjectContext, list[str]]:
    """Parse the named files (in listing order) into a ProjectContext."""
    want = set(subset)
    project = ProjectContext()
    errors: list[str] = []
    for rel, source in sources.items():
        if rel not in want:
            continue
        try:
            tree = ast.parse(source, filename=rel)
        except SyntaxError as exc:
            errors.append(f"{rel}: {exc}")
            continue
        project.files.append(FileContext(rel, source, tree))
    return project, errors


def analyze_paths_cached(
    paths,
    rule_ids=None,
    cache_dir: str | Path = CACHE_DIR,
) -> tuple[list[Finding], list[str], CacheStats]:
    """The incremental counterpart of :func:`core.analyze_paths`.

    Hashing reads every file's bytes; parsing and the rule passes run
    only over what the manifest diff demands — nothing at all on a
    no-change run, the dirty closure (plus the tree-scope pass) on a
    changed run, the whole tree when the cache is cold.
    """
    from . import rules as _rules  # noqa: F401 - populate RULES for scopes

    cache_dir = Path(cache_dir)
    salt = linter_salt(rule_ids)

    sources: dict[str, str] = {}
    read_errors: list[str] = []
    for root in paths:
        for file in iter_python_files(Path(root)):
            rel = file.as_posix()
            try:
                sources[rel] = file.read_text(encoding="utf-8")
            except (OSError, UnicodeDecodeError) as exc:
                read_errors.append(f"{rel}: {exc}")

    def cold(manifest_ok: bool) -> tuple[list[Finding], list[str], CacheStats]:
        project, errors = _parse_subset(sources, sources)
        errors = read_errors + errors
        findings = analyze_project(project, rule_ids)
        stats = CacheStats(
            cold=True, changed=sorted(sources),
            analyzed=[c.path for c in project.files], tree_pass=True,
        )
        if manifest_ok and not errors:
            deps = {
                c.path: _import_edges(c.path, c.tree, set(sources))
                for c in project.files
            }
            hashes = {
                p: _sha(s.encode("utf-8")) for p, s in sources.items()
            }
            _write_manifest(cache_dir, _manifest_doc(
                salt, sources, hashes, findings, deps,
            ))
        return findings, errors, stats

    # a tree that doesn't read cleanly can't be diffed reliably — run
    # cold and don't touch the manifest
    if read_errors:
        return cold(manifest_ok=False)

    manifest = _load_manifest(cache_dir / "manifest.json", salt)
    if manifest is None:
        return cold(manifest_ok=True)

    hashes = {p: _sha(s.encode("utf-8")) for p, s in sources.items()}
    cached = manifest["files"]
    changed = {
        p for p in sources
        if cached.get(p, {}).get("hash") != hashes[p]
    }
    removed = set(cached) - set(sources)

    if not changed and not removed:
        findings = sorted(
            (f for p in sources for f in _thaw(cached[p]["findings"])),
            key=lambda f: (f.path, f.line, f.col, f.rule),
        )
        return findings, [], CacheStats(cold=False, reused=len(sources))

    selected = sorted(RULES) if rule_ids is None else sorted(set(rule_ids))
    tree_ids = [r for r in selected if _scope_of(r) == "tree"]
    local_ids = [r for r in selected if _scope_of(r) != "tree"]

    # dependency edges: the manifest's (pre-edit) graph, refreshed for
    # the changed files so NEWLY added import edges pull their targets
    # into the closure too
    changed_project, errors = _parse_subset(sources, changed)
    if errors:
        # a syntax error can't be analyzed incrementally; fall back to
        # a cold run (which reports it) without clobbering the manifest
        return cold(manifest_ok=False)
    old_deps = {p: e.get("deps", []) for p, e in cached.items()}
    merged = dict(old_deps)
    fresh_edges = {
        c.path: _import_edges(c.path, c.tree, set(sources))
        for c in changed_project.files
    }
    for p, targets in fresh_edges.items():
        merged[p] = sorted(set(targets) | set(merged.get(p, [])))
    dirty = _closure(changed | removed, merged) & set(sources)

    if tree_ids:
        full_project, errors = _parse_subset(sources, sources)
        sub = ProjectContext(files=[
            c for c in full_project.files if c.path in dirty
        ])
    else:
        full_project = None
        sub, errors = _parse_subset(sources, dirty)
    if errors:  # unchanged files parsed clean when cached; belt anyway
        return cold(manifest_ok=False)

    fresh_local = analyze_project(sub, local_ids) if local_ids else []
    fresh_tree = (
        analyze_project(full_project, tree_ids) if tree_ids else []
    )
    spliced = [
        f
        for p in sorted(set(sources) - dirty)
        for f in _thaw(cached[p]["findings"])
        if _scope_of(f.rule) != "tree"
    ]
    findings = sorted(
        fresh_local + fresh_tree + spliced,
        key=lambda f: (f.path, f.line, f.col, f.rule),
    )

    deps = dict(old_deps)
    for ctx in sub.files:
        deps[ctx.path] = _import_edges(ctx.path, ctx.tree, set(sources))
    _write_manifest(cache_dir, _manifest_doc(
        salt, sources, hashes, findings, deps,
    ))
    return findings, [], CacheStats(
        cold=False,
        changed=sorted(changed | removed),
        analyzed=sorted(dirty),
        reused=len(sources) - len(dirty),
        tree_pass=bool(tree_ids),
    )


def _manifest_doc(
    salt: str,
    sources: dict[str, str],
    hashes: dict[str, str],
    findings: list[Finding],
    deps: dict[str, list[str]],
) -> dict:
    """Manifest document: per-file content hash, findings (all scopes —
    a no-change warm run splices them verbatim), and import edges."""
    by_file: dict[str, list[dict]] = {p: [] for p in sources}
    for f in findings:
        if f.path in by_file:
            by_file[f.path].append(
                {k: getattr(f, k) for k in _FINDING_FIELDS}
            )
    return {
        "version": MANIFEST_VERSION,
        "salt": salt,
        "files": {
            p: {
                "hash": hashes[p],
                "findings": by_file[p],
                "deps": deps.get(p, []),
            }
            for p in sources
        },
    }
