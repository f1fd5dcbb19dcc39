"""SD023-SD026 — the cross-plane race detector.

Built on the two engine passes this PR adds: execution-context
inference (:mod:`tools.sdlint.contexts`) and shared-state effect
summaries (:mod:`tools.sdlint.effects`). Each rule covers one bug
class this repo has actually shipped (or nearly shipped):

- **SD023** cross-context shared-state race — the PR 12 history-tail
  deque bug: state written in one context and touched from another
  with no common lock and no sanctioned hand-off seam.
- **SD024** loop-affinity violation — ``create_task``/``call_soon``
  from a thread; asyncio's loop machinery is not thread-safe and the
  failure mode is a silently lost callback.
- **SD025** post-submit payload aliasing — mutating a batch after it
  was handed to a queue; the consumer's view of the batch races the
  mutation.
- **SD026** hot-thread blocking — an unbounded wait on the sampler or
  feeder thread; a stall there corrupts profiling cadence or starves
  the device of windows.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..contexts import CTX_PROC, ContextMap
from ..core import (
    FileContext,
    Finding,
    ProjectContext,
    call_name,
    rule,
    walk_shallow,
)
from ..effects import WRITE, effect_summaries

#: contexts that share the host address space (proc is a separate
#: process with its own address space)
_HOST = lambda ctxs: frozenset(ctxs) - {CTX_PROC}  # noqa: E731


class _Site:
    """Duck-typed AST-node stand-in so findings can anchor at an
    :class:`~tools.sdlint.effects.Access` site."""

    def __init__(self, line: int, col: int):
        self.lineno = line
        self.col_offset = col


def _render_key(key: tuple[str, str, str]) -> str:
    kind, scope, name = key
    if kind == "attr":
        cls = scope.split("::", 1)[1]
        return f"`self.{name}` on {cls}"
    return f"module global `{name}`"


# --------------------------------------------------------------------------
# SD023 — cross-context shared-state race


@rule(
    "SD023",
    "cross-context-race",
    "state written in one execution context and touched from another "
    "with no common lock or sanctioned hand-off seam",
    project=True,
    scope="closure",
)
def check_cross_context_race(project: ProjectContext) -> Iterator[Finding]:
    ctxmap = ContextMap.of(project)
    summary_of = effect_summaries(project)
    files = {c.path: c for c in project.files}

    # escape filter: instance state can only race across contexts when
    # the INSTANCE is shared across them. A class whose objects are
    # only ever locals (one per call — parsers, rasterizers) keys all
    # its per-call instances to one class and would cross-pair them;
    # require the class to escape through a module-level singleton or
    # a typed self-attribute before pairing its attributes.
    resolver = ctxmap.resolver
    escaping = set(resolver.global_instances.values()) | set(
        resolver.attr_types.values()
    )

    # every seeded function is a root: its composed summary carries
    # each reachable access with the guards held along that path, and
    # the root's inferred context set says where those paths can run
    occurrences: dict[tuple, list[tuple[frozenset, object]]] = {}
    for key in sorted(ctxmap.seed_reasons):
        path, qual = key
        info = ctxmap.graph.functions.get(key)
        if info is None:
            continue
        root_ctxs = _HOST(ctxmap.contexts_of(path, qual))
        if not root_ctxs:
            continue
        for acc in summary_of(files[path], info):
            if not acc.init:
                occurrences.setdefault(acc.key, []).append((root_ctxs, acc))

    for key in sorted(occurrences):
        if key[0] == "attr":
            cpath, cls = key[1].split("::", 1)
            if (cpath, cls) not in escaping:
                continue
        occ = occurrences[key]
        writes = [(c, a) for c, a in occ if a.kind == WRITE]
        if not writes:
            continue
        hit = None
        for wctxs, w in sorted(
            writes, key=lambda t: (t[1].path, t[1].line, t[1].col)
        ):
            for actxs, a in sorted(
                occ, key=lambda t: (t[1].path, t[1].line, t[1].col)
            ):
                if w.guards & a.guards:
                    continue
                pairs = sorted(
                    (c1, c2)
                    for c1 in wctxs for c2 in actxs if c1 != c2
                )
                if not pairs:
                    continue
                hit = (w, a, pairs[0])
                break
            if hit:
                break
        if hit is None:
            continue
        w, a, (c1, c2) = hit
        ctx = files[w.path]
        if a is w or (a.path == w.path and a.line == w.line):
            witness = (
                f"this site itself can run in both the {c1} and {c2} "
                f"contexts"
            )
        else:
            verb = "written" if a.kind == WRITE else "read"
            witness = (
                f"{verb} from the {c2} context at {a.path}:{a.line}"
            )
        yield ctx.finding(
            "SD023",
            _Site(w.line, w.col),
            f"{_render_key(key)} is written here in the {c1} context and "
            f"{witness} with no common lock — cross-context race; guard "
            f"both sides with one lock or hand off via a queue/Condition",
        )


# --------------------------------------------------------------------------
# SD024 — loop-affinity violation


_LOOP_ONLY_CALLS = {"create_task", "ensure_future", "call_soon",
                    "call_later", "call_at"}
_LOOP_ONLY_NAMES = {"asyncio.create_task", "asyncio.ensure_future"}


@rule(
    "SD024",
    "loop-affinity-violation",
    "asyncio loop machinery driven from a non-loop context without the "
    "threadsafe entry points",
    project=True,
    scope="closure",
)
def check_loop_affinity(project: ProjectContext) -> Iterator[Finding]:
    ctxmap = ContextMap.of(project)
    for ctx in project.files:
        for info in ctx.functions:
            if isinstance(info.node, ast.AsyncFunctionDef):
                continue  # async bodies are loop-affine by definition
            ctxs = _HOST(ctxmap.contexts(ctx, info))
            offending = sorted(ctxs - {"loop"})
            if not offending:
                continue
            for node in walk_shallow(info.node):
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                hit = name in _LOOP_ONLY_NAMES or (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _LOOP_ONLY_CALLS
                )
                if not hit:
                    continue
                display = name or node.func.attr  # type: ignore[union-attr]
                yield ctx.finding(
                    "SD024",
                    node,
                    f"`{display}(...)` schedules work on the event loop, "
                    f"but `{info.qualname}` can run in the "
                    f"{'/'.join(offending)} context — use "
                    f"loop.call_soon_threadsafe(...) or "
                    f"asyncio.run_coroutine_threadsafe(...) off-loop",
                )


# --------------------------------------------------------------------------
# SD025 — post-submit payload aliasing


_HANDOFF_QUEUE_METHODS = {"put", "put_nowait"}


def _mutation_root(stmt: ast.AST) -> Iterator[tuple[str, ast.AST]]:
    """Names a statement mutates in place (not rebinds)."""
    from ..effects import MUTATORS, _name_root
    from .flowrules import walk_shallow_stmt

    if isinstance(stmt, ast.AugAssign):
        root = _name_root(stmt.target)
        if root is not None:
            yield root, stmt
        return
    for sub in walk_shallow_stmt(stmt):
        if isinstance(sub, (ast.Subscript, ast.Attribute)) and isinstance(
            sub.ctx, (ast.Store, ast.Del)
        ):
            root = _name_root(sub)
            if root is not None:
                yield root, sub
        elif (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Attribute)
            and sub.func.attr in MUTATORS
            and isinstance(sub.func.value, ast.Name)
        ):
            yield sub.func.value.id, sub


@rule(
    "SD025",
    "post-submit-aliasing",
    "a payload mutated after it was handed to a queue — the consumer "
    "sees the mutation race",
)
def check_post_submit_aliasing(ctx: FileContext) -> Iterator[Finding]:
    from ..cfg import STMT, solve_forward
    from .flowrules import walk_shallow_stmt

    for info in ctx.functions:
        fn = info.node
        if not any(isinstance(n, ast.Call) for n in walk_shallow(fn)):
            continue

        def ships_in(stmt: ast.AST) -> Iterator[tuple[str, int, str]]:
            for sub in walk_shallow_stmt(stmt):
                if not (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                ):
                    continue
                if (
                    sub.func.attr in _HANDOFF_QUEUE_METHODS and sub.args
                    and isinstance(sub.args[0], ast.Name)
                ):
                    yield (sub.args[0].id, sub.lineno,
                           f"`{sub.func.attr}(...)`")

        def rebinds_in(stmt: ast.AST) -> set[str]:
            out: set[str] = set()
            if isinstance(stmt, ast.Assign):
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Name):
                        out.add(tgt.id)
                    elif isinstance(tgt, ast.Tuple):
                        out |= {
                            el.id for el in tgt.elts
                            if isinstance(el, ast.Name)
                        }
            elif isinstance(stmt, ast.AugAssign) and isinstance(
                stmt.target, ast.Name
            ):
                out.add(stmt.target.id)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                for sub in ast.walk(stmt.target):
                    if isinstance(sub, ast.Name):
                        out.add(sub.id)
            return out

        def transfer(node, state: frozenset) -> frozenset:
            if node.kind != STMT or node.ast is None:
                return state
            shipped = set(state)
            for name, line, dest in ships_in(node.ast):
                shipped.add((name, line, dest))
            dead = rebinds_in(node.ast)
            if dead:
                shipped = {t for t in shipped if t[0] not in dead}
            return frozenset(shipped)

        cfg = ctx.cfg(fn)
        in_states = solve_forward(cfg, frozenset(), transfer)
        reported: set[int] = set()
        for node in cfg.nodes:
            if node.kind != STMT or node.ast is None:
                continue
            state = in_states[node.idx]
            if not state:
                continue
            by_name: dict[str, tuple[int, str]] = {}
            for name, line, dest in sorted(state):
                by_name.setdefault(name, (line, dest))
            for name, site in _mutation_root(node.ast):
                if name not in by_name or id(site) in reported:
                    continue
                reported.add(id(site))
                line, dest = by_name[name]
                yield ctx.finding(
                    "SD025",
                    site,
                    f"`{name}` was handed to {dest} at line {line}; "
                    f"mutating it afterwards races the consumer's view "
                    f"of the batch — build a fresh payload instead",
                )


# --------------------------------------------------------------------------
# SD026 — sampler/feeder hot-thread blocking


_HOT_CONSEQUENCE = {
    "sampler": "every missed tick corrupts the continuous profile",
    "feeder": "a stalled producer starves the device of windows",
}
_SUBPROCESS_BLOCKING = {"run", "call", "check_call", "check_output"}


@rule(
    "SD026",
    "hot-thread-blocking",
    "an unbounded wait or blocking I/O call on the sampler or feeder "
    "thread, whose stall corrupts profiling or starves the device",
    project=True,
    scope="closure",
)
def check_hot_thread_blocking(project: ProjectContext) -> Iterator[Finding]:
    ctxmap = ContextMap.of(project)
    for ctx in project.files:
        for info in ctx.functions:
            hot = sorted(
                ctxmap.contexts(ctx, info) & set(_HOT_CONSEQUENCE)
            )
            if not hot:
                continue
            consequence = _HOT_CONSEQUENCE[hot[0]]
            label = "/".join(hot)
            for node in walk_shallow(info.node):
                if not isinstance(node, ast.Call):
                    continue
                what = None
                name = call_name(node) or ""
                has_timeout = any(
                    kw.arg == "timeout" for kw in node.keywords
                )
                if isinstance(node.func, ast.Attribute):
                    attr = node.func.attr
                    if (
                        attr in ("wait", "join")
                        and not node.args
                        and not node.keywords
                    ):
                        what = f"unbounded `.{attr}()`"
                if what is None and name:
                    parts = name.split(".")
                    if (
                        parts[0] == "subprocess"
                        and parts[-1] in _SUBPROCESS_BLOCKING
                        and not has_timeout
                    ):
                        what = f"`{name}(...)` without a timeout"
                    elif parts[-1] == "urlopen" and not has_timeout and \
                            len(node.args) < 3:
                        what = "`urlopen(...)` without a timeout"
                    elif name == "socket.create_connection" and \
                            not has_timeout and len(node.args) < 2:
                        what = "`socket.create_connection` without a timeout"
                if what is None:
                    continue
                yield ctx.finding(
                    "SD026",
                    node,
                    f"{what} on the {label} hot thread — {consequence}; "
                    f"bound the wait with a timeout or move the blocking "
                    f"work off the hot thread",
                )
