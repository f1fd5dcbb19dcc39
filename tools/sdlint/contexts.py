"""Execution-context inference: which contexts can each function run in?

The production tree runs four execution contexts at once — the asyncio
event loop, the feeder ("sd-window-pipeline") producer thread, the
~19 Hz sampler ("sd-profiler") thread, and `asyncio.to_thread` /
`run_in_executor` helper threads — and a fifth, worker processes, is
modeled for ``multiprocessing.Process`` targets.
The concurrency rules (SD023-SD026) need to know, for every function,
the set of contexts it can execute in; this module infers that set in
two steps:

1. **Seeding at the spawn seams.** Contexts enter the program at a
   handful of syntactic seams, all statically visible:

   - ``async def`` bodies run on the event loop (``loop``);
   - ``threading.Thread(target=f, name=...)`` targets run on a helper
     thread — the two long-lived named production threads get their
     own contexts (name starting ``sd-profiler`` → ``sampler``,
     ``sd-window-pipeline`` → ``feeder``) so rules can reason about
     *which* thread stalls or races, everything else is ``thread``;
   - ``asyncio.to_thread(f, ...)`` and ``loop.run_in_executor(ex, f,
     ...)`` callables run on executor threads (``thread``);
   - ``loop.call_soon(f)`` / ``call_soon_threadsafe`` / ``call_later``
     / ``call_at`` callbacks run on the loop;
   - ``multiprocessing.Process`` targets run in worker processes
     (``proc``).

2. **Propagation over the call graph.** A function called from a
   context runs in that context, so seed contexts flow caller→callee
   along every resolvable call edge (:class:`~tools.sdlint.summaries.
   CallGraph`) to a worklist fixpoint. Context sets only grow and the
   vocabulary is finite, so the fixpoint terminates — cycles included.
   One deliberate exception: *calling* an ``async def`` only creates a
   coroutine object; the body runs wherever it is scheduled (the
   loop), so caller contexts never flow into async callees.

A function no seed reaches has the empty context set ("unknown" —
import-time helpers, CLI entry points, dead code); rules must treat
unknown as out of scope, not as safe.

Known soundness limits, by design (documented in
docs/static-analysis.md): function *references* passed through
variables or containers other than the seams above are not tracked,
and two workers in the *same* context (e.g. two ``to_thread`` calls)
are not modeled as racing with each other.
"""

from __future__ import annotations

import ast

from .core import (
    FileContext,
    FunctionInfo,
    ProjectContext,
    call_name,
    dotted_name,
)
from .summaries import CallGraph, InstanceResolver

CTX_LOOP = "loop"
CTX_THREAD = "thread"
CTX_FEEDER = "feeder"
CTX_SAMPLER = "sampler"
CTX_PROC = "proc"

ALL_CONTEXTS = frozenset(
    {CTX_LOOP, CTX_THREAD, CTX_FEEDER, CTX_SAMPLER, CTX_PROC}
)

#: thread-name prefix -> dedicated context (order matters: first match)
THREAD_NAME_CONTEXTS = (
    ("sd-profiler", CTX_SAMPLER),
    ("sd-window-pipeline", CTX_FEEDER),
)

_THREAD_FACTORIES = {"threading.Thread", "Thread"}
_PROC_FACTORIES = {"multiprocessing.Process", "mp.Process", "Process"}
#: loop.X(callback, ...) seams scheduling the callback on the loop;
#: value = index of the callback argument
_LOOP_CALLBACK_ATTRS = {"call_soon": 0, "call_soon_threadsafe": 0,
                        "call_later": 1, "call_at": 1}


def _thread_context(name_expr: ast.AST | None) -> str:
    if isinstance(name_expr, ast.Constant) and isinstance(name_expr.value, str):
        for prefix, ctx_name in THREAD_NAME_CONTEXTS:
            if name_expr.value.startswith(prefix):
                return ctx_name
    return CTX_THREAD


class ContextMap:
    """Inferred execution contexts for every function in the project.

    Build once per :class:`ProjectContext` via :meth:`of`; query with
    :meth:`contexts`. ``seed_reasons`` keeps a human-readable note per
    seeded function for witness messages and tests.
    """

    def __init__(self, project: ProjectContext):
        self.project = project
        self.graph = CallGraph.of(project)
        self.resolver = InstanceResolver.of(project)
        #: (path, qualname) -> set of context tags
        self._contexts: dict[tuple[str, str], set[str]] = {}
        #: (path, qualname) -> why it was seeded (spawn seams only)
        self.seed_reasons: dict[tuple[str, str], list[str]] = {}
        self._infer()

    @classmethod
    def of(cls, project: ProjectContext) -> "ContextMap":
        got = getattr(project, "_context_map", None)
        if got is None:
            got = cls(project)
            project._context_map = got  # type: ignore[attr-defined]
        return got

    def contexts(self, ctx: FileContext, info: FunctionInfo) -> frozenset[str]:
        return frozenset(self._contexts.get((ctx.path, info.qualname), ()))

    def contexts_of(self, path: str, qualname: str) -> frozenset[str]:
        return frozenset(self._contexts.get((path, qualname), ()))

    # -- seeding -----------------------------------------------------------

    def _seed(self, path: str, qualname: str, context: str, reason: str):
        key = (path, qualname)
        self._contexts.setdefault(key, set()).add(context)
        reasons = self.seed_reasons.setdefault(key, [])
        if reason not in reasons:
            reasons.append(reason)

    def _seed_callable(
        self, ctx: FileContext, expr: ast.AST, site: ast.AST,
        context: str, reason: str,
    ) -> None:
        """Resolve a function *reference* (``self._run``, ``mod.f``,
        bare name) and seed it. Lambdas and unresolvable refs are
        silently skipped — the context set stays unknown."""
        name = dotted_name(expr)
        if name is None:
            return
        resolved = self.resolver.resolve_name(ctx, name, site)
        if resolved is None:
            return
        tctx, tinfo = resolved
        self._seed(tctx.path, tinfo.qualname, context, reason)

    def _seed_file(self, ctx: FileContext) -> None:
        for info in ctx.functions:
            if isinstance(info.node, ast.AsyncFunctionDef):
                self._seed(ctx.path, info.qualname, CTX_LOOP, "async def")

        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)

            if name in _THREAD_FACTORIES or name in _PROC_FACTORIES:
                target = None
                name_kw = None
                for kw in node.keywords:
                    if kw.arg == "target":
                        target = kw.value
                    elif kw.arg == "name":
                        name_kw = kw.value
                if target is None and len(node.args) >= 2:
                    target = node.args[1]  # Thread(group, target, ...)
                if target is None:
                    continue
                if name in _PROC_FACTORIES:
                    self._seed_callable(
                        ctx, target, node, CTX_PROC,
                        f"spawned via {name}(target=...)",
                    )
                else:
                    tctx = _thread_context(name_kw)
                    self._seed_callable(
                        ctx, target, node, tctx,
                        f"spawned via {name}(target=...)",
                    )
                continue

            if name is not None and (
                name == "to_thread" or name.endswith(".to_thread")
            ):
                if node.args:
                    self._seed_callable(
                        ctx, node.args[0], node, CTX_THREAD,
                        "handed to asyncio.to_thread",
                    )
                continue

            if isinstance(node.func, ast.Attribute):
                attr = node.func.attr
                if attr == "run_in_executor" and len(node.args) >= 2:
                    self._seed_callable(
                        ctx, node.args[1], node, CTX_THREAD,
                        "handed to run_in_executor",
                    )
                elif attr in _LOOP_CALLBACK_ATTRS:
                    idx = _LOOP_CALLBACK_ATTRS[attr]
                    if len(node.args) > idx:
                        self._seed_callable(
                            ctx, node.args[idx], node, CTX_LOOP,
                            f"scheduled on the loop via {attr}",
                        )

    # -- propagation -------------------------------------------------------

    def _infer(self) -> None:
        for ctx in self.project.files:
            self._seed_file(ctx)

        # worklist fixpoint: contexts flow caller -> callee. Sets only
        # grow over a finite vocabulary, so this terminates on cycles.
        pending = list(self._contexts)
        queued = set(pending)
        while pending:
            key = pending.pop()
            queued.discard(key)
            info = self.graph.functions.get(key)
            if info is None:
                continue
            fctx = self.graph.modules[key[0]]
            flowing = self._contexts.get(key, set())
            if not flowing:
                continue
            for _call, resolved in self.resolver.calls_in(fctx, info):
                if resolved is None:
                    continue
                cctx, cinfo = resolved
                # calling an async def just creates the coroutine; its
                # body runs on the loop regardless of the caller
                if isinstance(cinfo.node, ast.AsyncFunctionDef):
                    continue
                ckey = (cctx.path, cinfo.qualname)
                have = self._contexts.setdefault(ckey, set())
                new = flowing - have
                if new:
                    have |= new
                    if ckey not in queued:
                        queued.add(ckey)
                        pending.append(ckey)
