"""Unit tests for the sdlint analysis engine itself — the CFG builder,
dominator computation, suspension/exception edge placement, the forward
dataflow solver, and call-graph summary composition.

The rule fixtures in test_sdlint.py are end-to-end; these pin the
engine's *semantics* so a rule regression can be localized: when a rule
misfires, either the graph it reads is wrong (these tests) or its
reading of the graph is (those tests).
"""

import ast
import textwrap
from pathlib import Path

from tools.sdlint.cfg import (
    EXC,
    FINALLY,
    HANDLER,
    WITH_CLEANUP,
    WITH_EXIT,
    build_cfg,
    solve_forward,
)
from tools.sdlint.core import FileContext, ProjectContext
from tools.sdlint.summaries import CallGraph


def cfg_of(src: str):
    fn = ast.parse(textwrap.dedent(src)).body[0]
    return build_cfg(fn)


def node_by_line(cfg, line: int, kind: str = "stmt"):
    for n in cfg.nodes:
        if n.line == line and n.kind == kind:
            return n
    raise AssertionError(f"no {kind} node at line {line}")


def succ_idxs(cfg, node, kind=None):
    return {t for t, k in cfg.succs[node.idx] if kind is None or k == kind}


# --- CFG construction -------------------------------------------------------


def test_cfg_straight_line_and_exit():
    cfg = cfg_of("""
    def f(x):
        a = x
        b = a
        return b
    """)
    a, b, ret = (node_by_line(cfg, ln) for ln in (3, 4, 5))
    assert succ_idxs(cfg, a) == {b.idx}
    assert ret.idx in succ_idxs(cfg, b)
    assert cfg.exit in succ_idxs(cfg, ret)


def test_cfg_if_joins_both_arms():
    cfg = cfg_of("""
    def f(x):
        if x:
            a = 1
        else:
            a = 2
        after(a)
    """)
    test = node_by_line(cfg, 3)
    then, other, after = (node_by_line(cfg, ln) for ln in (4, 6, 7))
    assert succ_idxs(cfg, test, "normal") == {then.idx, other.idx}
    assert succ_idxs(cfg, then) == {after.idx}
    assert succ_idxs(cfg, other) == {after.idx}


def test_cfg_loop_back_edge_break_and_continue():
    cfg = cfg_of("""
    def f(xs):
        for x in xs:
            if x:
                break
            continue
        after()
    """)
    hdr = node_by_line(cfg, 3)
    brk, cont, after = (node_by_line(cfg, ln) for ln in (5, 6, 7))
    assert succ_idxs(cfg, brk) == {after.idx}       # break exits the loop
    assert succ_idxs(cfg, cont) == {hdr.idx}        # continue re-enters
    assert after.idx in succ_idxs(cfg, hdr)         # exhaustion falls out


def test_cfg_while_true_has_no_fallthrough():
    cfg = cfg_of("""
    def f():
        while True:
            spin()
        never()
    """)
    hdr = node_by_line(cfg, 3)
    body = node_by_line(cfg, 4)
    assert succ_idxs(cfg, hdr, "normal") == {body.idx}
    # the statement after an infinite loop is unreachable
    never = node_by_line(cfg, 5)
    assert cfg.dominators()[never.idx] is None


def test_cfg_try_finally_builds_normal_and_abrupt_copies():
    """The finally body exists twice (the CPython strategy): the NORMAL
    copy continues to the code after the try; the ABRUPT copy carries
    exception/return continuations outward and to EXIT. One shared copy
    used to let an early `return` masquerade as fall-through."""
    cfg = cfg_of("""
    def f():
        try:
            work()
        finally:
            cleanup()
        after()
    """)
    work = node_by_line(cfg, 4)
    fins = [n for n in cfg.nodes if n.kind == FINALLY]
    assert len(fins) == 2
    normal_fin, abrupt_fin = fins
    copies = [n for n in cfg.nodes if n.line == 6 and n.kind == "stmt"]
    assert len(copies) == 2
    normal_body, abrupt_body = copies
    # normal completion: body -> normal copy -> after (no raise edge)
    assert normal_fin.idx in succ_idxs(cfg, work, "normal")
    assert node_by_line(cfg, 7).idx in succ_idxs(cfg, normal_body)
    assert cfg.raise_ not in succ_idxs(cfg, normal_body, EXC) or \
        normal_body.can_raise  # only its own cleanup() call may raise
    # exceptional exit: body -exc-> abrupt copy -> RAISE and EXIT
    assert abrupt_fin.idx in succ_idxs(cfg, work, EXC)
    assert cfg.raise_ in succ_idxs(cfg, abrupt_body, EXC)
    assert cfg.exit in succ_idxs(cfg, abrupt_body, "normal")


def test_cfg_return_through_finally_not_around_it():
    cfg = cfg_of("""
    def f():
        try:
            return 1
        finally:
            cleanup()
        never()
    """)
    ret = node_by_line(cfg, 4)
    fins = [n.idx for n in cfg.nodes if n.kind == FINALLY]
    # the return must run the finally (abrupt copy) first — no direct
    # exit edge, and it must NOT fall through to the code after
    assert succ_idxs(cfg, ret) & set(fins)
    assert cfg.exit not in succ_idxs(cfg, ret)
    abrupt_body = [n for n in cfg.nodes
                   if n.line == 6 and n.kind == "stmt"][1]
    never = node_by_line(cfg, 7)
    assert never.idx not in succ_idxs(cfg, abrupt_body)
    assert cfg.exit in succ_idxs(cfg, abrupt_body)


def test_cfg_handler_catches_and_continues():
    cfg = cfg_of("""
    def f():
        try:
            work()
        except OSError:
            handle()
        after()
    """)
    work = node_by_line(cfg, 4)
    handler = next(n for n in cfg.nodes if n.kind == HANDLER)
    assert handler.idx in succ_idxs(cfg, work, EXC)
    # OSError is a *possible* catch: propagation to RAISE remains
    assert cfg.raise_ in succ_idxs(cfg, work, EXC)
    # the handler body falls through to the statement after the try
    assert node_by_line(cfg, 7).idx in succ_idxs(cfg, node_by_line(cfg, 6))


def test_cfg_with_has_separate_commit_and_cleanup_exits():
    cfg = cfg_of("""
    def f(db):
        with db.transaction() as conn:
            conn.execute("INSERT")
        after()
    """)
    body = node_by_line(cfg, 4)
    wexit = next(n for n in cfg.nodes if n.kind == WITH_EXIT)
    cleanup = next(n for n in cfg.nodes if n.kind == WITH_CLEANUP)
    # normal body exit -> commit exit -> after
    assert wexit.idx in succ_idxs(cfg, body, "normal")
    assert node_by_line(cfg, 5).idx in succ_idxs(cfg, wexit)
    # exceptional body exit -> cleanup (rollback), which propagates,
    # and deliberately NOT through the commit exit
    assert cleanup.idx in succ_idxs(cfg, body, EXC)
    assert cfg.raise_ in succ_idxs(cfg, cleanup, EXC)
    assert wexit.idx not in succ_idxs(cfg, body, EXC)


def test_cfg_async_with_suspends():
    cfg = cfg_of("""
    async def f(self):
        async with self._sem:
            work()
    """)
    header = node_by_line(cfg, 3)
    assert header.suspends


# --- await / cancellation edges ---------------------------------------------


def test_await_nodes_suspend_and_cancellation_skips_except_exception():
    cfg = cfg_of("""
    async def f(self):
        try:
            await self.work()
        except Exception:
            pass
    """)
    aw = node_by_line(cfg, 4)
    assert aw.suspends
    handler = next(n for n in cfg.nodes if n.kind == HANDLER)
    # ordinary exceptions can land in the handler...
    assert handler.idx in succ_idxs(cfg, aw, EXC)
    # ...but CancelledError still escapes the function entirely
    assert cfg.raise_ in succ_idxs(cfg, aw, EXC)


def test_cancellation_stopped_by_baseexception_and_cancelled_handlers():
    # `except BaseException` definitely catches EVERYTHING — no escape
    cfg = cfg_of("""
    async def f(self):
        try:
            await self.work()
        except BaseException:
            pass
    """)
    aw = node_by_line(cfg, 4)
    assert cfg.raise_ not in succ_idxs(cfg, aw, EXC)
    # `except CancelledError` stops the cancellation kind; ordinary
    # exceptions from the awaited call still propagate to RAISE
    cfg = cfg_of("""
    async def f(self):
        try:
            await self.work()
        except asyncio.CancelledError:
            raise
    """)
    aw = node_by_line(cfg, 4)
    handler = next(n for n in cfg.nodes if n.kind == HANDLER)
    assert handler.idx in succ_idxs(cfg, aw, EXC)
    assert cfg.raise_ in succ_idxs(cfg, aw, EXC)  # the non-cancel kinds


def test_plain_assignment_has_no_exception_edge():
    cfg = cfg_of("""
    def f(x):
        a = 1
        b = g(a)
    """)
    assert succ_idxs(cfg, node_by_line(cfg, 3), EXC) == set()
    assert cfg.raise_ in succ_idxs(cfg, node_by_line(cfg, 4), EXC)


# --- dominators -------------------------------------------------------------


def test_dominators_linear_and_branch():
    cfg = cfg_of("""
    def f(x):
        a = 1
        if x:
            b = g()
        c = 2
    """)
    a = node_by_line(cfg, 3)
    test = node_by_line(cfg, 4)
    b = node_by_line(cfg, 5)
    c = node_by_line(cfg, 6)
    doms_c = cfg.dominators()[c.idx]
    # the straight-line prefix dominates the join; the branch arm not
    assert a.idx in doms_c and test.idx in doms_c
    assert b.idx not in doms_c
    assert cfg.dominated_by(c.idx, {a.idx})
    assert not cfg.dominated_by(c.idx, {b.idx})


def test_dominators_with_exit_dominates_post_block_only():
    cfg = cfg_of("""
    def f(db, flag):
        if flag:
            with db.transaction() as conn:
                conn.execute("X")
        after()
    """)
    wexit = next(n for n in cfg.nodes if n.kind == WITH_EXIT)
    after = node_by_line(cfg, 6)
    assert not cfg.dominated_by(after.idx, {wexit.idx})


def test_dominators_loop_header_dominates_body():
    cfg = cfg_of("""
    def f(xs):
        for x in xs:
            body(x)
    """)
    hdr = node_by_line(cfg, 3)
    body = node_by_line(cfg, 4)
    assert hdr.idx in cfg.dominators()[body.idx]


# --- dataflow solver --------------------------------------------------------


def test_solve_forward_reaches_fixpoint_through_loop():
    cfg = cfg_of("""
    def f(xs):
        acquire()
        for x in xs:
            touch(x)
        release()
    """)

    def transfer(node, state):
        if node.ast is None:
            return state
        text = ast.dump(node.ast)
        if "acquire" in text:
            return state | {"lock"}
        if "release" in text:
            return state - {"lock"}
        return state

    in_states = solve_forward(cfg, frozenset(), transfer)
    body = node_by_line(cfg, 4)
    rel = node_by_line(cfg, 5)
    assert "lock" in in_states[body.idx]
    assert "lock" in in_states[rel.idx]
    assert "lock" not in in_states[cfg.exit] or True  # exit in-state is post-release
    assert in_states[cfg.exit] == frozenset()


# --- call graph + summaries -------------------------------------------------


def _project(tmp_path: Path, files: dict[str, str]) -> ProjectContext:
    project = ProjectContext()
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        src = textwrap.dedent(src)
        path.write_text(src)
        posix = path.relative_to(tmp_path).as_posix()
        project.files.append(
            FileContext(posix, src, ast.parse(src, filename=posix))
        )
    return project


def test_call_graph_resolves_self_module_and_imports(tmp_path):
    project = _project(tmp_path, {
        "pkg/a.py": """
        from pkg.b import helper
        from pkg import b as bee

        def local():
            pass

        class C:
            def m(self):
                self.n()
                local()
                helper()
                bee.other()

            def n(self):
                pass
        """,
        "pkg/b.py": """
        def helper():
            pass

        def other():
            pass
        """,
    })
    graph = CallGraph.of(project)
    actx = project.files[0]
    minfo = next(i for i in actx.functions if i.qualname == "C.m")
    resolved = {
        (r[0].path, r[1].qualname)
        for _call, r in graph.calls_in(actx, minfo) if r is not None
    }
    assert resolved == {
        ("pkg/a.py", "C.n"),
        ("pkg/a.py", "local"),
        ("pkg/b.py", "helper"),
        ("pkg/b.py", "other"),
    }


def test_call_graph_relative_imports(tmp_path):
    project = _project(tmp_path, {
        "pkg/sub/a.py": """
        from ..core import boom

        def go():
            boom()
        """,
        "pkg/core.py": """
        def boom():
            pass
        """,
    })
    graph = CallGraph.of(project)
    actx = next(c for c in project.files if c.path.endswith("a.py"))
    ginfo = next(i for i in actx.functions if i.qualname == "go")
    [(call, resolved)] = list(graph.calls_in(actx, ginfo))
    assert resolved is not None
    assert resolved[0].path == "pkg/core.py"
    assert resolved[1].qualname == "boom"


def test_summaries_compose_transitively_and_survive_cycles(tmp_path):
    project = _project(tmp_path, {
        "m.py": """
        def leaf():
            mark()

        def mid():
            leaf()

        def top():
            mid()

        def spin_a():
            spin_b()

        def spin_b():
            spin_a()
        """,
    })
    graph = CallGraph.of(project)
    ctx = project.files[0]

    def compute(fctx, info, summary_of):
        import ast as _ast

        from tools.sdlint.core import walk_shallow

        for node in walk_shallow(info.node):
            if isinstance(node, _ast.Call):
                name = getattr(node.func, "id", None)
                if name == "mark":
                    return True
                resolved = graph.resolve(fctx, node, node)
                if resolved is not None and summary_of(*resolved):
                    return True
        return False

    summary_of = graph.summarize(compute, default=False)
    by_name = {i.qualname: i for i in ctx.functions}
    assert summary_of(ctx, by_name["leaf"]) is True
    assert summary_of(ctx, by_name["mid"]) is True      # one hop
    assert summary_of(ctx, by_name["top"]) is True      # two hops
    # a mutual-recursion cycle terminates with the default
    assert summary_of(ctx, by_name["spin_a"]) is False


def test_callers_of_reverse_edges(tmp_path):
    project = _project(tmp_path, {
        "m.py": """
        def callee():
            pass

        def one():
            callee()

        def two():
            callee()
        """,
    })
    graph = CallGraph.of(project)
    ctx = project.files[0]
    callee = next(i for i in ctx.functions if i.qualname == "callee")
    callers = {info.qualname for _c, info, _call in graph.callers_of(ctx, callee)}
    assert callers == {"one", "two"}


def test_cfg_module_body_and_class_body_build():
    """SD004 replays module-level and class-body code (it runs at
    import time): build_cfg accepts the Module node and class bodies
    wire inline."""
    import ast as _ast

    tree = _ast.parse(textwrap.dedent("""
    setup()

    class C:
        _x = make()

        def method(self):
            pass

    teardown()
    """))
    cfg = build_cfg(tree)
    lines = {n.line for n in cfg.nodes if n.kind == "stmt"}
    assert {2, 4, 5, 7, 10} <= lines  # incl. the class-body assignment


# --- execution-context inference --------------------------------------------


def _ctxs(project, path, qual):
    from tools.sdlint.contexts import ContextMap

    return set(ContextMap.of(project).contexts_of(path, qual))


def test_context_seeding_at_each_spawn_seam(tmp_path):
    project = _project(tmp_path, {
        "m.py": """
        import asyncio
        import multiprocessing
        import threading

        async def on_loop():
            await asyncio.to_thread(helper)
            loop = asyncio.get_event_loop()
            loop.run_in_executor(None, exec_helper)
            loop.call_soon(cb)
            loop.call_later(1.0, later_cb)

        def helper(): pass
        def exec_helper(): pass
        def cb(): pass
        def later_cb(): pass

        def sampler_loop(): pass
        def feeder_loop(): pass
        def plain_loop(): pass
        def worker_main(): pass
        def stage_handler(payload): return payload

        def spawn():
            threading.Thread(
                target=sampler_loop, name="sd-profiler-7").start()
            threading.Thread(
                target=feeder_loop, name="sd-window-pipeline").start()
            threading.Thread(target=plain_loop).start()
            multiprocessing.Process(target=worker_main).start()

        STAGES = {"stage.x": stage_handler}
        """,
    })
    assert _ctxs(project, "m.py", "on_loop") == {"loop"}
    assert _ctxs(project, "m.py", "helper") == {"thread"}
    assert _ctxs(project, "m.py", "exec_helper") == {"thread"}
    assert _ctxs(project, "m.py", "cb") == {"loop"}
    assert _ctxs(project, "m.py", "later_cb") == {"loop"}
    assert _ctxs(project, "m.py", "sampler_loop") == {"sampler"}
    assert _ctxs(project, "m.py", "feeder_loop") == {"feeder"}
    assert _ctxs(project, "m.py", "plain_loop") == {"thread"}
    assert _ctxs(project, "m.py", "worker_main") == {"proc"}
    # a dispatch-table dict is data, not a spawn seam: unknown
    assert _ctxs(project, "m.py", "stage_handler") == set()
    # no seam reaches spawn itself: unknown, not safe
    assert _ctxs(project, "m.py", "spawn") == set()


def test_context_propagation_multi_context_and_cycle_termination(tmp_path):
    project = _project(tmp_path, {
        "m.py": """
        import threading

        def shared():
            ping()

        def ping():
            pong()

        def pong():
            ping()

        async def from_loop():
            shared()

        def spawn():
            threading.Thread(target=shared).start()
        """,
    })
    # reached from both an async body and a thread target
    assert _ctxs(project, "m.py", "shared") == {"loop", "thread"}
    # the ping/pong cycle reaches the same fixpoint and terminates
    assert _ctxs(project, "m.py", "ping") == {"loop", "thread"}
    assert _ctxs(project, "m.py", "pong") == {"loop", "thread"}


def test_context_does_not_flow_into_async_callees(tmp_path):
    project = _project(tmp_path, {
        "m.py": """
        import threading

        async def coro():
            pass

        def runner():
            return coro()  # creates the coroutine, does not run it

        def spawn():
            threading.Thread(target=runner).start()
        """,
    })
    assert _ctxs(project, "m.py", "runner") == {"thread"}
    assert _ctxs(project, "m.py", "coro") == {"loop"}


def test_context_seeds_resolve_instance_method_targets(tmp_path):
    # the production idiom: Thread(target=self._run) on a singleton,
    # and to_thread(self._pipeline.take) through a typed attribute
    project = _project(tmp_path, {
        "m.py": """
        import asyncio
        import threading

        class Pipe:
            def take(self):
                pass

        class Job:
            def __init__(self):
                self._pipeline = Pipe()

            async def step(self):
                await asyncio.to_thread(self._pipeline.take)

        class Sampler:
            def start(self):
                threading.Thread(
                    target=self._run, name="sd-profiler").start()

            def _run(self):
                pass

        SAMPLER = Sampler()
        """,
    })
    assert _ctxs(project, "m.py", "Pipe.take") == {"thread"}
    assert _ctxs(project, "m.py", "Sampler._run") == {"sampler"}


# --- shared-state effect summaries ------------------------------------------


def _summary(project, path, qual):
    from tools.sdlint.effects import effect_summaries

    summary_of = effect_summaries(project)
    graph = CallGraph.of(project)
    info = graph.functions[(path, qual)]
    return summary_of(graph.modules[path], info)


def test_effects_attr_and_global_keying_with_guards(tmp_path):
    project = _project(tmp_path, {
        "m.py": """
        import threading

        COUNT = 0
        TABLE = {}

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []
                self.n = 0

            def add(self, x):
                with self._lock:
                    self._items.append(x)
                self.n = self.n + 1

        def bump(k):
            global COUNT
            COUNT += 1
            TABLE[k] = COUNT
        """,
    })
    accs = _summary(project, "m.py", "Box.add")
    by = {(a.key, a.kind): a for a in accs}
    assert by[(("attr", "m.py::Box", "_items"), "write")].guards == frozenset(
        {"m.py::Box._lock"}
    )
    assert by[(("attr", "m.py::Box", "n"), "write")].guards == frozenset()
    assert (("attr", "m.py::Box", "n"), "read") in by
    # the lock attribute itself is a synchronizer, never state
    assert not any(a.key[2] == "_lock" for a in accs)
    # __init__ accesses carry the pre-publication marker
    init_accs = _summary(project, "m.py", "Box.__init__")
    assert init_accs and all(a.init for a in init_accs)

    kinds = {(a.key, a.kind) for a in _summary(project, "m.py", "bump")}
    assert (("global", "m.py", "COUNT"), "write") in kinds
    assert (("global", "m.py", "COUNT"), "read") in kinds
    assert (("global", "m.py", "TABLE"), "write") in kinds


def test_effects_compose_caller_locks_onto_callee_accesses(tmp_path):
    project = _project(tmp_path, {
        "m.py": """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []

            def _drain(self):
                self._items.clear()

            def flush(self):
                with self._lock:
                    self._drain()

            def leak(self):
                self._drain()
        """,
    })
    flush = _summary(project, "m.py", "Box.flush")
    w = next(a for a in flush if a.kind == "write")
    assert w.key == ("attr", "m.py::Box", "_items")
    assert "m.py::Box._lock" in w.guards
    # the same callee access reached without the lock stays unguarded
    leak = _summary(project, "m.py", "Box.leak")
    w = next(a for a in leak if a.kind == "write")
    assert w.guards == frozenset()


def test_effects_typed_deep_store_keys_to_final_owner(tmp_path):
    project = _project(tmp_path, {
        "m.py": """
        class Stats:
            def __init__(self):
                self.read_time = 0.0

        class Pipe:
            def __init__(self):
                self.stats = Stats()

            def tick(self, s):
                self.stats.read_time += s

            def opaque(self, other):
                other.field = 1
        """,
    })
    keys = {(a.key, a.kind) for a in _summary(project, "m.py", "Pipe.tick")}
    # the store lands on the typed final owner, not the reference
    assert (("attr", "m.py::Stats", "read_time"), "write") in keys
    assert (("attr", "m.py::Pipe", "stats"), "read") in keys
    assert (("attr", "m.py::Pipe", "stats"), "write") not in keys
    # an untyped receiver records no phantom write
    assert not any(
        a.kind == "write"
        for a in _summary(project, "m.py", "Pipe.opaque")
    )


def test_effects_safe_factories_are_not_state(tmp_path):
    project = _project(tmp_path, {
        "m.py": """
        import queue
        import threading

        class Pump:
            def __init__(self):
                self._q = queue.Queue()
                self._evt = threading.Event()

            def feed(self, x):
                self._q.put(x)
                self._evt.set()
        """,
    })
    assert _summary(project, "m.py", "Pump.feed") == frozenset()


# --- instance resolver ------------------------------------------------------


def test_instance_resolver_singletons_attrs_and_facade_reexports(tmp_path):
    from tools.sdlint.summaries import InstanceResolver

    project = _project(tmp_path, {
        "pkg/__init__.py": """
        from .impl import Engine, ENGINE
        """,
        "pkg/impl.py": """
        class Engine:
            def __init__(self):
                pass

            def start(self):
                pass

        ENGINE = Engine()
        """,
        "app.py": """
        from pkg import ENGINE, Engine

        class Holder:
            def __init__(self):
                self._eng = Engine()

            def kick(self):
                self._eng.start()

        def poke():
            ENGINE.start()

        def local_use():
            e = Engine()
            e.start()

        def construct():
            return Engine()
        """,
    })
    r = InstanceResolver.of(project)
    actx = next(c for c in project.files if c.path == "app.py")

    def resolved_of(qual):
        info = next(i for i in actx.functions if i.qualname == qual)
        return {
            res[1].qualname
            for _call, res in r.calls_in(actx, info)
            if res is not None
        }

    # typed self-attr through the package facade re-export
    assert "Engine.start" in resolved_of("Holder.kick")
    # module singleton imported through the facade
    assert "Engine.start" in resolved_of("poke")
    # typed local
    assert "Engine.start" in resolved_of("local_use")
    # constructor call resolves to __init__
    assert "Engine.__init__" in resolved_of("construct")
    # the typing tables name the defining module, not the facade
    assert r.attr_types[("app.py", "Holder", "_eng")] == (
        "pkg/impl.py", "Engine",
    )
