"""Spans on the profiler's clock: every `telemetry.span` is also a
`jax.profiler.TraceAnnotation` named `sd.<dotted path>`, ids cost no
system call, `sd_db_txn_seconds` counts commits, and the operator's
`SD_JAX_PROFILE` session covers a whole job chain."""

import asyncio
import glob
import os
import re
import threading

import pytest

from spacedrive_tpu import telemetry
from spacedrive_tpu.telemetry import metrics, spans, trace


# --- spans inside a profiler session ---------------------------------------


@pytest.fixture(scope="module")
def profiled_names(tmp_path_factory):
    """One profiler session on the CPU; spans opened on the loop thread,
    under `asyncio.to_thread` and on the feeder's producer thread. → the
    `sd.*` event names of the `.xplane.pb`, each with its line index."""
    import jax
    from jax.profiler import ProfileData

    from spacedrive_tpu.parallel.feeder import WindowPipeline

    logdir = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0

    def in_thread():
        with telemetry.span("threaded"):
            pass

    def fetch(key):
        if key:
            return None
        with telemetry.span("probe"):
            pass
        return key + 1, "window"

    async def main():
        async with telemetry.span("outer"):
            with telemetry.span("inner"):
                pass
            await asyncio.to_thread(in_thread)
            pipeline = WindowPipeline(fetch, 0, depth=1)
            try:
                assert await asyncio.to_thread(pipeline.take) == "window"
                assert await asyncio.to_thread(pipeline.take) is None
            finally:
                pipeline.close()

    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        asyncio.run(main())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for event in line.events:
                if event.name.startswith(spans.ANNOTATION_PREFIX):
                    names.setdefault(event.name, set()).add((plane.name, i))
    return names


@pytest.mark.parametrize("name", [
    "sd.outer",                      # the loop thread, an async span
    "sd.outer.inner",                # nested: the parent's prefix
    "sd.outer.threaded",             # asyncio.to_thread copies the context
    "sd.feeder.fetch",               # the producer thread starts a root path,
    "sd.feeder.fetch.probe",         # and what it calls nests under it
    "sd.outer.feeder.wait",          # the consumer side, under to_thread
])
def test_span_is_on_the_profile_under_its_dotted_path(profiled_names, name):
    assert name in profiled_names, sorted(profiled_names)


def test_threads_land_on_lines_of_their_own(profiled_names):
    loop_line = profiled_names["sd.outer"]
    assert profiled_names["sd.outer.inner"] == loop_line
    assert profiled_names["sd.feeder.fetch.probe"] == \
        profiled_names["sd.feeder.fetch"]
    assert profiled_names["sd.feeder.fetch"] != loop_line


# --- with no session: everything as before ---------------------------------


def test_span_without_a_session_records_as_before():
    telemetry.reset()
    with telemetry.span("plain", nbytes=7) as outer:
        with telemetry.span("child") as inner:
            pass
    assert inner.path == "plain.child" and inner.parent_id == outer.span_id
    assert inner.trace_id == outer.trace_id and outer.parent_id is None
    assert re.fullmatch(r"[0-9a-f]{32}", outer.trace_id)
    assert re.fullmatch(r"[0-9a-f]{16}", outer.span_id)
    assert outer.duration >= inner.duration >= 0
    recs = {r["stage"]: r for r in telemetry.recent_spans()}
    assert recs["plain"]["bytes"] == 7 and recs["plain"]["error"] is None
    assert recs["plain.child"]["parent_id"] == outer.span_id
    assert metrics.SPAN_SECONDS.stats(stage="plain")["count"] == 1
    assert metrics.SPAN_SECONDS.stats(stage="plain.child")["count"] == 1
    assert metrics.SPAN_BYTES.value(stage="plain") == 7
    assert [r["stage"] for r in trace.recent()] == ["plain.child", "plain"]


def test_annotation_is_not_bound_before_jax_is_imported(monkeypatch):
    """`procworker.py` imports the telemetry package without jax: a span
    there must neither import it nor fail."""
    import sys

    monkeypatch.setattr(spans, "_annotation_cls", None)
    monkeypatch.setitem(sys.modules, "jax", None)
    with telemetry.span("light") as sp:
        assert sp._annotation is None
    assert spans._annotation_cls is None and sys.modules["jax"] is None


def test_a_span_that_has_ended_is_nobodys_parent():
    """A task started inside a span keeps a copy of its context: the
    actors `Node.start` spawns must not file their later work under
    `node.start`."""
    seen = []

    async def main():
        release = asyncio.Event()

        async def actor():
            await release.wait()
            with telemetry.span("work") as sp:
                seen.append(sp.path)

        async with telemetry.span("starting"):
            task = asyncio.ensure_future(actor())
            with telemetry.span("early") as sp:
                seen.append(sp.path)
        release.set()
        await task

    asyncio.run(main())
    assert seen == ["starting.early", "work"]


def test_a_span_that_has_ended_hands_back_what_was_ambient_before_it():
    """The copy of the context that such a task keeps reads past the
    ended span, on both sides: its spans nest under the span that was
    open around the ended one, and `trace.current()` (what a job
    ingested from a watcher's task adopts) is the context from before
    it, not one trace shared by everything the node ever starts."""
    seen = {}

    async def main():
        release = asyncio.Event()
        boundary = trace.new_context()

        async def actor():
            await release.wait()
            seen["ambient"] = trace.current()
            seen["wire"] = trace.wire_current()
            with telemetry.span("work") as sp:
                seen["work"] = (sp.path, sp.trace_id, sp.parent_id)

        with trace.use(boundary):
            async with telemetry.span("around") as around:
                async with telemetry.span("starting") as starting:
                    task = asyncio.ensure_future(actor())
                    seen["inside"] = trace.current().span_id
                release.set()
                await task
                seen["around"] = around.span_id
                seen["starting"] = starting.span_id
            # both spans over: a second task's copy reads back to the boundary
            late = asyncio.ensure_future(asyncio.sleep(0, trace.current()))
            assert await late is boundary
        return boundary

    boundary = asyncio.run(main())
    assert seen["inside"] == seen["starting"]
    assert seen["ambient"].span_id == seen["around"]
    assert seen["wire"] == {"trace_id": boundary.trace_id,
                            "span_id": seen["around"]}
    assert seen["work"] == ("around.work", boundary.trace_id, seen["around"])


def test_ids_are_unique_across_threads_and_draw_no_randomness(monkeypatch):
    def refuse(_n):
        raise AssertionError("os.urandom drawn for a span or trace id")

    monkeypatch.setattr(os, "urandom", refuse)
    per_thread, threads = 25_000, 4
    drawn: list[list[str]] = [[] for _ in range(threads)]

    def draw(out):
        for i in range(per_thread):
            if i % 1000 == 0:
                with telemetry.span("idprobe") as sp:  # a root draws both
                    out.append(sp.span_id)
                    out.append(sp.trace_id[:16])
            else:
                out.append(trace.new_span_id())

    workers = [threading.Thread(target=draw, args=(out,)) for out in drawn]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    ids = [i for out in drawn for i in out]
    assert len(ids) >= threads * per_thread == 100_000
    assert len(set(ids)) == len(ids)
    assert all(len(i) == 16 for i in ids)
    # two traces of one process differ in the head a viewer's lane reads
    a, b = trace.new_trace_id(), trace.new_trace_id()
    assert len(a) == len(b) == 32 and a[:8] != b[:8] and a[16:] == b[16:]


# --- sd_db_txn_seconds: one per commit -------------------------------------


def _db():
    from spacedrive_tpu.db.database import LibraryDb

    db = LibraryDb(None, memory=True)
    db.execute("CREATE TABLE t (a INTEGER)")
    return db


def _transaction(db):
    with db.transaction() as conn:
        for i in range(50):  # fifty rows, one commit
            conn.execute("INSERT INTO t VALUES (?)", (i,))


def _nested(db):
    with db.transaction() as conn:
        conn.execute("INSERT INTO t VALUES (1)")
        db.execute("INSERT INTO t VALUES (2)")
        db.executemany("INSERT INTO t VALUES (?)", [(3,), (4,)])


def _rolled_back(db):
    with pytest.raises(ZeroDivisionError):
        with db.transaction() as conn:
            conn.execute("INSERT INTO t VALUES (1)")
            1 / 0


@pytest.mark.parametrize("call, commits", [
    (_transaction, 1),
    (lambda db: db.execute("INSERT INTO t VALUES (1)"), 1),
    (lambda db: db.executemany("INSERT INTO t VALUES (?)",
                               [(i,) for i in range(50)]), 1),
    (lambda db: db.insert("t", a=1), 1),
    (_nested, 1),
    (lambda db: db.query("SELECT * FROM t"), 0),
    (lambda db: db.query_one("SELECT COUNT(*) AS n FROM t"), 0),
    (lambda db: db.execute("SELECT 1"), 0),
    (_rolled_back, 0),
], ids=["transaction", "execute", "executemany", "insert", "nested",
        "query", "query_one", "reading_execute", "rolled_back"])
def test_db_txn_counts_one_per_commit(call, commits):
    db = _db()
    before = metrics.DB_TXN_SECONDS.stats()
    with telemetry.span("stage"):
        call(db)
    after = metrics.DB_TXN_SECONDS.stats()
    assert after["count"] - before["count"] == commits
    assert after["sum"] >= before["sum"]
    if commits:
        # the block is a span under the stage that issued it, its COMMIT
        # a leaf under the block
        assert [r["stage"] for r in telemetry.recent_spans()[-3:]] == [
            "stage.db.txn.commit", "stage.db.txn", "stage"]


# --- the operator's profile covers the chain --------------------------------


@pytest.mark.asyncio
async def test_sd_jax_profile_is_one_session_over_a_job_chain(
        monkeypatch, tmp_path):
    import sys
    import types

    from spacedrive_tpu.jobs import JobManager
    from spacedrive_tpu.jobs.job import StatefulJob, StepResult
    from spacedrive_tpu.jobs.manager import JOB_REGISTRY, JobBuilder
    from spacedrive_tpu.node import Libraries
    from spacedrive_tpu.tasks import TaskSystem
    from spacedrive_tpu.telemetry import profiler

    calls = []
    fake_jax = types.SimpleNamespace(profiler=types.SimpleNamespace(
        start_trace=lambda d: calls.append(("start", d)),
        stop_trace=lambda: calls.append(("stop", None)),
    ))
    monkeypatch.setitem(sys.modules, "jax", fake_jax)
    monkeypatch.setattr(spans, "_annotation_cls", None)
    monkeypatch.setenv(profiler.ENV_VAR, str(tmp_path / "prof"))

    class First(StatefulJob):
        NAME = "chain_first"

        async def init_job(self, ctx):
            self.steps.append({})

        async def execute_step(self, ctx, step, n):
            calls.append((self.NAME, profiler.profiling_active()))
            return StepResult()

    class Second(First):
        NAME = "chain_second"

    JOB_REGISTRY.update({First.NAME: First, Second.NAME: Second})
    try:
        library = Libraries(tmp_path).create("chain")
        mgr = JobManager(TaskSystem(2))
        await JobBuilder(First()).queue_next(Second()).spawn(mgr, library)
        for _ in range(200):
            await asyncio.sleep(0.01)
            if calls and calls[-1][0] == "stop":
                break
        await mgr.wait_idle()
    finally:
        JOB_REGISTRY.pop(First.NAME, None)
        JOB_REGISTRY.pop(Second.NAME, None)
    assert [c[0] for c in calls] == ["start", "chain_first", "chain_second",
                                     "stop"], calls
    assert calls[1][1] is True and calls[2][1] is True
    assert calls[0][1] == os.path.join(str(tmp_path / "prof"), "chain_first")
    assert not profiler.profiling_active()


# --- the job manager's own part of a job has names ---------------------------


@pytest.mark.asyncio
async def test_job_manager_spans_ride_the_jobs_trace(tmp_path):
    """`job.ingest`, `job.finalize` and `job.settle` are one span each
    per job (never per step), under the job's trace; a chained job's
    ingest nests in its predecessor's settle, and the chained job's own
    spans do not (the settle is over by then)."""
    from spacedrive_tpu.jobs import JobManager
    from spacedrive_tpu.jobs.job import StatefulJob, StepResult
    from spacedrive_tpu.jobs.manager import JOB_REGISTRY, JobBuilder
    from spacedrive_tpu.node import Libraries
    from spacedrive_tpu.tasks import TaskSystem

    class First(StatefulJob):
        NAME = "spans_first"

        async def init_job(self, ctx):
            self.steps.extend([{}, {}, {}])

        async def execute_step(self, ctx, step, n):
            with telemetry.span("step"):
                pass
            return StepResult()

    class Second(First):
        NAME = "spans_second"

    telemetry.reset()
    JOB_REGISTRY.update({First.NAME: First, Second.NAME: Second})
    try:
        library = Libraries(tmp_path).create("spans")
        mgr = JobManager(TaskSystem(2))
        first = First()
        await JobBuilder(first).queue_next(Second()).spawn(mgr, library)
        await mgr.wait_idle()
    finally:
        JOB_REGISTRY.pop(First.NAME, None)
        JOB_REGISTRY.pop(Second.NAME, None)
    mine = [r["stage"] for r in trace.recent(first.trace_ctx.trace_id)]
    counts = {s: mine.count(s) for s in set(mine)}
    assert counts["job.ingest"] == 1
    assert counts["job.settle.job.ingest"] == 1
    assert counts["job.finalize"] == 2 and counts["job.settle"] == 2
    assert counts["step"] == 6
    # report rows: created and set running in ingest, settled in settle
    assert counts["job.ingest.db.txn"] == 2
    assert counts["job.settle.job.ingest.db.txn"] == 2
    assert counts["job.settle.db.txn"] >= 2
    # and every one of them ends in its `commit` leaf
    for path in ("job.ingest.db.txn", "job.settle.job.ingest.db.txn",
                 "job.settle.db.txn"):
        assert counts[path + ".commit"] == counts[path]
