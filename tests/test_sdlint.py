"""sdlint self-tests: per-rule positive/negative fixtures plus the
whole-tree gate.

Every shipped rule must (a) fire on a minimal reproduction of the bug
class it encodes and (b) stay silent on the clean idiom this repo
actually uses — the negative fixtures are the spec for what the rules
must NOT nag about. The gate test invokes the exact same entry point as
`make lint` (`python -m tools.sdlint spacedrive_tpu --format=json`), so
tier-1 and CI cannot drift apart.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from tools.sdlint import Baseline, analyze_paths
from tools.sdlint.baseline import BaselineError, DEFAULT_BASELINE

REPO = Path(__file__).resolve().parents[1]


def run_on(tmp_path, source, rules=None):
    f = tmp_path / "fixture.py"
    f.write_text(textwrap.dedent(source))
    findings, errors = analyze_paths([f], rules)
    assert not errors, errors
    return findings


def rules_of(findings):
    return sorted({f.rule for f in findings})


# --- SD001 async-blocking-call --------------------------------------------


def test_sd001_flags_blocking_calls_in_async(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import subprocess, time

        async def pump():
            time.sleep(1)
            subprocess.run(["ls"])
            with open("/tmp/x") as f:
                return f.read()
        """,
        ["SD001"],
    )
    assert len(findings) == 3
    assert rules_of(findings) == ["SD001"]


def test_sd001_silent_on_clean_async(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import asyncio, time

        async def pump():
            await asyncio.sleep(1)
            data = await asyncio.to_thread(open, "/tmp/x")

            def sync_helper():
                # runs via to_thread, not on the loop
                time.sleep(1)

            return await asyncio.to_thread(sync_helper)

        def plain():
            time.sleep(1)  # not async: fine
        """,
        ["SD001"],
    )
    assert findings == []


# --- SD002 sync-lock-across-await -----------------------------------------


def test_sd002_flags_await_under_threading_lock(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import asyncio, threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            async def bad(self):
                with self._lock:
                    await asyncio.sleep(0)

            async def also_bad(self):
                self._lock.acquire()
        """,
        ["SD002"],
    )
    assert len(findings) == 2


def test_sd002_asyncio_lock_not_mistaken_for_threading_lock(tmp_path):
    """A same-named `asyncio.Lock` on another class (or an awaited
    `.acquire()`) must not resolve as the module's threading lock."""
    findings = run_on(
        tmp_path,
        """
        import asyncio, threading

        class SyncThing:
            def __init__(self):
                self._lock = threading.Lock()

        class AsyncThing:
            def __init__(self):
                self._lock = asyncio.Lock()

            async def go(self):
                await self._lock.acquire()
                try:
                    await asyncio.sleep(0)
                finally:
                    self._lock.release()
        """,
        ["SD002"],
    )
    assert findings == []


def test_sd002_silent_on_asyncio_lock_and_await_free_sections(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import asyncio, threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._alock = asyncio.Lock()

            async def ok(self):
                with self._lock:
                    x = 1  # no await while held
                async with self._alock:
                    await asyncio.sleep(0)
                got = self._lock.acquire(False)  # non-blocking probe
                return x, got
        """,
        ["SD002"],
    )
    assert findings == []


# --- SD003 orphaned-task ---------------------------------------------------


def test_sd003_flags_dropped_and_lambda_spawns(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import asyncio

        def kick(loop, coro, entry):
            asyncio.create_task(coro())
            loop.call_later(1.0, lambda: loop.create_task(coro()))
        """,
        ["SD003"],
    )
    assert len(findings) == 2


def test_sd003_silent_on_retained_tasks(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import asyncio

        class Actor:
            def __init__(self):
                self._tasks = set()

            def spawn(self, coro):
                task = asyncio.create_task(coro())
                self._tasks.add(task)
                task.add_done_callback(self._tasks.discard)

            async def direct(self, coro):
                await asyncio.create_task(coro())
                return asyncio.gather(asyncio.create_task(coro()))
        """,
        ["SD003"],
    )
    assert findings == []


# --- SD004 lock-order-cycle ------------------------------------------------


def test_sd004_flags_abba_cycle_through_helper_call(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def path1():
            with _a:
                with _b:
                    pass

        def path2():
            with _b:
                helper()

        def helper():
            with _a:
                pass
        """,
        ["SD004"],
    )
    assert len(findings) == 1
    assert "cycle" in findings[0].message


def test_sd004_multi_item_with_orders_left_to_right(tmp_path):
    """`with a, b:` acquires a before b — it must create the same
    ordering edge as the nested form, so the opposite nesting elsewhere
    is a cycle."""
    findings = run_on(
        tmp_path,
        """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def path1():
            with _a, _b:
                pass

        def path2():
            with _b:
                with _a:
                    pass
        """,
        ["SD004"],
    )
    assert len(findings) == 1
    assert "cycle" in findings[0].message


def test_sd004_flags_nested_nonreentrant_self_deadlock(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    pass
        """,
        ["SD004"],
    )
    assert len(findings) == 1
    assert "self-deadlock" in findings[0].message


def test_sd004_callback_closure_does_not_fabricate_edges(tmp_path):
    """A lock acquired inside a nested def defined while another lock is
    held is NOT acquired there — the closure runs later. No cycle."""
    findings = run_on(
        tmp_path,
        """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def schedule():
            def callback():
                with _b:
                    pass
            return callback

        def path1():
            with _a:
                schedule()  # only defines the _b closure

        def path2():
            with _b:
                with _a:
                    pass
        """,
        ["SD004"],
    )
    assert findings == []


def test_sd004_with_item_call_runs_before_lock_is_held(tmp_path):
    """`with helper(), _a:` evaluates helper() BEFORE _a is acquired —
    no held->acquired edge, no phantom cycle with a consistent
    `_b before _a` order elsewhere."""
    findings = run_on(
        tmp_path,
        """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def helper():
            with _b:
                pass
            return open("/dev/null")

        def path1():
            with helper(), _a:
                pass

        def path2():
            with _b:
                with _a:
                    pass
        """,
        ["SD004"],
    )
    assert findings == []


def test_sd004_silent_on_consistent_order_and_rlock(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        class C:
            def __init__(self):
                self._r = threading.RLock()

            def reenter(self):
                with self._r:
                    self.helper()

            def helper(self):
                with self._r:  # RLock: reentry is the point
                    pass

        def path1():
            with _a:
                with _b:
                    pass

        def path2():
            with _a:  # same global order everywhere
                with _b:
                    pass
        """,
        ["SD004"],
    )
    assert findings == []


# --- SD005 host-sync-in-jit ------------------------------------------------


def test_sd005_flags_host_sync_inside_jit(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import functools
        import jax

        @jax.jit
        def f(x):
            y = (x + 1)
            y.block_until_ready()
            return float(x)

        @functools.partial(jax.jit, static_argnames=("n",))
        def g(x, n):
            return x.item()

        def kernel(x_ref, o_ref):
            o_ref[...] = jax.device_get(x_ref[...])

        out = pl.pallas_call(kernel, out_shape=None)
        """,
        ["SD005"],
    )
    assert len(findings) == 4


def test_sd005_silent_outside_jit_and_on_static_args(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import functools
        import jax

        def host_wrapper(x):
            # not jitted: sync is the point here
            return jax.device_get(compiled(x).block_until_ready())

        @functools.partial(jax.jit, static_argnames=("scale",))
        def f(x, scale):
            return x * float(scale)  # static: a Python number at trace time
        """,
        ["SD005"],
    )
    assert findings == []


def test_sd005_flags_host_sync_inside_shard_map_body(tmp_path):
    # the dp-sharded dispatch path: bodies handed to shard_map trace
    # per-device exactly like jit bodies
    findings = run_on(
        tmp_path,
        """
        import jax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        def body(m, l):
            m.block_until_ready()
            return m

        def dispatch(mesh, m, l):
            return shard_map(
                body, mesh=mesh, in_specs=(P("dp"), P("dp")),
                out_specs=P("dp"),
            )(m, l)
        """,
        ["SD005"],
    )
    assert len(findings) == 1


# --- SD006 tracer-branch ---------------------------------------------------


def test_sd006_flags_python_branch_on_tracer(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            while x.sum() > 0:
                x = x - 1
            return x
        """,
        ["SD006"],
    )
    assert len(findings) == 2


def test_sd006_silent_on_static_branches(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            if n > 2:  # static arg
                return x
            if x is None:  # identity check resolves at trace time
                return x
            if x.shape[0] > 4 and x.ndim == 2:  # shapes are static
                return x
            if len(x) > 3:  # len == shape[0]
                return x
            return x
        """,
        ["SD006"],
    )
    assert findings == []


def test_sd006_shard_map_body_branches(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import jax
        from jax.experimental.shard_map import shard_map

        def body(x):
            if x.sum() > 0:  # traced per-device shard
                return x
            if x.shape[0] > 4:  # static: local shard shape
                return x
            return x

        out = shard_map(body, mesh=None, in_specs=None, out_specs=None)
        """,
        ["SD006"],
    )
    assert len(findings) == 1


# --- SD007 metric-label-cardinality ---------------------------------------


def test_sd007_flags_unbounded_label_values(tmp_path):
    findings = run_on(
        tmp_path,
        """
        def record(path, labels, FILES, BYTES, SECONDS, RETRIES):
            FILES.inc(result=f"error:{path}")
            BYTES.inc(1, stage=str(path))
            SECONDS.observe(0.1, stage=path)
            RETRIES.inc(**labels)
        """,
        ["SD007"],
    )
    assert len(findings) == 4


def test_sd007_silent_on_bounded_labels(tmp_path):
    findings = run_on(
        tmp_path,
        """
        def record(ok, FILES, helper):
            FILES.inc(result="generated")
            FILES.inc(result="hit" if ok else "miss")  # two-constant domain
            helper.inc(result=f"{ok}")  # not a metric handle (lowercase)
        """,
        ["SD007"],
    )
    assert findings == []


def test_sd007_sanctions_peer_label_scheme(tmp_path):
    """peer_label(...) — direct or through a same-function local — is
    the approved per-peer label shape and must not trip SD007."""
    findings = run_on(
        tmp_path,
        """
        from spacedrive_tpu.telemetry.peers import peer_label

        def record(op, lag, SYNC_LAG, SKEW):
            SYNC_LAG.set(lag, peer=peer_label(op.instance))
            label = peer_label(op.instance)
            SKEW.set(0.5, peer=label)
        """,
        ["SD007"],
    )
    assert findings == []


def test_sd007_peer_label_dataflow_is_same_function_only(tmp_path):
    findings = run_on(
        tmp_path,
        """
        from spacedrive_tpu.telemetry.peers import peer_label

        def mk(op):
            return peer_label(op.instance)

        def record(op, SYNC_LAG):
            label = mk(op)  # not a visible peer_label assignment
            SYNC_LAG.set(1.0, peer=label)
        """,
        ["SD007"],
    )
    assert len(findings) == 1


# --- SD010 peer-identifier-metric-label ------------------------------------


def test_sd010_flags_raw_peer_identifier_labels(tmp_path):
    findings = run_on(
        tmp_path,
        """
        def record(op, peer, identity, SYNC_LAG, FED_AGE, PULLS):
            SYNC_LAG.set(1.0, peer=str(op.instance))
            FED_AGE.set(2.0, peer=peer)
            PULLS.inc(result=str(identity))
        """,
        ["SD010"],
    )
    assert len(findings) == 3
    assert rules_of(findings) == ["SD010"]
    assert "peer_label" in findings[0].message


def test_sd010_silent_on_peer_label_and_non_peer_values(tmp_path):
    findings = run_on(
        tmp_path,
        """
        from spacedrive_tpu.telemetry.peers import peer_label

        def record(op, stage, OPS, SYNC_LAG, SKEW):
            OPS.inc(result="applied")          # constant — no peer shape
            OPS.observe(0.1, stage=stage)      # dynamic but not peer-ish
            SYNC_LAG.set(1.0, peer=peer_label(op.instance))
            label = peer_label(op.instance)
            SKEW.set(0.5, peer=label)
        """,
        ["SD010"],
    )
    assert findings == []


# --- SD027 tenant-label-discipline -----------------------------------------


def test_sd027_flags_raw_tenant_identifier_labels(tmp_path):
    findings = run_on(
        tmp_path,
        """
        def record(op, library_id, lib_key, TENANT_OPS, CACHE_OPS):
            TENANT_OPS.inc(tenant=str(op.library_id))
            TENANT_OPS.inc(tenant=library_id)
            CACHE_OPS.inc(lib=lib_key)
        """,
        ["SD027"],
    )
    assert len(findings) == 3
    assert rules_of(findings) == ["SD027"]
    assert "tenant_label" in findings[0].message


def test_sd027_silent_on_tenant_label_and_peer_label_values(tmp_path):
    findings = run_on(
        tmp_path,
        """
        from spacedrive_tpu.telemetry.peers import peer_label
        from spacedrive_tpu.telemetry.tenants import tenant_label

        def record(op, stage, TENANT_OPS, SYNC_OPS):
            TENANT_OPS.inc(tenant=tenant_label(op.library_id))
            label = tenant_label(op.library_id)
            TENANT_OPS.inc(tenant=label)
            # peer_label is the same hash discipline — also sanctioned
            TENANT_OPS.inc(tenant=peer_label(op.instance))
            SYNC_OPS.inc(result="applied")     # constant — no tenant shape
            SYNC_OPS.observe(0.1, stage=stage)  # dynamic but not tenant-ish
        """,
        ["SD027"],
    )
    assert findings == []


# --- SD009 event-ring-cardinality -----------------------------------------


def test_sd009_flags_dynamic_event_types_and_field_expansion(tmp_path):
    findings = run_on(
        tmp_path,
        """
        def record(kind, fields, P2P_EVENTS, JOB_EVENTS, ring):
            P2P_EVENTS.emit(f"retx_{kind}")      # runtime-built type
            P2P_EVENTS.emit(kind)                # variable type
            JOB_EVENTS.emit("ok", **fields)      # unauditable field names
            JOB_EVENTS.emit()                    # no type at all
            ring("custom").emit(kind)            # ring(...) results too
        """,
        ["SD009"],
    )
    assert len(findings) == 5


def test_sd009_silent_on_constant_types_and_literal_fields(tmp_path):
    findings = run_on(
        tmp_path,
        """
        def record(n, err, P2P_EVENTS, bus):
            P2P_EVENTS.emit("retransmit", remote=str(n), count=n)
            P2P_EVENTS.emit("stream_failed", error=str(err)[:200])
            bus.emit(("JobProgress", n))  # the EventBus, not a ring
        """,
        ["SD009"],
    )
    assert findings == []


# --- SD008 unclosed-on-exception ------------------------------------------


def test_sd008_flags_happy_path_only_close(tmp_path):
    findings = run_on(
        tmp_path,
        """
        def transfer(lock, path):
            lock.acquire()
            do_work()
            lock.release()  # skipped if do_work raises

        def read(path):
            f = open(path)
            data = f.read()
            f.close()
            return data
        """,
        ["SD008"],
    )
    assert len(findings) == 2


def test_sd008_silent_on_finally_and_with(tmp_path):
    findings = run_on(
        tmp_path,
        """
        def transfer(lock, path):
            lock.acquire()
            try:
                do_work()
            finally:
                lock.release()
            with open(path) as f:
                return f.read()

        class Span:
            def __enter__(self):
                return self

            async def __aenter__(self):
                return self.__enter__()  # protocol delegation, not a leak
        """,
        ["SD008"],
    )
    assert findings == []


# --- baseline semantics ----------------------------------------------------


def test_baseline_requires_justifications(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({
        "version": 1,
        "entries": [{"key": "SD001:x.py:time.sleep(1)", "justification": ""}],
    }))
    with pytest.raises(BaselineError):
        Baseline.load(bl)
    # non-strict load (the --write-baseline path) tolerates the TODO
    assert Baseline.load(bl, strict=False).entries


def test_baseline_split_suppresses_and_reports_stale(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import time

        async def pump():
            time.sleep(1)
        """,
        ["SD001"],
    )
    assert len(findings) == 1
    bl = Baseline(entries={
        findings[0].key: "fixture",
        "SD001:gone.py:time.sleep(2)": "stale entry",
    })
    unbaselined, suppressed, stale = bl.split(findings)
    assert unbaselined == []
    assert len(suppressed) == 1
    assert stale == ["SD001:gone.py:time.sleep(2)"]


def test_duplicate_lines_get_distinct_baseline_keys(tmp_path):
    """A new byte-identical copy of a baselined line must get a fresh
    key — one suppression must not cover every future duplicate."""
    findings = run_on(
        tmp_path,
        """
        import time

        async def one():
            time.sleep(1)

        async def two():
            time.sleep(1)
        """,
        ["SD001"],
    )
    assert len(findings) == 2
    assert findings[0].key != findings[1].key
    assert findings[1].key.endswith("#2")
    # suppressing only the first occurrence leaves the second unbaselined
    bl = Baseline(entries={findings[0].key: "grandfathered"})
    unbaselined, suppressed, _ = bl.split(findings)
    assert len(suppressed) == 1 and len(unbaselined) == 1


def test_write_baseline_merges_instead_of_wiping(tmp_path):
    """A scoped --write-baseline run must keep entries it didn't
    analyze — wiping the project baseline from a subdirectory run would
    silently delete every justification outside that subtree."""
    findings = run_on(
        tmp_path,
        """
        import time

        async def pump():
            time.sleep(1)
        """,
        ["SD001"],
    )
    bl_path = tmp_path / "baseline.json"
    existing = Baseline(
        entries={"SD007:elsewhere.py:METRIC.inc(stage=path)": "bounded"}
    )
    existing.write(bl_path, findings)
    merged = Baseline.load(bl_path, strict=False)
    assert findings[0].key in merged.entries  # new entry added (empty TODO)
    assert (
        merged.entries["SD007:elsewhere.py:METRIC.inc(stage=path)"]
        == "bounded"
    )  # unrelated entry + justification preserved


def test_baseline_keys_survive_line_moves(tmp_path):
    src = """
    import time

    async def pump():
        time.sleep(1)
    """
    before = run_on(tmp_path, src, ["SD001"])
    after = run_on(tmp_path, "# a new comment shifts every line\n"
                   + textwrap.dedent(src), ["SD001"])
    assert before[0].line != after[0].line
    assert before[0].key == after[0].key


# --- SD011 unbounded-retry -------------------------------------------------


def test_sd011_flags_sleep_free_retry(tmp_path):
    findings = run_on(
        tmp_path,
        """
        async def hammer(client):
            while True:
                try:
                    return await client.fetch()
                except Exception:
                    continue
        """,
        ["SD011"],
    )
    assert len(findings) == 1
    assert "sleep-free" in findings[0].message


def test_sd011_flags_flag_gated_sleep_free_retry(tmp_path):
    findings = run_on(
        tmp_path,
        """
        async def pump(self):
            while not self._stopped:
                try:
                    self.push()
                except OSError:
                    pass
        """,
        ["SD011"],
    )
    assert len(findings) == 1
    assert "sleep-free" in findings[0].message


def test_sd011_flags_unbounded_retry_with_backoff(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import asyncio

        async def forever(client):
            while True:
                try:
                    await client.push()
                except Exception:
                    pass
                await asyncio.sleep(1.0)
        """,
        ["SD011"],
    )
    assert len(findings) == 1
    assert "unbounded" in findings[0].message


def test_sd011_silent_on_paced_bounded_and_actor_loops(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import asyncio

        async def bounded(client):
            # bounded: the success path returns, failures break out
            while True:
                try:
                    return await client.fetch()
                except Exception:
                    break

        async def actor(self, loop, sock):
            # recv-paced loop: the outside world paces it, typed
            # handlers are deliberate control flow
            while not self._stopped:
                try:
                    data = await loop.sock_recvfrom(sock, 65535)
                except (ValueError, KeyError):
                    continue
                await asyncio.sleep(0)

        async def progress(self, task):
            # the condition makes progress (calls something)
            while not task.done():
                try:
                    await asyncio.shield(task)
                except Exception:
                    continue

        async def policy_routed(self, policy, client):
            while not self._stopped:
                try:
                    await policy.call("relay", client.fetch)
                except Exception:
                    pass
        """,
        ["SD011"],
    )
    assert findings == []


# --- SD012 journal-bypass --------------------------------------------------


def run_scoped(tmp_path, relpath, source, rules=None):
    """Like run_on, but places the fixture at a repo-shaped relative
    path — SD012 scopes by path (journal-governed modules only)."""
    f = tmp_path / relpath
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    findings, errors = analyze_paths([f], rules)
    assert not errors, errors
    return findings


SD012_SOURCE = """
    import os
    from pathlib import Path

    def sizes(paths):
        return [os.stat(p).st_size for p in paths]

    def slurp(p):
        return open(p, "rb").read()

    def slurp2(p):
        return Path(p).read_bytes()
"""


def test_sd012_flags_stat_and_full_read_in_scoped_modules(tmp_path):
    findings = run_scoped(
        tmp_path,
        "spacedrive_tpu/location/indexer/helper.py",
        SD012_SOURCE,
        ["SD012"],
    )
    assert len(findings) == 3
    assert rules_of(findings) == ["SD012"]
    findings = run_scoped(
        tmp_path,
        "spacedrive_tpu/object/file_identifier/job.py",
        "import os\n\ndef f(p):\n    return os.path.getsize(p)\n",
        ["SD012"],
    )
    assert len(findings) == 1
    # the stat of an open descriptor is the journal's too (fd_identity)
    findings = run_scoped(
        tmp_path,
        "spacedrive_tpu/object/file_identifier/job.py",
        "import os\n\ndef f(fd):\n    return os.fstat(fd).st_ino\n",
        ["SD012"],
    )
    assert len(findings) == 1 and "fd_identity" in findings[0].message


def test_sd012_silent_outside_scope_and_in_journal_itself(tmp_path):
    # the journal module OWNS the raw stat (allowlisted)
    assert run_scoped(
        tmp_path,
        "spacedrive_tpu/location/indexer/journal.py",
        SD012_SOURCE,
        ["SD012"],
    ) == []
    # leaf codec modules are out of scope: they do the decided work
    assert run_scoped(
        tmp_path,
        "spacedrive_tpu/object/media/thumbnail/process.py",
        SD012_SOURCE,
        ["SD012"],
    ) == []


def test_sd012_silent_on_journal_idiom(tmp_path):
    findings = run_scoped(
        tmp_path,
        "spacedrive_tpu/location/indexer/helper.py",
        """
        from . import journal as _journal

        def check(path, f):
            ident = _journal.stat_identity(path)  # sanctioned stat
            head = f.read(1024)                   # bounded read is fine
            exists = __import__("os").path.exists(path)
            return ident, head, exists
        """,
        ["SD012"],
    )
    assert findings == []


# --- SD013 policy-bypass-constant ------------------------------------------


SD013_SOURCE = """
    DEVICE_BATCH = 32
    PIPELINE_DEPTH = 3
    CHUNK_SIZE = 100
    BATCH_LADDER = (32, 256, 1024)
    WINDOW_ROWS = 8 * 1024

    class Feeder:
        MAX_DEPTH = 8
"""


def test_sd013_flags_hardcoded_sizing_in_pipeline_modules(tmp_path):
    findings = run_scoped(
        tmp_path,
        "spacedrive_tpu/parallel/feeder.py",
        SD013_SOURCE,
        ["SD013"],
    )
    assert len(findings) == 6  # incl. the class-level MAX_DEPTH
    assert rules_of(findings) == ["SD013"]
    findings = run_scoped(
        tmp_path,
        "spacedrive_tpu/ops/cas.py",
        "DEVICE_BATCH = 1024\n",
        ["SD013"],
    )
    assert len(findings) == 1


def test_sd013_silent_on_derived_and_non_sizing_constants(tmp_path):
    findings = run_scoped(
        tmp_path,
        "spacedrive_tpu/object/media/thumbnail/actor.py",
        """
        from ....parallel.autotune import BATCH_LADDER

        DEVICE_BATCH = BATCH_LADDER[-1]   # derived: follows the seam
        GENERATION_TIMEOUT_S = 30         # not a sizing knob

        def chunk(policy, n):
            rows = 32 * n                 # function-local: policy-fed
            return policy.thumb_chunk_rows(n)

        def fetch(depth=3):               # defaults come from callers
            return depth
        """,
        ["SD013"],
    )
    assert findings == []


def test_sd013_silent_outside_scope_and_in_autotune_itself(tmp_path):
    # the policy module OWNS the real constants (allowlisted)
    assert run_scoped(
        tmp_path,
        "spacedrive_tpu/parallel/autotune.py",
        SD013_SOURCE,
        ["SD013"],
    ) == []
    # media/job.py's BATCH_SIZE batches DB writes (reference parity),
    # not device work — deliberately out of scope
    assert run_scoped(
        tmp_path,
        "spacedrive_tpu/object/media/job.py",
        "BATCH_SIZE = 10\n",
        ["SD013"],
    ) == []


def test_sd013_covers_semantic_search_modules(tmp_path):
    # ISSUE 16: the embed forward + vector-index scoring size through
    # PipelinePolicy("embed") — a local EMBED_DEVICE_BATCH re-opens the
    # pre-autotuner world exactly like a thumbnail one would
    findings = run_scoped(
        tmp_path,
        "spacedrive_tpu/ops/embed_jax.py",
        "EMBED_DEVICE_BATCH = 64\n",
        ["SD013"],
    )
    assert len(findings) == 1
    assert rules_of(findings) == ["SD013"]
    findings = run_scoped(
        tmp_path,
        "spacedrive_tpu/object/search/index.py",
        "SCORE_CHUNK_ROWS = 4096\n",
        ["SD013"],
    )
    assert len(findings) == 1
    # derived-from-policy stays the sanctioned idiom here too
    assert run_scoped(
        tmp_path,
        "spacedrive_tpu/ops/embed_jax.py",
        """
        from ..parallel.autotune import EMBED_DEVICE_BATCH

        DEVICE_BATCH = EMBED_DEVICE_BATCH
        """,
        ["SD013"],
    ) == []


# --- SD014 p2p-unguarded-request -------------------------------------------


SD014_SOURCE = """
    from spacedrive_tpu.p2p.operations import ping, request_telemetry
    from spacedrive_tpu.p2p.rspc import remote_exec

    async def raw_pull(p2p, peer):
        # unguarded: every dead peer costs a dial timeout here
        snap = await request_telemetry(p2p, peer.identity)
        rtt = await ping(p2p, peer.identity)
        return snap, rtt

    async def raw_exec(p2p, peer):
        return await remote_exec(p2p, peer, "telemetry.debug_bundle")
"""


def test_sd014_flags_unguarded_p2p_requests(tmp_path):
    findings = run_on(tmp_path, SD014_SOURCE, ["SD014"])
    assert len(findings) == 3
    assert rules_of(findings) == ["SD014"]
    assert all("ResiliencePolicy" in f.message for f in findings)


def test_sd014_silent_on_policy_wrapped_calls(tmp_path):
    findings = run_on(
        tmp_path,
        """
        from spacedrive_tpu.p2p.operations import request_telemetry
        from spacedrive_tpu.p2p.rspc import remote_exec

        async def guarded(policy, p2p, peers):
            out = []
            for peer in peers:
                out.append(await policy.call(
                    str(peer.identity),
                    lambda peer=peer: request_telemetry(p2p, peer.identity),
                ))
            return out

        async def guarded_exec(policy, p2p, peer):
            return await policy.call(
                str(peer),
                lambda: remote_exec(p2p, peer, "telemetry.mesh"),
            )

        def unrelated(call, ping):
            # names that merely LOOK like the wire ops but are locals
            return call(ping)
        """,
        ["SD014"],
    )
    assert findings == []


def test_sd014_exempts_defining_modules(tmp_path):
    # the module that defines a request helper may dial directly — the
    # client half itself is the implementation, not an adoption gap
    assert run_scoped(
        tmp_path,
        "spacedrive_tpu/p2p/work.py",
        """
        async def announce_loop(p2p, peer, lib_id):
            return await request_work(p2p, peer, lib_id, {"op": "status"})

        async def request_work(p2p, peer, lib_id, body):
            return {}
        """,
        ["SD014"],
    ) == []


# --- SD015 ungated-handler --------------------------------------------------


def run_tree(tmp_path, files, rules=None):
    """Multi-file fixture tree (SD015 is a project rule: it reads the
    NAMESPACE_CLASSES coverage map out of serve/policy.py)."""
    for relpath, source in files.items():
        f = tmp_path / relpath
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent(source))
    findings, errors = analyze_paths([tmp_path], rules)
    assert not errors, errors
    return findings


SD015_POLICY = """
    NAMESPACE_CLASSES: dict[str, str] = {
        "files": "interactive",
        "telemetry": "control",
    }
"""


def test_sd015_flags_bare_route_and_uncovered_namespace(tmp_path):
    findings = run_tree(
        tmp_path,
        {
            "spacedrive_tpu/serve/policy.py": SD015_POLICY,
            "spacedrive_tpu/api/mod.py": """
                from aiohttp import web

                def routes(self):
                    return [
                        web.get("/bare", self._bare),
                        self._gated(web.get("/ok", self._ok), "control"),
                    ]

                def mount(r):
                    @r.query("newthing.list", library=True)
                    def list_things(node, library):
                        return []

                    @r.query("files.get", library=True)
                    def covered(node, library):
                        return []
            """,
        },
        ["SD015"],
    )
    assert len(findings) == 2
    assert rules_of(findings) == ["SD015"]
    messages = sorted(f.message for f in findings)
    assert "web.get" in messages[0] or "_gated" in messages[0]
    assert any("newthing" in m for m in messages)


def test_sd015_nonliteral_key_requires_priority(tmp_path):
    findings = run_tree(
        tmp_path,
        {
            "spacedrive_tpu/serve/policy.py": SD015_POLICY,
            "spacedrive_tpu/api/mod.py": """
                def mount(r, ns):
                    @r.query(f"{ns}.list", library=True)
                    def list_all(node, library):
                        return []

                    @r.mutation(f"{ns}.create", library=True,
                                priority="interactive")
                    def create(node, library, arg):
                        return None
            """,
        },
        ["SD015"],
    )
    assert len(findings) == 1
    assert "non-literal" in findings[0].message


def test_sd015_silent_on_clean_api_module(tmp_path):
    findings = run_tree(
        tmp_path,
        {
            "spacedrive_tpu/serve/policy.py": SD015_POLICY,
            "spacedrive_tpu/api/mod.py": """
                from aiohttp import web

                def routes(self):
                    return [
                        self._gated(web.get("/x", self._x), "interactive"),
                        self._gated(web.post("/y", self._y), "background"),
                    ]

                def mount(r):
                    @r.query("telemetry.snapshot")
                    def snapshot(node):
                        return {}

                    @r.subscription("files.changes", library=True)
                    def changes(node, library):
                        return None

                def unrelated(db, sql):
                    # same attr names OUTSIDE decorator position: not
                    # registrations (the db.query(...) shape)
                    return db.query(sql)
            """,
        },
        ["SD015"],
    )
    assert findings == []


def test_sd015_out_of_scope_modules_ignored(tmp_path):
    # route defs outside spacedrive_tpu/api/ (e.g. a test harness) are
    # not this rule's business
    findings = run_tree(
        tmp_path,
        {
            "spacedrive_tpu/desktop_helper.py": """
                from aiohttp import web

                def routes(h):
                    return [web.get("/internal", h)]
            """,
        },
        ["SD015"],
    )
    assert findings == []


# --- SARIF export ----------------------------------------------------------


def test_sarif_round_trip_preserves_every_finding_field(tmp_path):
    """to_sarif -> from_sarif must reconstruct the findings exactly —
    including the ordinal a duplicate snippet carries — so nothing the
    baseline or a diff tool needs gets dropped from the log."""
    from tools.sdlint.sarif import from_sarif, to_sarif

    findings = run_on(
        tmp_path,
        """
        import time

        async def one():
            time.sleep(1)

        async def two():
            time.sleep(1)
        """,
        ["SD001"],
    )
    assert len(findings) == 2 and findings[1].ordinal == 1
    entries = {findings[0].key: "grandfathered fixture entry"}
    doc = to_sarif([findings[1]], [findings[0]], entries)
    # the document must survive JSON serialization (what the CLI emits)
    doc = json.loads(json.dumps(doc))

    unbaselined, suppressed = from_sarif(doc)
    assert unbaselined == [findings[1]]
    assert suppressed == [findings[0]]
    result = doc["runs"][0]["results"][1]
    assert result["suppressions"][0]["justification"] == (
        "grandfathered fixture entry"
    )
    # the catalog rides along: every registered rule, indexed
    rules = doc["runs"][0]["tool"]["driver"]["rules"]
    assert [r["id"] for r in rules] == sorted(
        r["id"] for r in rules
    ) and len(rules) >= 26
    assert result["ruleId"] == rules[result["ruleIndex"]]["id"]


def test_sarif_cli_format(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    proc = _run_cli(str(bad), "--no-baseline", "--format=sarif")
    assert proc.returncode == 1  # exit semantics unchanged by format
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    assert len(results) == 1 and results[0]["ruleId"] == "SD001"
    assert not results[0].get("suppressions")
    region = results[0]["locations"][0]["physicalLocation"]["region"]
    assert region["startLine"] == 3 and region["startColumn"] >= 1
    assert results[0]["partialFingerprints"]["sdlintKey/v1"].startswith(
        "SD001:")


# --- the gate (same entry point as `make lint` / CI) -----------------------


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.sdlint", *args],
        cwd=REPO, capture_output=True, text=True, timeout=180,
    )


def test_whole_tree_gate_zero_unbaselined_findings():
    proc = _run_cli("spacedrive_tpu", "--format=json")
    doc = json.loads(proc.stdout)
    assert proc.returncode == 0, (
        "unbaselined sdlint findings:\n"
        + "\n".join(
            f"{f['path']}:{f['line']}: {f['rule']} {f['message']}"
            for f in doc["findings"]
        )
    )
    assert doc["ok"] is True
    assert doc["counts"]["unbaselined"] == 0
    # the baseline must not rot: every entry still matches a finding
    assert doc["stale_baseline_keys"] == []


def test_checked_in_baseline_entries_all_justified():
    bl = Baseline.load(DEFAULT_BASELINE)  # strict: raises on empty reason
    for key, justification in bl.entries.items():
        assert len(justification) > 10, f"thin justification for {key}"


def test_cli_exit_codes_and_rule_listing(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    proc = _run_cli(str(bad), "--no-baseline")
    assert proc.returncode == 1
    assert "SD001" in proc.stdout

    proc = _run_cli(str(bad), "--no-baseline", "--rules", "SD003")
    assert proc.returncode == 0  # only the orphan rule ran: clean

    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    for rid in ("SD001", "SD004", "SD008"):
        assert rid in proc.stdout


# --- SD016 cancellation-unsafe async resource flow -------------------------


def test_sd016_flags_pr10_admission_slot_leak_shape(tmp_path):
    """Reconstruction of the PR 10 bug class: a slot counter taken,
    then a cancellation point, then the release — CancelledError
    delivered at the await leaks the slot forever."""
    findings = run_on(
        tmp_path,
        """
        class Gate:
            async def admit(self):
                self._inflight += 1
                await self._work()   # cancelled here -> slot leaked
                self._inflight -= 1
        """,
        ["SD016"],
    )
    assert len(findings) == 1
    assert "CancelledError" in findings[0].message


def test_sd016_flags_semaphore_released_on_happy_path_only(tmp_path):
    findings = run_on(
        tmp_path,
        """
        async def fetch(self):
            await self._slots.acquire()
            data = await self._pull()
            self._slots.release()
            return data
        """,
        ["SD016"],
    )
    assert len(findings) == 1


def test_sd016_flags_bookkeeping_between_acquire_and_try(tmp_path):
    """The exact serve/gate.py finding: statements that can raise
    between the acquire and the try/finally leak on their exception
    path even though a finally exists."""
    findings = run_on(
        tmp_path,
        """
        class Gate:
            async def admit(self):
                self._inflight += 1
                self._metrics.inc()   # raises -> finally never entered
                try:
                    await self._work()
                finally:
                    self._inflight -= 1
        """,
        ["SD016"],
    )
    assert len(findings) == 1
    assert "exception path" in findings[0].message


def test_sd016_silent_on_finally_async_with_and_knob_nudges(tmp_path):
    findings = run_on(
        tmp_path,
        """
        class C:
            async def ok_finally(self):
                await self._slots.acquire()
                try:
                    return await self._pull()
                finally:
                    self._slots.release()

            async def ok_async_with(self):
                async with self._slots:
                    await self._pull()

            async def ok_knob(self):
                # += / -= in SIBLING branches is tuning, not a resource
                if self._hot():
                    self._rung += 1
                else:
                    self._rung -= 1
                await self._apply()

            async def __aenter__(self):
                await self._sem.acquire()  # cross-method protocol
                return self
        """,
        ["SD016"],
    )
    assert findings == []


def test_sd016_cancellation_sails_past_except_exception(tmp_path):
    """`except Exception` does not catch CancelledError — a handler-
    based release still leaks on the cancellation path."""
    findings = run_on(
        tmp_path,
        """
        async def f(self):
            await self._sem.acquire()
            try:
                await self._work()
            except Exception:
                pass
            self._sem.release()
        """,
        ["SD016"],
    )
    assert len(findings) == 1
    assert "CancelledError" in findings[0].message


# --- SD017 vouch-before-commit ---------------------------------------------


def test_sd017_flags_pr7_pre_commit_journal_vouch(tmp_path):
    """Reconstruction of the PR 7 invariant's bug shape: the journal
    vouches BEFORE (or inside) the transaction that stores what it
    vouches for."""
    findings = run_on(
        tmp_path,
        """
        def persist_before(db, journal, entry):
            journal.record(entry.key, entry.cas)
            with db.transaction() as conn:
                conn.execute("INSERT INTO t VALUES (?)", (entry.cas,))

        def persist_inside(db, journal, entry):
            with db.transaction() as conn:
                conn.execute("INSERT INTO t VALUES (?)", (entry.cas,))
                journal.record(entry.key, entry.cas)
        """,
        ["SD017"],
    )
    assert len(findings) == 2
    assert all(f.rule == "SD017" for f in findings)


def test_sd017_silent_on_post_commit_vouch_and_facade(tmp_path):
    findings = run_on(
        tmp_path,
        """
        def persist(db, journal, entry):
            with db.transaction() as conn:
                conn.execute("INSERT INTO t VALUES (?)", (entry.cas,))
            journal.record(entry.key, entry.cas)

        def facade(db, journal, rows):
            db.executemany("UPDATE t SET x = ?", rows)
            journal.record_phash(1, rows)

        def via_write_ops(library, journal, ops, rows):
            library.sync.write_ops(ops)
            journal.record_many(1, rows)
        """,
        ["SD017"],
    )
    assert findings == []


def test_sd017_interprocedural_carrier_through_helper(tmp_path):
    """A helper that vouches makes its CALL SITES carry the obligation:
    ordered after the commit is clean, a guard path that skips the
    commit is a finding."""
    clean = run_on(
        tmp_path,
        """
        def _finalize(journal, entry):
            journal.record_many(1, [entry])

        def persist(db, journal, entry):
            with db.transaction() as conn:
                conn.execute("INSERT")
            _finalize(journal, entry)
        """,
        ["SD017"],
    )
    assert clean == []
    holed = run_on(
        tmp_path,
        """
        def _finalize(journal, entry):
            journal.record_many(1, [entry])

        def persist(db, journal, entry, bad):
            if not bad:
                with db.transaction() as conn:
                    conn.execute("INSERT")
            _finalize(journal, entry)
        """,
        ["SD017"],
    )
    assert len(holed) == 1
    assert "_finalize" in holed[0].message


def test_sd017_watermark_advance_needs_commit(tmp_path):
    findings = run_on(
        tmp_path,
        """
        def ingest(sync, op, _tm):
            _tm.SYNC_WATERMARK.set(op.ts, peer="x")
            with sync.db.transaction() as conn:
                conn.execute("INSERT")
        """,
        ["SD017"],
    )
    assert len(findings) == 1
    assert "SYNC_WATERMARK" in findings[0].message


# --- SD018 frozen-dataclass mutation ---------------------------------------


def test_sd018_flags_delta_guard_latent_bug_shape(tmp_path):
    """Reconstruction of the delta-guard FrozenInstanceError: stashing
    a rejection reason on the frozen op instead of returning it."""
    findings = run_on(
        tmp_path,
        """
        from dataclasses import dataclass

        @dataclass(frozen=True)
        class CRDTOperation:
            ts: int

        def guard(op: CRDTOperation, reason: str) -> bool:
            if reason:
                op.reject_reason = reason   # FrozenInstanceError
                return False
            return True

        def from_factory(raw):
            op = CRDTOperation.from_wire(raw)
            op.ts += 1

        def over_params(ops: list[CRDTOperation]):
            for op in ops:
                op.ts = 0
        """,
        ["SD018"],
    )
    assert len(findings) == 3
    assert all("FrozenInstanceError" in f.message for f in findings)


def test_sd018_silent_on_replace_unfrozen_and_untyped(tmp_path):
    findings = run_on(
        tmp_path,
        """
        from dataclasses import dataclass, replace

        @dataclass(frozen=True)
        class Op:
            ts: int

        @dataclass
        class Mutable:
            ts: int

        def ok(op: Op, m: Mutable, anything):
            m.ts = 1           # not frozen
            anything.ts = 2    # untyped: unknown
            return replace(op, ts=3)   # the sanctioned idiom
        """,
        ["SD018"],
    )
    assert findings == []


# --- SD019 breaker-feed discipline -----------------------------------------


def test_sd019_flags_policies_that_feed_negative_answers(tmp_path):
    findings = run_on(
        tmp_path,
        """
        PASS = "pass"
        RETRY = "retry"

        def no_pass(exc):
            return RETRY

        P1 = ResiliencePolicy("a")                       # no classify
        P2 = ResiliencePolicy("b", classify=no_pass)     # cannot PASS
        P3 = ResiliencePolicy("c", classify=lambda e: RETRY)
        """,
        ["SD019"],
    )
    assert len(findings) == 3


def test_sd019_silent_on_pass_capable_classifiers(tmp_path):
    findings = run_on(
        tmp_path,
        """
        PASS = "pass"
        RETRY = "retry"

        def classify(exc):
            if isinstance(exc, (PermissionError, ValueError)):
                return PASS
            return RETRY

        P1 = ResiliencePolicy("a", classify=classify)
        P2 = ResiliencePolicy("b", classify=lambda e: PASS if e else RETRY)
        P3 = ResiliencePolicy("c", classify=some.dynamic.thing)  # unknowable
        """,
        ["SD019"],
    )
    assert findings == []


# --- flow-sensitivity upgrades of the migrated rules -----------------------


def test_sd008_branch_structured_close_is_clean_now(tmp_path):
    """The old syntax-level rule demanded a `finally`; the CFG version
    proves every path closes (no exception-capable statement runs while
    the handle is open here)."""
    findings = run_on(
        tmp_path,
        """
        def read_mode(path, header_only):
            fh = open(path)
            if header_only:
                fh.close()
                return None
            fh.close()
            return path
        """,
        ["SD008"],
    )
    assert findings == []


def test_sd008_early_return_leak_is_caught_now(tmp_path):
    findings = run_on(
        tmp_path,
        """
        def read_mode(path, header_only):
            fh = open(path)
            if header_only:
                return None   # leaks fh
            fh.close()
            return path
        """,
        ["SD008"],
    )
    assert len(findings) == 1
    assert "early-return" in findings[0].message


def test_sd002_await_after_early_release_is_clean(tmp_path):
    """Flow-sensitivity cut: an await AFTER `.release()` inside the
    with-region used to be unreachable to the syntax-level rule's
    reasoning (it flagged any await lexically inside the body)."""
    findings = run_on(
        tmp_path,
        """
        import asyncio, threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            async def ok(self):
                with self._lock:
                    x = 1
                await asyncio.sleep(0)
                return x
        """,
        ["SD002"],
    )
    assert findings == []


def test_sd002_await_in_branch_under_lock_is_caught(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import asyncio, threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            async def bad(self, flag):
                with self._lock:
                    if flag:
                        await asyncio.sleep(0)
        """,
        ["SD002"],
    )
    assert len(findings) == 1


def test_sd004_manual_acquire_release_protocol_orders(tmp_path):
    """Blind-spot cut: explicit `.acquire()` / `.release()` pairs now
    produce ordering edges, not just `with` blocks."""
    findings = run_on(
        tmp_path,
        """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def one():
            _a.acquire()
            try:
                with _b:
                    pass
            finally:
                _a.release()

        def two():
            with _b:
                _a.acquire()
                _a.release()
        """,
        ["SD004"],
    )
    assert len(findings) == 1
    assert "cycle" in findings[0].message


# --- baseline pruning + CI annotations -------------------------------------


def test_prune_baseline_removes_only_stale_entries(tmp_path):
    fx = tmp_path / "fx.py"
    fx.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    live_key = f"SD001:{fx}:time.sleep(1)"
    stale_key = f"SD001:{fx}:time.sleep(99)"
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({
        "version": 1,
        "entries": [
            {"key": live_key, "justification": "still grandfathered"},
            {"key": stale_key, "justification": "edited away long ago"},
        ],
    }))
    proc = _run_cli(str(fx), "--baseline", str(bl), "--prune-baseline")
    assert proc.returncode == 0
    assert stale_key in proc.stdout
    kept = json.loads(bl.read_text())["entries"]
    assert [e["key"] for e in kept] == [live_key]
    # justifications survive the rewrite
    assert kept[0]["justification"] == "still grandfathered"
    # second run: nothing left to prune
    proc = _run_cli(str(fx), "--baseline", str(bl), "--prune-baseline")
    assert "no stale entries" in proc.stdout


def test_annotate_emits_github_error_lines(tmp_path):
    fx = tmp_path / "fx.py"
    fx.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    proc = _run_cli(str(fx), "--no-baseline", "--annotate")
    assert proc.returncode == 1
    # annotations ride STDERR so --format=json stdout stays parseable
    # (the Actions runner scans both streams for workflow commands)
    line = next(
        ln for ln in proc.stderr.splitlines() if ln.startswith("::error ")
    )
    assert f"file={fx}" in line
    assert "line=3" in line
    assert "title=sdlint SD001" in line

    env_proc = subprocess.run(
        [sys.executable, "-m", "tools.sdlint", str(fx), "--no-baseline",
         "--format=json"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "SDLINT_ANNOTATE": "1"},
    )
    assert any(
        ln.startswith("::error ") for ln in env_proc.stderr.splitlines()
    )
    json.loads(env_proc.stdout)  # the JSON document stays machine-stable


def test_sd008_early_return_through_finally_still_leaks(tmp_path):
    """Review-found soundness gap: a `return` routed through a
    `finally` must not masquerade as fall-through into the close after
    the try (the finally is built twice — normal + abrupt copies)."""
    findings = run_on(
        tmp_path,
        """
        def f(cond, path):
            fh = open(path)
            try:
                if cond:
                    return None   # leaks fh through the finally
            finally:
                log("x")
            fh.close()
            return path

        def g(cond, path):
            fh = open(path)
            try:
                if cond:
                    return None
            finally:
                fh.close()        # close IN the finally: every path
            return path
        """,
        ["SD008"],
    )
    assert len(findings) == 1
    assert findings[0].line == 3  # f's open, not g's


def test_sd004_module_level_lock_order_still_counts(tmp_path):
    """Review-found regression guard: module-level (import-time) lock
    acquisition must still produce ordering edges."""
    findings = run_on(
        tmp_path,
        """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def one():
            with _a:
                with _b:
                    pass

        with _b:
            with _a:
                pass
        """,
        ["SD004"],
    )
    assert len(findings) == 1
    assert "cycle" in findings[0].message


def test_prune_baseline_is_scope_aware(tmp_path):
    """A path- or rules-scoped prune run must not treat out-of-scope
    entries as stale (their findings never had a chance to fire)."""
    fx_dir = tmp_path / "pkg"
    fx_dir.mkdir()
    fx = fx_dir / "fx.py"
    fx.write_text("import time\nasync def f():\n    time.sleep(1)\n")
    other_key = f"SD001:{tmp_path}/elsewhere.py:time.sleep(2)"
    sd3_key = f"SD003:{fx}:something"
    live_key = f"SD001:{fx}:time.sleep(1)"
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({
        "version": 1,
        "entries": [
            {"key": live_key, "justification": "still grandfathered"},
            {"key": other_key, "justification": "file not analyzed here"},
            {"key": sd3_key, "justification": "rule not run here"},
        ],
    }))
    # scoped by path AND rules: neither out-of-scope entry may vanish
    proc = _run_cli(str(fx), "--baseline", str(bl), "--rules", "SD001",
                    "--prune-baseline")
    assert proc.returncode == 0
    assert "no stale entries" in proc.stdout
    kept = {e["key"] for e in json.loads(bl.read_text())["entries"]}
    assert kept == {live_key, other_key, sd3_key}


def test_prune_baseline_project_rules_need_whole_package_scope(tmp_path):
    """A PROJECT rule's verdict depends on files anywhere in the tree
    (classify helpers, frozen-class defs, caller sets) — a subdir-scoped
    prune must not treat its entries as stale, while a whole-package
    run may."""
    pkg = tmp_path / "pkg"
    sub = pkg / "sub"
    sub.mkdir(parents=True)
    (pkg / "fx.py").write_text("x = 1\n")
    (sub / "inner.py").write_text("y = 2\n")
    sd19_key = "pkg/fx.py gone-stale"
    bl = tmp_path / "bl.json"
    entry = {"key": f"SD019:pkg/fx.py:P = ResiliencePolicy(",
             "justification": "context lives outside any subdir"}
    import copy
    bl.write_text(json.dumps({"version": 1, "entries": [entry]}))
    # subdir scope: SD019 ran, but the whole package was NOT analyzed —
    # the entry survives even though no finding fired
    proc = _run_cli(str(sub), "--baseline", str(bl), "--prune-baseline")
    assert proc.returncode == 0, proc.stderr
    assert "no stale entries" in proc.stdout
    assert json.loads(bl.read_text())["entries"], "project entry pruned"
    # whole-package scope (run from tmp_path so the root is `pkg`):
    # now the entry is honestly stale and goes
    proc = subprocess.run(
        [sys.executable, "-m", "tools.sdlint", "pkg",
         "--baseline", str(bl), "--prune-baseline"],
        capture_output=True, text=True, timeout=180,
        cwd=tmp_path, env={**os.environ, "PYTHONPATH": str(REPO)},
    )
    assert proc.returncode == 0, proc.stderr
    assert json.loads(bl.read_text())["entries"] == []


def test_sd016_conditional_release_in_handler_still_leaks(tmp_path):
    """Review-found blind spot: a release inside an except handler used
    to be attributed to the handler HEADER, stopping the leak search
    even when the release was conditional."""
    findings = run_on(
        tmp_path,
        """
        async def f(self):
            await self._sem.acquire()
            try:
                await self._work()
            except BaseException:
                if self._rare():
                    self._sem.release()
                raise
            self._sem.release()
        """,
        ["SD016"],
    )
    assert len(findings) == 1


def test_sd016_unconditional_release_in_handler_is_clean(tmp_path):
    findings = run_on(
        tmp_path,
        """
        async def f(self):
            await self._sem.acquire()
            try:
                await self._work()
            except BaseException:
                self._sem.release()
                raise
            self._sem.release()
        """,
        ["SD016"],
    )
    assert findings == []


def test_sd017_carrier_caller_subsumes_callee_obligation(tmp_path):
    """Review-found false positive: when a function with its own vouch
    ALSO calls another carrier, the callee-derived obligation must climb
    the call graph with it — not fire at the call site when every caller
    is provably post-commit."""
    findings = run_on(
        tmp_path,
        """
        def a_vouch(journal, entry):
            journal.record_many(1, [entry])

        def b(sync, journal, entry, _tm):
            _tm.SYNC_OPS.inc(result="applied")
            a_vouch(journal, entry)

        def top(sync, journal, entry, _tm):
            with sync.db.transaction() as conn:
                conn.execute("INSERT")
            b(sync, journal, entry, _tm)
        """,
        ["SD017"],
    )
    assert findings == []


# --- SD020 metric-catalog-drift --------------------------------------------


def _catalog(tmp_path, rows):
    doc = tmp_path / "telemetry.md"
    lines = ["# Telemetry", "", "| metric | type | labels | source |",
             "|---|---|---|---|"]
    lines += [f"| `{name}` | counter | – | fixture |" for name in rows]
    doc.write_text("\n".join(lines) + "\n")
    return doc


def run_sd020(tmp_path, source, catalog_rows, monkeypatch):
    doc = _catalog(tmp_path, catalog_rows)
    monkeypatch.setenv("SDLINT_TELEMETRY_CATALOG", str(doc))
    return run_on(tmp_path, source, ["SD020"])


def test_sd020_minted_family_without_catalog_row(tmp_path, monkeypatch):
    findings = run_sd020(
        tmp_path,
        """
        from .registry import REGISTRY

        CATALOGED = REGISTRY.counter("sd_cataloged_total", "fine")
        ORPHANED = REGISTRY.gauge("sd_orphaned_gauge", "missing from docs")
        """,
        ["sd_cataloged_total"],
        monkeypatch,
    )
    assert rules_of(findings) == ["SD020"]
    assert len(findings) == 1
    assert "sd_orphaned_gauge" in findings[0].message
    assert findings[0].path.endswith("fixture.py")


def test_sd020_stale_catalog_row(tmp_path, monkeypatch):
    findings = run_sd020(
        tmp_path,
        """
        from .registry import REGISTRY

        LIVE = REGISTRY.histogram("sd_live_seconds", "fine")
        """,
        ["sd_live_seconds", "sd_deleted_long_ago_total"],
        monkeypatch,
    )
    assert len(findings) == 1
    assert "sd_deleted_long_ago_total" in findings[0].message
    assert findings[0].path.endswith("telemetry.md")
    assert findings[0].line > 0


def test_sd020_complete_catalog_is_clean(tmp_path, monkeypatch):
    findings = run_sd020(
        tmp_path,
        """
        import telemetry
        from .registry import REGISTRY

        A = REGISTRY.counter("sd_a_total", "x", labels=("k",))
        B = telemetry.gauge("sd_b")
        NOT_A_METRIC = other.thing("sd_not_minted_here")
        """,
        ["sd_a_total", "sd_b"],
        monkeypatch,
    )
    assert findings == []


def test_sd020_missing_catalog_flags_once(tmp_path, monkeypatch):
    monkeypatch.setenv(
        "SDLINT_TELEMETRY_CATALOG", str(tmp_path / "nonexistent.md"))
    findings = run_on(
        tmp_path,
        """
        from .registry import REGISTRY

        A = REGISTRY.counter("sd_a_total", "x")
        B = REGISTRY.counter("sd_b_total", "x")
        """,
        ["SD020"],
    )
    assert len(findings) == 1
    assert "missing" in findings[0].message


def test_sd020_tree_without_metrics_needs_no_catalog(tmp_path, monkeypatch):
    monkeypatch.setenv(
        "SDLINT_TELEMETRY_CATALOG", str(tmp_path / "nonexistent.md"))
    findings = run_on(
        tmp_path,
        """
        def plain():
            return 1
        """,
        ["SD020"],
    )
    assert findings == []

# --- SD021 env-knob-catalog-drift -------------------------------------------


def _knob_catalog(tmp_path, rows):
    """rows: list of (knob, scope) tuples."""
    doc = tmp_path / "knobs.md"
    lines = ["# Knobs", "", "| knob | scope | default | effect |",
             "|---|---|---|---|"]
    lines += [f"| `{name}` | {scope} | `1` | fixture |"
              for name, scope in rows]
    doc.write_text("\n".join(lines) + "\n")
    return doc


def run_sd021(tmp_path, source, rows, monkeypatch):
    doc = _knob_catalog(tmp_path, rows)
    monkeypatch.setenv("SDLINT_KNOB_CATALOG", str(doc))
    return run_on(tmp_path, source, ["SD021"])


def test_sd021_read_knob_without_catalog_row(tmp_path, monkeypatch):
    findings = run_sd021(
        tmp_path,
        """
        import os

        CATALOGED = os.environ.get("SD_CATALOGED", "1")
        ORPHANED = os.environ.get("SD_ORPHANED")
        """,
        [("SD_CATALOGED", "core")],
        monkeypatch,
    )
    assert rules_of(findings) == ["SD021"]
    assert len(findings) == 1
    assert "SD_ORPHANED" in findings[0].message
    assert findings[0].path.endswith("fixture.py")


def test_sd021_stale_row_flagged_script_row_exempt(tmp_path, monkeypatch):
    findings = run_sd021(
        tmp_path,
        """
        import os

        LIVE = os.getenv("SD_LIVE")
        """,
        [("SD_LIVE", "core"), ("SD_GONE", "core"),
         ("SD_BENCH_ONLY", "script")],
        monkeypatch,
    )
    assert len(findings) == 1
    assert "SD_GONE" in findings[0].message
    assert findings[0].path.endswith("knobs.md")
    assert findings[0].line > 0


def test_sd021_all_read_idioms_and_const_indirection(tmp_path, monkeypatch):
    findings = run_sd021(
        tmp_path,
        """
        import os
        from os import environ

        ENV_VAR = "SD_CONSTANT"

        A = os.environ["SD_SUBSCRIPT"]
        B = "SD_MEMBERSHIP" in os.environ
        C = environ.setdefault("SD_SETDEFAULT", "x")
        D = os.environ.get(ENV_VAR)
        """,
        [("SD_SUBSCRIPT", "core"), ("SD_MEMBERSHIP", "core"),
         ("SD_SETDEFAULT", "core"), ("SD_CONSTANT", "core")],
        monkeypatch,
    )
    assert findings == []


def test_sd021_missing_catalog_flags_once(tmp_path, monkeypatch):
    monkeypatch.setenv(
        "SDLINT_KNOB_CATALOG", str(tmp_path / "nonexistent.md"))
    findings = run_on(
        tmp_path,
        """
        import os

        A = os.environ.get("SD_A")
        B = os.environ.get("SD_B")
        """,
        ["SD021"],
    )
    assert len(findings) == 1
    assert "missing" in findings[0].message


def test_sd021_tree_reading_no_knobs_needs_no_catalog(tmp_path, monkeypatch):
    monkeypatch.setenv(
        "SDLINT_KNOB_CATALOG", str(tmp_path / "nonexistent.md"))
    findings = run_on(
        tmp_path,
        """
        import os

        HOME = os.environ.get("HOME")  # not an SD_* knob
        """,
        ["SD021"],
    )
    assert findings == []


# --- SD022 process-boundary-purity -----------------------------------------


def test_sd022_flags_rich_objects_in_pool_payloads(tmp_path):
    findings = run_on(
        tmp_path,
        """
        from spacedrive_tpu.parallel import procpool as _procpool

        def ship(self, library, entries):
            pool = _procpool.get()
            pool.submit("identify.hash_entries",
                        {"db": self.db, "entries": entries})
            pool.request("link.prep", {"library": library})
            _procpool.POOL.run("thumb.cpu", {"cb": lambda p: p})
        """,
        ["SD022"],
    )
    assert len(findings) == 3
    assert rules_of(findings) == ["SD022"]
    assert any("`db`" in f.message for f in findings)
    assert any("`library`" in f.message for f in findings)
    assert any("`lambda`" in f.message for f in findings)


def test_sd022_follows_payload_dict_assignment(tmp_path):
    findings = run_on(
        tmp_path,
        """
        from spacedrive_tpu.parallel import procpool as _procpool

        def ship(self, loc_path, entries):
            payload = {"loc_path": loc_path, "conn": self._conn}
            pool = _procpool.get()
            pool.submit("identify.hash_entries", payload, rows=len(entries))
        """,
        ["SD022"],
    )
    assert len(findings) == 1
    assert "_conn" in findings[0].message


def test_sd022_silent_on_plain_payloads_and_foreign_submits(tmp_path):
    findings = run_on(
        tmp_path,
        """
        from spacedrive_tpu.parallel import procpool as _procpool

        def ship(loc_path, wire_items, wire_rows, executor, inode):
            pool = _procpool.get()
            payload = {"loc_path": loc_path, "items": wire_items}
            pool.submit("journal.match", payload, rows=len(wire_items))
            pool.request("identify.hash_entries",
                         {"rows": wire_rows, "inode": inode})
            # a NON-pool submit (thread executor) is out of scope
            executor.submit(lambda: None)
        """,
        ["SD022"],
    )
    assert findings == []


def test_sd022_covers_embed_decode_leg(tmp_path):
    # ISSUE 16: the embed stage ships decode work to the pool exactly
    # like identify/thumb — the same purity bar applies to its payload
    findings = run_on(
        tmp_path,
        """
        from spacedrive_tpu.parallel import procpool as _procpool

        def decode(self, paths):
            pool = _procpool.get()
            pool.request("embed.decode",
                         {"paths": paths, "lib": self.library})
        """,
        ["SD022"],
    )
    assert len(findings) == 1
    assert "library" in findings[0].message
    # the real leg's plain payload ({"paths": [...]}) stays silent
    assert run_on(
        tmp_path,
        """
        from spacedrive_tpu.parallel import procpool as _procpool

        def decode(paths):
            pool = _procpool.get()
            pool.request("embed.decode", {"paths": list(paths)},
                         rows=len(paths))
        """,
        ["SD022"],
    ) == []


# --- SD023 cross-context-race ----------------------------------------------


def test_sd023_flags_history_tail_deque_race(tmp_path):
    """The PR 12 bug class: the sampler thread appends to a deque that
    the loop snapshots with no common lock — the exact history-tail
    race the rule exists to catch."""
    findings = run_on(
        tmp_path,
        """
        import threading
        from collections import deque

        class Sampler:
            def __init__(self):
                self._hist = deque(maxlen=512)
                self._thread = None

            def start(self):
                self._thread = threading.Thread(
                    target=self._run, name="sd-profiler-1", daemon=True,
                )
                self._thread.start()

            def _run(self):
                while True:
                    self._hist.append(1)

        SAMPLER = Sampler()

        async def snapshot():
            return list(SAMPLER._hist)
        """,
        ["SD023"],
    )
    assert rules_of(findings) == ["SD023"]
    msgs = " ".join(f.message for f in findings)
    assert "_hist" in msgs and "sampler" in msgs and "loop" in msgs


def test_sd023_silent_on_sanctioned_seams(tmp_path):
    """Queue hand-off, a common lock, contextvars, and the process
    boundary are the sanctioned ways across contexts — none may fire."""
    findings = run_on(
        tmp_path,
        """
        import contextvars
        import queue
        import threading

        # seam 1: queue hand-off
        class Pump:
            def __init__(self):
                self._q = queue.Queue()

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                self._q.put(1)

        PUMP = Pump()

        async def drain():
            return PUMP._q.get()

        # seam 2: one lock guards both sides
        class Registry:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = {}

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                with self._lock:
                    self._items["x"] = 1

            def snapshot(self):
                with self._lock:
                    return dict(self._items)

        REG = Registry()

        async def read_items():
            return REG.snapshot()

        # seam 3: contextvars
        _current = contextvars.ContextVar("cur")

        def set_worker():
            _current.set("worker")

        def spawn_tracer():
            threading.Thread(target=set_worker, daemon=True).start()

        async def who():
            return _current.get()

        # seam 4: the process boundary (msgpack'd payloads, no shared
        # address space) — a STAGES handler writing a worker-local
        # global does not race loop-side readers of the host's copy
        _CACHE = {}

        def match(payload):
            _CACHE[payload["k"]] = payload
            return payload

        STAGES = {"journal.match": match}

        async def peek(k):
            return _CACHE.get(k)
        """,
        ["SD023"],
    )
    assert findings == []


def test_sd023_init_and_single_context_state_silent(tmp_path):
    """Pre-publication writes in __init__ and state only ever touched
    from one context must not pair."""
    findings = run_on(
        tmp_path,
        """
        import threading

        class Worker:
            def __init__(self):
                self.tally = 0  # pre-publication write

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                self.tally += 1  # only the helper thread ever touches it

        W = Worker()
        """,
        ["SD023"],
    )
    assert findings == []


# --- SD024 loop-affinity-violation ------------------------------------------


def test_sd024_flags_loop_calls_from_thread(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import asyncio
        import threading

        class Notifier:
            def __init__(self, loop):
                self.loop = loop

            def start(self):
                threading.Thread(target=self._watch, daemon=True).start()

            def _watch(self):
                self.loop.call_soon(print)
                asyncio.create_task(noop())

        async def noop():
            pass
        """,
        ["SD024"],
    )
    assert len(findings) == 2
    assert all("thread" in f.message for f in findings)
    assert "call_soon_threadsafe" in findings[0].message


def test_sd024_silent_on_threadsafe_entry_points_and_loop_context(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import asyncio
        import threading

        class Notifier:
            def __init__(self, loop):
                self.loop = loop

            def start(self):
                threading.Thread(target=self._watch, daemon=True).start()

            def _watch(self):
                # the threadsafe entry points exist for exactly this
                self.loop.call_soon_threadsafe(print)
                asyncio.run_coroutine_threadsafe(noop(), self.loop)

        async def noop():
            # loop context may drive the loop machinery freely
            asyncio.get_event_loop().call_soon(print)

        async def kick():
            t = asyncio.create_task(noop())
            await t
        """,
        ["SD024"],
    )
    assert findings == []


# --- SD025 post-submit-aliasing ---------------------------------------------


def test_sd025_flags_mutation_after_pool_submit_and_queue_put(tmp_path):
    findings = run_on(
        tmp_path,
        """
        from spacedrive_tpu.parallel import procpool as _procpool

        def ship(rows, q):
            payload = {"rows": rows}
            pool = _procpool.get()
            pool.submit("identify.hash", payload, rows=len(rows))
            payload["rows"] = []          # races the worker's view

            batch = [1, 2]
            q.put(batch)
            batch.append(3)               # races the consumer's view
        """,
        ["SD025"],
    )
    assert len(findings) == 2
    assert "payload" in findings[0].message
    assert "batch" in findings[1].message


def test_sd025_silent_on_rebind_and_pre_submit_mutation(tmp_path):
    findings = run_on(
        tmp_path,
        """
        from spacedrive_tpu.parallel import procpool as _procpool

        def ship(rows, q):
            payload = {"rows": rows}
            payload["extra"] = 1          # before the hand-off: fine
            pool = _procpool.get()
            pool.submit("identify.hash", payload, rows=len(rows))
            payload = {"rows": []}        # rebind severs the alias
            payload["rows"] = rows

            batch = [1, 2]
            q.put(list(batch))            # defensive copy shipped
            batch.append(3)
        """,
        ["SD025"],
    )
    assert findings == []


# --- SD026 hot-thread-blocking ----------------------------------------------


def test_sd026_flags_unbounded_blocking_on_hot_threads(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import subprocess
        import threading

        class Pipe:
            def __init__(self):
                self._evt = threading.Event()
                self._thread = threading.Thread(
                    target=self._run, name="sd-window-pipeline",
                    daemon=True,
                )

            def _run(self):
                self._evt.wait()
                subprocess.run(["sync"])
        """,
        ["SD026"],
    )
    assert len(findings) == 2
    assert "feeder" in findings[0].message
    assert "starves the device" in findings[0].message


def test_sd026_silent_on_bounded_waits_and_cold_threads(tmp_path):
    findings = run_on(
        tmp_path,
        """
        import subprocess
        import threading

        class Pipe:
            def __init__(self):
                self._evt = threading.Event()
                self._thread = threading.Thread(
                    target=self._run, name="sd-window-pipeline",
                    daemon=True,
                )

            def _run(self):
                self._evt.wait(0.5)
                subprocess.run(["sync"], timeout=5)

        class Background:
            def start(self):
                threading.Thread(target=self._run, name="helper",
                                 daemon=True).start()

            def _run(self):
                # a plain helper thread may block; only the sampler and
                # feeder hot loops are cadence-critical
                threading.Event().wait()
        """,
        ["SD026"],
    )
    assert findings == []
