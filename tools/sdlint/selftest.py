"""``python -m tools.sdlint --selftest`` — prove every rule still fires.

Before the cold whole-tree pass, ``make lint`` runs each registered
rule over a minimal positive fixture: the smallest program that must
trip it. A rule that stops firing on its own fixture is dead weight —
its checks silently stopped protecting the tree (an engine refactor
that loses an edge kind, a scope pattern that no longer matches the
repo layout) — and this catches that in the same command that trusts
the rules, not in a test tier someone has to remember to run.

The corpus is the *floor*, not the spec: tests/test_sdlint.py carries
the full positive/negative fixture matrix per rule. Every entry in
:data:`CORPUS` runs as its own scoped analysis (``--rules`` with just
that id) over a throwaway tree, so path-scoped rules get repo-shaped
relative paths and catalog rules get their lookup env pinned inside
the sandbox. Registering a rule without adding a corpus entry fails
the selftest by construction.
"""

from __future__ import annotations

import os
import sys
import tempfile
import textwrap
from pathlib import Path

from .core import RULES, analyze_paths

#: rule id -> {"files": {relpath: source}, "env": {VAR: relpath}}.
#: Each source must make the rule fire at least once; "env" values are
#: joined to the sandbox root (the catalog rules report a *missing*
#: catalog as a finding, which is the minimal positive for them).
CORPUS: dict[str, dict] = {
    "SD001": {"files": {"pkg/mod.py": """
        import time

        async def pump():
            time.sleep(1)
    """}},
    "SD002": {"files": {"pkg/mod.py": """
        import asyncio
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            async def bad(self):
                with self._lock:
                    await asyncio.sleep(0)
    """}},
    "SD003": {"files": {"pkg/mod.py": """
        import asyncio

        def kick(coro):
            asyncio.create_task(coro())
    """}},
    "SD004": {"files": {"pkg/mod.py": """
        import threading

        _a = threading.Lock()
        _b = threading.Lock()

        def path1():
            with _a:
                with _b:
                    pass

        def path2():
            with _b:
                with _a:
                    pass
    """}},
    "SD005": {"files": {"pkg/mod.py": """
        import jax

        @jax.jit
        def f(x):
            y = x + 1
            y.block_until_ready()
            return y
    """}},
    "SD006": {"files": {"pkg/mod.py": """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """}},
    "SD007": {"files": {"pkg/mod.py": """
        def record(path, FILES):
            FILES.inc(result=f"error:{path}")
    """}},
    "SD008": {"files": {"pkg/mod.py": """
        def transfer(lock, work):
            lock.acquire()
            work()
            lock.release()
    """}},
    "SD009": {"files": {"pkg/mod.py": """
        def record(kind, P2P_EVENTS):
            P2P_EVENTS.emit(kind)
    """}},
    "SD010": {"files": {"pkg/mod.py": """
        def record(op, SYNC_LAG):
            SYNC_LAG.set(1.0, peer=str(op.instance))
    """}},
    "SD027": {"files": {"pkg/mod.py": """
        def record(op, TENANT_OPS):
            TENANT_OPS.inc(tenant=str(op.library_id))
    """}},
    "SD011": {"files": {"pkg/mod.py": """
        async def hammer(client):
            while True:
                try:
                    return await client.fetch()
                except Exception:
                    continue
    """}},
    "SD012": {"files": {"spacedrive_tpu/location/indexer/helper.py": """
        import os

        def sizes(paths):
            return [os.stat(p).st_size for p in paths]
    """}},
    "SD013": {"files": {"spacedrive_tpu/parallel/feeder.py": """
        DEVICE_BATCH = 32
    """}},
    "SD014": {"files": {"pkg/mod.py": """
        from spacedrive_tpu.p2p.operations import ping

        async def raw_pull(p2p, peer):
            return await ping(p2p, peer.identity)
    """}},
    "SD015": {"files": {
        "spacedrive_tpu/serve/policy.py": """
            NAMESPACE_CLASSES: dict[str, str] = {
                "files": "interactive",
            }
        """,
        "spacedrive_tpu/api/mod.py": """
            from aiohttp import web

            def routes(self):
                return [web.get("/bare", self._bare)]
        """,
    }},
    "SD016": {"files": {"pkg/mod.py": """
        async def fetch(self):
            await self._slots.acquire()
            data = await self._pull()
            self._slots.release()
            return data
    """}},
    "SD017": {"files": {"pkg/mod.py": """
        def persist(db, journal, entry):
            journal.record(entry.key, entry.cas)
            with db.transaction() as conn:
                conn.execute("INSERT INTO t VALUES (?)", (entry.cas,))
    """}},
    "SD018": {"files": {"pkg/mod.py": """
        from dataclasses import dataclass

        @dataclass(frozen=True)
        class Op:
            ts: int

        def guard(op: Op, reason: str):
            op.reject_reason = reason
    """}},
    "SD019": {"files": {"pkg/mod.py": """
        POLICY = ResiliencePolicy("selftest")
    """}},
    "SD020": {
        "files": {"pkg/mod.py": """
            from .registry import REGISTRY

            ORPHANED = REGISTRY.counter("sd_selftest_total", "orphan")
        """},
        "env": {"SDLINT_TELEMETRY_CATALOG": "nonexistent.md"},
    },
    "SD021": {
        "files": {"pkg/mod.py": """
            import os

            ORPHANED = os.environ.get("SD_SELFTEST_ORPHAN")
        """},
        "env": {"SDLINT_KNOB_CATALOG": "nonexistent.md"},
    },
    "SD023": {"files": {"pkg/mod.py": """
        import threading
        from collections import deque

        class Sampler:
            def __init__(self):
                self._hist = deque(maxlen=512)

            def start(self):
                threading.Thread(
                    target=self._run, name="sd-profiler-1", daemon=True,
                ).start()

            def _run(self):
                while True:
                    self._hist.append(1)

        SAMPLER = Sampler()

        async def snapshot():
            return list(SAMPLER._hist)
    """}},
    "SD024": {"files": {"pkg/mod.py": """
        import threading

        class Notifier:
            def __init__(self, loop):
                self.loop = loop

            def start(self):
                threading.Thread(target=self._watch, daemon=True).start()

            def _watch(self):
                self.loop.call_soon(print)
    """}},
    "SD025": {"files": {"pkg/mod.py": """
        def ship(rows, q):
            payload = {"rows": rows}
            q.put(payload)
            payload["rows"] = []
    """}},
    "SD026": {"files": {"pkg/mod.py": """
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
    """}},
}


def _check_rule(rid: str, spec: dict) -> str | None:
    """Run one rule over its fixture tree; None on pass, else why."""
    with tempfile.TemporaryDirectory(prefix="sdlint-selftest-") as tmp:
        root = Path(tmp)
        for rel, source in spec["files"].items():
            f = root / rel
            f.parent.mkdir(parents=True, exist_ok=True)
            f.write_text(textwrap.dedent(source))
        saved = {}
        for var, rel in spec.get("env", {}).items():
            saved[var] = os.environ.get(var)
            os.environ[var] = str(root / rel)
        try:
            findings, errors = analyze_paths([root], [rid])
        finally:
            for var, old in saved.items():
                if old is None:
                    os.environ.pop(var, None)
                else:
                    os.environ[var] = old
    if errors:
        return f"fixture failed to parse: {errors}"
    if not findings:
        return "rule did not fire on its positive fixture"
    wrong = sorted({f.rule for f in findings} - {rid})
    if wrong:
        return f"fixture tripped other rules: {', '.join(wrong)}"
    return None


def run_selftest() -> int:
    from . import rules as _rules  # noqa: F401 - populate RULES

    failures: list[str] = []
    for rid in sorted(set(RULES) | set(CORPUS)):
        if rid not in CORPUS:
            failures.append(f"{rid}: registered rule has no selftest "
                            f"fixture — add one to selftest.CORPUS")
            continue
        if rid not in RULES:
            failures.append(f"{rid}: corpus entry for an unregistered "
                            f"rule — delete it or restore the rule")
            continue
        why = _check_rule(rid, CORPUS[rid])
        if why is not None:
            failures.append(f"{rid}: {why}")
    if failures:
        for line in failures:
            print(f"selftest FAIL {line}", file=sys.stderr)
        print(f"sdlint selftest: {len(failures)} of "
              f"{len(set(RULES) | set(CORPUS))} rules failing",
              file=sys.stderr)
        return 1
    print(f"sdlint selftest: all {len(RULES)} rules fire on their "
          f"fixtures")
    return 0
