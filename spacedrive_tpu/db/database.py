"""LibraryDb — thread-safe SQLite access for one library.

The reference connects one SQLite file per library through a typed
Prisma client (ref:core/src/library/manager/mod.rs library load). Here:
WAL-mode sqlite3 with a single writer lock, dict rows, tiny typed
helpers (insert/update/upsert), and explicit transactions — everything
the job/sync layers need, with no ORM in the way.
"""

from __future__ import annotations

import contextlib
import datetime as _dt
import os
import sqlite3
import threading
import time
import uuid
from typing import Any, Iterable, Iterator, Sequence

from ..telemetry import metrics as _tm
from ..telemetry import span
from .schema import MIGRATIONS


def dict_row(cursor: sqlite3.Cursor, row: tuple) -> dict[str, Any]:
    return {d[0]: row[i] for i, d in enumerate(cursor.description)}


def now_iso() -> str:
    return _dt.datetime.now(_dt.timezone.utc).isoformat(timespec="milliseconds")


def new_pub_id() -> bytes:
    """16-byte UUIDv4 — the sync identity of shared rows."""
    return uuid.uuid4().bytes


def escape_like(s: str) -> str:
    r"""Escape LIKE wildcards in user-derived path fragments; pair with
    ``LIKE ? ESCAPE '\'`` so a directory named ``50% off`` can't match
    unrelated rows."""
    return s.replace("\\", "\\\\").replace("%", "\\%").replace("_", "\\_")


def u64_blob(value: int) -> bytes:
    """u64 -> 8-byte LE BLOB (inode / size columns; SQLite lacks u64,
    same workaround as ref:core/prisma/schema.prisma:164)."""
    return int(value).to_bytes(8, "little")


def blob_u64(blob: bytes | None) -> int | None:
    return None if blob is None else int.from_bytes(blob, "little")


class LibraryDb:
    """One library database. All writes hold the writer lock; reads use
    the same connection (SQLite serializes internally under WAL)."""

    def __init__(self, path: str | os.PathLike | None, *, memory: bool = False):
        self.path = ":memory:" if memory or path is None else os.fspath(path)
        if self.path != ":memory:":
            os.makedirs(os.path.dirname(os.path.abspath(self.path)) or ".", exist_ok=True)
        self._conn = sqlite3.connect(self.path, check_same_thread=False)
        self._conn.row_factory = dict_row
        self._lock = threading.RLock()
        # guarded by _lock: only the outermost committing call is timed
        self._in_txn, self._txn_wrote = False, False
        self._commit_s = 0.0  # of the open outermost block, nested ones' too
        # reads since the last flush into sd_db_reads_total / _read_seconds
        self._reads, self._read_s = 0, 0.0
        with self._lock:
            self._conn.execute("PRAGMA journal_mode=WAL")
            self._conn.execute("PRAGMA foreign_keys=ON")
            self._conn.execute("PRAGMA synchronous=NORMAL")
            self._migrate()

    # --- lifecycle -----------------------------------------------------------

    def _migrate(self) -> None:
        version = self._conn.execute("PRAGMA user_version").fetchone()["user_version"]
        if version >= len(MIGRATIONS):
            return
        with span("db.migrate"):  # a fresh library builds its whole schema here
            while version < len(MIGRATIONS):
                with self._conn:
                    for stmt in MIGRATIONS[version]:
                        self._conn.execute(stmt)
                    version += 1
                    self._conn.execute(f"PRAGMA user_version={version}")

    def close(self) -> None:
        with self._lock:
            self._flush_reads()
            self._conn.close()

    # --- core access ---------------------------------------------------------

    @contextlib.contextmanager
    def transaction(self) -> Iterator[sqlite3.Connection]:
        """Exclusive write transaction (the sync layer's atomicity
        guarantee: domain rows + crdt_operation rows in one tx,
        ref:core/crates/sync/src/manager.rs:70-93), and the one door
        every commit goes through: a `db.txn` span under whatever span
        encloses it (so the profile shows which stage waited for
        SQLite), its `COMMIT` alone a child span `commit`, and, if
        anything was written, one observation each of
        `sd_db_txn_seconds` (the block) and `sd_db_commit_seconds` (the
        commits in it) and the rows changed on `sd_db_changes_total`.
        One per commit, never one per row; a block nested inside
        another's rides the outer one's span. Either block ends as
        sqlite3's own connection block does: commit on a clean exit,
        rollback on an exception."""
        with self._lock:
            if self._in_txn:
                # as sqlite3's connection block: the inner one commits
                # what the outer has written so far
                try:
                    yield self._conn
                    self._txn_wrote |= self._conn.in_transaction
                except BaseException:
                    self._conn.rollback()
                    raise
                self._commit_s += self._commit()
                return
            self._in_txn, self._txn_wrote, self._commit_s = True, False, 0.0
            changes = self._conn.total_changes
            try:
                with span("db.txn") as txn:
                    try:
                        yield self._conn
                        self._txn_wrote |= self._conn.in_transaction
                    except BaseException:
                        self._conn.rollback()
                        raise
                    with span("commit"):
                        self._commit_s += self._commit()
            finally:
                self._in_txn = False
                self._flush_reads()
            if self._txn_wrote:
                _tm.DB_TXN_SECONDS.observe(txn.duration)
                _tm.DB_COMMIT_SECONDS.observe(self._commit_s)
                _tm.DB_CHANGES.inc(self._conn.total_changes - changes)

    def _commit(self) -> float:
        """`COMMIT`, and the seconds it took. A commit that fails rolls
        back, so that the database is not left locked."""
        t0 = time.perf_counter()
        try:
            self._conn.commit()
        except BaseException:
            self._conn.rollback()
            raise
        return time.perf_counter() - t0

    def _flush_reads(self) -> None:
        """Hands the reads counted on the connection to the registry: two
        calls a transaction, not two a read. Under `_lock`."""
        if self._reads:
            _tm.DB_READS.inc(self._reads)
            _tm.DB_READ_SECONDS.inc(self._read_s)
            self._reads, self._read_s = 0, 0.0

    def execute(self, sql: str, params: Sequence | dict = ()) -> sqlite3.Cursor:
        with self.transaction() as conn:
            return conn.execute(sql, params)

    def executemany(self, sql: str, seq: Iterable[Sequence]) -> None:
        with self.transaction() as conn:
            conn.executemany(sql, seq)

    @staticmethod
    def _maybe_slow() -> None:
        """`db.slow` fault point: one `is None` check in production; an
        armed `stall` spec sleeps delay_s per read — the deterministic
        stand-in for a slow/contended disk that the serve layer's
        overload chaos tests put under the whole read surface."""
        from ..utils import faults as _faults

        spec = _faults.hit("db.slow")
        if spec is not None:
            time.sleep(spec.delay_s)

    def query(self, sql: str, params: Sequence | dict = ()) -> list[dict[str, Any]]:
        self._maybe_slow()
        with self._lock:
            t0 = time.perf_counter()
            try:
                return self._conn.execute(sql, params).fetchall()
            finally:
                self._reads += 1
                self._read_s += time.perf_counter() - t0

    def query_one(self, sql: str, params: Sequence | dict = ()) -> dict[str, Any] | None:
        self._maybe_slow()
        with self._lock:
            t0 = time.perf_counter()
            try:
                return self._conn.execute(sql, params).fetchone()
            finally:
                self._reads += 1
                self._read_s += time.perf_counter() - t0

    # --- typed helpers -------------------------------------------------------

    @staticmethod
    def _quote(col: str) -> str:
        return f'"{col}"'

    def insert(self, table: str, **cols: Any) -> int:
        names = ", ".join(self._quote(c) for c in cols)
        ph = ", ".join("?" for _ in cols)
        cur = self.execute(
            f"INSERT INTO {table} ({names}) VALUES ({ph})", tuple(cols.values())
        )
        return cur.lastrowid

    def insert_many(self, table: str, columns: Sequence[str], rows: Iterable[Sequence]) -> None:
        names = ", ".join(self._quote(c) for c in columns)
        ph = ", ".join("?" for _ in columns)
        self.executemany(f"INSERT INTO {table} ({names}) VALUES ({ph})", rows)

    def update(self, table: str, where: dict[str, Any], **cols: Any) -> int:
        sets = ", ".join(f"{self._quote(c)}=?" for c in cols)
        conds = " AND ".join(f"{self._quote(c)}=?" for c in where)
        cur = self.execute(
            f"UPDATE {table} SET {sets} WHERE {conds}",
            tuple(cols.values()) + tuple(where.values()),
        )
        return cur.rowcount

    def upsert(self, table: str, key_cols: dict[str, Any], **cols: Any) -> None:
        all_cols = {**key_cols, **cols}
        names = ", ".join(self._quote(c) for c in all_cols)
        ph = ", ".join("?" for _ in all_cols)
        keys = ", ".join(self._quote(c) for c in key_cols)
        sets = ", ".join(f"{self._quote(c)}=excluded.{self._quote(c)}" for c in cols) or \
            f"{next(iter(key_cols))}={next(iter(key_cols))}"
        self.execute(
            f"INSERT INTO {table} ({names}) VALUES ({ph}) "
            f"ON CONFLICT ({keys}) DO UPDATE SET {sets}",
            tuple(all_cols.values()),
        )

    def delete(self, table: str, **where: Any) -> int:
        conds = " AND ".join(f"{self._quote(c)}=?" for c in where)
        cur = self.execute(f"DELETE FROM {table} WHERE {conds}", tuple(where.values()))
        return cur.rowcount

    def find(self, table: str, **where: Any) -> list[dict[str, Any]]:
        if not where:
            return self.query(f"SELECT * FROM {table}")
        conds = " AND ".join(f"{self._quote(c)}=?" for c in where)
        return self.query(f"SELECT * FROM {table} WHERE {conds}", tuple(where.values()))

    def find_one(self, table: str, **where: Any) -> dict[str, Any] | None:
        rows = self.find(table, **where)
        return rows[0] if rows else None

    def count(self, table: str, where_sql: str = "", params: Sequence = ()) -> int:
        sql = f"SELECT COUNT(*) AS n FROM {table}"
        if where_sql:
            sql += f" WHERE {where_sql}"
        return self.query_one(sql, params)["n"]
