"""Sharded on-disk thumbnail storage with versioned directory layout.

Parity: ref:core/src/object/media/thumbnail/{shard.rs,directory.rs,
clean_up.rs} — thumbs live at
`<data>/thumbnails/<library_id | ephemeral>/<cas_id[0..3]>/<cas_id>.webp`
(actor.rs:53-62, shard.rs:10), the directory carries a version file
migrated by the same version-manager pattern as configs (directory.rs),
and cleanup removes shards/files whose cas_ids no longer exist in the
library DB (clean_up.rs).
"""

from __future__ import annotations

import logging
import os
import shutil
import threading

from ....utils.version_manager import VersionManager

logger = logging.getLogger(__name__)

THUMBNAIL_DIR_VERSION = 1
_VERSION_FILE = "version.txt"
EPHEMERAL_DIR = "ephemeral"

_dir_vm = VersionManager(THUMBNAIL_DIR_VERSION)


def get_shard_hex(cas_id: str) -> str:
    """First 3 hex chars → up to 4096 shard dirs (ref:shard.rs:10)."""
    return cas_id[:3]


class ThumbnailStore:
    """The `thumbnails/` tree under a node's data dir."""

    def __init__(self, data_dir: str | os.PathLike):
        self.root = os.path.join(os.fspath(data_dir), "thumbnails")
        os.makedirs(self.root, exist_ok=True)
        self._migrate_directory()

    def _migrate_directory(self) -> None:
        """Versioned layout migration (ref:directory.rs)."""
        vfile = os.path.join(self.root, _VERSION_FILE)
        try:
            with open(vfile) as f:
                version = int(f.read().strip() or 0)
        except (OSError, ValueError):
            version = 0
        if version != THUMBNAIL_DIR_VERSION:
            # v0 → v1: flat files move into shard dirs
            for name in os.listdir(self.root):
                if name.endswith(".webp") and os.path.isfile(
                    os.path.join(self.root, name)
                ):
                    cas = name[: -len(".webp")]
                    dst = os.path.join(self.root, EPHEMERAL_DIR, get_shard_hex(cas))
                    os.makedirs(dst, exist_ok=True)
                    os.replace(
                        os.path.join(self.root, name), os.path.join(dst, name)
                    )
            with open(vfile, "w") as f:
                f.write(str(THUMBNAIL_DIR_VERSION))

    def namespace(self, library_id) -> str:
        """Namespace dir: stringified library id (UUIDs welcome) or the
        ephemeral dir (ref:actor.rs:53-62)."""
        return str(library_id) if library_id is not None else EPHEMERAL_DIR

    def path_for(self, library_id: str | None, cas_id: str) -> str:
        return os.path.join(
            self.root, self.namespace(library_id), get_shard_hex(cas_id),
            f"{cas_id}.webp",
        )

    def exists(self, library_id: str | None, cas_id: str) -> bool:
        return os.path.exists(self.path_for(library_id, cas_id))

    def write(self, library_id: str | None, cas_id: str, webp: bytes) -> str:
        path = self.path_for(library_id, cas_id)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # one tmp per writer: a double-leased shard has two threads
        # publishing the same cas_id, and a shared tmp name lets the
        # first replace() pull the file from under the second
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        with open(tmp, "wb") as f:
            f.write(webp)
        os.replace(tmp, path)  # atomic publish
        return path

    def remove(self, library_id: str | None, cas_ids: list[str]) -> int:
        """Delete thumbs by cas_id (ref:actor.rs delete channel)."""
        n = 0
        for cas in cas_ids:
            try:
                os.remove(self.path_for(library_id, cas))
                n += 1
            except OSError:
                pass
        return n

    def remove_library(self, library_id: str) -> None:
        shutil.rmtree(os.path.join(self.root, library_id), ignore_errors=True)

    def cleanup(self, library_id: str, live_cas_ids: set[str]) -> int:
        """Remove thumbs whose cas_id is no longer referenced
        (ref:clean_up.rs process_clean_up)."""
        base = os.path.join(self.root, library_id)
        removed = 0
        if not os.path.isdir(base):
            return 0
        for shard in os.listdir(base):
            sdir = os.path.join(base, shard)
            if not os.path.isdir(sdir):
                continue
            for name in os.listdir(sdir):
                if name.endswith(".webp") and name[: -len(".webp")] not in live_cas_ids:
                    try:
                        os.remove(os.path.join(sdir, name))
                        removed += 1
                    except OSError:
                        pass
            try:
                os.rmdir(sdir)  # only succeeds when empty
            except OSError:
                pass
        return removed
