"""Sorted-run persistence: save/load runs as codec files.

Reference analog: SSTable files on disk (block_based_table_builder.cc) +
MANIFEST tracking. Both engines persist the same logical content (key ->
MVCC versions); the TPU engine rebuilds its columnar planes from it at load
time. Columnar plane snapshots (zero-rebuild load) come later; this format
is the durable source of truth either way.

File format: codec.encode of
  ["run1", [ [key, [ [ht, tombstone, liveness, {col: val}, expire_ht], ...ht-desc ], ...key-asc ] ]
"""

from __future__ import annotations

import json
import os
import threading

from yugabyte_db_tpu.utils import codec
from yugabyte_db_tpu.storage.row_version import RowVersion

_MAGIC = "run1"


def save_run(path: str, entries: list[tuple[bytes, list[RowVersion]]]) -> None:
    payload = [
        [key, [[v.ht, v.tombstone, v.liveness,
                {str(c): val for c, val in v.columns.items()}, v.expire_ht,
                v.write_id]
               for v in versions]]
        for key, versions in entries
    ]
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(codec.encode([_MAGIC, payload]))
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


MANIFEST = "MANIFEST.json"


class RunPersistence:
    """Tracks a directory of numbered run files for one engine instance.
    ``None`` data_dir = in-memory engine (tests, caches).

    ``files`` lists the live runs' paths in AGE order, oldest first, and
    is what ``MANIFEST.json`` holds once the set has changed under this
    class (a directory without one lists by name, which is by age as
    long as nothing was merged in the middle). A run file that the
    manifest does not name is the remains of a crash, before a publish
    (a new file) or after it (a replaced one), and is removed at open:
    whatever the last published manifest names is read, each version
    once."""

    def __init__(self, data_dir: str | None):
        self.data_dir = data_dir
        self._seq = 0
        self._seq_lock = threading.Lock()
        self.files: list[str] = []
        if data_dir:
            os.makedirs(data_dir, exist_ok=True)
            names = sorted(n for n in os.listdir(data_dir)
                           if n.startswith("run-") and n.endswith(".dat"))
            if names:
                self._seq = max(int(n[4:-4]) for n in names) + 1
            live = self._read_manifest()
            if live is not None:
                for n in set(names) - set(live):
                    os.unlink(os.path.join(data_dir, n))
                names = [n for n in live if n in names]
            self.files = [os.path.join(data_dir, n) for n in names]

    @property
    def enabled(self) -> bool:
        return self.data_dir is not None

    def _read_manifest(self) -> list[str] | None:
        try:
            with open(os.path.join(self.data_dir, MANIFEST)) as f:
                return json.load(f)["runs"]
        except FileNotFoundError:
            return None

    def _publish(self, files: list[str]) -> None:
        """The one commit point of a change to the run set."""
        path = os.path.join(self.data_dir, MANIFEST)
        with open(path + ".tmp", "w") as f:
            json.dump({"runs": [os.path.basename(p) for p in files]}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(path + ".tmp", path)
        self.files = files

    def load_all(self):
        return [load_run(p) for p in self.files]

    def write_run(self, entries) -> str:
        """A new run file, durable and not yet part of the set."""
        with self._seq_lock:
            path = os.path.join(self.data_dir, f"run-{self._seq:010d}.dat")
            self._seq += 1
        save_run(path, entries)
        return path

    def install(self, old: list[str], new: str | None) -> None:
        """Publish ``new`` (a :meth:`write_run` path, or None when
        nothing survived) in the place of ``old``, paths that stand side
        by side in ``files``; as the newest run when ``old`` is empty.
        The old files stay on disk until :meth:`remove`: write the new
        run, publish, remove, in that order, and a reopen in between
        reads one set or the other."""
        at = self.files.index(old[0]) if old else len(self.files)
        if self.files[at:at + len(old)] != old:
            raise ValueError("the replaced runs do not stand side by side")
        self._publish(self.files[:at] + ([new] if new else [])
                      + self.files[at + len(old):])

    @staticmethod
    def remove(paths: list[str]) -> None:
        for p in paths:
            try:
                os.unlink(p)
            except FileNotFoundError:
                pass

    def save_new(self, entries) -> str | None:
        """One more run, the newest; its path."""
        if not self.enabled:
            return None
        path = self.write_run(entries)
        self.install([], path)
        return path

    def replace_all(self, entries) -> str | None:
        """Every run file for one merged run (a full compaction, a
        restore); its path, or None when ``entries`` is empty."""
        if not self.enabled:
            return None
        old = list(self.files)
        new = self.write_run(entries) if entries else None
        self.install(old, new)
        self.remove(old)
        return new


def load_run(path: str) -> list[tuple[bytes, list[RowVersion]]]:
    with open(path, "rb") as f:
        magic, payload = codec.decode(f.read())
    if magic != _MAGIC:
        raise ValueError(f"{path}: bad run file magic {magic!r}")
    out = []
    for key, versions in payload:
        out.append((key, [
            RowVersion(key, ht=rec[0], tombstone=rec[1], liveness=rec[2],
                       columns={int(c): val for c, val in rec[3].items()},
                       expire_ht=rec[4],
                       write_id=rec[5] if len(rec) > 5 else 0)
            for rec in versions
        ]))
    return out
