"""The plain host reference that decides ``correct``.

``HostStore`` is copied from ``chip_smoke.py`` (a dict store that shares no
code with the engine: newest version wins, stat records of size and data
location) and changed in two ways: it keeps a chunk's stamp and where its
bytes came from instead of the bytes, and it counts answers that differ
instead of raising at the first.

``Checker`` replays, in call order, what the timed path answered and counts
every row that differs from the reference:

- ``read_rows``: found mask and stamp of every chunk read, and every byte
  of the reads kept whole (a sample drawn from the seed);
- ``stat_rows``: found, size and location of every stat;
- ``create_rows`` / ``remove_rows``: found masks;
- ``stage_out``: at every drain (and once after the window) the number of
  stored chunk rows and a checksum of every byte of the data table, against
  the rows written since the previous drain;
- ``dropped``: the engine's own drop counter.

The checksum of a row is ``sum(W[i] * row[i]) mod 2**32`` over its int32
words read as uint32, with ``W[i] = (2 i + 1) * 0x9E3779B1 mod 2**32``; the
benchmark computes it over the device table, and this module over the
payload pool's bytes as the generator made them.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from generator import STAMP_WORDS

_GOLDEN = 0x9E3779B1


def digest_weights(words: int) -> np.ndarray:
    return ((2 * np.arange(words, dtype=np.uint64) + 1) * _GOLDEN
            % 2**32).astype(np.uint32)


def row_digests(rows: np.ndarray) -> np.ndarray:
    """(..., words) int32 → (...,) uint32 row checksums."""
    w = digest_weights(rows.shape[-1]).astype(np.uint64)
    u = rows.view(np.uint32).astype(np.uint64)
    return ((u * w) % 2**32).sum(axis=-1, dtype=np.uint64) % 2**32


class HostStore:
    """Newest-version-wins chunk store and stat records, in dicts.

    A file's size is one past its highest written chunk id; its location
    is the last writer's node for layouts that record it (HYBRID) and -1
    otherwise."""

    def __init__(self, records_loc: bool):
        self.records_loc = records_loc
        self.chunks: Dict[Tuple[str, int], tuple] = {}
        self.files: Dict[str, Tuple[int, int]] = {}

    def write(self, paths, cids, values) -> None:
        for node, row in enumerate(paths):
            for j, path in enumerate(row):
                c = int(cids[node][j])
                self.chunks[(path, c)] = values[node][j]
                size, loc = self.files.get(path, (0, -1))
                self.files[path] = (max(size, c + 1),
                                    node if self.records_loc else loc)

    def create(self, paths) -> None:
        for row in paths:
            for path in row:
                self.files.setdefault(path, (0, -1))

    def remove(self, paths) -> np.ndarray:
        return np.array([[self.files.pop(p, None) is not None for p in row]
                         for row in paths], bool)

    def stat(self, paths) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        recs = [[self.files.get(p) for p in row] for row in paths]
        found = np.array([[r is not None for r in row] for row in recs], bool)
        size = np.array([[r[0] if r else -1 for r in row] for row in recs],
                        np.int64)
        loc = np.array([[r[1] if r else -1 for r in row] for row in recs],
                       np.int64)
        return found, size, loc

    def read(self, paths, cids) -> List[List[Optional[tuple]]]:
        return [[self.chunks.get((p, int(cids[n][j])))
                 for j, p in enumerate(row)] for n, row in enumerate(paths)]

    def stage_out(self) -> None:
        """The drain empties the data table (metadata stays)."""
        self.chunks.clear()


class Checker:
    """Replays recorded answers against ``HostStore``; counts mismatches."""

    def __init__(self, records_loc: bool, pool: Sequence[np.ndarray]):
        self.store = HostStore(records_loc)
        # the pool's bytes as the generator made them: stamp words zero
        self.pool = [np.array(p, np.int32) for p in pool]
        for p in self.pool:
            p[..., :STAMP_WORDS] = 0
        self.pool_digest = [row_digests(p) for p in self.pool]
        words = self.pool[0].shape[-1] if self.pool else STAMP_WORDS
        self.stamp_w = digest_weights(words)[:STAMP_WORDS].astype(np.uint64)
        self.rows = 0            # chunk rows written since the last drain
        self.digest = 0
        self.counts: Dict[str, int] = {}

    def _miss(self, name: str, n: int) -> None:
        self.counts[name] = self.counts.get(name, 0) + int(n)

    def write(self, paths, cids, stamps: np.ndarray, slot: int) -> None:
        vals = [[(tuple(int(v) for v in stamps[n, j]), slot, n, j)
                 for j in range(stamps.shape[1])]
                for n in range(stamps.shape[0])]
        self.store.write(paths, cids, vals)
        s = stamps.view(np.uint32).astype(np.uint64)
        rows = (self.pool_digest[slot] +
                ((s * self.stamp_w) % 2**32).sum(-1)) % 2**32
        self.rows += rows.size
        self.digest = int((self.digest + int(rows.sum())) % 2**32)

    def read(self, paths, cids, got_stamps, found, full=None) -> None:
        want = self.store.read(paths, cids)
        bad = 0
        for n, row in enumerate(want):
            for j, rec in enumerate(row):
                if rec is None:
                    bad += bool(found[n, j])
                    continue
                stamp, slot, wn, wj = rec
                if not found[n, j] or \
                        tuple(int(v) for v in got_stamps[n, j]) != stamp:
                    bad += 1
                elif full is not None:
                    exp = self.pool[slot][wn, wj].copy()
                    exp[:STAMP_WORDS] = stamp
                    bad += not np.array_equal(full[n, j], exp)
        self._miss("read_rows", bad)

    def create(self, paths, found) -> None:
        self.store.create(paths)
        self._miss("create_rows", int((~np.asarray(found, bool)).sum()))

    def stat(self, paths, found, size, loc) -> None:
        wf, ws, wl = self.store.stat(paths)
        bad = (np.asarray(found, bool) != wf) | (np.asarray(size) != ws) | \
            (np.asarray(loc) != wl)
        self._miss("stat_rows", int(bad.sum()))

    def remove(self, paths, found) -> None:
        want = self.store.remove(paths)
        self._miss("remove_rows",
                   int((np.asarray(found, bool) != want).sum()))

    def stage_out(self, count: int, digest: int) -> None:
        """One drain: the table's row count and checksum."""
        ok = int(count) == self.rows and int(digest) % 2**32 == self.digest
        self._miss("stage_out", 0 if ok else 1)
        self.store.stage_out()
        self.rows, self.digest = 0, 0
