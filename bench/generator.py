"""The one traffic generator: a mix's parameters in, rounds of calls out.

A mix is a data file, ``bench/traffic/<name>.json``.  Its keys:

- ``job``: the Table-I job (``core/workloads.py``) whose layout the decision
  layer picks for the cell;
- ``path``: a file-name pattern with ``{tag}``, ``{rank}`` and ``{file}``;
  ``tag`` is drawn from the seed, so each seed hashes other names to other
  nodes with the same sizes and the same order of operations;
- ``files_per_rank``, ``chunks_per_call``: a data call moves
  ``chunks_per_call`` consecutive chunks of one file per rank; a metadata
  call (``chunks_per_call`` 0) touches one file per rank;
- ``phases``: the ops of a round in order, each ``{"op", "node_offset",
  "rank_offset"}``: rank ``r`` acts on the files of rank
  ``r + node_offset * ranks_per_node + rank_offset`` (IOR's ``-C``,
  mdtest's ``-N``);
- ``drain``: end every round with a remove of the round's files and a fresh
  data table (the stand-in for stage-out);
- ``payload_pool``: device payload blocks that writes cycle through;
- ``rehearse``: overrides for a tiny run on the CPU.

A data phase has ``cap // (ranks_per_node * chunks_per_call)`` calls, so a
round of writes fills every node's slots exactly; a metadata phase has
``files_per_rank`` calls.  Every round issues the same calls; what differs
between rounds is the stamp that ``stamps`` writes into each chunk.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

#: leading int32 words of every chunk that carry its stamp:
#: (file id, chunk id, round, tag)
STAMP_WORDS = 4
DATA_OPS = ("write", "read")
META_OPS = ("create", "stat", "remove")


@dataclass(frozen=True)
class Call:
    """One bulk-synchronous step: every rank of every node issues one op."""
    op: str
    paths: List[List[str]]          # (nodes, q) path strings
    cids: Optional[np.ndarray]      # (nodes, q) int32 chunk ids; data ops
    uid: np.ndarray                 # (nodes, q) int32 file ids


class Mix:
    """The calls of one round of a traffic mix, for one deployment."""

    def __init__(self, spec: dict, *, nodes: int, ranks_per_node: int,
                 cap: int, seed: int):
        self.spec = spec
        self.nodes, self.rpn = nodes, ranks_per_node
        self.ranks = nodes * ranks_per_node
        self.files_per_rank = int(spec["files_per_rank"])
        self.cpc = int(spec["chunks_per_call"])
        self.drain = bool(spec["drain"])
        self.pool = int(spec["payload_pool"])
        self.tag = int(np.random.default_rng(seed).integers(1, 2**31 - 1))
        phases = spec["phases"]
        if not phases:
            raise ValueError("a mix needs at least one phase")
        for ph in phases:
            if ph["op"] not in DATA_OPS + META_OPS:
                raise ValueError(f"unknown op {ph['op']!r}")
            if (ph["op"] in DATA_OPS) != (self.cpc > 0):
                raise ValueError(f"op {ph['op']!r} does not fit "
                                 f"chunks_per_call {self.cpc}")
        if self.cpc and self.files_per_rank != 1:
            raise ValueError("a data mix writes one file per rank")
        if self.cpc and not self.pool:
            raise ValueError("a data mix needs a payload pool")
        self.data_calls = cap // (ranks_per_node * self.cpc) if self.cpc \
            else 0
        if self.cpc and self.data_calls < 1:
            raise ValueError(f"cap {cap} holds no call of "
                             f"{ranks_per_node * self.cpc} chunks per node")
        self.calls = [self._call(ph["op"], k, self._targets(ph))
                      for ph in phases
                      for k in range(self.data_calls if self.cpc
                                     else self.files_per_rank)]
        self.round_files = [[self.path(r, f) for r in self._node_ranks(n)
                             for f in range(self.files_per_rank)]
                            for n in range(nodes)]

    @property
    def q(self) -> int:
        """Requests per node in one call."""
        return self.rpn * max(self.cpc, 1)

    def path(self, rank: int, file: int) -> str:
        return self.spec["path"].format(tag=f"{self.tag:08x}", rank=rank,
                                        file=file)

    def _node_ranks(self, node: int) -> range:
        return range(node * self.rpn, (node + 1) * self.rpn)

    def _targets(self, phase: dict) -> np.ndarray:
        """(ranks,) the rank whose files each rank acts on in ``phase``."""
        shift = (int(phase.get("node_offset", 0)) * self.rpn +
                 int(phase.get("rank_offset", 0)))
        return (np.arange(self.ranks) + shift) % self.ranks

    def _call(self, op: str, k: int, target: np.ndarray) -> Call:
        paths, cids, uid = [], [], []
        for n in range(self.nodes):
            prow, crow, urow = [], [], []
            for r in self._node_ranks(n):
                t = int(target[r])
                if self.cpc:
                    for c in range(self.cpc):
                        prow.append(self.path(t, 0))
                        crow.append(k * self.cpc + c)
                        urow.append(t)
                else:
                    prow.append(self.path(t, k))
                    urow.append(t * self.files_per_rank + k)
            paths.append(prow)
            cids.append(crow)
            uid.append(urow)
        return Call(op, paths,
                    np.asarray(cids, np.int32) if self.cpc else None,
                    np.asarray(uid, np.int32))

    def stamps(self, call: Call, rnd: int) -> np.ndarray:
        """(nodes, q, STAMP_WORDS) int32 stamp of each chunk a call writes."""
        shape = call.uid.shape
        return np.stack([call.uid, call.cids,
                         np.full(shape, rnd, np.int32),
                         np.full(shape, self.tag, np.int32)],
                        axis=-1).astype(np.int32)
