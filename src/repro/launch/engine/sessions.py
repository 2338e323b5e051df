"""Persistent per-user memory sessions: an LRU host-side cache with disk
spill, holding the state a user's session needs to survive between
requests — the SAM memory/usage (and, for cells that carry one, ANN index)
leaves plus whatever else rides in the session tree (KV-cache rows,
per-lane position, step counters).

Layout contract
---------------
Sessions are stored in the **canonical single-device layout** (shards=1,
one scratch row), whatever layout the live batch runs: ``put`` re-lays-out
every slot-dimension leaf via `elastic.relayout_memory_state` (the same
transform a cross-mesh checkpoint restore applies — a session cache is
that machinery pointed at an in-memory store), and the engine re-lays the
canonical tree back out to the live mesh's shard count on admission. The
logical rows round-trip bit-exactly; scratch rows are reinitialized (their
contents are meaningless by contract, docs/memory-model.md). ANN
(buckets, cursor) pairs re-partition by the same ownership remap the
checkpoint path uses (`mem_shard.np_relayout_ann`). Int8 memory storage
(``mem_dtype="int8"``) extends the bit-exactness guarantee to the
quantized pair: the int8 ``memory`` bits and the f32 ``mem_scale`` leaf
are both in `core.types.SLOT_LEAVES`, so they re-lay-out, spill, and
restore together without ever being de/re-quantized — an evicted session
resumes with the exact rows the uninterrupted run would hold
(tests/test_int8_memory.py).

Spill
-----
Beyond ``capacity`` hot sessions, the least-recently-used session spills
to disk through `checkpoint/ckpt.py` (atomic commit, manifest, ``.npy``
leaves — the identical format a training checkpoint uses, with
``mem_layout=(num_slots, 1)`` recorded so a spilled session is even
restorable under a different mesh by the ordinary checkpoint machinery).
``take`` transparently restores spilled sessions.
"""
from __future__ import annotations

import os
import shutil
from collections import OrderedDict
from typing import Any, Optional

import numpy as np

import jax

from repro.checkpoint import ckpt
from repro.distributed import elastic
from repro.launch.engine.telemetry import span, tree_nbytes


def _host(tree):
    return jax.tree.map(lambda t: np.asarray(jax.device_get(t)), tree)


def _template(tree):
    """ShapeDtypeStruct skeleton of a host tree (for checkpoint restore)."""
    return jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(np.shape(t), np.asarray(t).dtype),
        tree)


class SessionStore:
    """user -> canonical-layout session tree, LRU, disk-spillable.

    ``num_slots`` enables the canonicalizing re-layout of memory/usage/ANN
    leaves (None = store trees as-is — memoryless sessions). ``capacity``
    bounds the number of *hot* (in-RAM) sessions; older sessions spill to
    ``spill_dir`` (required if capacity is set) and restore on ``take``.

    ``spills`` counts sessions written to disk and ``restores`` sessions
    read back from disk; a session taken from memory counts in neither.
    """

    def __init__(self, num_slots: Optional[int] = None,
                 capacity: Optional[int] = None,
                 spill_dir: Optional[str] = None):
        if capacity is not None and (capacity < 1 or spill_dir is None):
            raise ValueError(
                "capacity needs >= 1 hot sessions and a spill_dir to evict "
                "the overflow to")
        self.num_slots = num_slots
        self.capacity = capacity
        self.spill_dir = spill_dir
        self._hot: OrderedDict[str, Any] = OrderedDict()
        self._spilled: dict[str, tuple[str, Any]] = {}   # user -> (dir, tmpl)
        self.spills = 0
        self.restores = 0

    # -- core API ----------------------------------------------------------

    def put(self, user: str, tree) -> int:
        """Store `user`'s session. Slot-dimension leaves are re-laid-out to
        the canonical (shards=1) layout and moved to host memory. Returns
        the bytes of the stored (host) tree."""
        with span("session.put"):
            if self.num_slots is not None:
                with span("session.relayout"):
                    tree = elastic.relayout_memory_state(tree,
                                                         self.num_slots, 1)
            with span("session.to_host"):
                host = _host(tree)
            self._hot[user] = host
            self._hot.move_to_end(user)
            self._drop_spilled(user)          # the fresh copy supersedes it
            self._maybe_spill()
        return tree_nbytes(host)

    def take(self, user: str):
        """Remove and return `user`'s canonical-layout session tree (host
        numpy leaves), restoring it from disk if it was spilled. None for
        an unknown user (a cold session — the caller builds a fresh zero
        state)."""
        if user in self._hot:
            return self._hot.pop(user)
        if user in self._spilled:
            return self._unspill(user)
        return None

    def peek(self, user: str):
        """Return `user`'s session tree without removing it from the store
        (restoring it into the hot set first if it was spilled). None for
        an unknown user. Lets a caller validate a request against the
        stored state *before* committing to `take` — rejecting then loses
        nothing."""
        if user in self._hot:
            return self._hot[user]
        if user in self._spilled:
            self._hot[user] = self._unspill(user)
            self._hot.move_to_end(user)
            self._maybe_spill()
            return self._hot[user]
        return None

    def __contains__(self, user: str) -> bool:
        return user in self._hot or user in self._spilled

    def __len__(self) -> int:
        return len(self._hot) + len(self._spilled)

    @property
    def users(self):
        return list(self._hot) + list(self._spilled)

    # -- spill machinery ---------------------------------------------------

    def _session_dir(self, user: str) -> str:
        safe = "".join(c if c.isalnum() or c in "-_." else "_" for c in user)
        return os.path.join(self.spill_dir, f"session_{safe}")

    def _unspill(self, user: str):
        """Read `user`'s spilled session back from disk (host leaves)."""
        with span("session.unspill"):
            directory, template = self._spilled.pop(user)
            tree, _ = ckpt.restore_checkpoint(directory, template)
            shutil.rmtree(directory, ignore_errors=True)
            self.restores += 1
            return _host(tree)

    def _maybe_spill(self) -> None:
        if self.capacity is None:
            return
        while len(self._hot) > self.capacity:
            user, tree = self._hot.popitem(last=False)    # LRU-oldest
            directory = self._session_dir(user)
            mem_layout = (None if self.num_slots is None
                          else (self.num_slots, 1))
            with span("session.spill"):
                ckpt.save_checkpoint(directory, 0, tree,
                                     mem_layout=mem_layout)
            self._spilled[user] = (directory, _template(tree))
            self.spills += 1

    def _drop_spilled(self, user: str) -> None:
        if user in self._spilled:
            directory, _ = self._spilled.pop(user)
            shutil.rmtree(directory, ignore_errors=True)
