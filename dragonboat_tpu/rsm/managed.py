"""Uniform internal wrapper over the three public SM types.

reference: internal/rsm/managed.go / nativesm.go [U].  Normalizes
everything to the batched interface and supplies the right locking:
regular SMs get an RW mutex (snapshot blocks writes), concurrent/on-disk
SMs run lock-free with PrepareSnapshot.
"""
from __future__ import annotations

import enum
import threading
import time
from typing import BinaryIO, List, Optional

from ..profiling import annotate
from ..statemachine import (
    IConcurrentStateMachine,
    IOnDiskStateMachine,
    IStateMachine,
    ISnapshotFileCollection,
    Result,
    SMEntry,
)


class SMType(enum.IntEnum):
    REGULAR = 0
    CONCURRENT = 1
    ON_DISK = 2


def wrap_state_machine(sm) -> "ManagedStateMachine":
    if isinstance(sm, IOnDiskStateMachine):
        return ManagedStateMachine(sm, SMType.ON_DISK)
    if isinstance(sm, IConcurrentStateMachine):
        return ManagedStateMachine(sm, SMType.CONCURRENT)
    if isinstance(sm, IStateMachine):
        return ManagedStateMachine(sm, SMType.REGULAR)
    raise TypeError(f"not a state machine: {type(sm)}")


class ManagedStateMachine:
    def __init__(self, sm, sm_type: SMType):
        self.sm = sm
        self.type = sm_type
        self._mu = threading.RLock()  # regular SM: excludes update vs snapshot
        # seconds inside the user's update(), cumulative; its one writer
        # is the apply worker holding the node's apply lock
        self.update_s = 0.0

    @property
    def on_disk(self) -> bool:
        return self.type == SMType.ON_DISK

    @property
    def concurrent_snapshot(self) -> bool:
        return self.type in (SMType.CONCURRENT, SMType.ON_DISK)

    def open(self, stopc) -> int:
        if self.type != SMType.ON_DISK:
            return 0
        return self.sm.open(stopc)

    def batched_update(self, entries: List[SMEntry]) -> List[SMEntry]:
        t0 = time.perf_counter()
        try:
            with annotate("raft-sm-update"):
                if self.type == SMType.REGULAR:
                    with self._mu:
                        for e in entries:
                            e.result = self.sm.update(e)
                        return entries
                return self.sm.update(entries)
        finally:
            self.update_s += time.perf_counter() - t0

    def wal_counts(self) -> tuple:
        """``(appends, bytes)`` the user state machine wrote to its own
        log: an on-disk tier's ``wal_counts()``, zeros for the others."""
        return self.sm.wal_counts() if self.type == SMType.ON_DISK else (0, 0)

    def lookup(self, query):
        if self.type == SMType.REGULAR:
            with self._mu:
                return self.sm.lookup(query)
        return self.sm.lookup(query)

    def sync(self) -> None:
        if self.type == SMType.ON_DISK:
            self.sm.sync()

    def prepare_snapshot(self):
        if self.type == SMType.REGULAR:
            return None
        return self.sm.prepare_snapshot()

    def save_snapshot(
        self,
        ctx,
        w: BinaryIO,
        files: Optional[ISnapshotFileCollection],
        done,
    ) -> None:
        if self.type == SMType.REGULAR:
            with self._mu:
                self.sm.save_snapshot(w, files, done)
        elif self.type == SMType.CONCURRENT:
            self.sm.save_snapshot(ctx, w, files, done)
        else:
            self.sm.save_snapshot(ctx, w, done)

    def recover_from_snapshot(self, r: BinaryIO, files, done) -> None:
        if self.type == SMType.ON_DISK:
            self.sm.recover_from_snapshot(r, done)
        else:
            self.sm.recover_from_snapshot(r, files, done)

    def close(self) -> None:
        self.sm.close()
