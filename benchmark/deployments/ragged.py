"""``harness.deploy.Deployment`` with ragged membership, and the closed
loop with leader transfers running through its window.

**Membership.**  The configuration's ``cluster.sizes`` (3, 5, 7) are
cycled over the groups: group ``s`` has ``k = sizes[s mod len(sizes)]``
replicas, ids ``1..k``, on NodeHosts ``1..k`` (as
``tests/test_scale.py::shard_members`` lays them out).  Only members are
started, each with its group's own address map; NodeHost ``r`` therefore
carries every group of at least ``r`` replicas.  ``cluster.replicas`` is
the NodeHost count (7): the width of the engine's peer lane, the bound
the ``--dryrun`` capacity is cut from, and the length of ``replicas``.

**``replica_read(r, shard, key)``** answers for slot ``r`` of
``cluster.replicas`` from member ``1 + (r - 1) mod k`` of a ``k``-member
group.  ``reference.compare`` loops over ``system.replicas`` and wants
one value from all of them: slots 1..7 map onto members 1,2,3,1,2,3,1 of
a group of three, 1..5,1,2 of a group of five, and 1..7 of a group of
seven, so every member of every group is read at least once and no
NodeHost that is no member is ever asked.

**Build.**  ``Deployment.build`` starts every shard on every NodeHost
with one address map and has no hook for membership.  Rather than copy
its ninety lines, :class:`RaggedDeployment` builds with a ``NodeHost``
subclass in the program's place whose ``start_replica`` drops the calls
for non-members and cuts the address map to the group's members; the
rest of the build (warm-up, gateway, election wait) is the parent's.

**Churn.**  :class:`ChurnedThreadsClosed` is ``ThreadsClosed`` with the
window's two callbacks wrapped: ``on_open`` then the churn starts, the
churn stops then ``on_close``, so transfers run from the moment the
window opens to the moment it closes and never in the load phase, the
warm-up, the drain or the read-back.  One transfer is due every
``churn_every_ms`` (the cell's parameter, so ``--set churn_every_ms=...``
reaches it).  **The rate does not scale with ``--shards``**: a rehearsal
on nine groups moves each of them about a hundred times as often as the
cell moves one of its 1,050.  Victims are a permutation of the groups
drawn from ``--seed``, taken in order and round again when used up; the
target is the voter after the current leader in replica-id order,
wrapping; the call is ``NodeHost.request_leader_transfer`` on the
leader's NodeHost.  A group with no leader at its turn is skipped and
counted.  ``counters()`` adds ``churn.requested`` and ``churn.skipped``.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from harness.deploy import Deployment
from harness.loadgen import ThreadsClosed


def transfer_order(seed: int, n_shards: int) -> list:
    """The groups in the order their leaders are moved: a permutation
    from the seed, gone round again when it is used up."""
    return (1 + np.random.default_rng(seed).permutation(n_shards)).tolist()


class RaggedDeployment(Deployment):
    def __init__(self, cfg: dict, shards: int | None = None):
        from dragonboat_tpu import request

        if not hasattr(request, "HOST_TOTALS"):
            # a program from before PR 32 could run the deployment, but
            # not show that its churn happened (the cell's health checks
            # read engine.leader_transfers_done), and it parks a client
            # thread for its whole deadline on every write a moved leader
            # drops unseen: it ends here, at once, before anything is built
            raise RuntimeError(
                "this program counts no leader transfers "
                "(dragonboat_tpu.request.HOST_TOTALS): the churn cell "
                "needs PR 32's program")
        super().__init__(cfg, shards)
        self.sizes = list(cfg["cluster"]["sizes"])
        self.churn = {"requested": 0, "skipped": 0}

    def size_of(self, shard: int) -> int:
        return self.sizes[shard % len(self.sizes)]

    def members(self, shard: int) -> list:
        return list(range(1, self.size_of(shard) + 1))

    def rows(self) -> int:
        return sum(self.size_of(s) for s in self.shards)

    def build(self) -> None:
        import dragonboat_tpu

        size_of = self.size_of

        class MemberHost(dragonboat_tpu.NodeHost):
            def start_replica(self, members, join, sm, config):
                k = size_of(config.shard_id)
                if config.replica_id <= k:
                    super().start_replica(
                        {r: a for r, a in members.items() if r <= k},
                        join, sm, config)

        program = dragonboat_tpu.NodeHost
        dragonboat_tpu.NodeHost = MemberHost
        try:
            super().build()
        finally:
            dragonboat_tpu.NodeHost = program
        self.diag["rows"] = self.rows()

    def replica_read(self, rid: int, shard: int, key: str):
        member = 1 + (rid - 1) % self.size_of(shard)
        return self.nhs[member].stale_read(shard, key)

    # -- what the churn drives -------------------------------------------
    def transfer_leader(self, shard: int) -> bool:
        """Ask the group's leader to hand over to the voter after it.
        False where no leader is known at this moment."""
        lid, _ok = self.nhs[1].get_leader_id(shard)
        if lid:
            # the leader's own NodeHost knows better than a follower's
            lid, _ok = self.nhs[lid].get_leader_id(shard)
        self.churn["requested"] += 1
        if not lid:
            self.churn["skipped"] += 1
            return False
        target = lid % self.size_of(shard) + 1
        try:
            self.nhs[lid].request_leader_transfer(shard, target)
        except Exception:  # noqa: BLE001 - the host is closing
            self.churn["skipped"] += 1
            return False
        return True

    def counters(self) -> dict:
        out = super().counters()
        out.update({"churn." + k: v for k, v in self.churn.items()})
        return out


class Churn:
    """One thread that moves one leader every ``every_ms``, from
    ``start()`` to ``stop()``."""

    def __init__(self, system, rules: dict, every_ms: float, seed: int):
        want = {"kind": "leader_transfer", "victims": "seeded_permutation",
                "target": "next_voter", "window_only": True}
        if rules != want:
            raise ValueError(f"churn rules {rules}: this driver has {want}")
        self.system = system
        self.period_s = every_ms / 1000.0
        self.order = transfer_order(seed, system.n_shards)
        self.log = []   # (time, shard, asked)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._main, daemon=True,
                                        name="bench-churn")

    def _main(self) -> None:
        due = time.monotonic() + self.period_s
        i = 0
        while not self._stop.wait(max(0.0, due - time.monotonic())):
            shard = self.order[i % len(self.order)]
            self.log.append((time.monotonic(), shard,
                             self.system.transfer_leader(shard)))
            i += 1
            # a turn that came late does not bring a burst after it
            due = max(due + self.period_s, time.monotonic())

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(10.0)


class ChurnedThreadsClosed(ThreadsClosed):
    def run(self, on_open, on_close) -> None:
        system = self.system
        if not hasattr(system, "transfer_leader"):
            # the plain reference in the program's place has no leaders
            return super().run(on_open, on_close)
        churn = Churn(system, self.cfg["churn"], self.p["churn_every_ms"],
                      self.seed)

        def opened() -> None:
            on_open()
            churn.start()

        def closing() -> None:
            churn.stop()
            on_close()

        super().run(opened, closing)
        asked = [t for t, _s, ok in churn.log if ok]
        system.diag["churn"] = {
            **system.churn, "every_ms": self.p["churn_every_ms"],
            "first_at_s": asked[0] - self.t0 if asked else None,
            "last_at_s": asked[-1] - self.t0 if asked else None,
        }
