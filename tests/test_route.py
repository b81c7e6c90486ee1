"""Device-side routing: unit tests + routed-consensus parity.

The routed path closes the step->route->step loop entirely on device;
these tests verify (a) the static route tables, (b) that a routed
cluster reaches and sustains consensus with zero drops in steady state,
and (c) bit-parity: the oracle stepping EXACTLY the inbox the router
produced reaches the same state every round (so the router's message
reconstruction — including REPLICATE entry terms gathered from the
sender's ring — is semantically faithful).
"""
from __future__ import annotations

from typing import List

import numpy as np
import jax.numpy as jnp

from dragonboat_tpu.ops import route as R
from dragonboat_tpu.ops import sync as S
from dragonboat_tpu.ops import types as T
from dragonboat_tpu.pb import Entry, EntryType, Message, MessageType
from dragonboat_tpu.raft.raft import Raft

P, W, M, E, O = 5, 32, 32, 4, 32
BUDGET, BASE = 6, 2


def make_cluster_rafts(groups):
    """groups: {shard: [replica_ids]} -> (rafts_in_row_order, rows)."""
    rafts, rows = [], []
    for shard, replicas in sorted(groups.items()):
        voters = {r: f"a{r}" for r in replicas}
        for rid in sorted(replicas):
            rafts.append(
                Raft(
                    shard_id=shard,
                    replica_id=rid,
                    peers=dict(voters),
                    election_timeout=10,
                    heartbeat_timeout=2,
                    max_entries_per_replicate=E,
                )
            )
            rows.append((shard, rid))
    return rafts, rows


def tables_for(rafts):
    shard_ids = np.array([r.shard_id for r in rafts], np.int32)
    replica_ids = np.array([r.replica_id for r in rafts], np.int32)
    peer_ids = np.zeros((len(rafts), P), np.int32)
    for g, r in enumerate(rafts):
        for s, (pid, _) in enumerate(S.peer_layout(r)):
            peer_ids[g, s] = pid
    return R.build_route_tables(shard_ids, replica_ids, peer_ids)


def inbox_row_messages(inbox_np, g, shard_id) -> List[Message]:
    """Decode device inbox row g into oracle Messages (slot order)."""
    msgs = []
    for i in range(M):
        mt = int(inbox_np["mtype"][g, i])
        if mt == 0:
            continue
        n = int(inbox_np["n_entries"][g, i])
        li = int(inbox_np["log_index"][g, i])
        ents = ()
        if mt == int(MessageType.REPLICATE):
            ents = tuple(
                Entry(
                    term=int(inbox_np["ent_term"][g, i, j]),
                    index=li + 1 + j,
                    type=(
                        EntryType.CONFIG_CHANGE
                        if inbox_np["ent_cc"][g, i, j]
                        else EntryType.APPLICATION
                    ),
                )
                for j in range(n)
            )
        elif mt == int(MessageType.PROPOSE):
            ents = tuple(
                Entry(type=EntryType.APPLICATION) for _ in range(n)
            )
        msgs.append(
            Message(
                type=MessageType(mt),
                from_=int(inbox_np["from_id"][g, i]),
                shard_id=shard_id,
                term=int(inbox_np["term"][g, i]),
                log_term=int(inbox_np["log_term"][g, i]),
                log_index=li,
                commit=int(inbox_np["commit"][g, i]),
                reject=bool(inbox_np["reject"][g, i]),
                hint=int(inbox_np["hint"][g, i]),
                hint_high=int(inbox_np["hint_high"][g, i]),
                entries=ents,
            )
        )
    return msgs


def test_route_tables_uniform_layout():
    """Generic builder matches the analytic group-major formulas."""
    GROUPS, REPL = 4, 3
    shard_ids = np.repeat(np.arange(1, GROUPS + 1), REPL).astype(np.int32)
    replica_ids = np.tile(np.arange(1, REPL + 1), GROUPS).astype(np.int32)
    peer_ids = np.broadcast_to(
        np.arange(1, REPL + 1, dtype=np.int32), (GROUPS * REPL, REPL)
    ).copy()
    dest, rank = R.build_route_tables(shard_ids, replica_ids, peer_ids)
    g = np.arange(GROUPS * REPL)
    want_dest = (g // REPL * REPL)[:, None] + np.arange(REPL)[None, :]
    want_rank = np.broadcast_to((g % REPL)[:, None], dest.shape)
    assert np.array_equal(dest, want_dest)
    assert np.array_equal(rank, want_rank)


def test_route_tables_off_device():
    """Peers not hosted in the layout route to -1."""
    shard_ids = np.array([7, 7], np.int32)
    replica_ids = np.array([1, 2], np.int32)
    peer_ids = np.zeros((2, P), np.int32)
    peer_ids[:, :3] = [1, 2, 3]  # replica 3 is remote
    dest, _ = R.build_route_tables(shard_ids, replica_ids, peer_ids)
    assert dest[0, 0] == 0 and dest[0, 1] == 1 and dest[0, 2] == -1
    assert dest[1, 0] == 0 and dest[1, 1] == 1 and dest[1, 2] == -1


class RoutedSim:
    """Routed device cluster + oracle shadow fed the routed inboxes."""

    def __init__(self, groups):
        self.rafts, self.rows = make_cluster_rafts(groups)
        self.state = S.state_from_rafts(self.rafts, P, W)
        dest, rank = tables_for(self.rafts)
        self.dest = jnp.asarray(dest)
        self.rank = jnp.asarray(rank)
        self.inbox = R.make_prefill(self.state, M, E)
        self.stats = None
        self.esc_total = 0
        self.round = 0

    def run(self, n, *, propose=False, compare=True):
        for _ in range(n):
            # oracle shadow consumes the SAME inbox the device will
            inbox_np = {
                k: np.asarray(getattr(self.inbox, k))
                for k in self.inbox._fields
            }
            for g, r in enumerate(self.rafts):
                for m in inbox_row_messages(inbox_np, g, r.shard_id):
                    r.handle(m)
                r.drain_messages()  # device routing is authoritative
            self.state, self.inbox, stats, n_esc = R.routed_round(
                self.state,
                self.inbox,
                self.dest,
                self.rank,
                out_capacity=O,
                budget=BUDGET,
                base=BASE,
                propose_leaders=propose,
            )
            self.esc_total += int(n_esc)
            self.stats = stats if self.stats is None else self.stats + stats
            self.round += 1
            assert self.esc_total == 0, (
                f"unexpected escalation at round {self.round}"
            )
            if compare:
                self.compare()

    def compare(self):
        for g, r in enumerate(self.rafts):
            errs = S.row_diff(self.state, g, r)
            assert not errs, (
                f"row ({r.shard_id},{r.replica_id}) diverged at round "
                f"{self.round}:\n  " + "\n  ".join(errs)
            )

    def committed(self):
        return np.asarray(self.state.committed)

    def leaders(self):
        role = np.asarray(self.state.role)
        return int((role == T.ROLE_LEADER).sum())


def test_routed_consensus_parity():
    """3 groups (two 3-replica, one 5-replica) co-located on one device:
    elections + steady-state replication with proposals, oracle parity
    every round, zero drops / zero escalations."""
    sim = RoutedSim({1: [1, 2, 3], 2: [1, 2, 3], 3: [1, 2, 3, 4, 5]})
    sim.run(60)  # elections settle
    assert sim.leaders() == 3, "every group should have elected a leader"
    c0 = sim.committed()
    sim.run(40, propose=True)
    c1 = sim.committed()
    # every group's commit index advanced by roughly one entry per round
    per_group = (c1 - c0).reshape(-1)
    assert (c1 > c0).all(), f"commit stalled: {c0} -> {c1}"
    adv = c1.max() - c0.max()
    assert adv >= 30, f"commit advance too slow: {adv} in 40 rounds"
    st = sim.stats
    assert int(st.dropped_budget) == 0
    assert int(st.dropped_ring) == 0
    assert int(st.dropped_off_device) == 0
    assert int(st.suppressed) == 0


def test_routed_drop_liveness():
    """A starvation budget forces drops; raft retries must still elect a
    leader and advance commit (drops are safe, only slow)."""
    rafts, rows = make_cluster_rafts({1: [1, 2, 3]})
    state = S.state_from_rafts(rafts, P, W)
    dest, rank = tables_for(rafts)
    dest, rank = jnp.asarray(dest), jnp.asarray(rank)
    m_small = BASE + P * 1  # budget=1 -> a 7-slot inbox layout
    inbox = R.make_prefill(state, m_small, E)
    dropped = 0
    for _ in range(160):
        # escalations are allowed here: starved followers can fall past
        # the ring window, and the routed loop's restore-and-drop
        # handling must keep the cluster safe and live regardless
        state, inbox, stats, n_esc = R.routed_round(
            state, inbox, dest, rank,
            out_capacity=O, budget=1, base=BASE, propose_leaders=True,
        )
        dropped += int(stats.dropped_budget)
    assert dropped > 0, "budget=1 should have forced drops"
    role = np.asarray(state.role)
    assert (role == T.ROLE_LEADER).sum() == 1
    assert np.asarray(state.committed).max() > 0
