"""BASELINE config 4 at a small size: ragged 3/5/7 membership at P=7 on
seven NodeHosts, under leader transfers (PR 32).

Nine groups — three of three, three of five, three of seven replicas, as
``tests/test_scale.py::shard_members`` lays them out — on seven NodeHosts
sharing one ``ColocatedEngineGroup(capacity=64, P=7)``, a Gateway in
front.  One engine group for the module: the warm set of a new geometry
is the dear part.

(a) dense churn: every group's leader moved once a second for five
    seconds under one lease reader and one writer a group; the history
    replayed into a plain register a key (the benchmark's own reference):
    no stale read, no lost acknowledged write, every member of every
    group converges, no operation fails, the transfer counters add up;
(b) the lease lane at ragged quorums: a leader of three in seven slots is
    fresh on one follower's answer, a leader of seven on three, on fewer
    neither is;
(c) the lease's end: from the launch that carries a transfer request on
    (a slot of the leader's own row, the mirror's target set at the plan),
    the old leader's ``lease_probe`` does not answer ``LEASE_HELD``;
(d) a proposal whose entry another leader's entries replaced is told
    ``DROPPED`` inside its deadline, on the scalar path and on the device
    path, and only once another entry is committed at its index.

Counts and relations only: a CPU run tells no time that matters.

(ISSUE 32 called the file ``tests/test_ragged_churn.py``.  Its name sorts
last for ``tests/test_zz_lease_launch.py``'s reason: tier-1 hands files
to its six workers in collection order, several older files share
``/tmp/nh-*`` directories and pass or collide by which of them overlap,
and under the issue's name the whole run lost ``tests/test_rebase.py`` to
a locked directory.)
"""
import os
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    FaultController,
    Gateway,
    GatewayConfig,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.node import LEASE_HELD
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.ops.engine import _summarize_flags
from dragonboat_tpu.ops.types import (
    ACTIVE_FRESH,
    ACTIVE_LIVE,
    F_QUORUM_ACTIVE,
    F_QUORUM_FRESH,
    ROLE_LEADER,
    make_out,
    make_state,
)
from dragonboat_tpu.pb import Entry, EntryType
from dragonboat_tpu.raft.log import InMemory
from dragonboat_tpu.request import HOST_TOTALS, RequestResultCode
from dragonboat_tpu.transport.inproc import reset_inproc_network

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "benchmark"))
sys.path.insert(0, _ROOT)

from examples.kv_gateway import KV  # noqa: E402
from harness import traffic  # noqa: E402
from harness.loadgen import (  # noqa: E402
    FAILED, GOT, KEY, KIND, OK, READ, SHARD, STATUS, T_DONE, T_ISSUE, WRITE)
from harness.reference import PlainRegisters  # noqa: E402

GEOM = dict(capacity=64, P=7, W=16, M=8, E=4, O=32, budget=4)
SIZES = (3, 5, 7)
HOSTS = tuple(range(1, 8))
N_SHARDS = 9
SHARDS = tuple(range(1, N_SHARDS + 1))
SEED = 32
RTT_MS = 20          # the benchmark's tick (tests/test_ondisk_served.py)
ET = 20              # election_rtt: the lease's length in ticks
# the nine ragged groups' own: a second, so that no launch of a CPU that
# five other test workers load outlasts the window a transfer has
ET_RAGGED = 50
OP_TIMEOUT_S = 30.0
CHURN_SECONDS = 5


def size_of(shard: int) -> int:
    return SIZES[shard % len(SIZES)]


def members(shard: int, addrs: dict) -> dict:
    return {r: addrs[r] for r in range(1, size_of(shard) + 1)}


def shard_config(shard: int, rid: int, et: int = ET) -> Config:
    return Config(replica_id=rid, shard_id=shard, election_rtt=et,
                  heartbeat_rtt=et // 10, pre_vote=True, check_quorum=True)


class Ragged:
    def __init__(self, root: str):
        reset_inproc_network()
        self.group = ColocatedEngineGroup(**GEOM)
        self.addrs = {r: f"ragged-{r}" for r in HOSTS}
        self.nhs = {}
        for rid, addr in self.addrs.items():
            self.nhs[rid] = NodeHost(NodeHostConfig(
                nodehost_dir=os.path.join(root, f"nh-{rid}"),
                rtt_millisecond=RTT_MS, raft_address=addr,
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=2),
                    step_engine_factory=self.group.factory),
            ))
        self.gw = Gateway({self.addrs[r]: nh for r, nh in self.nhs.items()},
                          GatewayConfig(workers=2))
        for s in SHARDS:
            for rid in members(s, self.addrs):
                self.nhs[rid].start_replica(
                    members(s, self.addrs), False, KV,
                    shard_config(s, rid, ET_RAGGED))
        self.core = self.group.core   # the first NodeHost built it
        deadline = time.monotonic() + 120.0
        while not all(self.nhs[1].get_leader_id(s)[1] for s in SHARDS):
            assert time.monotonic() < deadline, "no leader everywhere"
            time.sleep(0.05)

    def leader(self, shard: int, deadline_s: float = 30.0) -> int:
        end = time.monotonic() + deadline_s
        while time.monotonic() < end:
            for rid in members(shard, self.addrs):
                if self.nhs[rid].is_leader_of(shard):
                    return rid
            time.sleep(0.01)
        raise AssertionError(f"shard {shard}: no leader")

    def totals(self) -> dict:
        out = dict.fromkeys(HOST_TOTALS, 0)
        for nh in self.nhs.values():
            for k, v in nh.host_totals.snapshot().items():
                out[k] += v
        return out

    def settled_totals(self, deadline_s: float = 15.0) -> dict:
        """Totals once every request has ended (a request that nobody
        answers ends at its 5 s deadline)."""
        end = time.monotonic() + deadline_s
        while True:
            t = self.totals()
            if t["leader_transfers_requested"] == (
                    t["leader_transfers_done"]
                    + t["leader_transfers_aborted"]):
                return t
            assert time.monotonic() < end, f"requests still open: {t}"
            time.sleep(0.1)

    def close(self) -> None:
        self.gw.close()
        for nh in self.nhs.values():
            nh.close()


@pytest.fixture(scope="module")
def ragged(tmp_path_factory):
    c = Ragged(str(tmp_path_factory.mktemp("ragged")))
    yield c
    c.close()


# -- the membership --------------------------------------------------------
def test_only_members_are_started_and_every_group_has_its_quorum(ragged):
    for s in SHARDS:
        k = size_of(s)
        for rid in HOSTS:
            assert (s in ragged.nhs[rid]._nodes) == (rid <= k)
        r = ragged.nhs[1]._nodes[s].peer.raft
        assert sorted(r.remotes) == list(range(1, k + 1))
        assert r.quorum() == k // 2 + 1
    assert sorted(size_of(s) for s in SHARDS) == [3] * 3 + [5] * 3 + [7] * 3
    # every replica row is resident on the one core, 45 of 64
    assert len(ragged.core._row_of) == sum(size_of(s) for s in SHARDS)


# -- a read that asked for leader-or-nothing, at a replica that follows ----
@pytest.mark.parametrize("forward", [False, True])
def test_a_follower_drops_the_read_that_asked_for_the_leader(ragged, forward):
    """The gateway's ReadIndex fallback (``sync_read(forward=False)``): a
    replica that does not lead tells it DROPPED at the plan, and its row
    stays on the device; any other reader's is forwarded, the host
    path's, and answered."""
    shard = 2
    gw, core = ragged.gw, ragged.core
    gw.noop_handle(shard).propose(b"fr=1", timeout=OP_TIMEOUT_S).result(
        OP_TIMEOUT_S)
    lead = ragged.leader(shard)
    nh = ragged.nhs[lead % size_of(shard) + 1]
    row = core._row_of[(shard, lead % size_of(shard) + 1)]
    end = time.monotonic() + 30.0
    while core._meta[row].dirty:   # resident before the read
        assert time.monotonic() < end
        time.sleep(0.01)
    out0 = core.stats.get("evict_host_plan", 0)
    rs = nh.read_index(shard, 10.0, forward=forward)
    code = rs.wait(10.0)
    if forward:
        assert code == RequestResultCode.COMPLETED
        assert core.stats.get("evict_host_plan", 0) > out0
    else:
        assert code == RequestResultCode.DROPPED
        assert not core._meta[row].dirty
        assert ragged.leader(shard) == lead
        assert gw.read(shard, "fr", timeout=OP_TIMEOUT_S) == "1"


# -- (c) the lease's end ---------------------------------------------------
@pytest.mark.parametrize("shard", [3, 1, 2])   # three, five, seven members
def test_from_the_launch_that_carries_the_request_the_lease_is_gone(
        ragged, shard):
    gw, core = ragged.gw, ragged.core
    gw.noop_handle(shard).propose(b"lease=1", timeout=OP_TIMEOUT_S).result(
        OP_TIMEOUT_S)
    old = ragged.leader(shard)
    nh_old = ragged.nhs[old]
    node = nh_old._nodes[shard]
    end = time.monotonic() + 30.0
    while node.lease_probe(gw.config.lease_margin_ticks)[0] != LEASE_HELD:
        assert time.monotonic() < end, "the lease never held"
        time.sleep(0.01)
    target = old % size_of(shard) + 1
    before = ragged.totals()
    carried = core.stats["device_transfers"]
    nh_old.request_leader_transfer(shard, target)
    held_after = []
    saw_carried = False
    end = time.monotonic() + 30.0
    while time.monotonic() < end:
        # the counter first, the probe second: a probe that follows a
        # counter that had moved is a probe after the launch's encode
        # (the mirror's target is set before that, at the plan)
        moved = core.stats["device_transfers"] > carried
        why, _left = node.lease_probe(gw.config.lease_margin_ticks)
        if moved:
            saw_carried = True
            if why == LEASE_HELD:
                held_after.append(node.tick_count)
        if saw_carried and node.leader_id not in (0, old):
            break
        time.sleep(0.001)
    assert saw_carried, "no launch carried the request"
    # the leader's own request is a slot of its row: no host excursion
    assert core.stats["device_transfers"] == carried + 1
    assert ragged.leader(shard) == target
    assert held_after == [], held_after
    after = ragged.settled_totals()
    assert (after["leader_transfers_requested"]
            == before["leader_transfers_requested"] + 1)
    assert after["leader_transfers_done"] == before["leader_transfers_done"] + 1
    assert after["t_transfer_s"] > before["t_transfer_s"]
    # the old leader's mirror does not keep the target past its term
    assert node.peer.raft.leader_transfer_target == 0
    # and the new leader comes to hold a lease of its own
    new_node = ragged.nhs[target]._nodes[shard]
    gw.noop_handle(shard).propose(b"lease=2", timeout=OP_TIMEOUT_S).result(
        OP_TIMEOUT_S)
    end = time.monotonic() + 30.0
    while new_node.lease_probe(gw.config.lease_margin_ticks)[0] != LEASE_HELD:
        assert time.monotonic() < end, "the new leader never held a lease"
        time.sleep(0.01)


# -- (a) dense churn -------------------------------------------------------
def _dense_pass(ragged) -> float:
    """Five seconds of churn under readers and writers, held to the
    history, the convergence and the counters' sums; returns the share
    of the transfers asked for that ended with the target leading."""
    gw, nhs = ragged.gw, ragged.nhs
    values = traffic.HexValues(SEED)
    keys = {s: [f"k{s}a", f"k{s}b"] for s in SHARDS}
    stop = threading.Event()
    logs = []
    before = ragged.totals()
    stats0 = dict(ragged.core.stats)
    gw0 = gw.stats()

    def writer(shard: int, log: list) -> None:
        n = 0
        while not stop.is_set():
            key = keys[shard][n % 2]
            vid = (shard << 32) | n
            cmd = f"{key}={values.encode(vid)}".encode()
            t_i = time.monotonic()
            try:
                gw.noop_handle(shard).propose(
                    cmd, timeout=OP_TIMEOUT_S).result(OP_TIMEOUT_S + 1.0)
                got, st = None, OK
            except Exception as e:  # noqa: BLE001
                got, st = repr(e), FAILED
            log.append([WRITE, shard, key, vid, t_i, t_i, time.monotonic(),
                        st, got])
            n += 1

    def reader(shard: int, log: list) -> None:
        n = 0
        while not stop.is_set():
            key = keys[shard][n % 2]
            t_i = time.monotonic()
            try:
                got, st = gw.read(shard, key, timeout=OP_TIMEOUT_S), OK
            except Exception as e:  # noqa: BLE001
                got, st = repr(e), FAILED
            log.append([READ, shard, key, -1, t_i, t_i, time.monotonic(),
                        st, got])
            n += 1
            time.sleep(0.002)

    threads = []
    for s in SHARDS:
        for fn in (writer, reader):
            logs.append([])
            threads.append(threading.Thread(
                target=fn, args=(s, logs[-1]), daemon=True))
    for t in threads:
        t.start()
    time.sleep(0.5)

    # the schedule, the cell's: a permutation of the groups from the
    # seed, taken in order and round again, one turn every ninth of a
    # second, so every group once a second
    order = (1 + np.random.default_rng(SEED).permutation(N_SHARDS)).tolist()
    asked = skipped = 0
    t_next = time.monotonic()
    for _second in range(CHURN_SECONDS):
        for s in order:
            time.sleep(max(0.0, t_next - time.monotonic()))
            t_next += 1.0 / N_SHARDS
            lid, _ok = nhs[1].get_leader_id(s)
            if lid:
                lid, _ok = nhs[lid].get_leader_id(s)
            if not lid:
                skipped += 1
                continue
            nhs[lid].request_leader_transfer(s, lid % size_of(s) + 1)
            asked += 1
    time.sleep(1.0)
    stop.set()
    for t in threads:
        t.join(OP_TIMEOUT_S + 5.0)
        assert not t.is_alive(), "an operation never came back"
    ops = [op for log in logs for op in log]
    writes = [op for op in ops if op[KIND] == WRITE]
    reads = [op for op in ops if op[KIND] == READ]
    assert len(writes) > N_SHARDS * 5 and len(reads) > N_SHARDS * 5

    # no operation fails
    failed = [op for op in ops if op[STATUS] != OK]
    assert failed == [], failed[:5]

    # the replay: one plain register a key
    regs = PlainRegisters(ops, values)
    stale = [op for op in reads if not regs.allows(
        op[SHARD], op[KEY], op[GOT], op[T_ISSUE], op[T_DONE])]
    assert stale == [], stale[:5]
    final = {}
    for s in SHARDS:
        for key in keys[s]:
            t_i = time.monotonic()
            got = gw.read(s, key, timeout=OP_TIMEOUT_S)
            assert regs.allows(s, key, got, t_i, time.monotonic()), (s, key)
            final[(s, key)] = got
    # every member of every group converges to it
    deadline = time.monotonic() + 15.0
    pending = list(final)
    while pending:
        pending = [(s, key) for s, key in pending if any(
            nhs[r].stale_read(s, key) != final[(s, key)]
            for r in members(s, ragged.addrs))]
        assert time.monotonic() < deadline, pending
        time.sleep(0.05)

    # the counters add up
    after = ragged.settled_totals()
    d = {k: after[k] - before[k] for k in after}
    assert d["leader_transfers_requested"] == asked
    assert asked + skipped == CHURN_SECONDS * N_SHARDS
    assert skipped <= 0.05 * (asked + skipped), (asked, skipped)
    assert d["leader_transfers_done"] > 0 and d["t_transfer_s"] > 0.0
    st = ragged.core.stats   # folds the members' totals once a step call
    deadline = time.monotonic() + 10.0
    while (st["leader_transfers_requested"]
           - stats0["leader_transfers_requested"]) < asked:
        assert time.monotonic() < deadline, dict(st)
        time.sleep(0.05)
    # a leader's own request is a slot of its row; the host path takes
    # those the churn asked of a replica that had just stopped leading
    # (a row the host path has just handed back is the host's once more)
    assert st["device_transfers"] - stats0["device_transfers"] >= 0.5 * asked
    assert st["leader_changes"] > stats0["leader_changes"]
    assert st["divergence_halts"] == 0 and st["pipeline_resets"] == 0
    # the gateway sent again what the moved leaders dropped
    assert gw.stats()["reroutes"] > gw0["reroutes"]
    assert gw.stats()["failed"] == gw0["failed"]
    return d["leader_transfers_done"] / asked


def test_dense_churn_leaves_a_clean_history_and_fails_no_operation(ragged):
    # what the cell's health check holds every run to on the chip
    assert _dense_pass(ragged) >= 0.9


# -- inputs past a row's host slots wait a launch, they do not evict it -----
@pytest.mark.parametrize("n_msg,n_ent,n_read,n_xfer,ticks,want", [
    (12, 0, 0, 0, 1, (7, 0, 0, 0)),     # heartbeat answers of a seven-way
    (3, 9, 3, 1, 1, (3, 9, 1, 0)),      # 3 + three chunks of E=4 + 1 = 7
    (0, 40, 0, 0, 0, (0, 32, 0, 0)),    # no tick: all eight slots
    (2, 2, 2, 3, 1, (2, 2, 2, 2)),      # 2 + 1 + 2 + 3 = 8: one too many
])
def test_inputs_past_the_rooms_of_a_row_go_back_in_their_order(
        n_msg, n_ent, n_read, n_xfer, ticks, want):
    """``_plan_device`` with more than ``M`` slots to fill: the first
    that fit ride this launch with the ticks, the rest go back to the
    head of the node's queues; the row is not sent to the host path."""
    from collections import Counter
    from types import SimpleNamespace

    from dragonboat_tpu.node import Node, StepInputs
    from dragonboat_tpu.ops.engine import VectorStepEngine

    back = {}
    node = SimpleNamespace(requeue_inputs=lambda **kw: back.update(kw))
    si = StepInputs(
        received=[f"m{i}" for i in range(n_msg)],
        proposals=[f"e{i}" for i in range(n_ent)],
        read_indexes=[f"r{i}" for i in range(n_read)],
        transfers=[f"x{i}" for i in range(n_xfer)], ticks=ticks)
    whole = [list(si.received), list(si.proposals), list(si.read_indexes),
             list(si.transfers)]
    eng = SimpleNamespace(M=8, E=4, stats=Counter())
    VectorStepEngine._defer_past_room(eng, node, si)
    kept = [list(si.received), list(si.proposals), list(si.read_indexes),
            list(si.transfers)]
    assert tuple(len(k) for k in kept) == want
    slots = (len(kept[0]) - (-len(kept[1]) // 4) + len(kept[2])
             + len(kept[3]) + (1 if ticks else 0))
    assert slots <= 8
    rest = [list(back[k]) for k in
            ("received", "proposals", "read_indexes", "transfers")]
    assert [k + r for k, r in zip(kept, rest)] == whole
    assert eng.stats["deferred_inputs"] == 1
    # ... and the node puts them AHEAD of what arrived since the drain
    import threading as _th
    woke = []
    n = SimpleNamespace(
        _qlock=_th.Lock(), _received=["late"], _proposals=["late"],
        _read_indexes=[], _leader_transfers=["late"],
        notify_work=lambda: woke.append(1))
    Node.requeue_inputs(n, received=rest[0], proposals=rest[1],
                        read_indexes=rest[2], transfers=rest[3])
    assert n._received == rest[0] + ["late"]
    assert n._proposals == rest[1] + ["late"]
    assert n._read_indexes == rest[2]
    assert n._leader_transfers == rest[3] + ["late"]
    assert woke == [1]


# -- (b) the lease lane at ragged quorums ----------------------------------
def _leader_row(members_n: int, fresh_followers: int, bit: int):
    """One leader row of ``members_n`` voters in seven slots (the others
    empty), ``fresh_followers`` of its followers carrying ``bit``."""
    peer_ids = np.zeros((1, 7), np.int32)
    peer_ids[0, :members_n] = np.arange(1, members_n + 1)
    st = make_state(1, 7, 8, shard_ids=np.array([1], np.int32),
                    replica_ids=np.ones((1,), np.int32), peer_ids=peer_ids,
                    election_timeout=10, heartbeat_timeout=2,
                    check_quorum=True)
    active = np.zeros((1, 7), np.int32)
    active[0, 1:1 + fresh_followers] = bit
    return st._replace(role=jnp.asarray(np.array([ROLE_LEADER], np.int32)),
                       term=jnp.asarray(np.array([1], np.int32)),
                       active=jnp.asarray(active))


@pytest.mark.parametrize("members_n,answers,fresh", [
    (3, 0, False), (3, 1, True),
    (5, 1, False), (5, 2, True),
    (7, 2, False), (7, 3, True), (7, 6, True),
])
def test_the_fresh_flag_counts_a_quorum_of_the_groups_own_members(
        members_n, answers, fresh):
    """The empty slots of a group smaller than the lane are no voters:
    two of three, three of five, four of seven, self among them."""
    out = make_out(1, 7, 4, 2, 8)
    st = _leader_row(members_n, answers, ACTIVE_FRESH)
    word = int(np.asarray(_summarize_flags(st, st, out))[0])
    assert bool(word & F_QUORUM_FRESH) == fresh
    assert not word & F_QUORUM_ACTIVE      # the two bits are read apart
    st = _leader_row(members_n, answers, ACTIVE_LIVE)
    word = int(np.asarray(_summarize_flags(st, st, out))[0])
    assert bool(word & F_QUORUM_ACTIVE) == fresh
    assert not word & F_QUORUM_FRESH


# -- (d) a proposal whose entry was overwritten ----------------------------
def _entry(index: int, term: int, key: int = 0) -> Entry:
    return Entry(term=term, index=index, type=EntryType.APPLICATION, key=key,
                 cmd=b"x=1")


def test_a_merge_records_the_conflict_and_the_keyed_entries_it_replaced():
    im = InMemory(4)
    im.merge([_entry(5, 1, key=50), _entry(6, 1, key=60), _entry(7, 1)])
    assert im.truncated == []
    # an append, and a resend of what is there, replace nothing
    im.merge([_entry(8, 1, key=80)])
    im.merge([_entry(6, 1, key=60), _entry(7, 1), _entry(8, 1, key=80)])
    assert im.truncated == []
    # term 2 over 7 and 8: the conflict is at 7 (key 0, so not listed
    # among the keyed), 8 goes with it, 6 is the same entry and stays
    im.merge([_entry(6, 1, key=60), _entry(7, 2), _entry(8, 2, key=81)])
    assert [(i, t, [e.key for e in es]) for i, t, es in im.truncated] == [
        (7, 1, [80])]
    assert [(e.index, e.term) for e in im.entries] == [
        (5, 1), (6, 1), (7, 2), (8, 2)]
    # a shorter tail over a longer one takes all of the rest
    im.truncated = []
    im.merge([_entry(6, 3, key=61)])
    assert [(i, t, [e.key for e in es]) for i, t, es in im.truncated] == [
        (6, 1, [60, 81])]
    # a stretch with nobody waiting makes no record
    im.merge([_entry(6, 4)])
    im.truncated = []
    im.merge([_entry(6, 5)])
    assert im.truncated == []


ADDRS3 = {1: "trunc-1", 2: "trunc-2", 3: "trunc-3"}


class Three:
    """Three NodeHosts, one group, the fault plane on their transports;
    on the colocated engine or on the scalar one."""

    def __init__(self, root: str, engine: str):
        reset_inproc_network()
        self.group = (ColocatedEngineGroup(
            capacity=16, P=5, W=32, M=8, E=4, O=32, budget=4)
            if engine == "device" else None)
        self.nemesis = FaultController(seed=SEED)
        self.nhs = {}
        for rid, addr in ADDRS3.items():
            self.nhs[rid] = NodeHost(NodeHostConfig(
                nodehost_dir=os.path.join(root, f"nh-{rid}"),
                rtt_millisecond=5 if self.group is None else RTT_MS,
                raft_address=addr,
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=2),
                    step_engine_factory=(self.group.factory
                                         if self.group else None)),
            ))
            self.nemesis.install_nodehost(rid, self.nhs[rid])
        for rid, nh in self.nhs.items():
            nh.start_replica(ADDRS3, False, KV, shard_config(1, rid))

    def leader(self, among=ADDRS3, deadline_s: float = 60.0) -> int:
        end = time.monotonic() + deadline_s
        while time.monotonic() < end:
            for rid in among:
                if self.nhs[rid].is_leader_of(1):
                    return rid
            time.sleep(0.01)
        raise AssertionError("no leader")

    def cut_off(self, rid: int) -> None:
        self.nemesis.set_partition({ADDRS3[rid]})
        if self.group is not None:
            core = self.group.core
            with core._lock:
                core._fence()
                core._part_fn = lambda s, r: 1 if r == rid else 0
                core._tables_dirty = True

    def heal(self) -> None:
        self.nemesis.heal_wire()
        if self.group is not None:
            self.group.core.set_partition(None)

    def close(self) -> None:
        self.nemesis.stop()
        for nh in self.nhs.values():
            nh.close()


@pytest.mark.parametrize("engine", ["scalar", "device"])
def test_an_overwritten_proposal_is_told_dropped_inside_its_deadline(
        tmp_path, engine):
    """A leader cut off from its followers appends a proposal it cannot
    commit; the others elect a leader and commit at that index; when the
    old leader is back, the new leader's entries replace its tail.  The
    proposal is told ``DROPPED`` — not at the truncation, which proves
    nothing yet, but when the entry committed at its index is applied
    here — long before its deadline, and counted."""
    c = Three(str(tmp_path), engine)
    try:
        old = c.leader()
        nh_old = c.nhs[old]
        nh_old.sync_propose(nh_old.get_noop_session(1), b"a=0", timeout=30.0)
        others = [r for r in ADDRS3 if r != old]
        by_merge_tail = []
        if c.group is not None:
            # which path takes the truncation: the device path's merge
            # tail rebuilds [append_lo, last_index] in _merge_appends
            core = c.group.core
            real = core._merge_appends

            def watched(r, *a, **kw):
                n = len(r.log.inmem.truncated)
                out = real(r, *a, **kw)
                if len(r.log.inmem.truncated) > n:
                    by_merge_tail.append(r.replica_id)
                return out

            core._merge_appends = watched
        c.cut_off(old)
        doomed = nh_old.propose(nh_old.get_noop_session(1), b"a=old", 120.0)
        new = c.leader(among=others)
        nh_new = c.nhs[new]
        for n in range(3):
            nh_new.sync_propose(nh_new.get_noop_session(1),
                                f"a=new{n}".encode(), timeout=30.0)
        # still cut off: nothing has told the old leader anything
        assert doomed.wait(0.2) == RequestResultCode.TIMEOUT
        assert nh_old.host_totals.values["proposals_dropped_truncated"] == 0
        t_heal = time.monotonic()
        c.heal()
        code = doomed.wait(30.0)
        assert code == RequestResultCode.DROPPED, code
        assert time.monotonic() - t_heal < 30.0
        assert nh_old.host_totals.values["proposals_dropped_truncated"] == 1
        assert nh_old._nodes[1]._doomed == []
        # the value it carried is nowhere, on any replica
        deadline = time.monotonic() + 15.0
        while any(nh.stale_read(1, "a") != "new2" for nh in c.nhs.values()):
            assert time.monotonic() < deadline
            time.sleep(0.05)
        if c.group is not None:
            # the device path took the truncation, in its merge tail
            assert by_merge_tail == [old]
            assert c.group.core.stats["divergence_halts"] == 0
    finally:
        c.close()


def test_the_scalar_follower_drops_a_leader_or_nothing_read_too(tmp_path):
    c = Three(str(tmp_path), "scalar")
    try:
        lead = c.leader()
        nh = c.nhs[next(r for r in ADDRS3 if r != lead)]
        c.nhs[lead].sync_propose(c.nhs[lead].get_noop_session(1), b"a=0",
                                 timeout=30.0)
        assert (nh.read_index(1, 10.0, forward=False).wait(10.0)
                == RequestResultCode.DROPPED)
        assert nh.read_index(1, 10.0).wait(10.0) == RequestResultCode.COMPLETED
    finally:
        c.close()


def test_a_transfer_that_never_lands_gives_the_lease_back(tmp_path):
    """The target is cut off: the kernel takes the request, never hears
    that the target caught up, and gives the transfer up after one
    election window, telling nobody.  The scalar mirror's target, which
    zeroes the lease from the plan on, goes with it — counted off on the
    row's own ticks, never before the kernel — and the old leader, still
    leading with its other follower, answers ``LEASE_HELD`` again."""
    c = Three(str(tmp_path), "device")
    try:
        old = c.leader()
        nh_old = c.nhs[old]
        nh_old.sync_propose(nh_old.get_noop_session(1), b"a=0", timeout=30.0)
        node = nh_old._nodes[1]
        core = c.group.core

        def held() -> bool:
            return node.lease_probe(2)[0] == LEASE_HELD

        end = time.monotonic() + 30.0
        while not held():
            assert time.monotonic() < end, "the lease never held"
            time.sleep(0.01)
        target = next(r for r in ADDRS3 if r != old)
        c.cut_off(target)
        end = time.monotonic() + 30.0
        while not held():   # (the cut fences the pipeline)
            assert time.monotonic() < end, "no lease on two of three"
            time.sleep(0.01)
        carried = core.stats["device_transfers"]
        nh_old.request_leader_transfer(1, target)
        end = time.monotonic() + 30.0
        while core.stats["device_transfers"] == carried:
            assert time.monotonic() < end, "no launch carried the request"
            time.sleep(0.001)
        assert node.peer.raft.leader_transfer_target == target
        assert not held()
        t0 = time.monotonic()
        while not held():
            assert time.monotonic() - t0 < 30.0, "the lease never came back"
            assert nh_old.is_leader_of(1)
            time.sleep(0.005)
        # not before the window the kernel gives the transfer
        assert time.monotonic() - t0 >= 0.9 * ET * RTT_MS / 1000.0
        assert node.peer.raft.leader_transfer_target == 0
        assert core._xfer_watch == {}
        assert nh_old.is_leader_of(1) and node.lease_cell is not None
        assert core.stats["divergence_halts"] == 0
    finally:
        c.close()


def test_the_entry_applied_at_the_conflict_index_settles_a_doomed_stretch(
        tmp_path):
    """The rule, on a replica's own tables: a proposal whose entry went
    stays pending until the conflict index is applied.  The term that
    stood there: the old branch won after all, the record is forgotten
    and the proposal is left to complete as it is applied.  Another
    term: ``DROPPED``, counted.  An index the batch never covers (a
    snapshot went over it): forgotten, left to its deadline."""
    reset_inproc_network()
    nh = NodeHost(NodeHostConfig(
        nodehost_dir=str(tmp_path / "nh"), rtt_millisecond=5,
        raft_address="doom-1"))
    try:
        nh.start_replica({1: "doom-1"}, False, KV, shard_config(1, 1))
        node = nh._nodes[1]
        session = nh.get_noop_session(1)
        far = node.tick_count + 10**6

        def doomed_proposal(index, term):
            entry, rs = node.pending_proposal.propose(session, b"a=1", far)
            node._note_doomed([(index, term, [
                _entry(index, term), _entry(index + 1, term, key=entry.key),
                _entry(index + 2, term, key=entry.key + 77)])])  # not ours
            assert [(i, t, [k for _i, _t, _tab, k in keyed])
                    for i, t, keyed in node._doomed] == [
                        (index, term, [entry.key])]
            return rs

        # nobody of ours in the stretch: no record
        node._note_doomed([(7, 3, [_entry(7, 3, key=12345)])])
        assert node._doomed == []
        # a batch below the conflict index settles nothing
        rs = doomed_proposal(7, 3)
        node._settle_doomed([_entry(5, 3), _entry(6, 3)])
        assert len(node._doomed) == 1 and rs.code is None
        # the old term at 7: it came back
        node._settle_doomed([_entry(7, 3), _entry(8, 3, key=rs.key)])
        assert node._doomed == [] and rs.code is None
        assert node.pending_proposal.has(rs.key)
        # another term at 7: dead, all of the stretch
        rs = doomed_proposal(7, 3)
        node._settle_doomed([_entry(6, 3), _entry(7, 4)])
        assert node._doomed == []
        assert rs.code == RequestResultCode.DROPPED
        assert not node.pending_proposal.has(rs.key)
        assert nh.host_totals.values["proposals_dropped_truncated"] == 1
        # the old term back at 7 and the batch ends there: the entry at
        # 8 waits, a stretch of its own, for the batch that covers it
        rs = doomed_proposal(7, 3)
        node._settle_doomed([_entry(6, 3), _entry(7, 3)])
        assert [(i, t) for i, t, _keyed in node._doomed] == [(8, 3)]
        assert rs.code is None
        # ... and that batch brings another leader's entry at 8: only
        # the head of the old branch came back, the rest is dead
        node._settle_doomed([_entry(8, 5)])
        assert node._doomed == []
        assert rs.code == RequestResultCode.DROPPED
        assert nh.host_totals.values["proposals_dropped_truncated"] == 2
        # the same in one batch: 7 as it stood, 8 replaced
        rs = doomed_proposal(7, 3)
        node._settle_doomed([_entry(7, 3), _entry(8, 5)])
        assert node._doomed == [] and rs.code == RequestResultCode.DROPPED
        assert nh.host_totals.values["proposals_dropped_truncated"] == 3
        # the index skipped: nothing can be said
        rs = doomed_proposal(7, 3)
        node._settle_doomed([_entry(9, 4)])
        assert node._doomed == [] and rs.code is None
        assert nh.host_totals.values["proposals_dropped_truncated"] == 3
    finally:
        nh.close()
