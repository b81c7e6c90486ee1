"""Headline benchmark: raft on one chip — kernel, device loop, product.

North star (BASELINE.json): step 100k concurrent raft groups at >=10k
ticks/sec on a single v5e-1 == 1e9 group-ticks/sec.

Three phases, one JSON line:

* **Phase A — tick throughput** (the north-star metric): all 3 replicas
  of 100k groups as 300k device rows, 32 logical ticks fused per launch,
  steady-state launch throughput.  This is the ceiling: the emptiest
  hot path, no message exchange.
* **Phase B — device loop** (the `device_loop` sub-object): the same
  topology runs consensus entirely on device via ops/route.py — every
  round each row ticks, every leader appends one proposal, messages
  are routed device-side into peer inboxes, and commit indexes advance
  through genuine REPLICATE/RESP quorum cycles.  This is a KERNEL-LOOP
  bench: no NodeHost, no WAL, no sessions, no futures (r4 reported it
  as "consensus", which invited misreading it as product throughput —
  verdict r4 weak #3).
* **Phase C — product-path consensus** (the `consensus` sub-object,
  `product_path: true`): committed proposals/sec through the PUBLIC
  NodeHost API — sessions, futures, colocated device engine, tan WAL,
  SM apply — pipelined over >=1k shards for >=60s, with latency
  percentiles.  This is the row comparable to the reference's headline
  (upstream README's ~9M proposals/sec on 3 Xeon boxes [U]).

The primary metric stays group-ticks/sec vs the 1e9 target.
"""
from __future__ import annotations

import functools
import json
import os
import time

import numpy as np


def _sync(jax, st):
    """Execution barrier closing a timed window: a tiny readback of the
    state, which returns only once every launch queued before it has
    run (dispatch is asynchronous; a window closed without a barrier
    measures the enqueue rate)."""
    np.asarray(jax.device_get(st.term[:1]))


def phase_a(jax, GROUPS: int, iters: int) -> float:
    from dragonboat_tpu.ops.kernel import state_to_internal, step_internal
    from dragonboat_tpu.ops.types import (
        DeviceState,
        Inbox,
        MT_TICK,
        make_state_np,
    )

    REPLICAS = 3
    G = GROUPS * REPLICAS
    # Every inbox slot carries one count-carrying fused tick (the product
    # engine's multi-tick fusion, one slot per planner generation); the
    # kernel's slot loop runs all M slots inside ONE dispatch, so a
    # launch advances M*TICKS logical ticks.  Each slot is capped at
    # election_timeout//2 (one timer threshold crossing per slot, same
    # cap the engine's planner applies), and phase A discards outbound
    # messages between slots exactly as it always discarded them
    # between launches — M slots per launch is the same computation as
    # M launches, minus the per-launch dispatch + boundary overhead
    # (measured r5: 2.6 ms dispatch + ~12 ms boundary transposes at
    # 300k rows, which together capped r5 at 1.4e8).
    # M=12/O=8 measured best on the v5e (r5 sweep: M=8 1.05e9, M=12
    # 1.20e9, M=16 overflows O=8 heavily; O=10/12 cost more buf traffic
    # than the 0.4% of rows that overflow at O=8 — those are handled
    # honestly by the escalation subtraction below)
    P, W, M, E, O = 3, 8, 12, 1, 8
    TICKS_PER_LAUNCH = 32
    TICKS = TICKS_PER_LAUNCH * M

    shard_ids = np.repeat(np.arange(1, GROUPS + 1, dtype=np.int32), REPLICAS)
    replica_ids = np.tile(np.arange(1, REPLICAS + 1, dtype=np.int32), GROUPS)
    peer_ids = np.broadcast_to(
        np.arange(1, REPLICAS + 1, dtype=np.int32), (G, P)
    ).copy()

    cols = make_state_np(
        G, P, W,
        shard_ids=shard_ids, replica_ids=replica_ids, peer_ids=peer_ids,
        election_timeout=2 * TICKS_PER_LAUNCH, heartbeat_timeout=2,
    )
    # INTERNAL (G-last) layout end to end: the state lives on device in
    # the kernel's packed-lane layout across launches, so no launch pays
    # the [G,P]/[G,W]/[G,O,F] boundary transposes (numpy transposes here
    # are host-side packed copies, paid once at setup)
    st = state_to_internal(DeviceState(**cols))
    zm = np.zeros((M, G), np.int32)
    tick_col = np.full((M, G), MT_TICK, np.int32)
    count_col = np.full((M, G), TICKS_PER_LAUNCH, np.int32)
    inbox = Inbox(
        mtype=tick_col, from_id=zm, term=zm, log_term=zm,
        log_index=count_col, commit=zm, reject=zm, hint=zm, hint_high=zm,
        n_entries=zm,
        ent_term=np.zeros((M, E, G), np.int32),
        ent_cc=np.zeros((M, E, G), np.int32),
    )

    from dragonboat_tpu.ops.placement import default_device

    dev = default_device(jax)
    # device_put packs the numpy transpose views into contiguous device
    # buffers (host-side copy, paid once)
    st = jax.device_put(
        jax.tree.map(np.ascontiguousarray, st), dev
    )
    inbox = jax.device_put(inbox, dev)

    # donate the state so XLA updates buffers in place (~1.7x on v5e);
    # the escalation accumulator rides the SAME program so the honesty
    # guard below sees every launch, not just the last (review finding)
    import jax.numpy as jnp

    def _step_acc(s, i, a):
        s, out = step_internal(s, i, out_capacity=O)
        return s, out, a + (out.escalate != 0).sum()

    donated_acc = jax.jit(_step_acc, donate_argnums=(0, 2))

    def sync(st):
        _sync(jax, st)

    acc = jax.device_put(jnp.zeros((), jnp.int32), dev)
    for _ in range(10):  # warmup: compile + settle into election churn
        st, out, acc = donated_acc(st, inbox, acc)
    sync(st)

    best_dt = float("inf")
    esc_rows_total = 0
    for _ in range(3):  # best-of-3 windows: host timing noise
        acc = jax.device_put(jnp.zeros((), jnp.int32), dev)
        t0 = time.perf_counter()
        for _ in range(iters):
            st, out, acc = donated_acc(st, inbox, acc)
        sync(st)
        dt = time.perf_counter() - t0
        if dt < best_dt:
            best_dt = dt
            # escalated-row-launches ACCUMULATED over the whole timed
            # window (an escalated row stops processing its remaining
            # slots that launch; one-launch sampling could overcount)
            esc_rows_total = int(np.asarray(jax.device_get(acc)))
    # units: the metric is GROUP-ticks; esc counts replica ROWS (G =
    # GROUPS*REPLICAS), so one escalated row forfeits its launch's
    # ticks for 1/REPLICAS of a group — still conservative, since a
    # row escalating on slot k already executed k slots
    ticks_total = max(
        0.0,
        (GROUPS * iters - esc_rows_total / REPLICAS) * TICKS,
    )
    return ticks_total / best_dt


def phase_b(jax, GROUPS: int, warm_launches: int, timed_launches: int,
            K: int) -> dict:
    # the persistent compile cache matters most here (minutes of XLA
    # compile for the routed programs); set it even when called outside
    # main() — e.g. in the per-attempt subprocess
    from dragonboat_tpu.ops.placement import configure_compile_cache

    configure_compile_cache(jax)

    import jax.numpy as jnp

    from dragonboat_tpu.ops import route as R
    from dragonboat_tpu.ops.types import ROLE_LEADER, make_state

    REPLICAS = 3
    G = GROUPS * REPLICAS
    P, W, E, O = 3, 32, 4, 16
    BUDGET, BASE = 4, 2
    M = BASE + P * BUDGET  # the inbox IS the routing region layout

    shard_ids = np.repeat(np.arange(1, GROUPS + 1, dtype=np.int32), REPLICAS)
    replica_ids = np.tile(np.arange(1, REPLICAS + 1, dtype=np.int32), GROUPS)
    peer_ids = np.broadcast_to(
        np.arange(1, REPLICAS + 1, dtype=np.int32), (G, P)
    ).copy()
    # group-major layout -> analytic route tables (validated against
    # build_route_tables in tests/test_route.py)
    g = np.arange(G)
    dest = (((g // REPLICAS) * REPLICAS)[:, None] + np.arange(REPLICAS)).astype(
        np.int32
    )
    rank = np.broadcast_to((g % REPLICAS)[:, None], (G, P)).copy()

    st = make_state(
        G, P, W,
        shard_ids=shard_ids, replica_ids=replica_ids, peer_ids=peer_ids,
        election_timeout=10, heartbeat_timeout=2,
    )
    from dragonboat_tpu.ops.placement import default_device

    dev = default_device(jax)
    st = jax.device_put(st, dev)
    dest = jax.device_put(jnp.asarray(dest), dev)
    rank = jax.device_put(jnp.asarray(rank), dev)
    inbox = jax.device_put(R.make_prefill(st, M, E), dev)

    from dragonboat_tpu.ops.kernel import step as kernel_step

    # TWO jit units per round, NOT one fused program: XLA's compile time
    # goes superlinear in program size on the TPU backend (measured:
    # step 33s + route 148s separately, >25min fused).  Execution stays
    # pipelined — async dispatch lets the host enqueue rounds ahead, so
    # throughput is device time per round, not dispatch round-trips.
    step_j = jax.jit(
        lambda s, i: kernel_step(s, i, out_capacity=O), donate_argnums=(1,)
    )

    # dest/rank are ARGUMENTS, never closure constants: closed-over
    # arrays become embedded XLA constants, and the [G,P,B,E] broadcasts
    # derived from them constant-fold into tens of MB — compile time
    # explodes superlinearly with G (measured: route compiled in 148s at
    # 30k rows as-args, never finished at 300k as-constants).
    # Routing stats + escalations ACCUMULATE ON DEVICE: the bench reads
    # back ONLY on-device reductions, never [G] row arrays, so the timed
    # window holds no per-round device->host copy.  The accumulation is
    # FOLDED INTO route_j (a separate acc_add program costs one extra
    # dispatch per round).
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 5))
    def route_j(old_st, new_st, out, dest, rank, acc):
        st, ib, stats, n_esc = R.merge_and_route(
            old_st, new_st, out, dest, rank,
            M=M, E=E, budget=BUDGET, base=BASE, propose_leaders=True,
        )
        # stats accumulate IN this program (see above)
        acc = acc + jnp.concatenate(
            [jnp.stack(list(stats)), n_esc[None]]
        )
        return st, ib, acc

    @jax.jit
    def snapshot_commits(st):
        # per-group commit maxima stay on device for the later delta
        return st.committed.reshape(GROUPS, REPLICAS).max(1)

    @jax.jit
    def summarize_consensus(st, commit0):
        commit1 = st.committed.reshape(GROUPS, REPLICAS).max(1)
        delta = commit1 - commit0
        return (
            jnp.sum(delta),
            jnp.sum(delta > 0),
            jnp.sum(st.role == ROLE_LEADER),
        )

    def one_round(st, ib, acc):
        new_st, out = step_j(st, ib)
        return route_j(st, new_st, out, dest, rank, acc)

    def sync(st):
        _sync(jax, st)

    acc = jax.device_put(jnp.zeros((7,), jnp.int32), dev)
    t_warm = time.perf_counter()
    for _ in range(warm_launches * K):  # compile + elections settle
        st, inbox, acc = one_round(st, inbox, acc)
    sync(st)
    warm_secs = time.perf_counter() - t_warm  # dominated by XLA compile

    commit0 = snapshot_commits(st)  # stays device-side
    acc = jax.device_put(jnp.zeros((7,), jnp.int32), dev)
    # int32 acc lanes: bound the timed window so no lane (worst case
    # O messages per row per round) can cross 2^31 — chunked host
    # accumulation would mean mid-window readbacks (see the route_j
    # comment)
    rounds = min(timed_launches * K, (2**31 - 1) // max(G * O, 1))
    t0 = time.perf_counter()
    for _ in range(rounds):
        st, inbox, acc = one_round(st, inbox, acc)
    sync(st)
    dt = time.perf_counter() - t0

    committed_d, advancing_d, leaders_d = summarize_consensus(st, commit0)
    committed = int(committed_d)
    acc_t = np.asarray(acc, np.int64)  # 7 scalars, one tiny readback
    return {
        "groups": GROUPS,
        "replicas": REPLICAS,
        "rounds": rounds,
        "committed_entries_per_sec": round(committed / dt, 1),
        "commit_advance_per_group_per_round": round(
            committed / GROUPS / rounds, 4
        ),
        "consensus_group_ticks_per_sec": round(GROUPS * rounds / dt, 1),
        "rounds_per_sec": round(rounds / dt, 2),
        "leaders": int(leaders_d),
        "groups_advancing": int(advancing_d),
        "escalations": int(acc_t[6]),
        "dropped": int(acc_t[1] + acc_t[2] + acc_t[3]),
        # host-only message classes (forwarded PROPOSE etc.): carried by
        # the transport in the product engine, genuinely lost in this
        # pure-device loop — recorded so routing loss is never invisible
        "host_carried_lost": int(acc_t[5]),
        "messages_routed_per_sec": round(int(acc_t[0]) / dt, 1),
        "compile_plus_warm_secs": round(warm_secs, 1),
        "timed_secs": round(dt, 3),
    }


def phase_c(jax, SHARDS: int, duration: float, *, inflight: int = 8,
            workers: int = 8) -> dict:
    """PRODUCT-PATH consensus throughput: pipelined proposals through the
    PUBLIC NodeHost API — sessions, futures, colocated device engine,
    tan WAL (native group-commit writer), apply to the SM — sustained
    for ``duration`` seconds.  This is the reference's headline metric
    shape (committed proposals/sec through the API, upstream README
    [U]); phase B's device loop is the kernel ceiling, THIS is what a
    user gets end-to-end.
    """
    import shutil
    import sys
    import threading
    import time as _time

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from dragonboat_tpu.ops.placement import configure_compile_cache

    configure_compile_cache(jax)

    from dragonboat_tpu import (
        Config,
        EngineConfig,
        ExpertConfig,
        NodeHost,
        NodeHostConfig,
    )
    from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
    from dragonboat_tpu.storage.tan import tan_logdb_factory
    from dragonboat_tpu.transport.inproc import reset_inproc_network

    REPLICAS = 3
    ADDRS = {r: f"bench-nh-{r}" for r in range(1, REPLICAS + 1)}
    cap = 1
    while cap < SHARDS * REPLICAS:
        cap <<= 1
    reset_inproc_network()
    group = ColocatedEngineGroup(
        capacity=cap, P=3, W=16, M=8, E=4, O=32, budget=4,
    )
    nhs = {}
    t_boot = _time.time()
    for rid, addr in ADDRS.items():
        shutil.rmtree(f"/tmp/nh-bench-{rid}", ignore_errors=True)
        nhs[rid] = NodeHost(
            NodeHostConfig(
                nodehost_dir=f"/tmp/nh-bench-{rid}",
                rtt_millisecond=20,
                raft_address=addr,
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=1, apply_shards=4),
                    step_engine_factory=group.factory,
                    logdb_factory=tan_logdb_factory,
                ),
            )
        )
    sm_cls = _bench_sm_cls()
    report = {"product_path": True, "shards": SHARDS, "replicas": REPLICAS,
              "wal": "tan"}
    try:
        for nh in nhs.values():
            nh.pause_ticks()
        for shard in range(1, SHARDS + 1):
            for rid, nh in nhs.items():
                nh.start_replica(
                    ADDRS, False,
                    sm_cls,
                    Config(replica_id=rid, shard_id=shard,
                           election_rtt=20, heartbeat_rtt=2,
                           pre_vote=True, check_quorum=True,
                           snapshot_entries=0),
                )
        for nh in nhs.values():
            nh.resume_ticks()
        report["boot_secs"] = round(_time.time() - t_boot, 1)

        # full leader coverage before the timed window
        t0 = _time.time()
        while _time.time() - t0 < max(120.0, SHARDS * 0.1):
            covered = sum(
                1 for s in range(1, SHARDS + 1)
                if nhs[1]._nodes[s].peer.raft.log.committed >= 1
            )
            if covered == SHARDS:
                break
            _time.sleep(0.5)
        report["election_secs"] = round(_time.time() - t0, 1)
        report["leader_coverage"] = covered

        # pipelined proposers: each worker owns SHARDS/workers shards and
        # keeps `inflight` proposals outstanding per shard via the async
        # propose future (RequestState)
        stop = _time.time() + duration
        counts = [0] * workers
        errors = [0] * workers
        lat_ms: list = []
        lat_lock = threading.Lock()
        payload = b"x" * 16

        def worker(w):
            my = list(range(1 + w, SHARDS + 1, workers))
            nh = nhs[1 + (w % REPLICAS)]
            sessions = {s: nh.get_noop_session(s) for s in my}
            pending: list = []  # (rs, t0, shard)
            done = 0
            while _time.time() < stop:
                still = []
                for rs, t_sub, s in pending:
                    if rs._event.is_set():
                        if rs.code == 1:  # COMPLETED
                            done += 1
                            if done % 16 == 0:
                                # observed latency: includes up to one
                                # proposer poll cycle past the commit
                                # (the probe below is cycle-exact)
                                with lat_lock:
                                    if len(lat_ms) < 100000:
                                        lat_ms.append(
                                            (_time.time() - t_sub)
                                            * 1000.0
                                        )
                        else:
                            errors[w] += 1
                    else:
                        still.append((rs, t_sub, s))
                pending = still
                by_shard: dict = {}
                for _rs, _t, s in pending:
                    by_shard[s] = by_shard.get(s, 0) + 1
                issued = 0
                for s in my:
                    while by_shard.get(s, 0) < inflight:
                        try:
                            rs = nh.propose(sessions[s], payload, 30.0)
                        except Exception:  # noqa: BLE001
                            errors[w] += 1
                            break
                        pending.append((rs, _time.time(), s))
                        by_shard[s] = by_shard.get(s, 0) + 1
                        issued += 1
                # unconditional yield: a spin loop here steals the one
                # CPU from the engine threads under test (review
                # finding); completions arrive per engine generation
                # (ms-scale), so a 1 ms pace costs no throughput
                _time.sleep(0.001)
                counts[w] = done
            # drain the in-flight tail so late commits are counted;
            # failures count as errors exactly like the main loop, and
            # anything STILL unset after the drain window is recorded
            # as an error too (it will be terminated at NodeHost close)
            drain_end = _time.time() + 10.0
            while pending and _time.time() < drain_end:
                still = []
                for rs, t_sub, s in pending:
                    if rs._event.is_set():
                        if rs.code == 1:
                            done += 1
                        else:
                            errors[w] += 1
                    else:
                        still.append((rs, t_sub, s))
                pending = still
                if pending:
                    _time.sleep(0.01)
            errors[w] += len(pending)
            counts[w] = done

        # cycle-exact latency probe: a dedicated thread issuing SERIAL
        # sync proposals to a few shards under the full ambient load —
        # each sample is a true submit->commit round-trip, free of the
        # workers' poll-cycle observation bias
        probe_ms: list = []

        def prober():
            nh = nhs[1]
            targets = [1, max(1, SHARDS // 2), SHARDS]
            sess = {s: nh.get_noop_session(s) for s in targets}
            i = 0
            while _time.time() < stop:
                s = targets[i % len(targets)]
                i += 1
                t1 = _time.time()
                try:
                    nh.sync_propose(sess[s], payload, timeout=30.0)
                except Exception:  # noqa: BLE001
                    continue
                probe_ms.append((_time.time() - t1) * 1000.0)

        threads = [
            threading.Thread(target=worker, args=(w,), daemon=True,
                             name=f"bench-c-worker-{w}")
            for w in range(workers)
        ] + [threading.Thread(target=prober, daemon=True, name="bench-c-probe")]
        t0 = _time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=duration + 60.0)
        dt = _time.time() - t0
        committed = sum(counts)
        lat_ms.sort()
        probe_ms.sort()

        def pct(arr, p):
            return round(arr[int(len(arr) * p)], 1) if arr else None

        report.update(
            committed_proposals_per_sec=round(committed / dt, 1),
            committed=committed,
            errors=sum(errors),
            timed_secs=round(dt, 1),
            # observed: worker-poll timestamps (<= one poll cycle late)
            latency_observed_ms={
                "p50": pct(lat_ms, 0.50), "p90": pct(lat_ms, 0.90),
                "p99": pct(lat_ms, 0.99), "n": len(lat_ms)},
            # probe: serial sync_propose round-trips under ambient load
            latency_probe_ms={
                "p50": pct(probe_ms, 0.50), "p90": pct(probe_ms, 0.90),
                "p99": pct(probe_ms, 0.99), "n": len(probe_ms)},
            engine={k: v for k, v in group.core.stats.items()},
        )
    finally:
        for nh in nhs.values():
            nh.pause_ticks()
        for nh in nhs.values():
            nh.close()
    return report


def _bench_sm_cls():
    from dragonboat_tpu import IStateMachine

    class _BenchSM(IStateMachine):
        """Minimal in-memory regular SM for the product-path bench."""

        def __init__(self, shard_id, replica_id):
            self.n = 0

        def update(self, entry):
            from dragonboat_tpu import Result

            self.n += 1
            return Result(value=self.n)

        def lookup(self, query):
            return self.n

        def save_snapshot(self, w, files, done):
            import pickle

            w.write(pickle.dumps(self.n))

        def recover_from_snapshot(self, r, files, done):
            import pickle

            self.n = pickle.loads(r.read())

    return _BenchSM


def _measure_3replica_proposals(
    tag: str,
    *,
    proposals: int,
    warmup: int,
    rtt_ms: int,
    nh_extra=None,
    mid_run=None,
):
    """Shared 3-replica in-proc proposal harness for the host-path
    bench guards (phase_obs / phase_lockcheck): bring-up, 30s leader
    wait, warmup + timed proposal loop with the 4-attempt
    leader-failover retry.  ``nh_extra`` adds NodeHostConfig kwargs;
    ``mid_run(nhs, leader)`` fires once at the loop midpoint (e.g. a
    leader transfer).  Returns ``{"p50_ms", "wall_s"}`` or
    ``{"error"}``.  One harness, one drift surface (review finding:
    two near-identical copies had already diverged)."""
    import shutil

    from dragonboat_tpu import (
        Config,
        EngineConfig,
        ExpertConfig,
        NodeHost,
        NodeHostConfig,
        RequestDropped,
        TimeoutError_,
    )
    from dragonboat_tpu.transport.inproc import reset_inproc_network

    sm_cls = _bench_sm_cls()
    reset_inproc_network()
    addrs = {r: f"bench-{tag}-{r}" for r in (1, 2, 3)}
    nhs = {}
    for r, addr in addrs.items():
        d = f"/tmp/nh-bench-{tag}-{r}"
        shutil.rmtree(d, ignore_errors=True)
        nhs[r] = NodeHost(NodeHostConfig(
            nodehost_dir=d,
            rtt_millisecond=rtt_ms,
            raft_address=addr,
            expert=ExpertConfig(
                engine=EngineConfig(exec_shards=2, apply_shards=2),
            ),
            **(nh_extra or {}),
        ))
    try:
        for r, nh in nhs.items():
            nh.start_replica(
                addrs, False, sm_cls,
                Config(shard_id=1, replica_id=r,
                       election_rtt=10, heartbeat_rtt=1),
            )
        deadline = time.monotonic() + 30.0
        leader = None
        while time.monotonic() < deadline and leader is None:
            lid, ok = nhs[1].get_leader_id(1)
            if ok:
                leader = nhs[lid]
            else:
                time.sleep(0.02)
        if leader is None:
            return {"error": f"no leader within 30s ({tag})"}
        s = leader.get_noop_session(1)
        lat = []
        t_wall = time.perf_counter()
        for i in range(warmup + proposals):
            if mid_run is not None and i == warmup + proposals // 2:
                mid_run(nhs, leader)
            t0 = time.perf_counter()
            # a freshly-elected leader drops proposals in its
            # pre-noop-commit window, and a load spike can trigger
            # re-election mid-run (timeout against the old leader):
            # re-resolve the leader and retry, like a real client
            # would — the retry wait lands in the sample, honestly
            # fattening the tail
            for attempt in range(4):
                try:
                    leader.sync_propose(s, b"x" * 32, timeout=5.0)
                    break
                except (RequestDropped, TimeoutError_) as e:
                    if attempt == 3:
                        e.args = (
                            f"{e.args[0] if e.args else e} "
                            f"(tag={tag} i={i})",
                        )
                        raise
                    time.sleep(0.05)
                    lid, ok = nhs[1].get_leader_id(1)
                    if ok and lid in nhs and nhs[lid] is not leader:
                        leader = nhs[lid]
                        s = leader.get_noop_session(1)
            if i >= warmup:
                lat.append(time.perf_counter() - t0)
        wall = time.perf_counter() - t_wall
        lat.sort()
        return {
            "p50_ms": round(lat[len(lat) // 2] * 1000.0, 4),
            "wall_s": round(wall, 3),
        }
    finally:
        for nh in nhs.values():
            try:
                nh.close()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass


def phase_obs(
    proposals: int = 400,
    *,
    rtt_ms: int = 2,
    warmup: int = 50,
) -> dict:
    """Observability bench guard (obs tentpole, docs/OBSERVABILITY.md):
    p50 proposal latency through the public NodeHost API on a 3-replica
    in-proc shard, measured with ``enable_tracing=False`` (the default
    — its hot-path cost is one attribute load) and again with tracing +
    flight recorder fully on at sample rate 1.0.  The "off" number is
    what the <2%-vs-seed acceptance gate compares; the on/off ratio
    bounds the worst-case cost of turning the layer on.  Pure host path
    — no device, no jax."""

    def measure(tracing: bool) -> float:
        r = _measure_3replica_proposals(
            f"obs-{'on' if tracing else 'off'}",
            proposals=proposals,
            warmup=warmup,
            rtt_ms=rtt_ms,
            nh_extra=dict(
                enable_tracing=tracing, enable_flight_recorder=tracing
            ),
        )
        return -1.0 if "error" in r else r["p50_ms"]

    p50_off = measure(False)
    p50_on = measure(True)
    if p50_off < 0 or p50_on < 0:
        # the no-leader sentinel must not masquerade as a (negative,
        # absurdly good) latency to the acceptance gate
        return {
            "proposals": proposals,
            "error": "no leader within 30s "
                     f"(off={p50_off >= 0} on={p50_on >= 0})",
        }
    return {
        "proposals": proposals,
        "p50_off_ms": round(p50_off, 4),
        "p50_on_ms": round(p50_on, 4),
        "tracing_overhead_pct": round((p50_on / p50_off - 1.0) * 100.0, 1),
    }


def _acquire_cost_ns(lock, iters: int = 200_000) -> float:
    t0 = time.perf_counter()
    for _ in range(iters):
        lock.acquire()
        lock.release()
    return (time.perf_counter() - t0) / iters * 1e9


def phase_lockcheck(
    proposals: int = 300,
    *,
    rtt_ms: int = 2,
    warmup: int = 40,
) -> dict:
    """Lock-order-witness bench guard (analysis/, docs/ANALYSIS.md).

    The number that actually PREDICTS what the witness costs the
    lock-churning chaos tests is the CPU-bound per-acquire micro-cost
    (``acquire_ns``: real lock vs tracked lock, uncontended and with
    another lock held — the held-stack/edge bookkeeping path); the
    cluster workload below is rtt-sleep-dominated, so its wall numbers
    are a sanity floor, not a bound (review finding: a wall-only guard
    would show ~0%% while the witness silently ate tier-1's headroom).
    The cluster pass still runs off vs on — with a mid-run leader
    transfer to churn election/transfer lock paths — to catch
    functional regressions (cycles on a green run, lost tracking).
    Pure host path — no device, no jax."""
    import threading

    from dragonboat_tpu.analysis import lockcheck

    real_ns = _acquire_cost_ns(threading.Lock())
    w_micro = lockcheck.install()
    try:
        tracked = w_micro.make_lock("bench:micro")
        on_ns = _acquire_cost_ns(tracked)
        with w_micro.make_lock("bench:outer"):
            on_held_ns = _acquire_cost_ns(tracked)
    finally:
        lockcheck.uninstall()

    def transfer(nhs, leader):
        lid, ok = nhs[1].get_leader_id(1)
        if ok:
            leader.request_leader_transfer(1, (lid % 3) + 1)

    witness_stats: dict = {}

    def measure(check: bool) -> dict:
        witness = lockcheck.install() if check else None
        try:
            return _measure_3replica_proposals(
                f"lck-{'on' if check else 'off'}",
                proposals=proposals,
                warmup=warmup,
                rtt_ms=rtt_ms,
                mid_run=transfer,
            )
        finally:
            if witness is not None:
                lockcheck.uninstall()
                r = witness.report()
                witness_stats.update(
                    tracked_locks=r["tracked_locks"],
                    acquires=r["acquires"],
                    edges=r["edges"],
                    cycles=len(r["cycles"]),
                    slow_waits=len(r["slow_waits"]),
                )

    off = measure(False)
    on = measure(True)
    acquire_ns = {
        "real": round(real_ns, 1),
        "tracked": round(on_ns, 1),
        "tracked_holding_another": round(on_held_ns, 1),
        "x_overhead": round(on_ns / real_ns, 2) if real_ns else None,
    }
    if "error" in off or "error" in on:
        return {
            "proposals": proposals,
            "acquire_ns": acquire_ns,
            "error": off.get("error") or on.get("error"),
        }
    return {
        "proposals": proposals,
        "acquire_ns": acquire_ns,
        "p50_off_ms": off["p50_ms"],
        "p50_on_ms": on["p50_ms"],
        "wall_off_s": off["wall_s"],
        "wall_on_s": on["wall_s"],
        "overhead_pct": round((on["wall_s"] / off["wall_s"] - 1.0) * 100.0, 1),
        "witness": witness_stats,
    }


def phase_jaxcheck() -> dict:
    """Device-plane auditor bench guard (analysis/jaxcheck,
    docs/ANALYSIS.md "Device-plane audit").

    Times the FULL static audit — tracing and lowering every registered
    ops/ entry point at the canonical geometry — which is the number
    scripts/lint.sh's <60s gate budget rides on, and reports the
    registry surface so a shrinking entry-point count (a silently
    dropped registration) shows in the bench record, not only in the
    lint gate.  Pure abstract tracing: no kernels compile, no device
    memory moves, safe on any backend."""
    import time as _time

    from dragonboat_tpu.analysis import jaxcheck
    from dragonboat_tpu.ops import registry

    t0 = _time.perf_counter()
    findings = jaxcheck.audit()
    wall = _time.perf_counter() - t0
    by_rule: dict = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    return {
        "entry_points": len(registry.ENTRY_POINTS),
        "donating": sum(1 for ep in registry.ENTRY_POINTS if ep.donate),
        "findings": len(findings),
        "by_rule": by_rule,
        "wall_s": round(wall, 2),
    }


def phase_wirecheck() -> dict:
    """Wire-plane auditor bench guard (analysis/wirecheck,
    docs/ANALYSIS.md "Wire-plane audit").

    Times the FULL audit at the lint-gate fuzz depth (goldens + skew
    matrix + 500 mutations/decoder + rot guards) — the number
    scripts/lint.sh's <60s gate budget rides on — and measures
    per-codec encode/decode throughput over the registry's canonical
    frames so a codec perf regression (a decoder growing an O(n^2)
    scan, an encoder copying twice) shows in the r-ledgers, not only
    as a mysteriously slower transport.  Host-only bytes work: no
    device, no sockets, no disk."""
    import time as _time

    from dragonboat_tpu.analysis import wire_registry, wirecheck

    t0 = _time.perf_counter()
    findings = wirecheck.audit(fuzz_n=500)
    wall = _time.perf_counter() - t0
    codecs: dict = {}
    for e in wire_registry.REGISTRY:
        label = next(iter(e.samples))
        blob = e.samples[label]()
        # enough reps for a stable number, capped so the big frames
        # (snapshotio container) don't dominate the phase budget
        n = max(20, min(2000, (4 << 20) // max(len(blob), 1)))
        t0 = _time.perf_counter()
        for _ in range(n):
            e.decode(blob)
        dt = _time.perf_counter() - t0
        row = {
            "bytes": len(blob),
            "dec_mb_s": round(len(blob) * n / dt / 1e6, 1),
        }
        if e.encode is not None:
            t0 = _time.perf_counter()
            for _ in range(n):
                e.encode()
            et = _time.perf_counter() - t0
            row["enc_mb_s"] = round(len(blob) * n / et / 1e6, 1)
        codecs[e.name] = row
    return {
        "codecs_registered": len(wire_registry.REGISTRY),
        "goldens": sum(len(e.samples) for e in wire_registry.REGISTRY),
        "findings": len(findings),
        "audit_wall_s": round(wall, 2),
        "codecs": codecs,
    }


def phase_hostplane(rows_list=None, launches: int = 6) -> dict:
    """Host-plane plan/merge stage cost, scalar (the r5 shape) vs
    vectorized (r6, ops/hostplane.py), over fabricated generations.

    The r5 ledger's Config 4 showed t_plan (887 s) + t_updates (538 s)
    of per-row Python dominating a 2,731 s 50k-shard election at 250k
    replica rows while the device plane cost ~4 s.  This phase times
    exactly the stages the r6 vectorization replaced, on fabricated
    generation traces at each ``rows`` tier:

    * plan  — the classifier's static-eligibility pass: per-row
      ``_RowMeta`` attribute probes behind dict lookups (scalar) vs
      one ``classify_static`` lane pass (vectorized);
    * updates — the merge row-set machinery: per-row flag probes,
      ``*_at`` dict builds and ``all(g in …)`` membership scans
      (scalar) vs ``build_merge_sets`` + ``pos_of``/``covered`` index
      arrays (vectorized).

    Two generation shapes run per launch — an election-storm mix
    (most rows live) and a steady-state mix (sparse) — because the
    scalar cost is O(rows) in BOTH (the storm pays it in the loop
    bodies, the steady state in the scans).  Parity is asserted every
    generation: the numbers are only comparable if the outputs are
    byte-identical.  Host-only (numpy; no device, no cluster).
    Default tier 10k rows rides the standard bench; the 50k/250k
    tiers (the r5 ledger's scale) run when BENCH_HOSTPLANE_HEAVY=1 —
    same env-gating convention as SCALE_CHURN.
    """
    import time as _time

    import numpy as np

    from dragonboat_tpu.ops import hostplane as hp

    if rows_list is None:
        rows_list = [10_000]
        if bool(int(os.environ.get("BENCH_HOSTPLANE_HEAVY", "0"))):
            rows_list += [50_000, 250_000]

    class _Meta:  # the r5 per-row probe target
        __slots__ = ("plan_ok", "dirty", "esc_hold")

        def __init__(self, plan_ok, dirty, esc_hold):
            self.plan_ok = plan_ok
            self.dirty = dirty
            self.esc_hold = esc_hold

    def _gen(rng, G, storm: bool):
        from dragonboat_tpu.ops.types import (
            F_APPEND, F_CHANGED, F_COUNT, F_ESC, F_NEED_SS,
        )

        flags = np.zeros((G,), np.int64)
        mix = (
            ((F_CHANGED, 0.9), (F_COUNT, 0.1), (F_APPEND, 0.5),
             (F_NEED_SS, 0.01), (F_ESC, 0.002))
            if storm else
            ((F_CHANGED, 0.02), (F_COUNT, 0.01), (F_APPEND, 0.005),
             (F_NEED_SS, 0.001), (F_ESC, 0.0005))
        )
        for bit, p in mix:
            flags |= np.where(rng.random(G) < p, bit, 0)
        alive = rng.random(G) < 0.98
        batch_gs = np.nonzero(
            rng.random(G) < (0.95 if storm else 0.05)
        )[0].astype(np.int64)
        prop_gs = (
            batch_gs[rng.random(len(batch_gs)) < 0.02]
            if len(batch_gs) else np.zeros((0,), np.int64)
        )
        return flags, alive, batch_gs, prop_gs

    def _scalar_r5_merge(flags_l, alive_l, batch_l, prop_l, G):
        """The RAW r5 loop shapes, canonicalization-free: what the old
        merge tail actually paid per launch.  (hostplane's
        build_merge_sets_scalar is the PARITY oracle and sorts/boxes
        its outputs for comparison — timing it overstated the scalar
        cost by ~20%, review finding.)"""
        from dragonboat_tpu.ops.types import (
            F_ANY_LIVE, F_APPEND, F_COUNT, F_ESC, F_NEED_SS,
        )

        batch_set = set(batch_l)
        esc_batch = [g for g in batch_l if flags_l[g] & F_ESC]
        esc_other = [
            g for g in range(G)
            if alive_l[g] and g not in batch_set and flags_l[g] & F_ESC
        ]
        esc_set = set(esc_batch) | set(esc_other)
        live = [g for g in batch_l if g not in esc_set]
        for g in range(G):
            if (
                alive_l[g]
                and g not in batch_set
                and g not in esc_set
                and flags_l[g] & F_ANY_LIVE
            ):
                live.append(g)
        slot_rows = [g for g in prop_l if g not in esc_set]
        slot_set = set(slot_rows)
        buf_rows = [g for g in live if flags_l[g] & F_COUNT]
        append_rows = [g for g in live if flags_l[g] & F_APPEND]
        need_rows = [g for g in live if flags_l[g] & F_NEED_SS]
        sum_rows = [
            g for g in live if (flags_l[g] & F_ANY_LIVE) or g in slot_set
        ]
        return buf_rows, append_rows, slot_rows, need_rows, sum_rows

    tiers = []
    for G in rows_list:
        rng = np.random.default_rng(6)
        lanes = hp.RowLanes(G)
        lanes.attached[:] = rng.random(G) < 0.98
        lanes.dirty[:] = rng.random(G) < 0.05
        lanes.plan_ok[:] = rng.random(G) < 0.9
        lanes.esc_hold[:] = np.where(rng.random(G) < 0.01, 3, 0)
        metas = {
            g: _Meta(bool(lanes.plan_ok[g]), bool(lanes.dirty[g]),
                     int(lanes.esc_hold[g]))
            for g in range(G) if lanes.attached[g]
        }
        gs = np.where(lanes.attached, np.arange(G), -1).astype(np.int64)
        gs_l = gs.tolist()
        t_plan_s = t_plan_v = 0.0
        t_upd_s = t_upd_v = 0.0
        for li in range(launches):
            # ---- plan classifier ---------------------------------
            t0 = _time.perf_counter()
            out_s = [False] * len(gs_l)
            for i, g in enumerate(gs_l):  # the r5 probe shape
                m = metas.get(g)
                if (
                    m is not None
                    and m.plan_ok
                    and not m.dirty
                    and m.esc_hold == 0
                ):
                    out_s[i] = True
            t_plan_s += _time.perf_counter() - t0
            t0 = _time.perf_counter()
            out_v = hp.classify_static(lanes, gs)
            t_plan_v += _time.perf_counter() - t0
            assert out_v.tolist() == out_s, "classify parity broke"
            # ---- merge row sets ----------------------------------
            for storm in (True, False):
                flags, alive, batch_gs, prop_gs = _gen(rng, G, storm)
                flags_l = flags.tolist()
                alive_l = alive.tolist()
                batch_l = batch_gs.tolist()
                prop_l = prop_gs.tolist()
                t0 = _time.perf_counter()
                raw = _scalar_r5_merge(flags_l, alive_l, batch_l,
                                       prop_l, G)
                # the r5 dict builds + membership scans (device rows =
                # the exact sets, the common single-sync launch shape)
                at = {g: k for k, g in enumerate(raw[4])}
                _ = all(g in at for g in raw[4])
                t_upd_s += _time.perf_counter() - t0
                t0 = _time.perf_counter()
                sets = hp.build_merge_sets(
                    flags, alive, batch_gs, prop_gs, G=G
                )
                pos = hp.pos_of(G, sets.sum_rows)
                _ = hp.covered(pos, sets.sum_rows)
                t_upd_v += _time.perf_counter() - t0
                # parity OUTSIDE the timed windows: the vectorized
                # sets against the canonical oracle, and the raw r5
                # shapes against the same sets
                hp.assert_merge_parity(
                    flags, alive, batch_gs, prop_gs, sets, G=G
                )
                assert sorted(raw[4]) == sets.sum_rows.tolist(), (
                    "raw r5 shape diverged from the oracle"
                )
        tiers.append({
            "rows": G,
            "launches": launches,
            "t_plan_scalar_ms": round(t_plan_s * 1000, 2),
            "t_plan_vec_ms": round(t_plan_v * 1000, 2),
            "plan_speedup": round(t_plan_s / max(t_plan_v, 1e-9), 1),
            "t_updates_scalar_ms": round(t_upd_s * 1000, 2),
            "t_updates_vec_ms": round(t_upd_v * 1000, 2),
            "updates_speedup": round(t_upd_s / max(t_upd_v, 1e-9), 1),
        })
    return {"tiers": tiers, "parity": True}


def phase_day(seed: int = 7, scale: float = 0.6) -> dict:
    """Production-day scenario guard (dragonboat_tpu/scenario/,
    docs/SCENARIO.md): one seeded mini-day over the mixed
    on-disk/in-memory/witness fleet under live gateway traffic — every
    disturbance class fired, every recovery under assert_recovery_sla,
    the whole history Wing-Gong-audited across the DR boundary.

    The emitted record is the DayReport's ledger surface: baseline
    committed/s, the per-fault-class throughput-dip table, worst/p99
    recovery per class and the audit verdict — the repo's end-to-end
    "can it run a real day in production" number.  Host path only (no
    device); BENCH_DAY gate; BENCH_DAY_SEED/BENCH_DAY_SCALE knobs."""
    from dragonboat_tpu.scenario import DayPlan, ScenarioRunner

    plan = DayPlan.mini(seed, scale=scale)
    r = ScenarioRunner(plan, tag=f"bench-day-{seed}").run()
    # the elastic loop's own ledger surface: load-driven moves fired,
    # the hot shard's p99 at the storm peak vs after the move, shed
    # delta over the storm window (ISSUE 18 acceptance numbers)
    el = next((p for p in r.phases if p.get("name") == "elastic"), {})
    elastic = {
        "moves": el.get("events", 0),
        "quiet_moves": el.get("quiet_moves", 0),
        "p99_storm_ms": round(el.get("p99_storm_s", 0.0) * 1000, 1),
        "p99_after_ms": round(el.get("p99_after_s", 0.0) * 1000, 1),
        "shed_delta": el.get("shed_delta", 0),
        "colocated_leaders": bool(el.get("colocated_leaders", False)),
    }
    return {
        "ok": r.ok,
        "seed": seed,
        "scale": scale,
        "wall_s": round(r.wall_s, 1),
        "baseline_committed_per_s": round(r.baseline_committed_per_s, 1),
        "fault_dips": {k: round(v, 3) for k, v in r.fault_dips.items()},
        "recovery": r.recovery,
        "disturbances_fired": r.disturbances_fired,
        "elastic": elastic,
        "audit_ok": bool(r.audit.get("ok", False)),
        "ops_ok": r.audit.get("ops", {}).get("ok", 0),
        "aborted": r.aborted,
        "sla_violations": sum(
            c.get("violations", 0) for c in r.recovery.values()
        ),
    }


def phase_readplane() -> dict:
    """Read-plane guard (dragonboat_tpu/readplane/, docs/READPLANE.md):
    the follower-served read claim measured over a REAL multi-process
    fleet (scenario/multiproc.ProcFleet — separate OS processes, TCP +
    gossip + RPC only, SIGKILL nemesis).

    Four planes, one record:

    * **the 100k-session plane** — exactly-once sessions registered
      over the RPC door across ``shards-1`` session shards (shard 1
      stays the audited traffic shard), each shard kept under the
      4096-per-SM session LRU cap so every registered session stays
      CONCURRENT (never evicted).  Registration is wall-budgeted
      (``BENCH_READPLANE_REG_SECS``) and the achieved count + rate are
      reported honestly — ``sessions.ok`` says whether the target was
      reached on this box.
    * **exactly-once probes** — per-shard canary sessions (the FIRST
      registered, so eviction would hit them first) replay the
      ambiguous-timeout retry verbatim: propose, re-send the SAME
      series with a DIFFERENT payload, read back.  Cached answer +
      unmoved state or it counts as a violation; a post-kill sample
      re-proves it across a leader SIGKILL + WAL replay.
    * **the saturation windows** — closed-loop readers against the hot
      keys through ``Gateway.read_at``: window A leader-only
      (LINEARIZABLE), window B the replica mix (70% BOUNDED_STALENESS /
      25% FOLLOWER_LINEARIZABLE / 5% LINEARIZABLE), window C the same
      mix with the shard leader SIGKILLed mid-window (bounded reads
      must keep serving off survivors; overruns must stay 0 — the
      router sheds StaleBoundExceeded instead of lying).  The serving
      capacity being scaled is the per-host RPC admission door
      (``BENCH_READPLANE_INFLIGHT`` slots shed SystemBusy beyond it):
      leader-only saturates ONE door, the replica mix has three.
      ``speedup`` = B/A reads-per-sec with both p99s under the same
      ``BENCH_READPLANE_P99_MS`` bound.  ``cpus`` is in the record
      because the ratio is core-starved below ~3 cores — judge the
      ≥2x acceptance number on a box with cores for 3 servers.
    * **the audit** — AuditClient traffic (writes + linearizable +
      follower + bounded reads) flows on shard 1 through all three
      windows and the kill; the offline Wing–Gong + stale + bounded
      passes must be green over everything that happened.

    BENCH_READPLANE gate; BENCH_READPLANE_{SESSIONS,SHARDS,SECS,
    REG_SECS,READERS,P99_MS,BOUND_TICKS,INFLIGHT,PORT} knobs;
    BENCH_SMOKE shrinks every default."""
    import shutil
    import threading
    from random import Random

    from dragonboat_tpu.audit import (
        AuditClient,
        HistoryRecorder,
        audit_set_cmd,
        run_audit,
    )
    from dragonboat_tpu.audit.history import run_workload
    from dragonboat_tpu.readplane import Consistency, StaleBoundExceeded
    from dragonboat_tpu.request import SystemBusy
    from dragonboat_tpu.scenario.multiproc import ProcFleet

    smoke = bool(int(os.environ.get("BENCH_SMOKE", "0")))

    def knob(name: str, dflt: str, smoke_dflt: str) -> str:
        return os.environ.get(name, smoke_dflt if smoke else dflt)

    target = int(knob("BENCH_READPLANE_SESSIONS", "100000", "2000"))
    shards = int(knob("BENCH_READPLANE_SHARDS", "33", "5"))
    win = float(knob("BENCH_READPLANE_SECS", "6", "3"))
    reg_budget = float(knob("BENCH_READPLANE_REG_SECS", "300", "45"))
    readers = int(knob("BENCH_READPLANE_READERS", "12", "6"))
    p99_bound_ms = float(os.environ.get("BENCH_READPLANE_P99_MS", "250"))
    bound_ticks = int(os.environ.get("BENCH_READPLANE_BOUND_TICKS", "100"))
    inflight = int(os.environ.get("BENCH_READPLANE_INFLIGHT", "32"))
    base_port = int(os.environ.get("BENCH_READPLANE_PORT", "29850"))

    AUDIT_SHARD = 1
    session_shards = list(range(2, shards + 1))
    # the SM session LRU holds 4096 per shard; 3800 leaves headroom so
    # a registered session is never silently evicted mid-phase (which
    # would turn the retry replay into a REAPPLY — the exact bug the
    # exactly-once probes exist to catch, not to manufacture)
    per_shard = min(3800, -(-target // max(1, len(session_shards))))
    quota = {sid: per_shard for sid in session_shards}
    extra = per_shard * len(session_shards) - target
    for sid in reversed(session_shards):
        take = min(max(0, extra), quota[sid])
        quota[sid] -= take
        extra -= take
    plane_capacity = sum(quota.values())

    out: dict = {
        "ok": False,
        "cpus": os.cpu_count(),
        # 3 server processes + the client need ~4 cores before the
        # replica-scaling ratio means anything: below that, every
        # window shares one core and the ratio measures the scheduler,
        # not the read plane (the strict `ok` still requires >=2x)
        "core_starved": (os.cpu_count() or 1) < 4,
        "serving_replicas": 3,
        "rpc_inflight_per_host": inflight,
        "p99_bound_ms": p99_bound_ms,
        "bound_ticks": bound_ticks,
    }
    workdir = "/tmp/bench-readplane"
    shutil.rmtree(workdir, ignore_errors=True)
    fleet = ProcFleet(3, workdir=workdir, base_port=base_port,
                      shards=shards, rpc_inflight=inflight)
    try:
        fleet.start()
        gw = fleet.gateway

        # ---- per-shard leader cache over the wire ---------------------
        # (replica ids == slot numbers, so get_leader_id maps straight
        # to fleet.handle; a kill clears the cache wholesale)
        cache_lock = threading.Lock()
        leader_cache: dict = {}

        def leader_handle(sid: int, wait: float = 0.0):
            deadline = time.monotonic() + wait
            while True:
                with cache_lock:
                    lid = leader_cache.get(sid)
                if lid is not None and fleet.procs[lid].poll() is None:
                    return fleet.handle(lid)
                for idx in fleet.live_slots():
                    try:
                        lid, lok = fleet.handle(idx).get_leader_id(sid)
                    except Exception:  # noqa: BLE001 — dark host
                        continue
                    if lok and lid in fleet.procs:
                        with cache_lock:
                            leader_cache[sid] = lid
                        return fleet.handle(lid)
                if time.monotonic() >= deadline:
                    return None
                time.sleep(0.05)

        def drop_leader(sid: int) -> None:
            with cache_lock:
                leader_cache.pop(sid, None)

        # ---- seed the hot keys on the audited shard -------------------
        h = gw.connect(AUDIT_SHARD, timeout=60.0)
        hot_keys = [f"hot{i}" for i in range(8)]
        for i, k in enumerate(hot_keys):
            h.sync_propose(audit_set_cmd(k, f"v{i}"), timeout=15.0)
        gw.close_handle(h)

        # ---- audited traffic through everything below -----------------
        rec = HistoryRecorder()
        audit_stop = threading.Event()
        hosts_now = lambda: {  # noqa: E731 — re-read per attempt
            fleet._key(i): fleet.handle(i) for i in fleet.live_slots()
        }
        audit_clients = [
            AuditClient(hosts_now, AUDIT_SHARD, rec, seed=40 + c,
                        op_timeout=10.0, per_try_timeout=2.0)
            for c in range(2)
        ]
        audit_threads = run_workload(
            audit_clients, [f"a{i}" for i in range(6)], audit_stop,
            read_ratio=0.3, stale_ratio=0.05, follower_ratio=0.15,
            bounded_ratio=0.15, bound_ticks=bound_ticks, pace=0.02,
        )

        # ---- the 100k-session plane -----------------------------------
        reg_lock = threading.Lock()
        pending = dict(quota)
        sessions_by_shard = {sid: [] for sid in session_shards}
        reg_deadline = time.monotonic() + reg_budget
        n_reg_threads = 8 if smoke else 16

        def reg_worker(w: int) -> None:
            rr = w
            while time.monotonic() < reg_deadline:
                with reg_lock:
                    open_s = [s for s in session_shards if pending[s] > 0]
                    if not open_s:
                        return
                    sid = open_s[rr % len(open_s)]
                    pending[sid] -= 1
                rr += 1
                hh = leader_handle(sid)
                if hh is None:
                    with reg_lock:
                        pending[sid] += 1
                    time.sleep(0.1)
                    continue
                try:
                    s = hh.sync_get_session(sid, timeout=5.0)
                except Exception:  # noqa: BLE001 — retry via fresh leader
                    drop_leader(sid)
                    with reg_lock:
                        pending[sid] += 1
                    continue
                with reg_lock:
                    sessions_by_shard[sid].append(s)

        t0 = time.monotonic()
        regs = [threading.Thread(target=reg_worker, args=(w,), daemon=True,
                                 name=f"rp-reg-{w}")
                for w in range(n_reg_threads)]
        for t in regs:
            t.start()
        for t in regs:
            t.join(reg_budget + 30)
        t_reg = time.monotonic() - t0
        registered = sum(len(v) for v in sessions_by_shard.values())
        out["sessions"] = {
            "target": target,
            "registered": registered,
            "session_shards": len(session_shards),
            "per_shard_lru_cap": 4096,
            "plane_capacity": plane_capacity,
            "reg_secs": round(t_reg, 1),
            "sessions_per_sec": round(registered / max(t_reg, 1e-9), 1),
            "ok": registered >= min(target, plane_capacity),
        }

        # ---- exactly-once probes (canary = FIRST session per shard) ---
        def eo_probe(sid: int, s, tag: str) -> bool:
            deadline = time.monotonic() + 30.0
            key = f"eo:{tag}"

            def call(fn):
                while True:
                    hh = leader_handle(sid, wait=5.0)
                    try:
                        if hh is None:
                            raise TimeoutError(f"no leader for {sid}")
                        return fn(hh)
                    except Exception:  # noqa: BLE001 — incl. kill window
                        drop_leader(sid)
                        if time.monotonic() > deadline:
                            raise
                        time.sleep(0.1)

            call(lambda hh: hh.sync_propose(
                s, audit_set_cmd(key, "once"), timeout=5.0))
            # the ambiguous-timeout retry, replayed verbatim: SAME
            # series id, DIFFERENT payload — exactly-once means the
            # cached answer comes back and the state does NOT move
            call(lambda hh: hh.sync_propose(
                s, audit_set_cmd(key, "twice"), timeout=5.0))
            s.proposal_completed()
            v = call(lambda hh: hh.sync_read(
                sid, ("get", key), timeout=5.0))
            if isinstance(v, bytes):
                v = v.decode()
            return v == "once"

        eo_probes = eo_failures = 0
        canaries = []
        rng = Random(4177)
        for sid in session_shards:
            ss = sessions_by_shard[sid]
            if not ss:
                continue
            canaries.append((sid, ss[0]))
            picks = [ss[0]]
            if len(ss) > 1:
                picks.append(ss[rng.randrange(1, len(ss))])
            for s in picks:
                eo_probes += 1
                try:
                    if not eo_probe(sid, s, f"{sid}:{s.client_id}"):
                        eo_failures += 1
                except Exception:  # noqa: BLE001 — an unverifiable probe
                    eo_failures += 1

        # ---- the saturation windows -----------------------------------
        LIN = Consistency.LINEARIZABLE
        FOL = Consistency.FOLLOWER_LINEARIZABLE
        BND = Consistency.BOUNDED_STALENESS
        # cumulative roll thresholds: 70% bounded / 25% follower / 5% lin
        MIX_REPLICA = ((0.70, BND), (0.95, FOL), (1.0, LIN))

        def window(name: str, mix, secs: float, kill_at=None) -> dict:
            per = [dict(ok=0, busy=0, shed=0, err=0, overrun=0)
                   for _ in range(readers)]
            lats = [[] for _ in range(readers)]
            stop_at = time.monotonic() + secs

            def rd(i: int) -> None:
                rr = Random(52000 + i)
                while time.monotonic() < stop_at:
                    key = hot_keys[rr.randrange(len(hot_keys))]
                    roll = rr.random()
                    level = mix[-1][1]
                    for p, lv in mix:
                        if roll < p:
                            level = lv
                            break
                    t1 = time.perf_counter()
                    try:
                        res = gw.read_at(
                            AUDIT_SHARD, key, consistency=level,
                            timeout=2.0, bound_ticks=bound_ticks,
                        )
                        per[i]["ok"] += 1
                        lats[i].append((time.perf_counter() - t1) * 1000)
                        if (level is BND
                                and res.staleness_ticks > bound_ticks):
                            per[i]["overrun"] += 1
                    except StaleBoundExceeded:
                        per[i]["shed"] += 1
                    except SystemBusy:
                        per[i]["busy"] += 1
                    except Exception:  # noqa: BLE001 — outage window
                        per[i]["err"] += 1

            rp0 = dict(gw.stats()["read_paths"])
            ths = [threading.Thread(target=rd, args=(i,), daemon=True,
                                    name=f"rp-{name}-{i}")
                   for i in range(readers)]
            w0 = time.monotonic()
            for t in ths:
                t.start()
            victim = None
            if kill_at is not None:
                time.sleep(kill_at)
                victim = fleet.leader_slot()
                fleet.kill(victim)
                with cache_lock:
                    leader_cache.clear()
            for t in ths:
                t.join(secs + 30)
            wall = time.monotonic() - w0
            rp1 = gw.stats()["read_paths"]
            tot = {k: sum(p[k] for p in per) for k in per[0]}
            all_lat = sorted(x for ls in lats for x in ls)

            def pctl(q: float) -> float:
                if not all_lat:
                    return -1.0
                return round(
                    all_lat[min(len(all_lat) - 1,
                                int(q * len(all_lat)))], 2)

            row = {
                "reads_ok": tot["ok"],
                "reads_per_sec": round(tot["ok"] / max(wall, 1e-9), 1),
                "busy_shed": tot["busy"],
                "bound_shed": tot["shed"],
                "errors": tot["err"],
                "bound_overruns": tot["overrun"],
                "p50_ms": pctl(0.50),
                "p99_ms": pctl(0.99),
                "wall_s": round(wall, 2),
                "read_paths": {
                    k: max(0, rp1.get(k, 0) - rp0.get(k, 0)) for k in rp1
                },
            }
            if victim is not None:
                row["killed_slot"] = victim
            return row

        wA = window("leader", ((1.0, LIN),), win)
        wB = window("replica", MIX_REPLICA, win)
        wC = window("replica-kill", MIX_REPLICA, max(win, 4.0),
                    kill_at=max(win, 4.0) * 0.4)
        out["windows"] = {
            "leader_only": wA,
            "replica_mix": wB,
            "replica_mix_kill": wC,
        }
        speedup = wB["reads_per_sec"] / max(wA["reads_per_sec"], 1e-9)
        out["speedup_replica_vs_leader"] = round(speedup, 2)
        out["speedup_ok"] = bool(
            speedup >= 2.0
            and 0 <= wA["p99_ms"] <= p99_bound_ms
            and 0 <= wB["p99_ms"] <= p99_bound_ms
        )

        # ---- recover the killed worker, re-prove exactly-once ---------
        victim = wC["killed_slot"]
        fleet.restart(victim)
        deadline = time.time() + 90
        while time.time() < deadline:
            try:
                if fleet.handle(victim).balance_shard_stats():
                    break
            except Exception:  # noqa: BLE001 — still replaying
                pass
            time.sleep(0.2)
        post_probes = post_failures = 0
        for sid, s in canaries[:8]:
            post_probes += 1
            try:
                if not eo_probe(sid, s, f"postkill:{sid}:{s.client_id}"):
                    post_failures += 1
            except Exception:  # noqa: BLE001
                post_failures += 1
        out["exactly_once"] = {
            "probes": eo_probes,
            "failures": eo_failures,
            "post_kill_probes": post_probes,
            "post_kill_failures": post_failures,
        }

        # ---- the offline audit over everything that happened ----------
        audit_stop.set()
        for t in audit_threads:
            t.join(timeout=20.0)
        ops = rec.ops()
        rep = run_audit(ops)  # no journals across process boundaries
        out["audit"] = {
            "ok": rep.ok,
            "ops": len(ops),
            "counts": rec.counts(),
            "problems": 0 if rep.ok else len(rep.describe().splitlines()),
        }

        overruns = sum(w["bound_overruns"] for w in out["windows"].values())
        out["bound_overruns"] = overruns
        out["ok"] = bool(
            rep.ok
            and overruns == 0
            and eo_failures == 0
            and post_failures == 0
            and out["sessions"]["ok"]
            and out["speedup_ok"]
        )
        return out
    finally:
        fleet.close()
        shutil.rmtree(workdir, ignore_errors=True)


def phase_fleetobs() -> dict:
    """Fleet-scope telemetry tax (dragonboat_tpu/obs/fleetscope.py,
    docs/OBSERVABILITY.md "Fleet scope"): what does polling the whole
    fleet's obs plane over RPC_OP_OBS cost the commit path?

    A real 3-process fleet (scenario/multiproc.ProcFleet — separate OS
    processes, TCP + gossip + RPC only) takes closed-loop traced
    gateway proposals through two equal windows: A with the parent's
    FleetScope poller OFF, B with it ON at BENCH_FLEETOBS_POLL_S.  The
    record carries committed/s for both, the overhead percentage, poll
    counts and reply bytes per poll (the bounded-ring payload the
    obs-bound lint rule caps), plus the cross-process stitch count and
    the SLO burn-rate ledger verdict — so the tax is judged against a
    telemetry plane that demonstrably WORKED during the measured
    window, not one that silently collected nothing.  ``cpus`` is in
    the record because on a core-starved box the poller thread
    competes with 3 server processes and the overhead reads high.

    BENCH_FLEETOBS gate; BENCH_FLEETOBS_{SECS,WRITERS,POLL_S,PORT}
    knobs; BENCH_SMOKE shrinks the windows."""
    import shutil
    import threading

    from dragonboat_tpu.audit import audit_set_cmd
    from dragonboat_tpu.scenario.multiproc import ProcFleet

    smoke = bool(int(os.environ.get("BENCH_SMOKE", "0")))

    def knob(name: str, dflt: str, smoke_dflt: str) -> str:
        return os.environ.get(name, smoke_dflt if smoke else dflt)

    win = float(knob("BENCH_FLEETOBS_SECS", "5", "2.5"))
    writers = int(knob("BENCH_FLEETOBS_WRITERS", "4", "2"))
    poll_s = float(os.environ.get("BENCH_FLEETOBS_POLL_S", "0.25"))
    base_port = int(os.environ.get("BENCH_FLEETOBS_PORT", "29950"))
    workdir = "/tmp/bench-fleetobs"
    shutil.rmtree(workdir, ignore_errors=True)

    SHARD = 1
    t0 = time.monotonic()
    fleet = ProcFleet(3, workdir=workdir, base_port=base_port)

    def window() -> int:
        """Closed-loop writers for ``win`` seconds; returns committed."""
        stop = threading.Event()
        counts = [0] * writers

        def w_main(w: int) -> None:
            h = fleet.gateway.connect(SHARD, timeout=30.0)
            seq = 0
            try:
                while not stop.is_set():
                    try:
                        h.sync_propose(
                            audit_set_cmd(f"fo-w{w}-k{seq % 8}", str(seq)),
                            timeout=5.0,
                        )
                        counts[w] += 1
                    except Exception:  # noqa: BLE001 — count only commits
                        pass
                    seq += 1
            finally:
                fleet.gateway.close_handle(h)

        ths = [threading.Thread(target=w_main, args=(w,), daemon=True,
                                name=f"fo-writer-{w}")
               for w in range(writers)]
        for t in ths:
            t.start()
        time.sleep(win)
        stop.set()
        for t in ths:
            t.join(timeout=15.0)
        return sum(counts)

    try:
        fleet.start()
        scope = fleet.scope
        # warm the leader/session path so window A doesn't pay startup
        h = fleet.gateway.connect(SHARD, timeout=30.0)
        for i in range(4):
            h.sync_propose(audit_set_cmd("fo-warm", str(i)), timeout=10.0)
        fleet.gateway.close_handle(h)

        off = window()                  # A: poller OFF
        scope.start_poller(poll_s)
        on = window()                   # B: poller ON
        scope.close()                   # stop the poller thread
        scope.poll()                    # final sweep picks up the tail

        stitches = scope.cross_process_stitches()
        rows = scope.slo_report()
        off_rate = off / win
        on_rate = on / win
        overhead_pct = (100.0 * (off_rate - on_rate) / off_rate
                        if off_rate > 0 else -1.0)
        return {
            "procs": 3,
            "writers": writers,
            "window_s": win,
            "poll_interval_s": poll_s,
            "committed_per_s_off": round(off_rate, 1),
            "committed_per_s_on": round(on_rate, 1),
            "overhead_pct": round(overhead_pct, 1),
            "polls": scope.polls,
            "reply_bytes": scope.reply_bytes,
            "bytes_per_poll": round(
                scope.reply_bytes / max(1, scope.polls)),
            "stitches": stitches,
            "slo_objectives": len(rows),
            "burning": [r["objective"] for r in rows if r["burning"]],
            "cpus": os.cpu_count(),
            "ok": bool(off > 0 and on > 0 and stitches >= 1
                       and scope.polls >= 2),
            "secs": round(time.monotonic() - t0, 1),
        }
    finally:
        fleet.close()
        shutil.rmtree(workdir, ignore_errors=True)


def phase_updatelanes(rows_list=None, reps: int = 3) -> dict:
    """Update-stage residual, scalar (the r8 per-row loop) vs lane
    (r9, ops/hostplane.UpdateLanes), over fabricated generations
    against REAL raft/pending-table/logdb objects.

    The r6 vectorization left one per-AFFECTED-row loop on the merge
    tail: scalar raft sync, ``peer.get_update`` (one Update/State/
    UpdateCommit object walk per row), ``_tick_bookkeeping``'s five
    pending-table GCs, and per-row save/process/commit plumbing —
    the residual ISSUE 13 names as the host-plane wall at 50k-250k
    rows.  This phase times exactly that stage END TO END (residual
    loop + persist + apply handoff; the downstream apply itself is
    excluded — identical both sides) on twin node populations:

    * scalar — the r8 loop verbatim: per-row 5-table GC, int
      unpacking, ``RaftRole(role)``, ``get_update``,
      ``dispatch_dropped``, ``_check_leader_change``, then the
      by-LogDB ``save_raft_state`` + ``process_update`` +
      ``peer.commit`` chain per row;
    * lane — ``hostplane.plan_update_sync`` over the update lanes +
      the residual lane loop (sync only what moved) + ONE batched
      ``save_state_lanes`` per LogDB + inline cursor/apply handoff
      (the ops/colocated.py ``_lane_commit_pass`` shape).

    Three generation shapes run per rep, mirroring the r5 Config-4
    mixed-election population: ``election`` (term/vote/leader churn on 30%
    of rows, no commits — the mass-election storm), ``commit_wave``
    (commit advance + real committed entries on 15%), ``steady``
    (ticks only).  Per-shape and aggregate speedups are reported; the
    acceptance gate reads the AGGREGATE (the election-dominated mix
    is the measured wall).  Parity runs OUTSIDE the timed windows:
    plan parity against the hostplane scalar twin every generation,
    and full raft-word equality across the twin populations at the
    end.  Host-only (numpy; no device).  Default tier 10k rows rides
    the standard bench; 50k/250k (the r5 ledger's scale) run when
    BENCH_UPDATELANES_HEAVY=1 — same convention as
    BENCH_HOSTPLANE_HEAVY.
    """
    import gc as _gc
    import threading
    import time as _time

    import numpy as np

    from dragonboat_tpu.ops import hostplane as hp
    from dragonboat_tpu.ops.engine import _ROLE_OF
    from dragonboat_tpu.ops.types import (
        N_VALS, R_COMMIT, R_LAST, R_LEADER, R_ROLE, R_TERM, R_VOTE,
        ROLE_LEADER, U_COMMIT, U_LEADER, U_LOST_LEAD, U_ROLE, U_STATE,
    )
    from dragonboat_tpu.pb import Entry, State, UpdateCommit
    from dragonboat_tpu.raft.log import InMemLogReader
    from dragonboat_tpu.raft.peer import Peer
    from dragonboat_tpu.raft.raft import Raft, RaftRole
    from dragonboat_tpu.request import (
        NO_DEADLINE, PendingConfigChange, PendingLeaderTransfer,
        PendingProposal, PendingReadIndex, PendingSnapshot, gc_tables,
    )
    from dragonboat_tpu.rsm.statemachine import Task, TaskType
    from dragonboat_tpu.storage.logdb import InMemLogDB

    if rows_list is None:
        rows_list = [10_000]
        if bool(int(os.environ.get("BENCH_UPDATELANES_HEAVY", "0"))):
            rows_list += [50_000, 250_000]

    N_ENTRIES = 16  # pre-appended log depth commits walk through

    class _TaskQueue:  # counts handoffs; apply itself is out of scope
        __slots__ = ("n",)

        def __init__(self):
            self.n = 0

        def add(self, t):
            self.n += 1

    class _SM:
        __slots__ = ("last_applied", "task_queue")

        def __init__(self):
            self.last_applied = 0
            self.task_queue = _TaskQueue()

    class _DevReads:
        __slots__ = ()

        def has_pending(self):
            return False

    _DR = _DevReads()

    class _BenchNode:
        """Light stand-in with the REAL cost centers: real Raft, real
        Peer, real shared-lock pending tables + deadline hint, the
        node.py process_update/dispatch_dropped/_check_leader_change
        statement shapes (Node itself needs transports/logdbs/SMs —
        unbuildable at 250k rows)."""

        __slots__ = (
            "peer", "tick_count", "pending_proposal",
            "pending_read_index", "pending_config_change",
            "pending_snapshot", "pending_leader_transfer",
            "pending_tables", "pending_deadline_hint", "sm", "stopped",
            "leader_id", "device_reads", "logdb", "shard_id",
            "replica_id", "engine_apply_ready", "_trace_spans",
            "hs_lane_slot",
        )

        def __init__(self, sid, rid, logdb):
            r = Raft(
                shard_id=sid, replica_id=rid,
                peers={rid: "a", 98: "b", 99: "c"},
                log_reader=InMemLogReader(),
            )
            self.peer = Peer(r)
            self.shard_id, self.replica_id = sid, rid
            self.tick_count = 0
            lock = threading.Lock()
            hint = [NO_DEADLINE]
            self.pending_deadline_hint = hint
            self.pending_proposal = PendingProposal(
                lock, deadline_hint=hint
            )
            self.pending_read_index = PendingReadIndex(
                lock, deadline_hint=hint
            )
            self.pending_config_change = PendingConfigChange(
                lock, deadline_hint=hint
            )
            self.pending_snapshot = PendingSnapshot(
                lock, deadline_hint=hint
            )
            self.pending_leader_transfer = PendingLeaderTransfer(
                lock, deadline_hint=hint
            )
            self.pending_tables = (
                self.pending_proposal, self.pending_read_index,
                self.pending_config_change, self.pending_snapshot,
                self.pending_leader_transfer,
            )
            self.sm = _SM()
            self.stopped = False
            self.leader_id = 0
            self.device_reads = _DR
            self.logdb = logdb
            self.engine_apply_ready = None
            self._trace_spans = {}
            self.hs_lane_slot = -1

        def dispatch_dropped(self, u):
            for e in u.dropped_entries:
                pass
            for _c in u.dropped_read_indexes:
                pass

        def _check_leader_change(self):
            lid = self.peer.leader_id()
            if lid != self.leader_id:
                self.leader_id = lid

        def process_update(self, u):  # node.py's statement shape
            if self._trace_spans:
                pass
            scheduled = False
            if not u.snapshot.is_empty():
                scheduled = True
            if u.entries_to_save:
                ents = u.entries_to_save
                assert all(
                    ents[i].index + 1 == ents[i + 1].index
                    for i in range(len(ents) - 1)
                )
            for _m in u.messages:
                pass
            if u.ready_to_reads:
                pass
            if u.committed_entries:
                self.sm.task_queue.add(
                    Task(type=TaskType.ENTRIES, entries=u.committed_entries)
                )
                scheduled = True
            self.peer.commit(u)
            return scheduled

    def _tick_bookkeeping_r8(node, ticks):
        """The pre-r9 bookkeeping verbatim: five per-table gc calls."""
        if not ticks:
            return
        node.tick_count += ticks
        node.peer.raft.tick_count += ticks
        node.pending_proposal.gc(node.tick_count)
        node.pending_read_index.gc(node.tick_count)
        node.pending_config_change.gc(node.tick_count)
        node.pending_snapshot.gc(node.tick_count)
        node.pending_leader_transfer.gc(node.tick_count)

    def _scalar_stage(db, nodes, vals_np, pos_l, ticks_l, G):
        """The r8 update-stage residual verbatim (the old
        _complete_generation tail + _persist_and_process chain)."""
        updates = []
        vals_l = vals_np.tolist()
        t0 = _time.perf_counter()
        for g in range(G):
            node = nodes[g]
            if node.stopped:
                continue
            r = node.peer.raft
            _tick_bookkeeping_r8(node, ticks_l[g])
            k = pos_l[g]
            if k < 0:
                continue
            sv = vals_l[k]
            term, vote, committed, leader, role, last = sv[:6]
            r.term, r.vote, r.leader_id = term, vote, leader
            r.role = RaftRole(role)
            if committed > r.log.committed:
                r.log.commit_to(committed)
            if (
                role != int(RaftRole.LEADER)
                and node.device_reads.has_pending()
            ):
                node.drop_device_reads()
            u = node.peer.get_update(last_applied=node.sm.last_applied)
            node.dispatch_dropped(u)
            updates.append((node, u))
            node._check_leader_change()
        by_db = {}
        for node, u in updates:
            by_db.setdefault(id(node.logdb), (node.logdb, []))[1].append(
                (node, u)
            )
        for db_, pairs in by_db.values():
            db_.save_raft_state([u for _, u in pairs], 0)
            for node, u in pairs:
                if node.process_update(u):
                    if node.engine_apply_ready is not None:
                        node.engine_apply_ready(node.shard_id)
        return _time.perf_counter() - t0, len(updates)

    def _lane_stage(db, nodes, vals_np, sum_rows, ticks_l, ulanes,
                    bases, G, slot_np):
        """The r9 lane path (ops/colocated._lane_commit_pass shape —
        open-coded in lockstep with both engine merge tails; see the
        note in engine._device_step's lane branch)."""
        t0 = _time.perf_counter()
        # batched bookkeeping, inlined like the engines' passes:
        # clock lockstep + hint-gated single-lock sweeps
        for node, t in zip(nodes, ticks_l):
            if not t or node.stopped:
                continue
            tc = node.tick_count + t
            node.tick_count = tc
            node.peer.raft.tick_count += t
            if tc >= node.pending_deadline_hint[0]:
                gc_tables(
                    node.pending_tables, node.pending_deadline_hint, tc
                )
        gs = sum_rows
        old_w = ulanes.words[:, gs]
        uplan = hp.plan_update_sync(
            old_w, np.arange(len(gs)), vals_np, bases[gs]
        )
        ulanes.words[:, gs] = uplan.words
        ub_l = uplan.ubits.tolist()
        w_term = uplan.words[R_TERM].tolist()
        w_vote = uplan.words[R_VOTE].tolist()
        w_com = uplan.words[R_COMMIT].tolist()
        w_lead = uplan.words[R_LEADER].tolist()
        w_role = uplan.words[R_ROLE].tolist()
        # slot-backed rows take the array-batched persist (the
        # engine's _persist_lane_batches shape): the loop only records
        # exceptions; commit rows hand (node, entries) to the
        # post-save apply leg
        so_mask = (uplan.ubits & (U_STATE | U_COMMIT)) != 0
        so_drop = []
        lane_rows = []
        lane_append = lane_rows.append
        lane_apply = []
        fulls = []
        for gi, ub, term, vote, committed, leader, role, so in zip(
            gs.tolist(), ub_l, w_term, w_vote, w_com, w_lead, w_role,
            so_mask.tolist(),
        ):
            node = nodes[gi]
            if node.stopped:
                if so:
                    so_drop.append(gi)
                continue
            r = node.peer.raft
            log = r.log
            im = log.inmem
            if (
                r.msgs or r.ready_to_reads or r.dropped_entries
                or r.dropped_read_indexes or im.snapshot.index
                or im.saved_to + 1 - im.marker < len(im.entries)
            ):
                if so:
                    so_drop.append(gi)
                r.term, r.vote, r.leader_id = term, vote, leader
                r.role = _ROLE_OF[role]
                if committed > log.committed:
                    log.commit_to(committed)
                u = node.peer.get_update(
                    last_applied=node.sm.last_applied
                )
                node.dispatch_dropped(u)
                fulls.append((node, u))
                node._check_leader_change()
                continue
            if ub & U_STATE:
                r.term = term
                r.vote = vote
            if ub & U_LEADER:
                r.leader_id = leader
            if ub & U_ROLE:
                r.role = _ROLE_OF[role]
            if ub & U_LOST_LEAD and node.device_reads.has_pending():
                node.drop_device_reads()
            if ub & U_COMMIT:
                log.commit_to(committed)
                ce = log.entries_to_apply()
                if so:
                    lane_apply.append((node, ce))
                else:
                    lane_append((node, term, vote, committed, ce))
            elif ub & U_STATE and not so:
                lane_append((node, term, vote, committed, None))
            if ub & U_LEADER:
                node._check_leader_change()
        n_so = 0
        if so_mask.any():
            if so_drop:
                so_mask &= ~np.isin(gs, np.asarray(so_drop))
            ii = np.nonzero(so_mask)[0]
            n_so = len(ii)
            if n_so:
                w = uplan.words
                db.save_state_slots(
                    slot_np[gs[ii]], w[R_TERM][ii], w[R_VOTE][ii],
                    w[R_COMMIT][ii], 0,
                )
                for node, ce in lane_apply:
                    node.sm.task_queue.add(
                        Task(type=TaskType.ENTRIES, entries=ce)
                    )
                    log = node.peer.raft.log
                    log.processed = ce[-1].index
                    # amortized in-mem GC (_persist_lane_batches)
                    im = log.inmem
                    if log.processed - im.marker >= 32:
                        im.applied_log_to(log.processed)
                    if node.engine_apply_ready is not None:
                        node.engine_apply_ready(node.shard_id)
        if lane_rows:
            by_db = {}
            for t in lane_rows:
                d = t[0].logdb
                by_db.setdefault(id(d), (d, []))[1].append(t)
            for d, rs in by_db.values():
                # commit rows keep the tuple form (their entries ride
                # along); cached-slot save like _persist_lane_rows
                get_slot = d.state_lane_slot
                slots = []
                for t in rs:
                    nd = t[0]
                    s = nd.hs_lane_slot
                    if s < 0:
                        s = get_slot(nd.shard_id, nd.replica_id)
                        nd.hs_lane_slot = s
                    slots.append(s)
                d.save_state_slots(
                    slots,
                    [t[1] for t in rs], [t[2] for t in rs],
                    [t[3] for t in rs], 0,
                )
                for node, _t, _v, _c, ce in rs:
                    if ce:
                        node.sm.task_queue.add(
                            Task(type=TaskType.ENTRIES, entries=ce)
                        )
                        log = node.peer.raft.log
                        log.processed = ce[-1].index
                        im = log.inmem
                        if log.processed - im.marker >= 32:
                            im.applied_log_to(log.processed)
                        if node.engine_apply_ready is not None:
                            node.engine_apply_ready(node.shard_id)
        if fulls:
            for node, u in fulls:
                node.logdb.save_raft_state([u], 0)
                node.process_update(u)
        return (
            _time.perf_counter() - t0,
            len(lane_rows) + len(fulls) + n_so,
        )

    def _gen(rng, G, ulanes, commits, mode, it):
        """One fabricated generation over the CURRENT lane state so
        both populations see identical, consistent inputs."""
        if mode == "steady":
            sr = np.zeros((0,), np.int64)
            v = np.zeros((0, N_VALS), np.int64)
            ticks = np.where(rng.random(G) < 0.8, 2, 0)
        else:
            aff = 0.30 if mode == "election" else 0.15
            sr = np.nonzero(rng.random(G) < aff)[0]
            n = len(sr)
            v = np.zeros((n, N_VALS), np.int64)
            v[:, R_ROLE] = int(RaftRole.FOLLOWER)
            v[:, R_LAST] = N_ENTRIES
            if mode == "election":
                # term/vote/leader churn, no commit movement — the
                # mass-election population of the r5 Config-4 ledger
                v[:, R_TERM] = 100 + it
                v[:, R_VOTE] = 1 + (it % 3)
                v[:, R_LEADER] = np.where(
                    rng.random(n) < 0.5, 1 + (it % 3), 0
                )
                v[:, R_COMMIT] = ulanes.words[R_COMMIT, sr]
            else:  # commit_wave: commit advances by 1 w/ real entries
                v[:, R_TERM] = ulanes.words[R_TERM, sr]
                v[:, R_VOTE] = ulanes.words[R_VOTE, sr]
                v[:, R_LEADER] = ulanes.words[R_LEADER, sr]
                v[:, R_COMMIT] = np.minimum(
                    ulanes.words[R_COMMIT, sr] + 1, N_ENTRIES
                )
            ticks = np.where(rng.random(G) < 0.3, 1, 0)
        pos = np.full((G,), -1, np.int32)
        if len(sr):
            pos[sr] = np.arange(len(sr), dtype=np.int32)
        return sr, v, pos, ticks.tolist()

    tiers = []
    for G in rows_list:
        db_s, db_l = InMemLogDB(), InMemLogDB()
        nodes_s = [_BenchNode(1 + i // 3, 1 + i % 3, db_s) for i in range(G)]
        nodes_l = [_BenchNode(1 + i // 3, 1 + i % 3, db_l) for i in range(G)]
        ents = [
            Entry(term=1, index=j + 1, cmd=b"x" * 16)
            for j in range(N_ENTRIES)
        ]
        for pop in (nodes_s, nodes_l):
            for nd in pop:
                nd.peer.raft.log.append(list(ents))
                nd.peer.raft.log.inmem.saved_log_to(N_ENTRIES, 1)
        ulanes = hp.UpdateLanes(G)
        slot_np = np.zeros((G,), np.int64)
        for g, nd in enumerate(nodes_l):
            r = nd.peer.raft
            ulanes.seed_row(
                g, r.term, r.vote, r.log.committed, r.leader_id,
                int(r.role), r.log.last_index(),
            )
            # slot resolution is an upload-time event in the engine
            # (ops/engine._upload_rows) — same here, outside the timer
            s = db_l.state_lane_slot(nd.shard_id, nd.replica_id)
            nd.hs_lane_slot = s
            slot_np[g] = s
        # a slice of rows holds live far-deadline futures (realistic
        # in-flight proposals; arms the hint without firing it)
        for pop in (nodes_s, nodes_l):
            for i in range(0, G, 50):
                pop[i].pending_proposal._alloc(10**9)
        bases = np.zeros((G,), np.int64)
        rng = np.random.default_rng(13)
        script = ["election"] * 4 + ["commit_wave"] * 2 + ["steady"] * 2
        shapes = {}
        tot_s = tot_l = 0.0
        for rep in range(reps + 1):
            for si, mode in enumerate(script):
                it = rep * len(script) + si
                sr, v, pos, ticks_l = _gen(rng, G, ulanes, None, mode, it)
                # plan parity OUTSIDE the timed window
                if len(sr):
                    old_w = np.array(ulanes.words[:, sr], copy=True)
                    hp.assert_update_plan_parity(
                        old_w, np.arange(len(sr)), v, bases[sr],
                        hp.plan_update_sync(
                            old_w, np.arange(len(sr)), v, bases[sr]
                        ),
                    )
                _gc.collect()
                ts, n_s = _scalar_stage(
                    db_s, nodes_s, v, pos.tolist(), ticks_l, G
                )
                _gc.collect()
                tl, n_l = _lane_stage(
                    db_l, nodes_l, v, sr, ticks_l, ulanes, bases, G,
                    slot_np,
                )
                if rep == 0:
                    continue  # warm rep: allocator/caches settle
                tot_s += ts
                tot_l += tl
                e = shapes.setdefault(mode, [0.0, 0.0, 0])
                e[0] += ts
                e[1] += tl
                e[2] += 1
        # full-population parity OUTSIDE the timed windows: both
        # loops must leave identical raft words + identical apply
        # handoff counts
        diverged = 0
        for g, (a, b) in enumerate(zip(nodes_s, nodes_l)):
            ta = (
                a.peer.raft.term, a.peer.raft.vote,
                a.peer.raft.log.committed, a.peer.raft.leader_id,
                a.peer.raft.role, a.peer.raft.log.processed,
            )
            tb = (
                b.peer.raft.term, b.peer.raft.vote,
                b.peer.raft.log.committed, b.peer.raft.leader_id,
                b.peer.raft.role, b.peer.raft.log.processed,
            )
            if ta != tb:
                diverged += 1
                if os.environ.get("BENCH_UL_PARITY_DEBUG") and diverged <= 8:
                    print(f"BENCHUL-DIVERGE g={g} scalar={ta} lane={tb}",
                          flush=True)
        tasks_s = sum(nd.sm.task_queue.n for nd in nodes_s)
        tasks_l = sum(nd.sm.task_queue.n for nd in nodes_l)
        # persisted hard state must match too (the lane path's batched
        # save_state_slots vs the scalar save_raft_state chain) —
        # sampled, and read AFTER the run so InMemLogDB materializes
        # any pending lane words through its reader path
        db_diverged = 0
        for i in range(0, G, 37):
            a, b = nodes_s[i], nodes_l[i]
            ra = db_s.read_raft_state(a.shard_id, a.replica_id, 0)
            rb = db_l.read_raft_state(b.shard_id, b.replica_id, 0)
            sa = ra.state if ra is not None else None
            sb = rb.state if rb is not None else None
            ta = (sa.term, sa.vote, sa.commit) if sa else None
            tb = (sb.term, sb.vote, sb.commit) if sb else None
            if ta != tb and os.environ.get("BENCH_UL_PARITY_DEBUG"):
                if db_diverged < 8:
                    print(f"BENCHUL-DB-DIVERGE g={i} scalar={ta} lane={tb}",
                          flush=True)
            db_diverged += ta != tb
        diverged += db_diverged
        tier = {
            "rows": G,
            "gens": reps * len(script),
            "t_stage_scalar_ms": round(tot_s * 1000, 1),
            "t_stage_lane_ms": round(tot_l * 1000, 1),
            "stage_speedup": round(tot_s / max(tot_l, 1e-9), 1),
            "parity_divergences": diverged,
            "apply_handoffs": [tasks_s, tasks_l],
        }
        for mode, (a, b, c) in shapes.items():
            tier[f"{mode}_speedup"] = round(a / max(b, 1e-9), 1)
            tier[f"{mode}_ms"] = [round(a * 1000, 1), round(b * 1000, 1)]
        tiers.append(tier)
        del nodes_s, nodes_l
        _gc.collect()
    ok = all(
        t["parity_divergences"] == 0
        and t["apply_handoffs"][0] == t["apply_handoffs"][1]
        for t in tiers
    )
    # ---- batched apply-handoff micro-split (ISSUE 15 satellite) -----
    # The per-row Task/cursor work above is identical either way; the
    # r10 cut is the WAKEUP: one WorkReady condition-lock take per row
    # vs one notify_all per partition per generation
    # (engine._apply_lane_commits).  Measure the notify leg directly
    # at a commit-wave-sized row count.
    import time as _t

    from dragonboat_tpu.engine.execengine import WorkReady

    n_rows, parts = 10_000, 4
    wr = WorkReady(parts)
    t0 = _t.perf_counter()
    for s in range(n_rows):
        wr.notify(s)
    per_row_s = _t.perf_counter() - t0
    for p in range(parts):
        wr._sets[p].clear()
    t0 = _t.perf_counter()
    wr.notify_all(range(n_rows))
    batched_s = _t.perf_counter() - t0
    handoff = {
        "rows": n_rows,
        "partitions": parts,
        "per_row_notify_ms": round(per_row_s * 1000, 2),
        "batched_notify_ms": round(batched_s * 1000, 2),
        "speedup": round(per_row_s / max(batched_s, 1e-9), 1),
    }
    return {"tiers": tiers, "parity": ok, "handoff_notify": handoff}


def phase_pipeline(jax, SHARDS: int = None, duration: float = None) -> dict:
    """Serial vs double-buffered colocated launch loop under a
    simulated link latency (ISSUE 11).

    The model: every device->host sync costs a fixed round-trip
    latency (the "floor") regardless of size, and sequential syncs do
    not pipeline — so a serial launch loop's generation time is
    floor-bound.  The pipelined loop (ops/colocated.py, depth 2)
    requests the readback at dispatch and collects it one generation
    later, overlapping the floor with the next launch's upload/dispatch
    and completing commit-proving rows from the head blob before the
    detail merge.

    The ``sync_floor_ms`` engine knob (env
    ``DRAGONBOAT_TPU_SYNC_FLOOR_MS``) is a test simulator: it delays
    every blob collect until <floor> ms after its D2H request.  On the
    v5e the measured request->ready latency of a small readback is
    under a millisecond (PERF.md), so floor 0 is the real machine and
    the larger floors model a remote device.  For each floor in
    ``BENCH_PIPELINE_FLOORS`` (default
    0,10,100 ms) it boots the same colocated 3-replica cluster once per
    depth in ``BENCH_PIPELINE_DEPTHS`` (default "1,2": the serial r6
    loop vs the double-buffered default; add 3 for the deep sweep) — and drives
    pipelined proposers plus a serial sync-propose probe, reporting
    committed proposals/sec, probe p50 and the engine's overlap/early-
    completion counters.  Headline: ``speedup_at_floor`` and
    ``probe_p50_ratio`` at the highest floor (the 100 ms model;
    targets >=1.7x and <=0.5x per ISSUE 11).  ``BENCH_PIPELINE_SHARDS``
    scales the fleet (default 16; the ROADMAP target geometry is 1000).
    """
    import shutil
    import sys
    import threading
    import time as _time

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from dragonboat_tpu import (
        Config,
        EngineConfig,
        ExpertConfig,
        NodeHost,
        NodeHostConfig,
    )
    from dragonboat_tpu.ops import hostplane
    from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
    from dragonboat_tpu.storage.tan import tan_logdb_factory
    from dragonboat_tpu.transport.inproc import reset_inproc_network

    if SHARDS is None:
        SHARDS = int(os.environ.get("BENCH_PIPELINE_SHARDS", "16"))
    if duration is None:
        duration = float(os.environ.get("BENCH_PIPELINE_SECS", "6"))
    floors = [
        float(x)
        for x in os.environ.get(
            "BENCH_PIPELINE_FLOORS", "0,10,100"
        ).split(",")
    ]
    depths = [
        int(x)
        for x in os.environ.get("BENCH_PIPELINE_DEPTHS", "1,2").split(",")
    ]
    # fused commit waves (ISSUE 15): depth>=2 configs run the product
    # default (K routed rounds per routable generation); depth-1
    # configs stay fused_k=1 — the serial r6 loop, the ledger's
    # baseline.  BENCH_FUSEDROUND=0 disables both the fusing and the
    # no-fuse control config (the `fusedround` split under this
    # phase's key); any other value is K.
    fused_k = int(os.environ.get("BENCH_FUSEDROUND", "3") or 3)
    REPLICAS = 3
    workers_n = int(os.environ.get("BENCH_PIPELINE_WORKERS", "4"))
    inflight = int(os.environ.get("BENCH_PIPELINE_INFLIGHT", "8"))
    probe_secs = float(os.environ.get("BENCH_PIPELINE_PROBE_SECS", "4"))
    payload = b"x" * 16

    def run_config(depth: int, floor_ms: float, fuse: int = 1) -> dict:
        tag = f"{depth}-{int(floor_ms)}-{fuse}"
        ADDRS = {r: f"pipe-nh-{tag}-{r}" for r in range(1, REPLICAS + 1)}
        cap = 1
        while cap < SHARDS * REPLICAS:
            cap <<= 1
        reset_inproc_network()
        group = ColocatedEngineGroup(
            capacity=cap, P=3, W=16, M=8, E=4, O=32, budget=4,
            pipeline_depth=depth, sync_floor_ms=floor_ms,
            fused_rounds=fuse,
        )
        nhs = {}
        for rid, addr in ADDRS.items():
            shutil.rmtree(f"/tmp/nh-pipe-{tag}-{rid}", ignore_errors=True)
            nhs[rid] = NodeHost(
                NodeHostConfig(
                    nodehost_dir=f"/tmp/nh-pipe-{tag}-{rid}",
                    rtt_millisecond=20,
                    raft_address=addr,
                    expert=ExpertConfig(
                        engine=EngineConfig(exec_shards=1, apply_shards=4),
                        step_engine_factory=group.factory,
                        logdb_factory=tan_logdb_factory,
                    ),
                )
            )
        out = {"depth": depth, "floor_ms": floor_ms, "shards": SHARDS,
               "fused_k": fuse}
        sm_cls = _bench_sm_cls()
        # per-config parity delta: the module counter is cumulative
        # across the matrix's configs (review finding)
        parity0 = hostplane.PARITY_FAILURE_COUNT
        try:
            for nh in nhs.values():
                nh.pause_ticks()
            for shard in range(1, SHARDS + 1):
                for rid, nh in nhs.items():
                    nh.start_replica(
                        ADDRS, False, sm_cls,
                        Config(replica_id=rid, shard_id=shard,
                               election_rtt=20, heartbeat_rtt=2,
                               pre_vote=True, check_quorum=True,
                               snapshot_entries=0),
                    )
            for nh in nhs.values():
                nh.resume_ticks()
            t0 = _time.time()
            covered = 0
            while _time.time() - t0 < max(120.0, SHARDS * 0.2):
                covered = sum(
                    1 for s in range(1, SHARDS + 1)
                    if nhs[1]._nodes[s].peer.raft.log.committed >= 1
                )
                if covered == SHARDS:
                    break
                _time.sleep(0.25)
            out["election_secs"] = round(_time.time() - t0, 1)
            out["leader_coverage"] = covered

            stop = _time.time() + duration
            counts = [0] * workers_n
            errors = [0] * workers_n

            def worker(w):
                my = list(range(1 + w, SHARDS + 1, workers_n))
                nh = nhs[1 + (w % REPLICAS)]
                sessions = {s: nh.get_noop_session(s) for s in my}
                pending = []
                done = 0
                while _time.time() < stop:
                    still = []
                    for rs, s in pending:
                        if rs._event.is_set():
                            if rs.code == 1:
                                done += 1
                            else:
                                errors[w] += 1
                        else:
                            still.append((rs, s))
                    pending = still
                    by_shard = {}
                    for _rs, s in pending:
                        by_shard[s] = by_shard.get(s, 0) + 1
                    for s in my:
                        while by_shard.get(s, 0) < inflight:
                            try:
                                rs = nh.propose(sessions[s], payload, 30.0)
                            except Exception:  # noqa: BLE001
                                errors[w] += 1
                                break
                            pending.append((rs, s))
                            by_shard[s] = by_shard.get(s, 0) + 1
                    _time.sleep(0.001)
                    counts[w] = done
                drain_end = _time.time() + 15.0
                while pending and _time.time() < drain_end:
                    pending = [
                        (rs, s) for rs, s in pending
                        if not rs._event.is_set()
                    ]
                    _time.sleep(0.01)
                counts[w] = done

            # cycle-exact probe: serial sync proposals under ambient
            # load — each sample a true submit->commit round trip.
            # Targets are shards LED by the probing host: a forwarded
            # proposal pays 2-3 extra transport-hop generations that
            # measure routing, not the launch pipeline (phase_c's
            # fixed-target probe includes that cost; this one isolates
            # the propose->commit launch chain the floor model covers).
            # The probing HOST follows leadership (whichever member
            # leads the most shards) — the old fixed-nhs[1] probe fell
            # into the forwarded mode whenever host 1 happened to lead
            # nothing, which read as a 2-4x probe regression purely on
            # leader placement (the r7 ledger's bimodal ranges).
            def _probe_targets():
                by_host = {}
                for s in range(1, SHARDS + 1):
                    for rid, nh in nhs.items():
                        if nh.is_leader_of(s):
                            by_host.setdefault(rid, []).append(s)
                            break
                if not by_host:
                    return 1, [1, max(1, SHARDS // 2), SHARDS]
                rid = max(by_host, key=lambda r: len(by_host[r]))
                return rid, by_host[rid][:3]

            probe_ms = []

            def prober():
                rid, targets = _probe_targets()
                nh = nhs[rid]
                sess = {s: nh.get_noop_session(s) for s in targets}
                i = 0
                while _time.time() < stop:
                    s = targets[i % len(targets)]
                    i += 1
                    t1 = _time.time()
                    try:
                        nh.sync_propose(sess[s], payload, timeout=30.0)
                    except Exception:  # noqa: BLE001
                        continue
                    probe_ms.append((_time.time() - t1) * 1000.0)

            threads = [
                threading.Thread(target=worker, args=(w,), daemon=True,
                                 name=f"bench-pipe-worker-{w}")
                for w in range(workers_n)
            ] + [threading.Thread(target=prober, daemon=True,
                                  name="bench-pipe-probe")]
            t0 = _time.time()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=duration + 60.0)
            # rate denominator is the LOAD WINDOW only: counts freeze
            # at `stop`, and the tail-drain/join time varies with the
            # config's backlog (the serial floor-bound config drains
            # longest), which would deflate its rate asymmetrically
            # (review finding)
            dt = max(stop - t0, 1e-9)
            committed = sum(counts)
            probe_ms.sort()

            # ---- unloaded probe window: serial sync proposals with NO
            # ambient workers.  On a saturated host core, loaded-probe
            # latency is dominated by CPU contention in BOTH configs
            # and hides the pipeline's latency signal; this window
            # isolates the launch pipeline's propose->commit path (the
            # number the sync-latency model predicts).
            quiet_ms = []
            qstop = _time.time() + probe_secs
            qrid, qtargets = _probe_targets()
            nh1 = nhs[qrid]
            qsess = {s: nh1.get_noop_session(s) for s in qtargets}
            qi = 0
            while _time.time() < qstop:
                s = qtargets[qi % len(qtargets)]
                qi += 1
                t1 = _time.time()
                try:
                    nh1.sync_propose(qsess[s], payload, timeout=30.0)
                except Exception:  # noqa: BLE001
                    continue
                quiet_ms.append((_time.time() - t1) * 1000.0)
            quiet_ms.sort()

            st = group.core.stats
            out.update(
                committed_per_sec=round(committed / dt, 1),
                committed=committed,
                errors=sum(errors),
                probe_p50_ms=(
                    round(probe_ms[len(probe_ms) // 2], 1)
                    if probe_ms else None
                ),
                probe_n=len(probe_ms),
                probe_unloaded_p50_ms=(
                    round(quiet_ms[len(quiet_ms) // 2], 1)
                    if quiet_ms else None
                ),
                probe_unloaded_n=len(quiet_ms),
                launches=st.get("launches", 0),
                overlap_s=round(st.get("pipeline_overlap_s", 0.0), 3),
                early_completions=st.get("early_completions", 0),
                detail_skipped=st.get("detail_skipped", 0),
                fences=st.get("pipeline_fences", 0),
                sel_fallbacks=st.get("sel_fallbacks", 0),
                fused_waves=st.get("fused_waves", 0),
                fused_rounds_stepped=st.get("fused_rounds_stepped", 0),
                fused_fences=st.get("fused_fences", 0),
                readback_windows=st.get("readback_windows", 0),
                parity_failures=hostplane.PARITY_FAILURE_COUNT - parity0,
            )
        finally:
            for nh in nhs.values():
                try:
                    nh.close()
                except Exception:  # noqa: BLE001
                    pass
        return out

    report = {
        "shards": SHARDS, "replicas": REPLICAS,
        "secs_per_config": duration, "configs": [],
    }
    for floor in floors:
        for depth in depths:
            # depth 1 = the serial r6 baseline (never fused);
            # depth >= 2 = the product pipeline with fused waves
            fuse = 1 if depth == 1 else max(1, fused_k)
            try:
                report["configs"].append(run_config(depth, floor, fuse))
            except Exception as e:  # noqa: BLE001 — record, keep going
                report["configs"].append(
                    {"depth": depth, "floor_ms": floor, "fused_k": fuse,
                     "error": str(e)}
                )
    by = {
        (c.get("depth"), c.get("floor_ms")): c for c in report["configs"]
    }
    fmax = max(floors)
    # ---- the fusedround split (ISSUE 15) ----------------------------
    # One no-fuse CONTROL config at the headline point (depth 2, the
    # highest floor) isolates the fusion win from the pipeline win:
    # fused-vs-control probe ratio is the 3-rounds-to-1-launch
    # collapse, and one_readback_per_wave pins the budget.
    if fused_k > 1 and 2 in depths:
        try:
            control = run_config(2, fmax, 1)
        except Exception as e:  # noqa: BLE001
            control = {"error": str(e)}
        fused_cfg = by.get((2, fmax), {})
        split = {
            "floor_ms": fmax, "fused_k": fused_k,
            "fused": fused_cfg, "control_nofuse": control,
            "one_readback_per_wave": bool(
                fused_cfg.get("fused_waves", 0) > 0
                and fused_cfg.get("readback_windows", 0)
                <= fused_cfg.get("launches", 0)
                + fused_cfg.get("sel_fallbacks", 0)
            ),
        }
        for key, name in (
            ("probe_p50_ms", "probe_p50_fused_vs_nofuse"),
            ("probe_unloaded_p50_ms",
             "probe_unloaded_p50_fused_vs_nofuse"),
            ("committed_per_sec", "committed_fused_vs_nofuse"),
        ):
            if fused_cfg.get(key) and control.get(key):
                split[name] = round(fused_cfg[key] / control[key], 2)
        report["fusedround"] = split
    s = by.get((1, fmax))
    headline = {}
    for depth in depths:
        if depth == 1:
            continue
        p = by.get((depth, fmax))
        if not (s and p and s.get("committed_per_sec")
                and p.get("committed_per_sec")):
            continue
        h = {
            "speedup": round(
                p["committed_per_sec"]
                / max(s["committed_per_sec"], 1e-9), 2
            )
        }
        for key, name in (
            ("probe_p50_ms", "probe_p50_ratio"),
            ("probe_unloaded_p50_ms", "probe_unloaded_p50_ratio"),
        ):
            if s.get(key) and p.get(key):
                h[name] = round(p[key] / s[key], 2)
        headline[str(depth)] = h
    if headline:
        report["floor_headline_ms"] = fmax
        report["headline_by_depth"] = headline
        # the product default (depth 2) keeps the flat headline keys;
        # loaded and unloaded probe ratios are DIFFERENT measurements
        # and keep their own names (review finding)
        h2 = headline.get("2") or next(iter(headline.values()))
        report["speedup_at_floor"] = h2.get("speedup")
        report["probe_p50_ratio"] = h2.get("probe_p50_ratio")
        report["probe_unloaded_p50_ratio"] = h2.get(
            "probe_unloaded_p50_ratio"
        )
    return report


def _multichip_worker(n_dev: int, groups: int, rounds: int,
                      launches: int) -> dict:
    """One forced-host-device-count mechanism run (executes in a fresh
    subprocess: the device count latches at first backend init).

    The 1-core container cannot show wall-clock scaling, so this gates
    on MECHANISM (ISSUE 12): (a) the sharded kernel/round is bit-exact
    with the single-device one over the same global topology, (b) the
    per-device group-tick counters balance within 10%, (c) the sharded
    programs are host-transfer-free (the jaxcheck transfer rule over
    registry.mesh_entry_points), and (d) cross-device raft traffic
    really rides the collective lane (delivered > 0 at n_dev > 1,
    zero lane drops at the xbudget_for sizing).
    """
    import time as _time

    import jax

    # forced host devices live on the CPU backend: the mechanism run
    # needs n_dev of them, not the chip (which belongs to one process)
    jax.config.update("jax_platforms", "cpu")
    import functools

    import jax.numpy as jnp
    from jax.sharding import Mesh

    from dragonboat_tpu.analysis import jaxcheck
    from dragonboat_tpu.ops import registry as REG
    from dragonboat_tpu.ops import route as R
    from dragonboat_tpu.ops.kernel import (
        inbox_to_internal,
        make_step_sharded,
        state_to_internal,
        step_internal,
    )
    from dragonboat_tpu.ops.types import (
        DeviceState,
        Inbox,
        MT_TICK,
        ROLE_LEADER,
        make_state,
        make_state_np,
    )

    devs = [d for d in jax.devices() if d.platform == "cpu"][:n_dev]
    if len(devs) < n_dev:
        return {"n_devices": n_dev, "error": "too few host devices"}
    mesh = Mesh(np.asarray(devs), ("groups",))
    out: dict = {"n_devices": n_dev}

    REPL = 3
    G = groups * REPL

    # ---- leg 1: phase-A mechanism (fused ticks, internal layout) -----
    P, W, M, E, O = 3, 8, 4, 1, 8
    TPL = 16  # ticks per slot
    shard_ids = np.repeat(np.arange(1, groups + 1, dtype=np.int32), REPL)
    replica_ids = np.tile(np.arange(1, REPL + 1, dtype=np.int32), groups)
    peer_ids = np.broadcast_to(
        np.arange(1, REPL + 1, dtype=np.int32), (G, P)
    ).copy()
    cols = make_state_np(
        G, P, W,
        shard_ids=shard_ids, replica_ids=replica_ids, peer_ids=peer_ids,
        election_timeout=2 * TPL, heartbeat_timeout=2,
    )
    st0 = state_to_internal(DeviceState(**cols))
    st0 = jax.tree.map(np.ascontiguousarray, st0)
    zm = np.zeros((M, G), np.int32)
    ib0 = Inbox(
        mtype=np.full((M, G), MT_TICK, np.int32), from_id=zm, term=zm,
        log_term=zm, log_index=np.full((M, G), TPL, np.int32), commit=zm,
        reject=zm, hint=zm, hint_high=zm, n_entries=zm,
        ent_term=np.zeros((M, E, G), np.int32),
        ent_cc=np.zeros((M, E, G), np.int32),
    )
    step_single = jax.jit(
        functools.partial(step_internal, out_capacity=O)
    )
    step_shard = make_step_sharded(
        mesh, st0, ib0, out_capacity=O, internal=True
    )
    st_a, st_b = st0, st0
    esc_dev = np.zeros((n_dev,), np.int64)
    t0 = _time.perf_counter()
    for _ in range(launches):
        st_a, out_a = step_single(st_a, ib0)
        st_b, out_b = step_shard(st_b, ib0)
        esc_dev += np.asarray(out_b.escalate).reshape(n_dev, -1).sum(1)
    jax.block_until_ready(st_b)
    dt = _time.perf_counter() - t0
    a_ok = all(
        np.array_equal(np.asarray(getattr(st_a, f)),
                       np.asarray(getattr(st_b, f)))
        for f in st_a._fields
    )
    gl = G // n_dev
    ticks_dev = (gl // REPL) * launches * M * TPL - esc_dev // REPL * M * TPL
    out["phase_a"] = {
        "parity_ok": bool(a_ok),
        "launches": launches,
        "group_ticks_per_sec": round(groups * launches * M * TPL / dt, 1),
        "per_device_group_ticks": [int(x) for x in ticks_dev],
        "balance_ratio": round(
            float(ticks_dev.max() / max(1, ticks_dev.min())), 4
        ),
    }

    # ---- leg 2: routed commit loop with the collective lane ----------
    # REPLICA-MAJOR layout: group i's replicas live at rows
    # {i, groups+i, 2*groups+i} — at n_dev > 1 every group straddles
    # device blocks, so ALL raft traffic crosses the lane (the maximal
    # mechanism stress; production placement colocates — this is the
    # proof the lane carries real elections/commits, not the layout
    # recommendation)
    P2, W2, E2, O2, BUD, BASE = 3, 16, 2, 16, 4, 2
    M2 = BASE + P2 * BUD
    sh2 = np.tile(np.arange(1, groups + 1, dtype=np.int32), REPL)
    rp2 = np.repeat(np.arange(1, REPL + 1, dtype=np.int32), groups)
    pe2 = np.broadcast_to(
        np.arange(1, REPL + 1, dtype=np.int32), (G, P2)
    ).copy()
    tabs = R.build_route_tables_mesh(sh2, rp2, pe2, n_dev)
    XB = R.xbudget_for(tabs, BUD, n_dev)
    dest, rank = R.build_route_tables(sh2, rp2, pe2)
    st = make_state(
        G, P2, W2, shard_ids=sh2, replica_ids=rp2, peer_ids=pe2,
        election_timeout=10, heartbeat_timeout=2,
    )
    ib = R.make_prefill(st, M2, E2)
    round_single = jax.jit(functools.partial(
        R.routed_round, out_capacity=O2, budget=BUD, base=BASE,
        propose_leaders=True,
    ))
    round_shard = R.make_sharded_round(
        mesh, M=M2, E=E2, out_capacity=O2, budget=BUD, xbudget=XB,
        base=BASE, propose_leaders=True,
    )
    dl, dd, rk = (jnp.asarray(tabs.dest_local), jnp.asarray(tabs.dest_dev),
                  jnp.asarray(tabs.rank_in_dest))
    dj, rj = jnp.asarray(dest), jnp.asarray(rank)
    st_r, ib_r = st, ib
    st_s, ib_s = st, ib
    lane_dev = np.zeros((n_dev, 7), np.int64)
    t0 = _time.perf_counter()
    for _ in range(rounds):
        st_r, ib_r, _stats, _nesc = round_single(st_r, ib_r, dj, rj)
        st_s, ib_s, _sstats, lane = round_shard(st_s, ib_s, dl, dd, rk)
        lane_dev += np.asarray(lane, np.int64)
    jax.block_until_ready(st_s)
    dt = _time.perf_counter() - t0
    r_ok = all(
        np.array_equal(np.asarray(getattr(st_r, f)),
                       np.asarray(getattr(st_s, f)))
        for f in st._fields
    ) and all(
        np.array_equal(np.asarray(getattr(ib_r, f)),
                       np.asarray(getattr(ib_s, f)))
        for f in ib._fields
    )
    commits = np.asarray(st_s.committed).reshape(REPL, groups).max(0)
    commit_dev = (
        np.asarray(st_s.committed).reshape(n_dev, gl).sum(1)
    )
    rows_live = lane_dev[:, 6]
    out["routed"] = {
        "parity_ok": bool(r_ok),
        "rounds": rounds,
        "xbudget": XB,
        "leaders": int((np.asarray(st_s.role) == ROLE_LEADER).sum()),
        "groups_committing": int((commits > 0).sum()),
        "cross_delivered": int(lane_dev[:, 1].sum()),
        "cross_dropped_xlane": int(lane_dev[:, 3].sum()),
        "cross_dropped_ring": int(lane_dev[:, 4].sum()),
        "escalations": int(lane_dev[:, 5].sum()),
        "per_device_commit_sum": [int(x) for x in commit_dev],
        "per_device_rows_live": [int(x) for x in rows_live],
        "balance_ratio": round(
            float(rows_live.max() / max(1, rows_live.min())), 4
        ),
        "rounds_per_sec": round(rounds / dt, 2),
    }

    # ---- leg 3: transfer-free gate over the sharded entry points -----
    findings = jaxcheck.audit(entries=REG.mesh_entry_points(mesh))
    out["jaxcheck"] = {
        "transfer_findings": sum(
            1 for f in findings if f.rule == "transfer"
        ),
        "total_findings": len(findings),
        "detail": [f.render() for f in findings][:8],
    }
    out["ok"] = bool(
        a_ok
        and r_ok
        and out["phase_a"]["balance_ratio"] <= 1.1
        and out["routed"]["balance_ratio"] <= 1.1
        and out["jaxcheck"]["transfer_findings"] == 0
        and out["routed"]["cross_dropped_xlane"] == 0
        and (n_dev == 1 or out["routed"]["cross_delivered"] > 0)
        and out["routed"]["groups_committing"] == groups
    )
    return out


def phase_multichip(jax=None) -> dict:
    """Multi-chip device-plane mechanism bench (ISSUE 12 / ROADMAP 3).

    Runs the sharded launch path at 1-8 FORCED HOST DEVICES
    (``--xla_force_host_platform_device_count``, the mechanism the
    MULTICHIP_r0*.json harness proves) — each count in a fresh
    subprocess because the device count latches at first backend init.
    Gates on mechanism, not wall-clock (1-core container): bit-exact
    sharded/single-device parity for both the fused-tick phase-A loop
    and the routed commit loop, per-device group-tick balance within
    10%, transfer-free sharded programs (jaxcheck), and live
    cross-device traffic on the collective lane.  The ~8e9 aggregate
    group-ticks/sec and 1M-group election numbers remain the recorded
    first-hardware targets (docs/MULTICHIP.md checklist).

    Env: BENCH_MULTICHIP_DEVICES (default "1,2,4,8"),
    BENCH_MULTICHIP_GROUPS (default 64; must divide by 8*... the row
    count 3*groups must divide every device count),
    BENCH_MULTICHIP_ROUNDS (default 64), BENCH_MULTICHIP_LAUNCHES
    (default 6), BENCH_MULTICHIP_TIMEOUT per count (default 420s).
    """
    import json as _json
    import subprocess
    import sys

    counts = [
        int(x)
        for x in os.environ.get(
            "BENCH_MULTICHIP_DEVICES", "1,2,4,8"
        ).split(",")
        if x.strip()
    ]
    groups = int(os.environ.get("BENCH_MULTICHIP_GROUPS", "64"))
    rounds = int(os.environ.get("BENCH_MULTICHIP_ROUNDS", "64"))
    launches = int(os.environ.get("BENCH_MULTICHIP_LAUNCHES", "6"))
    timeout = int(os.environ.get("BENCH_MULTICHIP_TIMEOUT", "420"))
    results = []
    for n in counts:
        env = dict(os.environ)
        kept = [
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        env["XLA_FLAGS"] = " ".join(
            kept + [f"--xla_force_host_platform_device_count={max(n, 1)}"]
        )
        env.setdefault("JAX_PLATFORMS", "cpu")
        code = (
            "import json, bench;"
            f"print('MCW ' + json.dumps(bench._multichip_worker("
            f"{n}, {groups}, {rounds}, {launches})))"
        )
        try:
            proc = subprocess.run(
                [sys.executable, "-c", code],
                capture_output=True, text=True, timeout=timeout,
                env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            row = None
            for line in (proc.stdout or "").splitlines():
                if line.startswith("MCW "):
                    row = _json.loads(line[4:])
            if row is None:
                row = {
                    "n_devices": n,
                    "error": (proc.stderr or "no output")[-800:],
                }
        except subprocess.TimeoutExpired:
            row = {"n_devices": n, "error": f"timeout {timeout}s"}
        results.append(row)
    return {
        "mechanism_gate": all(r.get("ok") for r in results),
        "by_devices": results,
        # first-hardware targets recorded, not measured here (1-core
        # container; docs/MULTICHIP.md "Hardware-run checklist")
        "hardware_targets": {
            "aggregate_group_ticks_per_sec": 8e9,
            "election_groups_one_host": 1_000_000,
        },
    }


def phase_balance(
    shards: int = 16,
    hosts: int = 4,
    *,
    rtt_ms: int = 2,
    replicas: int = 3,
    seed: int = 1,
) -> dict:
    """Balance control-plane convergence: drain one of ``hosts``
    in-proc NodeHosts carrying ``shards`` x ``replicas`` and measure
    how many logical ticks (and wall seconds) the control loop needs to
    reach the drain fixed point (zero replicas on the drained host,
    leader counts within ±1).  Pure host path — no device, no jax.
    """
    import shutil

    from dragonboat_tpu import (
        Config,
        EngineConfig,
        ExpertConfig,
        NodeHost,
        NodeHostConfig,
    )
    from dragonboat_tpu.balance import Balancer
    from dragonboat_tpu.transport.inproc import reset_inproc_network

    reset_inproc_network()
    sm_cls = _bench_sm_cls()
    keys = [f"bench-bal-{i}" for i in range(hosts)]
    nhs = {}
    for i, key in enumerate(keys):
        d = f"/tmp/nh-bench-bal-{i}"
        shutil.rmtree(d, ignore_errors=True)
        nhs[key] = NodeHost(NodeHostConfig(
            nodehost_dir=d,
            rtt_millisecond=rtt_ms,
            raft_address=key,
            expert=ExpertConfig(
                engine=EngineConfig(exec_shards=2, apply_shards=2),
            ),
        ))

    def cfg(sid, rid):
        return Config(shard_id=sid, replica_id=rid,
                      election_rtt=10, heartbeat_rtt=1)

    try:
        placements = {}
        for sid in range(1, shards + 1):
            ks = [keys[(sid + j) % hosts] for j in range(replicas)]
            members = {rid: ks[rid - 1] for rid in range(1, replicas + 1)}
            placements[sid] = members
            for rid, key in members.items():
                nhs[key].start_replica(members, False, sm_cls, cfg(sid, rid))
        t_boot = time.monotonic()
        deadline = t_boot + 60.0
        covered = 0
        while time.monotonic() < deadline:
            covered = 0
            for sid, members in placements.items():
                seen = set()
                for key in members.values():
                    lid, ok = nhs[key].get_leader_id(sid)
                    if not ok:
                        break
                    seen.add(lid)
                else:
                    covered += len(seen) == 1
            if covered == shards:
                break
            time.sleep(0.05)
        b = Balancer(sm_cls, cfg, hosts=dict(nhs), seed=seed,
                     replication_factor=replicas)
        drained = keys[0]
        survivors = [k for k in keys if k != drained]
        tick0 = max(nhs[k]._global_ticks for k in survivors)
        t0 = time.monotonic()
        report = b.drain(drained, timeout=240.0)
        secs = time.monotonic() - t0
        ticks = max(nhs[k]._global_ticks for k in survivors) - tick0
        view = b.view()
        lc = view.leader_counts()
        lc.pop(drained, None)
        b.stop()
        return {
            "shards": shards,
            "hosts": hosts,
            "replicas": replicas,
            "rtt_ms": rtt_ms,
            "seed": seed,
            "leader_coverage_at_start": covered,
            "drained_host_replicas_left": view.replicas_on(drained),
            "moves_passes": report.get("passes", 0),
            "convergence_ticks": int(ticks),
            "convergence_secs": round(secs, 2),
            "leader_spread_after": (
                max(lc.values()) - min(lc.values()) if lc else -1
            ),
        }
    finally:
        for nh in nhs.values():
            try:
                nh.close()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass


def phase_bigstate(
    *,
    state_mb: int = 16,
    caps_mb: tuple = (0, 16, 4),
    rtt_ms: int = 2,
) -> dict:
    """Big-state plane guard (bigstate/, docs/BIGSTATE.md): laggard
    catch-up MB/s at three bandwidth-cap levels (0 = uncapped) and the
    CONCURRENT commit-throughput delta — the number behind the "catch-up
    provably cannot starve the commit path" claim.  Host path + disk
    only, no device."""
    import os as _os
    import shutil
    import threading
    import time as _time

    from dragonboat_tpu import (
        Config,
        EngineConfig,
        ExpertConfig,
        NodeHost,
        NodeHostConfig,
        settings,
    )
    from dragonboat_tpu.bigstate.ondisk import ondisk_kv_factory, put_cmd
    from dragonboat_tpu.storage.logdb import in_mem_logdb_factory
    from dragonboat_tpu.transport.inproc import reset_inproc_network

    ADDRS = {1: "bb-1", 2: "bb-2", 3: "bb-3"}
    saved_chunk = settings.Soft.snapshot_chunk_size
    settings.Soft.snapshot_chunk_size = 256 * 1024
    report = {"state_mb": state_mb, "levels": []}

    def one_level(cap_mb: int) -> dict:
        reset_inproc_network()
        for rid in ADDRS:
            shutil.rmtree(f"/tmp/nh-bb-{rid}", ignore_errors=True)
        shutil.rmtree("/tmp/bb-sm", ignore_errors=True)
        fac = {
            rid: ondisk_kv_factory(f"/tmp/bb-sm/h{rid}") for rid in ADDRS
        }
        nhs = {
            rid: NodeHost(NodeHostConfig(
                nodehost_dir=f"/tmp/nh-bb-{rid}",
                rtt_millisecond=rtt_ms,
                raft_address=ADDRS[rid],
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=2, apply_shards=2),
                    logdb_factory=in_mem_logdb_factory,
                ),
            ))
            for rid in ADDRS
        }

        def cfg(rid):
            return Config(replica_id=rid, shard_id=1,
                          election_rtt=20, heartbeat_rtt=2)

        try:
            for rid, nh in nhs.items():
                nh.start_replica(ADDRS, False, fac[rid], cfg(rid))
            # leader + healthy-baseline probe
            deadline = _time.time() + 15
            lid = 0
            while _time.time() < deadline and not lid:
                for rid, nh in nhs.items():
                    l, ok = nh.get_leader_id(1)
                    if ok and l:
                        lid = l
                        break
                _time.sleep(0.05)
            nh = nhs[lid]
            s = nh.get_noop_session(1)

            def propose(cmd, deadline_s=10.0):
                end = _time.time() + deadline_s
                while True:
                    try:
                        return nh.sync_propose(s, cmd, timeout=1.0)
                    except Exception:  # noqa: BLE001 — retry to deadline
                        if _time.time() >= end:
                            raise

            def probe_rate(secs):
                n = 0
                end = _time.time() + secs
                while _time.time() < end:
                    propose(put_cmd(b"p", b"x"))
                    n += 1
                return n / secs

            probe_rate(0.5)
            base = probe_rate(1.5)
            fid = next(r for r in ADDRS if r != lid)
            nhs[fid].close()
            val = _os.urandom(1024 * 1024)
            for i in range(state_mb):
                propose(put_cmd(b"big-%d" % i, val))
            live = {r: h for r, h in nhs.items() if r != fid}
            for h in live.values():
                h.sync_request_snapshot(1, compaction_overhead=1)
                if cap_mb:
                    h.set_snapshot_send_rate(cap_mb * 1024 * 1024)
            nhf = NodeHost(NodeHostConfig(
                nodehost_dir=f"/tmp/nh-bb-{fid}",
                rtt_millisecond=rtt_ms,
                raft_address=ADDRS[fid],
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=2, apply_shards=2),
                    logdb_factory=in_mem_logdb_factory,
                ),
            ))
            nhs[fid] = nhf
            nhf.start_replica(ADDRS, False, fac[fid], cfg(fid))
            t0 = _time.time()
            n = 0
            last = b"big-%d" % (state_mb - 1)
            caught = None
            while _time.time() - t0 < 300:
                propose(put_cmd(b"p", b"x"))
                n += 1
                if n % 20 == 0 and nhf.stale_read(1, last) == val:
                    caught = _time.time()
                    break
            catchup_s = (caught or _time.time()) - t0
            during = n / catchup_s if catchup_s > 0 else -1.0
            return {
                "cap_mb_s": cap_mb,
                "caught_up": caught is not None,
                "catchup_secs": round(catchup_s, 2),
                "catchup_mb_s": round(state_mb / catchup_s, 1),
                "commit_base_per_sec": round(base, 1),
                "commit_during_per_sec": round(during, 1),
                "commit_delta_frac": round(during / base, 3) if base else -1,
            }
        finally:
            for h in nhs.values():
                try:
                    h.close()
                except Exception:  # noqa: BLE001 — best-effort teardown
                    pass

    try:
        for cap in caps_mb:
            report["levels"].append(one_level(int(cap)))
    finally:
        settings.Soft.snapshot_chunk_size = saved_chunk
    return report


def phase_gateway(
    *,
    shards: int = 4,
    handles_per_shard: int = 16,
    levels=(200, 800, 3200),
    level_secs: float = 3.0,
    overload_secs: float = 4.0,
    rtt_ms: int = 2,
    readers: int = 4,
) -> dict:
    """Serving-front-plane saturation curve (gateway tentpole,
    docs/GATEWAY.md): mixed read/write OPEN-LOOP load at high fan-in —
    ``shards * handles_per_shard`` exactly-once-shaped client handles
    submit writes at each offered rate regardless of completions while
    ``readers`` threads hammer lease reads — emitting per-level
    offered vs committed vs shed with write p50/p99, then an OVERLOAD
    scenario (tiny per-shard queues, offered >> capacity) where p99 of
    COMPLETED requests must stay bounded while ``gateway_shed_total``
    climbs: shedding at the door is what keeps the tail flat.  Also
    records the lease-read vs ReadIndex p50 split (the acceptance
    proxy when no hardware throughput run is possible).  Pure host
    path — no device, no jax.
    """
    import queue as _queue
    import shutil
    import threading

    from dragonboat_tpu import (
        Config,
        EngineConfig,
        ExpertConfig,
        Gateway,
        GatewayBusy,
        GatewayConfig,
        NodeHost,
        NodeHostConfig,
    )
    from dragonboat_tpu.transport.inproc import reset_inproc_network

    reset_inproc_network()
    sm_cls = _bench_sm_cls()
    keys = [f"bench-gw-{i}" for i in range(3)]
    nhs = {}
    for i, key in enumerate(keys):
        d = f"/tmp/nh-bench-gw-{i}"
        shutil.rmtree(d, ignore_errors=True)
        nhs[key] = NodeHost(NodeHostConfig(
            nodehost_dir=d,
            rtt_millisecond=rtt_ms,
            raft_address=key,
            expert=ExpertConfig(
                engine=EngineConfig(exec_shards=2, apply_shards=2),
            ),
        ))
    gw = None
    try:
        for sid in range(1, shards + 1):
            for rid, key in enumerate(keys, start=1):
                nhs[key].start_replica(
                    {r: k for r, k in enumerate(keys, start=1)}, False,
                    sm_cls,
                    Config(shard_id=sid, replica_id=rid, election_rtt=10,
                           heartbeat_rtt=1, check_quorum=True),
                )
        deadline = time.monotonic() + 30.0
        for sid in range(1, shards + 1):
            while time.monotonic() < deadline:
                if any(nh.is_leader_of(sid) for nh in nhs.values()):
                    break
                time.sleep(0.02)
            else:
                return {"error": f"no leader for shard {sid} within 30s"}

        def run_level(gw, offered_rate: float, secs: float) -> dict:
            """One open-loop level: submit writes at offered_rate,
            measure commit latency client-side via a waiter pool."""
            hs = [
                gw.noop_handle(1 + i % shards)
                for i in range(shards * handles_per_shard)
            ]
            lat: list = []
            lat_lock = threading.Lock()
            inbox: "_queue.Queue" = _queue.Queue()

            def waiter():
                while True:
                    item = inbox.get()
                    if item is None:
                        return
                    t0, fut = item
                    try:
                        fut.result(20.0)
                        with lat_lock:
                            lat.append(time.monotonic() - t0)
                    except Exception:  # noqa: BLE001 — sheds/timeouts
                        # are counted by the gateway, not the sampler
                        pass

            ws = [threading.Thread(target=waiter, daemon=True,
                                   name=f"gwbench-wait-{i}")
                  for i in range(8)]
            for w in ws:
                w.start()
            st0 = gw.stats()
            stop_readers = threading.Event()
            read_lat: list = []

            def read_loop():
                while not stop_readers.is_set():
                    t0 = time.monotonic()
                    try:
                        gw.read(1, None, timeout=5.0)
                        read_lat.append(time.monotonic() - t0)
                    except Exception:  # noqa: BLE001
                        pass

            rs = [threading.Thread(target=read_loop, daemon=True,
                                   name=f"gwbench-read-{i}")
                  for i in range(readers)]
            for r in rs:
                r.start()
            period = 1.0 / offered_rate
            t_end = time.monotonic() + secs
            offered = sheds = 0
            i = 0
            next_send = time.monotonic()
            while time.monotonic() < t_end:
                now = time.monotonic()
                if now < next_send:
                    time.sleep(min(next_send - now, 0.001))
                    continue
                next_send += period
                h = hs[i % len(hs)]
                i += 1
                offered += 1
                try:
                    inbox.put((now, h.propose(b"x" * 24, timeout=5.0)))
                except GatewayBusy:
                    sheds += 1
            # committed-rate snapshot at WINDOW END, before the drain:
            # up to queue-depth admitted requests commit during the
            # drain and counting them against `secs` inflated
            # committed_per_sec past the true service rate (review
            # finding); latency samples still collect through the
            # drain — an admitted request's latency is real wherever
            # it completes
            st_end = gw.stats()
            # drain: waiters consume the backlog, then stop
            t_drain = time.monotonic() + 10.0
            while not inbox.empty() and time.monotonic() < t_drain:
                time.sleep(0.02)
            for _ in ws:
                inbox.put(None)
            for w in ws:
                w.join(timeout=5.0)
            stop_readers.set()
            for r in rs:
                r.join(timeout=5.0)
            st1 = gw.stats()
            # SNAPSHOT into fresh names before sorting: a waiter/reader
            # stuck past its join timeout can still append to the
            # original lists, and an in-place .sort() racing an append
            # raises (review finding)
            lat_done = sorted(list(lat))
            read_done = sorted(list(read_lat))
            wall = secs

            def pct(xs, q):
                return round(xs[min(len(xs) - 1, int(q * len(xs)))] * 1000,
                             3) if xs else -1.0

            return {
                "offered_per_sec": round(offered / wall, 1),
                "committed_per_sec": round(
                    (st_end["committed"] - st0["committed"]) / wall, 1
                ),
                "shed_per_sec": round(sheds / wall, 1),
                "shed_total": sheds,
                "write_p50_ms": pct(lat_done, 0.50),
                "write_p99_ms": pct(lat_done, 0.99),
                "read_p50_ms": pct(read_done, 0.50),
                "lease_reads": st1["lease_reads"] - st0["lease_reads"],
                "read_fallbacks": (
                    st1["read_fallbacks"] - st0["read_fallbacks"]
                ),
            }

        gw = Gateway(nhs, GatewayConfig(workers=2,
                                        max_queue_per_shard=512))
        curve = []
        for rate in levels:
            curve.append(run_level(gw, float(rate), level_secs))
        # lease vs ReadIndex p50: the same read served both ways
        leader = next(k for k in keys if nhs[k].is_leader_of(1))

        def p50_of(fn, n=200):
            xs = []
            for _ in range(n):
                t0 = time.perf_counter()
                fn()
                xs.append(time.perf_counter() - t0)
            xs.sort()
            return round(xs[n // 2] * 1000, 4)

        lease_p50 = p50_of(lambda: gw.read(1, None, timeout=5.0))
        ri_p50 = p50_of(
            lambda: nhs[leader].sync_read(1, None, timeout=5.0)
        )
        gw.close()

        # OVERLOAD: tiny queues, offered far past the measured knee —
        # p99 of completed must stay bounded while shedding climbs
        sat = max(
            (lv["committed_per_sec"] for lv in curve), default=500.0
        )
        gw = Gateway(nhs, GatewayConfig(
            workers=2, max_queue_per_shard=32,
            shed_dump_threshold=200, shed_dump_cooldown=1.0,
        ))
        over = run_level(gw, max(sat * 5.0, 1000.0), overload_secs)
        base_p99 = max(
            (lv["write_p99_ms"] for lv in curve
             if lv["write_p99_ms"] > 0), default=100.0
        )
        over["p99_bounded"] = bool(
            0 < over["write_p99_ms"] <= max(4 * base_p99, 500.0)
        )
        over["shed_dumps"] = gw.stats()["shed_dumps"]
        return {
            "shards": shards,
            "handles": shards * handles_per_shard,
            "rtt_ms": rtt_ms,
            "curve": curve,
            "overload": over,
            "lease_read_p50_ms": lease_p50,
            "read_index_p50_ms": ri_p50,
            "lease_skips_quorum_rt": bool(lease_p50 * 2 < ri_p50),
        }
    finally:
        if gw is not None:
            try:
                gw.close()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        for nh in nhs.values():
            try:
                nh.close()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass


def main() -> None:
    import jax

    # persistent compile cache: the routed-consensus programs cost
    # minutes of XLA compile on the TPU backend the first time and
    # nothing afterwards
    from dragonboat_tpu.ops.placement import configure_compile_cache

    configure_compile_cache(jax)

    NORTH_STAR = 1e9  # group-ticks/sec

    # BENCH_PROFILE=<dir>: capture a JAX profiler trace (xplane) of a
    # small in-process phase-A run for TensorBoard/xprof — the §5.1
    # tracing story (the reference leans on Go pprof; the kernel's
    # equivalent is the XLA device trace)
    profile_dir = os.environ.get("BENCH_PROFILE", "")

    smoke = bool(int(os.environ.get("BENCH_SMOKE", "0")))
    groups = int(os.environ.get("BENCH_GROUPS", "1000" if smoke else "100000"))
    # launches at 300k rows are real execution (~0.3-1 s behind a true
    # barrier) — 100-launch windows assumed the old dispatch-rate
    # timing and blew the budget
    iters = 10 if smoke else 16
    # consensus rounds are sub-ms once compiled (device-side stats
    # accumulation; no row-array readbacks) — a long timed window is
    # nearly free and sharpens commit-advance
    warm, timed, K = (4, 3, 8) if smoke else (4, 8, 16)

    # The round-2 lesson (BENCH_r02 recorded rc=124 with an EMPTY tail):
    # the driver's wall-clock budget is finite and a single JSON line at
    # the very end records nothing when the run is killed early.  So the
    # headline line is (re)printed after EVERY milestone — phase A, then
    # each phase-B success — each line complete and parseable on its
    # own.  Whatever the driver's cutoff, the last line standing is a
    # valid result.
    def emit(ticks_per_sec: float, a_groups, device_loop, consensus,
             balance=None, obs=None, lockcheck=None, jaxcheck=None,
             gateway=None, bigstate=None, hostplane=None,
             pipeline=None, multichip=None, updatelanes=None,
             day=None, readplane=None, fleetobs=None,
             wirecheck=None) -> None:
        # schema note (r5, verdict #9): "device_loop" is phase B — the
        # raw kernel+router loop with NO NodeHost/WAL/sessions/futures
        # (the r4 JSON called this "consensus", inviting its 19k/s to be
        # read as product throughput).  "consensus" is now phase C: real
        # committed proposals/sec through the PUBLIC NodeHost API with
        # the tan WAL in the loop (product_path: true inside).
        print(
            json.dumps(
                {
                    "metric": "raft_group_ticks_per_sec_per_chip",
                    "value": round(ticks_per_sec, 1),
                    "unit": "group-ticks/sec",
                    "vs_baseline": round(ticks_per_sec / NORTH_STAR, 4),
                    # the scale the phase-A number was actually measured
                    # at — a fallback to a smaller G must be
                    # visible in the record, not silently comparable
                    "phase_a_groups": a_groups,
                    "device_loop": device_loop,
                    "consensus": consensus,
                    # r06 schema addition: balance control-plane
                    # convergence (host-only; see phase_balance)
                    "balance": balance,
                    # r07 schema addition: observability bench guard —
                    # p50 proposal latency tracing-off (the default
                    # path the <2%-vs-seed gate reads) vs fully on
                    "obs": obs,
                    # r08 schema addition: lock-order-witness overhead
                    # guard (analysis/lockcheck; what the chaos/fault
                    # test modules pay for running under the sanitizer)
                    "lockcheck": lockcheck,
                    # r09 schema addition: device-plane auditor guard
                    # (analysis/jaxcheck; audit wall time + registry
                    # surface the lint gate's <60s budget rides on)
                    "jaxcheck": jaxcheck,
                    # r10 schema addition: serving-front-plane guard
                    # (gateway/; open-loop saturation curve + overload
                    # p99-bounded-while-shedding + lease-read split)
                    "gateway": gateway,
                    # r11 schema addition: big-state plane guard
                    # (bigstate/; laggard catch-up MB/s at 3 cap levels
                    # + concurrent commit-throughput delta)
                    "bigstate": bigstate,
                    # r12 schema addition: host-plane vectorization
                    # guard (ops/hostplane.py; scalar-vs-vectorized
                    # plan/merge stage wall time per rows tier — the
                    # r6 ledgers track t_plan/t_updates through this)
                    "hostplane": hostplane,
                    # r13 schema addition: launch-pipeline guard
                    # (ops/colocated.py double-buffered generations;
                    # serial-vs-depth-2 committed/sec + probe p50 at
                    # simulated sync floors)
                    "pipeline": pipeline,
                    # r14 schema addition: multi-chip mechanism guard
                    # (shard_map G-sharding + collective exchange lane
                    # at 1-8 forced host devices — docs/MULTICHIP.md)
                    "multichip": multichip,
                    # r15 schema addition: update-lane guard
                    # (ops/hostplane.UpdateLanes; scalar-vs-lane
                    # update-stage residual per rows tier — the ISSUE-13
                    # "Raft-less host rows" wall, docs/BENCH_NOTES_r09.md)
                    "updatelanes": updatelanes,
                    # r16 schema addition: production-day scenario guard
                    # (scenario/; mini-day ledger — per-fault-class
                    # throughput dips + recovery table + audit verdict
                    # over the mixed fleet — docs/SCENARIO.md)
                    "day": day,
                    # r17 schema addition: read-plane guard (readplane/;
                    # multi-process fleet — the 100k-session plane +
                    # exactly-once retry probes, leader-only vs
                    # replica-mix saturation windows with a mid-window
                    # leader SIGKILL, audit verdict — docs/READPLANE.md)
                    "readplane": readplane,
                    # r18 schema addition: fleet-scope telemetry guard
                    # (obs/fleetscope.py; committed/s with the scope
                    # poller off vs on over a real 3-process fleet +
                    # reply bytes per bounded poll + stitch/SLO verdict
                    # — docs/OBSERVABILITY.md "Fleet scope")
                    "fleetobs": fleetobs,
                    # r19 schema addition: wire-plane auditor guard
                    # (analysis/wirecheck; full-audit wall time at the
                    # lint-gate fuzz depth + per-codec encode/decode
                    # MB/s over the golden corpus — docs/ANALYSIS.md
                    # "Wire-plane audit")
                    "wirecheck": wirecheck,
                }
            ),
            flush=True,
        )

    # Every measured phase runs in a FRESH subprocess, one at a time:
    # a chip belongs to one process, so the parent never initialises a
    # backend and each device phase's child holds the chip in turn; a
    # child that dies cannot take the printed line with it.
    def run_sub(code: str, marker: str, timeout: int):
        import subprocess
        import sys

        try:
            out = subprocess.run(
                [sys.executable, "-c", code],
                capture_output=True,
                text=True,
                timeout=timeout,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            for line in out.stdout.splitlines():
                if line.startswith(marker + " "):
                    return json.loads(line[len(marker) + 1:]), None
            return None, f"rc={out.returncode}"
        except Exception as e:  # noqa: BLE001 — incl. TimeoutExpired
            return None, type(e).__name__

    # GLOBAL wall-clock budget (the r02/r03 lesson, twice over): the
    # driver's window is finite and both rounds recorded rc=124 with no
    # phase-B result because the worst-case schedule (A + retry + a
    # 3-rung B ladder x 600s each) was ~50 minutes.  Everything now
    # spends from ONE budget: a single phase-A attempt sized to leave
    # phase B the lion's share, phase B launched IMMEDIATELY after the
    # first emit with (almost) all remaining time, and fallback rungs
    # only if time visibly remains.  rc is 0 regardless of outcomes —
    # failures are recorded in the JSON, not the exit code.
    # default sized under the driver's observed cutoff (r3 was killed at
    # rc=124 somewhere past phase A; a budget the driver never truncates
    # beats a longer one it does)
    budget = float(os.environ.get("BENCH_BUDGET_SECS", "540"))
    t_start = time.monotonic()

    def remaining() -> float:
        return budget - (time.monotonic() - t_start)

    a_timeout = min(
        int(os.environ.get("BENCH_A_TIMEOUT", "600")),
        max(60, int(remaining() * 0.4)),
    )
    ticks_per_sec = -1.0  # record failure rather than crash
    a_groups = 0
    code = (
        "import jax, json, bench;"
        f"print('BENCHA ' + json.dumps(bench.phase_a(jax, {groups}, "
        f"{iters})))"
    )
    val, a_err = run_sub(code, "BENCHA", a_timeout)
    if val is not None:
        ticks_per_sec = float(val)
        a_groups = groups
    emit(ticks_per_sec, a_groups, None, None)

    # Phase B runs NOW — before any retry polish — because a captured
    # consensus number at full scale is worth more than a prettier
    # phase-A number.  First rung gets all remaining budget minus a
    # 45s emit/teardown reserve; lower rungs only run if the first
    # fails with >=180s still on the clock.  (Compile risk dominates:
    # at 150k rows step ~70s + route ~200s cold on v5e-1, ~0 warm from
    # the persistent cache; execution is sub-ms per round.)
    b_top = int(os.environ.get("BENCH_B_GROUPS", str(min(groups // 10, 10000))))
    device_loop = None
    consensus = None
    rungs = (b_top, b_top // 5)
    for rung_i, scale in enumerate(rungs):
        if scale < 100 or remaining() < 90:
            break
        # the FIRST rung may not eat the whole budget: a captured number
        # at rung 2 beats a timeout at rung 1 (the r4 driver-rehearsal
        # failure mode)
        frac = 0.45 if rung_i == 0 and len(rungs) > 1 else 0.6
        b_timeout = min(
            int(os.environ.get("BENCH_B_TIMEOUT", "900")),
            max(60, int(remaining() * frac - 45)),
        )
        code = (
            "import jax, json, bench;"
            f"print('BENCHB ' + json.dumps(bench.phase_b(jax, {scale}, "
            f"{warm}, {timed}, {K})))"
        )
        device_loop, b_err = run_sub(code, "BENCHB", b_timeout)
        if device_loop is not None and "error" not in device_loop:
            break
        device_loop = {"error": f"{b_err or 'failed'} at {scale} groups"}
        emit(ticks_per_sec, a_groups, device_loop, None)  # record the rung
        if remaining() < 180:
            break
    emit(ticks_per_sec, a_groups, device_loop, None)

    # Phase C — PRODUCT-PATH consensus (the real "consensus" row):
    # committed proposals/sec through the public NodeHost API with the
    # colocated engine + tan WAL, sustained for >=60s.
    c_shards = int(os.environ.get("BENCH_C_SHARDS", "1000"))
    c_secs = float(os.environ.get("BENCH_C_SECS", "60"))
    if remaining() > 120:
        c_timeout = max(90, int(remaining() - 30))
        code = (
            "import jax, json, bench;"
            f"print('BENCHC ' + json.dumps(bench.phase_c(jax, {c_shards}, "
            f"{c_secs})))"
        )
        consensus, c_err = run_sub(code, "BENCHC", c_timeout)
        if consensus is None:
            consensus = {"error": f"{c_err or 'failed'} at {c_shards} shards"}
        emit(ticks_per_sec, a_groups, device_loop, consensus)

    # Balance control-plane convergence (host path only — cheap, no
    # device risk): rebalance ticks for the 16-shard/4-host drain
    balance = None
    if bool(int(os.environ.get("BENCH_BALANCE", "1"))) and remaining() > 90:
        code = (
            "import json, bench;"
            "print('BENCHBAL ' + json.dumps(bench.phase_balance(16, 4)))"
        )
        balance, bal_err = run_sub(
            code, "BENCHBAL", max(60, min(300, int(remaining() - 30)))
        )
        if balance is None:
            balance = {"error": bal_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance)

    # Observability bench guard (host path only — cheap, no device
    # risk): p50 proposal latency with tracing off vs fully on
    obs = None
    if bool(int(os.environ.get("BENCH_OBS", "1"))) and remaining() > 60:
        code = (
            "import json, bench;"
            "print('BENCHOBS ' + json.dumps(bench.phase_obs()))"
        )
        obs, obs_err = run_sub(
            code, "BENCHOBS", max(60, min(240, int(remaining() - 30)))
        )
        if obs is None:
            obs = {"error": obs_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs)

    # Lock-order-witness overhead guard (host path only — cheap, no
    # device risk): same workload with the sanitizer off vs installed
    lck = None
    if bool(int(os.environ.get("BENCH_LOCKCHECK", "1"))) and remaining() > 60:
        code = (
            "import json, bench;"
            "print('BENCHLCK ' + json.dumps(bench.phase_lockcheck()))"
        )
        lck, lck_err = run_sub(
            code, "BENCHLCK", max(60, min(240, int(remaining() - 30)))
        )
        if lck is None:
            lck = {"error": lck_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck)

    # Device-plane auditor guard (abstract tracing only — cheap, no
    # device risk): full jaxcheck audit wall time + registry surface
    jck = None
    if bool(int(os.environ.get("BENCH_JAXCHECK", "1"))) and remaining() > 60:
        code = (
            "import json, bench;"
            "print('BENCHJAX ' + json.dumps(bench.phase_jaxcheck()))"
        )
        jck, jck_err = run_sub(
            code, "BENCHJAX", max(60, min(180, int(remaining() - 30)))
        )
        if jck is None:
            jck = {"error": jck_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck, jck)

    # Serving-front-plane guard (host path only — cheap, no device
    # risk): gateway saturation curve + overload p99 + lease-read split
    gwb = None
    if bool(int(os.environ.get("BENCH_GATEWAY", "1"))) and remaining() > 60:
        code = (
            "import json, bench;"
            "print('BENCHGW ' + json.dumps(bench.phase_gateway()))"
        )
        gwb, gw_err = run_sub(
            code, "BENCHGW", max(60, min(240, int(remaining() - 30)))
        )
        if gwb is None:
            gwb = {"error": gw_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck, jck, gwb)

    # Big-state plane guard (host+disk path only — no device risk):
    # laggard catch-up MB/s at 3 cap levels + commit-throughput delta
    bsb = None
    if bool(int(os.environ.get("BENCH_BIGSTATE", "1"))) and remaining() > 90:
        code = (
            "import json, bench;"
            "print('BENCHBS ' + json.dumps(bench.phase_bigstate()))"
        )
        bsb, bs_err = run_sub(
            code, "BENCHBS", max(90, min(300, int(remaining() - 30)))
        )
        if bsb is None:
            bsb = {"error": bs_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck, jck, gwb, bsb)

    # Host-plane vectorization guard (pure numpy — no device, cheap):
    # scalar-vs-vectorized plan/merge stage costs per rows tier
    hpb = None
    if bool(int(os.environ.get("BENCH_HOSTPLANE", "1"))) and remaining() > 45:
        code = (
            "import json, bench;"
            "print('BENCHHP ' + json.dumps(bench.phase_hostplane()))"
        )
        hpb, hp_err = run_sub(
            code, "BENCHHP", max(45, min(240, int(remaining() - 30)))
        )
        if hpb is None:
            hpb = {"error": hp_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck, jck, gwb, bsb, hpb)

    # Launch-pipeline guard: serial vs double-buffered colocated loop
    # under the simulated link-latency floor (BENCH_PIPELINE gate)
    ppb = None
    if bool(int(os.environ.get("BENCH_PIPELINE", "1"))) and remaining() > 150:
        code = (
            "import jax, json, bench;"
            "print('BENCHPP ' + json.dumps(bench.phase_pipeline(jax)))"
        )
        ppb, pp_err = run_sub(
            code, "BENCHPP", max(150, min(600, int(remaining() - 30)))
        )
        if ppb is None:
            ppb = {"error": pp_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck, jck, gwb, bsb, hpb, ppb)

    # Multi-chip mechanism guard: sharded kernel/round parity + balance
    # + transfer-free gates at forced host device counts (BENCH_MULTICHIP
    # gate; the phase spawns its OWN per-count subprocesses, so it runs
    # in-process here rather than through run_sub)
    mcb = None
    if bool(int(os.environ.get("BENCH_MULTICHIP", "1"))) and remaining() > 200:
        try:
            mcb = phase_multichip()
        except Exception as e:  # noqa: BLE001 — the guard must not kill main
            mcb = {"error": str(e)[-400:]}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck, jck, gwb, bsb, hpb, ppb, mcb)

    # Update-lane guard (pure numpy — no device, cheap): scalar-vs-lane
    # update-stage residual per rows tier (BENCH_UPDATELANES gate; heavy
    # 50k/250k tiers ride BENCH_UPDATELANES_HEAVY=1 like the hostplane
    # guard — docs/BENCH_NOTES_r09.md)
    ulb = None
    if bool(int(os.environ.get("BENCH_UPDATELANES", "1"))) and remaining() > 45:
        code = (
            "import json, bench;"
            "print('BENCHUL ' + json.dumps(bench.phase_updatelanes()))"
        )
        ulb, ul_err = run_sub(
            code, "BENCHUL", max(45, min(240, int(remaining() - 30)))
        )
        if ulb is None:
            ulb = {"error": ul_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck, jck, gwb, bsb, hpb, ppb, mcb, ulb)

    # Production-day scenario guard (host path only, ~15-25s; BENCH_DAY
    # gate): the mini-day ledger — dips per fault class, recovery table,
    # audit verdict (docs/SCENARIO.md)
    dayb = None
    if bool(int(os.environ.get("BENCH_DAY", "1"))) and remaining() > 60:
        day_seed = int(os.environ.get("BENCH_DAY_SEED", "7"))
        day_scale = float(os.environ.get("BENCH_DAY_SCALE", "0.6"))
        code = (
            "import json, bench;"
            f"print('BENCHDAY ' + json.dumps(bench.phase_day({day_seed}, "
            f"{day_scale})))"
        )
        dayb, day_err = run_sub(
            code, "BENCHDAY", max(60, min(300, int(remaining() - 30)))
        )
        if dayb is None:
            dayb = {"error": day_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck, jck, gwb, bsb, hpb, ppb, mcb, ulb, dayb)

    # Read-plane guard (host path only; multi-process fleet + RPC door;
    # BENCH_READPLANE gate): the 100k-session plane, exactly-once retry
    # probes across a leader SIGKILL, and the leader-only vs replica-mix
    # saturation windows (docs/READPLANE.md).  At the default knobs the
    # session registration alone is minutes of wall, so the in-main run
    # drops to smoke-scale defaults unless BENCH_READPLANE_FULL=1 —
    # `python bench.py phase_readplane` is the full standalone run.
    rpb = None
    if bool(int(os.environ.get("BENCH_READPLANE", "1"))) and remaining() > 90:
        rp_env = ""
        if not bool(int(os.environ.get("BENCH_READPLANE_FULL", "0"))):
            rp_env = "import os; os.environ.setdefault('BENCH_SMOKE', '1');"
        code = (
            f"{rp_env}import json, bench;"
            "print('BENCHRP ' + json.dumps(bench.phase_readplane()))"
        )
        rpb, rp_err = run_sub(
            code, "BENCHRP", max(90, min(420, int(remaining() - 30)))
        )
        if rpb is None:
            rpb = {"error": rp_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck, jck, gwb, bsb, hpb, ppb, mcb, ulb, dayb, rpb)

    # Fleet-scope telemetry guard (host path only, ~20-25s;
    # BENCH_FLEETOBS gate): commit throughput with the FleetScope
    # poller off vs on over a real 3-process fleet — the obs-plane tax
    # plus the stitch/SLO working-plane verdict (docs/OBSERVABILITY.md
    # "Fleet scope")
    fob = None
    if bool(int(os.environ.get("BENCH_FLEETOBS", "1"))) and remaining() > 60:
        code = (
            "import json, bench;"
            "print('BENCHFO ' + json.dumps(bench.phase_fleetobs()))"
        )
        fob, fo_err = run_sub(
            code, "BENCHFO", max(60, min(180, int(remaining() - 30)))
        )
        if fob is None:
            fob = {"error": fo_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck, jck, gwb, bsb, hpb, ppb, mcb, ulb, dayb, rpb, fob)

    # Wire-plane auditor guard (host-only bytes work, ~5s;
    # BENCH_WIRECHECK gate): full wirecheck audit wall time at the
    # lint-gate fuzz depth + per-codec encode/decode MB/s over the
    # golden corpus (docs/ANALYSIS.md "Wire-plane audit")
    wck = None
    if bool(int(os.environ.get("BENCH_WIRECHECK", "1"))) and remaining() > 45:
        code = (
            "import json, bench;"
            "print('BENCHWIRE ' + json.dumps(bench.phase_wirecheck()))"
        )
        wck, wc_err = run_sub(
            code, "BENCHWIRE", max(45, min(120, int(remaining() - 30)))
        )
        if wck is None:
            wck = {"error": wc_err or "failed"}
        emit(ticks_per_sec, a_groups, device_loop, consensus, balance, obs,
             lck, jck, gwb, bsb, hpb, ppb, mcb, ulb, dayb, rpb, fob, wck)

    # phase-A retry polish: only with phases B/C already banked and time
    # left over (a failed A records -1 above; a smaller-G fallback is
    # clearly labeled via phase_a_groups)
    if ticks_per_sec < 0 and remaining() > 120:
        fallback = max(groups // 10, 100)
        code = (
            "import jax, json, bench;"
            f"print('BENCHA ' + json.dumps(bench.phase_a(jax, {fallback}, "
            f"{iters})))"
        )
        val, a_err = run_sub(
            code, "BENCHA", max(60, int(remaining() - 30))
        )
        if val is not None:
            ticks_per_sec = float(val)
            a_groups = fallback
            emit(ticks_per_sec, a_groups, device_loop, consensus, balance,
                 obs, lck)

    if profile_dir and remaining() > 60:
        # profiling runs a small phase A in-process with the tracer on;
        # LAST so it can never cost the measured phases their budget
        from dragonboat_tpu.profiling import trace

        try:
            with trace(profile_dir):
                phase_a(jax, min(groups, 10_000), 10)
        except Exception:  # noqa: BLE001 — tracing must not cost the run
            pass


if __name__ == "__main__":
    import sys as _sys

    if "phase_multichip" in _sys.argv[1:]:
        # standalone mechanism run: `python bench.py phase_multichip`
        # (spawns its own per-device-count subprocesses; no backend is
        # initialized in THIS process, so the forced counts latch)
        print("BENCHMC " + json.dumps(phase_multichip()), flush=True)
    elif "phase_day" in _sys.argv[1:]:
        # standalone mini-day run: `python bench.py phase_day`
        import json

        print("BENCHDAY " + json.dumps(phase_day()), flush=True)
    elif "phase_readplane" in _sys.argv[1:]:
        # standalone read-plane run: `python bench.py phase_readplane`
        # — full-scale defaults (100k sessions, 33 shards) unless
        # BENCH_SMOKE=1 or the BENCH_READPLANE_* knobs say otherwise
        print("BENCHRP " + json.dumps(phase_readplane()), flush=True)
    elif "phase_fleetobs" in _sys.argv[1:]:
        # standalone fleet-scope run: `python bench.py phase_fleetobs`
        # — full windows unless BENCH_SMOKE=1 / BENCH_FLEETOBS_* say
        # otherwise (docs/OBSERVABILITY.md "Fleet scope")
        print("BENCHFO " + json.dumps(phase_fleetobs()), flush=True)
    elif "phase_wirecheck" in _sys.argv[1:]:
        # standalone wire-plane run: `python bench.py phase_wirecheck`
        # (docs/ANALYSIS.md "Wire-plane audit")
        print("BENCHWIRE " + json.dumps(phase_wirecheck()), flush=True)
    elif "phase_updatelanes" in _sys.argv[1:]:
        # standalone update-lane run: `python bench.py phase_updatelanes`
        # (host-only numpy; BENCH_UPDATELANES_HEAVY=1 adds 50k/250k)
        print("BENCHUL " + json.dumps(phase_updatelanes()), flush=True)
    elif "phase_pipeline" in _sys.argv[1:]:
        # standalone launch-pipeline run: `python bench.py
        # phase_pipeline` — the floor × depth × fused-K matrix plus the
        # fusedround split (BENCH_PIPELINE_* / BENCH_FUSEDROUND knobs)
        import jax

        print("BENCHPP " + json.dumps(phase_pipeline(jax)), flush=True)
    else:
        main()
