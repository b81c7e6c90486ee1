#!/usr/bin/env bash
# Fused-commit-round smoke (ISSUE 15): boot a
# 3-replica colocated cluster with the launch pipeline at depth 2, a
# 10 ms simulated sync floor and fused waves at the product default
# (K=3), drive a small proposal workload with the hostplane parity
# oracle armed, then assert
#   1. fused waves actually fired (fused_waves > 0) and stepped K
#      rounds each (fused_rounds_stepped >= 3 * fused_waves),
#   2. the one-readback budget held: readback_windows == launches +
#      sel_fallbacks (ONE collect window per generation regardless of
#      its round count — a wave never pays K floors),
#   3. every future completes and the parity oracle stayed green on
#      every live generation (fused or single-round),
#   4. the pipeline drains clean at close (no in-flight generations or
#      deferred actions leak).
# Cheap (~5s) — wired into tier1.sh as a post-step.
cd "$(dirname "$0")/.." || exit 1
exec env JAX_PLATFORMS=cpu DRAGONBOAT_TPU_HOSTPLANE_PARITY=1 python - <<'EOF'
import shutil
import sys
import time

sys.path.insert(0, "tests")

from dragonboat_tpu import (
    Config,
    EngineConfig,
    ExpertConfig,
    NodeHost,
    NodeHostConfig,
)
from dragonboat_tpu.ops import hostplane
from dragonboat_tpu.ops.colocated import ColocatedEngineGroup
from dragonboat_tpu.transport.inproc import reset_inproc_network
from test_nodehost import KVStore, set_cmd

ADDRS = {1: "fused-smoke-1", 2: "fused-smoke-2", 3: "fused-smoke-3"}
reset_inproc_network()
group = ColocatedEngineGroup(
    capacity=16, P=5, W=32, M=8, E=4, O=32, budget=4,
    pipeline_depth=2, sync_floor_ms=10.0, fused_rounds=3,
)
nhs = {}
for rid, addr in ADDRS.items():
    d = f"/tmp/nh-fused-smoke-{rid}"
    shutil.rmtree(d, ignore_errors=True)
    nhs[rid] = NodeHost(NodeHostConfig(
        nodehost_dir=d,
        rtt_millisecond=5,
        raft_address=addr,
        expert=ExpertConfig(
            engine=EngineConfig(exec_shards=1, apply_shards=2),
            step_engine_factory=group.factory,
        ),
    ))
try:
    for rid, nh in nhs.items():
        nh.start_replica(
            ADDRS, False, KVStore,
            Config(replica_id=rid, shard_id=1, election_rtt=20,
                   heartbeat_rtt=2, pre_vote=True, check_quorum=True),
        )
    deadline = time.time() + 30.0
    leader = None
    while time.time() < deadline and leader is None:
        leader = next((r for r, nh in nhs.items() if nh.is_leader_of(1)),
                      None)
        time.sleep(0.02)
    assert leader, "no leader within 30s"

    nh = nhs[leader]
    sess = nh.get_noop_session(1)
    pending = []
    for i in range(40):
        pending.append(nh.propose(sess, set_cmd(f"k{i}", str(i)), 20.0))
        if len(pending) >= 8:
            rs = pending.pop(0)
            rs._event.wait(20.0)
            assert rs.code == 1, f"proposal failed: code={rs.code}"
    for rs in pending:
        rs._event.wait(20.0)
        assert rs.code == 1, f"tail proposal failed: code={rs.code}"  # (3)

    core = group.core
    # one-readback budget, snapshotted UNDER the core lock so a tick
    # generation dispatching mid-read can't skew it: every launched
    # generation is either completed (one window counted, plus one per
    # exact-gather fallback round) or still in flight — exact, not <=
    with core._lock:
        st = dict(core.stats)
        inflight = len(core._inflight)
    assert st["fused_waves"] > 0, st                       # (1)
    assert st["fused_rounds_stepped"] >= 3 * st["fused_waves"], st
    assert st["readback_windows"] + inflight == (          # (2)
        st["launches"] + st.get("sel_fallbacks", 0)
    ), (st, inflight)
    assert hostplane.PARITY_FAILURE_COUNT == 0, hostplane.PARITY_FAILURES
finally:
    for nh in nhs.values():
        try:
            nh.close()
        except Exception:
            pass

core = group.core
assert not core._inflight and not core._deferred, (        # (4)
    f"pipeline leaked: inflight={len(core._inflight)} "
    f"deferred={len(core._deferred)}"
)
print(
    f"FUSEDROUND_SMOKE_OK waves={st['fused_waves']} "
    f"rounds={st['fused_rounds_stepped']} "
    f"launches={st['launches']} "
    f"readback_windows={st['readback_windows']} "
    f"fences={st['fused_fences']} parity_green=1"
)
EOF
