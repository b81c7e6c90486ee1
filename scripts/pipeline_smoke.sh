#!/usr/bin/env bash
# Launch-pipeline smoke (double-buffered generations): boot a
# 3-replica colocated cluster with the
# pipeline at depth 2 and a 10 ms simulated sync floor
# (DRAGONBOAT_TPU_SYNC_FLOOR_MS semantics via the engine kwarg), drive
# a small proposal workload, then assert
#   1. every future completes (zero lost/duplicated completions — the
#      merge tail running one generation behind must not strand any),
#   2. overlap actually occurred: pipeline_overlap_seconds_total > 0
#      (host work ran concurrently with an in-flight readback — the
#      double-buffering win, visible without hardware),
#   3. the pipeline drains clean at close (no in-flight generations or
#      deferred actions leak) and the hostplane parity oracle stayed
#      green across every pipelined generation.
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

ADDRS = {1: "pipe-smoke-1", 2: "pipe-smoke-2", 3: "pipe-smoke-3"}
reset_inproc_network()
group = ColocatedEngineGroup(
    capacity=16, P=5, W=32, M=8, E=4, O=32, budget=4,
    pipeline_depth=2, sync_floor_ms=10.0,
)
nhs = {}
for rid, addr in ADDRS.items():
    d = f"/tmp/nh-pipe-smoke-{rid}"
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
    # async proposals keep generations flowing so readbacks overlap
    # the next launch's upload/dispatch
    pending = []
    for i in range(40):
        pending.append(nh.propose(sess, set_cmd(f"k{i}", str(i)), 20.0))
        if len(pending) >= 8:
            rs = pending.pop(0)
            rs._event.wait(20.0)
            assert rs.code == 1, f"proposal failed: code={rs.code}"
    done = 0
    for rs in pending:
        rs._event.wait(20.0)
        assert rs.code == 1, f"tail proposal failed: code={rs.code}"  # (1)
        done += 1

    core = group.core
    st = core.stats
    overlap = st.get("pipeline_overlap_s", 0.0)
    assert overlap > 0, (                                  # (2)
        f"no pipeline overlap recorded: stats={overlap}"
    )
    assert st["launches"] > 5, st
    assert hostplane.PARITY_FAILURE_COUNT == 0, hostplane.PARITY_FAILURES
finally:
    for nh in nhs.values():
        try:
            nh.close()
        except Exception:
            pass

core = group.core
assert not core._inflight and not core._deferred, (        # (3)
    f"pipeline leaked: inflight={len(core._inflight)} "
    f"deferred={len(core._deferred)}"
)
print(
    f"PIPELINE_SMOKE_OK launches={core.stats['launches']} "
    f"overlap_s={core.stats['pipeline_overlap_s']:.3f} "
    f"early={core.stats.get('early_completions', 0)} "
    f"fences={core.stats.get('pipeline_fences', 0)} parity_green=1"
)
EOF
