"""A KV service front-end on the Gateway: the production client path.

Replaces the raw-NodeHost pattern of examples/multigroup.py for client
traffic: instead of each client resolving the leader and driving
``sync_propose``/``sync_read`` itself, clients hold cheap
:class:`~dragonboat_tpu.gateway.ClientHandle` sessions (exactly-once
via the replicated session registry) and the :class:`Gateway` does the
rest — leader routing off ``leader_updated`` events, per-shard batch
submission, admission control, and CheckQuorum lease reads that skip
the per-read ReadIndex quorum round trip (docs/GATEWAY.md).  Run:

    python examples/kv_gateway.py

When the backing NodeHosts run the colocated device engine, client
latency also rides the launch pipeline: generations double-buffer
(engine keyword ``pipeline_depth``, 2), and fused commit waves (engine
keyword ``fused_rounds``, 3) collapse a proposal's propose→commit
rounds into one launch + one readback window.  ``chip_smoke.py`` at
the repo root runs this served path on the device engine, on the chip.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dragonboat_tpu import (  # noqa: E402
    Config,
    EngineConfig,
    ExpertConfig,
    Gateway,
    GatewayConfig,
    IStateMachine,
    NodeHost,
    NodeHostConfig,
    Result,
)

ADDRS = {1: "kvgw-1", 2: "kvgw-2", 3: "kvgw-3"}
SHARDS = (1, 2)


class KV(IStateMachine):
    """cmd: b"key=value"; lookup: key -> value."""

    def __init__(self, shard_id, replica_id):
        self.d = {}

    def update(self, entry):
        k, v = entry.cmd.decode().split("=", 1)
        self.d[k] = v
        return Result(value=len(self.d))

    def lookup(self, q):
        return self.d.get(q)

    def save_snapshot(self, w, files, done):
        w.write(json.dumps(self.d).encode())

    def recover_from_snapshot(self, r, files, done):
        self.d = json.loads(r.read(-1).decode())


def main() -> None:
    for rid in ADDRS:
        shutil.rmtree(f"/tmp/nh-kvgw-{rid}", ignore_errors=True)
    nhs = {
        addr: NodeHost(
            NodeHostConfig(
                nodehost_dir=f"/tmp/nh-kvgw-{rid}",
                rtt_millisecond=5,
                raft_address=addr,
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=2, apply_shards=2)
                ),
            )
        )
        for rid, addr in ADDRS.items()
    }
    gw = None
    try:
        for sid in SHARDS:
            for rid, addr in ADDRS.items():
                # check_quorum=True is what backs the leader lease: a
                # follower that heard from a live leader refuses votes
                # for an election window, so the leader can serve local
                # reads while its lease holds
                nhs[addr].start_replica(
                    ADDRS, False, KV,
                    Config(replica_id=rid, shard_id=sid, election_rtt=10,
                           heartbeat_rtt=1, check_quorum=True),
                )
        gw = Gateway(nhs, GatewayConfig(workers=2))

        # register session → put: one handle per client, exactly-once
        handles = {sid: gw.connect(sid, timeout=10.0) for sid in SHARDS}
        for sid, h in handles.items():
            for i in range(20):
                h.sync_propose(f"k{i}=s{sid}v{i}".encode(), timeout=10.0)

        # get with lease reads: served on the leader host WITHOUT a
        # ReadIndex quorum round trip while the CheckQuorum lease holds
        for sid in SHARDS:
            assert gw.read(sid, "k0", timeout=10.0) == f"s{sid}v0"
            assert gw.read(sid, "k19", timeout=10.0) == f"s{sid}v19"
        st = gw.stats()
        print("route table:", st["route_table"])
        print(
            f"committed={st['committed']} lease_reads={st['lease_reads']} "
            f"fallbacks={st['read_fallbacks']} shed={st['shed']}"
        )

        # measure the lease win: p50 of lease reads vs ReadIndex reads
        def p50(fn, n=60):
            lat = []
            for _ in range(n):
                t0 = time.perf_counter()
                fn()
                lat.append(time.perf_counter() - t0)
            lat.sort()
            return lat[n // 2] * 1000.0

        lease_p50 = p50(lambda: gw.read(1, "k0", timeout=10.0))
        leader = next(a for a in ADDRS.values() if nhs[a].is_leader_of(1))
        ri_p50 = p50(lambda: nhs[leader].sync_read(1, "k0", timeout=10.0))
        print(
            f"read p50: lease {lease_p50:.3f} ms vs read_index "
            f"{ri_p50:.3f} ms"
        )
        for h in handles.values():
            h.close()
        print("ok")
    finally:
        if gw is not None:
            gw.close()
        for nh in nhs.values():
            nh.close()


if __name__ == "__main__":
    main()
