"""Child process for the multi-process production-day fleet.

One OS process == one NodeHost over real TCP + gossip on loopback,
fronted by a :class:`~dragonboat_tpu.gateway.rpc.RpcServer` — the
externally-observable deployment shape (docs/SCENARIO.md
"Multi-process gear").  Unlike ``tests/multiproc_runner.py``'s file
protocol, ALL client traffic arrives over the RPC ingress: the parent
drives commits, reads, session registration and even the nemesis
(``RPC_OP_FAULT`` is enabled — this worker exists to be shaken) through
the same wire a production client would use.  ``kill -9`` therefore
looks exactly like a machine crash from both sides: no shared memory,
no atexit, the parent's pending RPCs fail per the degradation matrix
and recovery is WAL replay + gossip re-resolution + raft catch-up.

Usage::

    python -m dragonboat_tpu.scenario.procworker <idx> <n> <workdir> \
        <base_port>

Port layout (loopback): raft = base+idx, gossip = base+100+idx,
RPC = base+200+idx — fixed per slot so a restarted worker is reachable
at the same RPC address (the parent's RemoteHostHandle reconnects
through its breaker without re-registration).

The worker writes ``ready-<idx>.json`` ({nhid, rpc, raft, gossip, pid})
once serving, then runs until ``stop-<idx>`` appears (graceful close,
for teardown) or it is killed outright (the interesting path).
"""
import json
import os
import sys
import time


def _write_atomic(path: str, obj) -> None:
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def main() -> None:
    idx = int(sys.argv[1])
    n = int(sys.argv[2])
    workdir = sys.argv[3]
    base_port = int(sys.argv[4])
    # a chip belongs to one process at a time: a child must never take
    # it from its parent, and the host engine path used here needs no
    # device at all — pin the cpu backend before anything imports jax
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    os.environ["JAX_PLATFORMS"] = "cpu"

    from dragonboat_tpu import (
        Config,
        EngineConfig,
        ExpertConfig,
        GossipConfig,
        NodeHost,
        NodeHostConfig,
    )
    from dragonboat_tpu.audit.model import AuditKV
    from dragonboat_tpu.faults import FaultController
    from dragonboat_tpu.gateway.rpc import RpcServer
    from dragonboat_tpu.transport.tcp import tcp_transport_factory

    raft_addr = f"127.0.0.1:{base_port + idx}"
    gossip_addr = f"127.0.0.1:{base_port + 100 + idx}"
    rpc_addr = f"127.0.0.1:{base_port + 200 + idx}"
    # fleet-scope observability: tracing + flight recorder ON by
    # default so the parent's FleetScope has something to poll over
    # RPC_OP_OBS; DRAGONBOAT_PROC_OBS=0 runs the worker dark (the
    # degrade-matrix shape where recorder_tail answers enabled=False)
    obs_on = bool(int(os.environ.get("DRAGONBOAT_PROC_OBS", "1")))
    nh = NodeHost(
        NodeHostConfig(
            nodehost_dir=f"{workdir}/nh-{idx}",
            rtt_millisecond=20,
            raft_address=raft_addr,
            address_by_nodehost_id=True,
            enable_tracing=obs_on,
            trace_sample_rate=1.0,
            enable_flight_recorder=obs_on,
            gossip=GossipConfig(
                bind_address=gossip_addr,
                # every worker seeds at slot 1's gossip port; the
                # parent's observer joins through the same seed
                seed=[f"127.0.0.1:{base_port + 100 + 1}"],
            ),
            expert=ExpertConfig(
                engine=EngineConfig(exec_shards=1, apply_shards=1),
                transport_factory=tcp_transport_factory,
            ),
        )
    )
    # publish our nodehost id, then wait for the full member map:
    # gossip addressing resolves replica -> nodehost-id -> address
    # dynamically (a restarted peer is re-found wherever it binds)
    _write_atomic(f"{workdir}/nhid-{idx}.json", {"nhid": nh.nodehost_id})
    members = {}
    deadline = time.time() + 60
    while len(members) < n:
        for r in range(1, n + 1):
            p = f"{workdir}/nhid-{r}.json"
            if r not in members and os.path.exists(p):
                try:
                    with open(p) as f:
                        members[r] = json.load(f)["nhid"]
                except (json.JSONDecodeError, KeyError):
                    pass
        if time.time() > deadline:
            raise TimeoutError(f"worker {idx}: member map incomplete")
        time.sleep(0.1)
    nh.start_replica(
        members, False, AuditKV,
        Config(replica_id=idx, shard_id=1, election_rtt=20,
               heartbeat_rtt=2, pre_vote=True, check_quorum=True),
    )

    # the nemesis plane, remotely drivable: the parent injects
    # asym_drop/asym_delay/partition windows on THIS host's transport
    # through the same RPC ingress clients use
    ctl = FaultController(seed=1000 + idx)
    ctl.install_nodehost(f"w{idx}", nh)
    srv = RpcServer(nh, rpc_addr, fault_controller=ctl,
                    allow_fault_ops=True)
    srv.start()
    _write_atomic(
        f"{workdir}/ready-{idx}.json",
        {"nhid": nh.nodehost_id, "rpc": srv.listen_address,
         "raft": raft_addr, "gossip": gossip_addr, "pid": os.getpid()},
    )

    stop_path = f"{workdir}/stop-{idx}"
    while not os.path.exists(stop_path):
        time.sleep(0.1)
    srv.close()
    nh.close()


if __name__ == "__main__":
    main()
