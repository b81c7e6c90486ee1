"""Child process for the multi-process cluster test.

One OS process == one NodeHost over real TCP + gossip on loopback —
the reference's normal deployment shape (drummer ran real multi-process
clusters [U]); every in-repo integration test before this ran all
NodeHosts in one process.  Driven by the parent via a file protocol
(commands in, results out) so kill -9 looks exactly like a machine
crash: no atexit, no graceful close.

Usage: python multiproc_runner.py <rid> <workdir> <base_port>
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
    rid = int(sys.argv[1])
    workdir = sys.argv[2]
    base_port = int(sys.argv[3])
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    # a chip belongs to one process at a time: a child must never take
    # it from its parent, and the host engine path used here needs no
    # device at all — pin the cpu backend before anything imports jax
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    os.environ["JAX_PLATFORMS"] = "cpu"

    from dragonboat_tpu import (
        GossipConfig,
        EngineConfig,
        ExpertConfig,
        NodeHost,
        NodeHostConfig,
    )
    from dragonboat_tpu.transport.tcp import tcp_transport_factory
    from test_nodehost import KVStore, shard_config

    nh = NodeHost(
        NodeHostConfig(
            nodehost_dir=f"{workdir}/nh-{rid}",
            rtt_millisecond=20,
            raft_address=f"127.0.0.1:{base_port + rid}",
            address_by_nodehost_id=True,
            gossip=GossipConfig(
                bind_address=f"127.0.0.1:{base_port + 100 + rid}",
                seed=[f"127.0.0.1:{base_port + 100 + 1}"],
            ),
            expert=ExpertConfig(
                engine=EngineConfig(exec_shards=1, apply_shards=1),
                transport_factory=tcp_transport_factory,
            ),
        )
    )
    # publish our nodehost id, then wait for the full member map: gossip
    # addressing resolves replica -> nodehost-id -> address dynamically,
    # so peers can restart on new ports and still be found
    _write_atomic(f"{workdir}/nhid-{rid}.json", {"nhid": nh.nodehost_id})
    members = {}
    deadline = time.time() + 60
    while len(members) < 3:
        for r in (1, 2, 3):
            p = f"{workdir}/nhid-{r}.json"
            if r not in members and os.path.exists(p):
                try:
                    with open(p) as f:
                        members[r] = json.load(f)["nhid"]
                except (json.JSONDecodeError, KeyError):
                    pass
        if time.time() > deadline:
            raise TimeoutError(f"runner {rid}: member map incomplete")
        time.sleep(0.1)
    nh.start_replica(
        members, False, KVStore,
        shard_config(rid, election_rtt=20, heartbeat_rtt=2,
                     pre_vote=True, check_quorum=True),
    )

    # command loop: cmd-<rid>-<n>.json in, res-<rid>-<n>.json out
    n = 0
    session = nh.get_noop_session(1)
    while True:
        lid, ok = nh.get_leader_id(1)
        _write_atomic(
            f"{workdir}/status-{rid}.json",
            {"leader": lid if ok else 0, "pid": os.getpid(),
             "t": time.time()},
        )
        cmd_path = f"{workdir}/cmd-{rid}-{n}.json"
        if not os.path.exists(cmd_path):
            time.sleep(0.05)
            continue
        with open(cmd_path) as f:
            cmd = json.load(f)
        res = {"ok": False}
        try:
            if cmd["op"] == "propose":
                import pickle

                payload = pickle.dumps(("set", cmd["key"], cmd["val"].encode()))
                end = time.time() + cmd.get("deadline", 30.0)
                while True:
                    try:
                        nh.sync_propose(session, payload, timeout=3.0)
                        res = {"ok": True}
                        break
                    except Exception as e:  # noqa: BLE001 — retry
                        if time.time() > end:
                            res = {"ok": False, "err": type(e).__name__}
                            break
                        time.sleep(0.05)
            elif cmd["op"] == "read":
                end = time.time() + cmd.get("deadline", 30.0)
                while True:
                    try:
                        v = nh.stale_read(1, cmd["key"])
                        if v is not None or time.time() > end:
                            res = {
                                "ok": v is not None,
                                "val": v.decode() if v is not None else None,
                            }
                            break
                    except Exception as e:  # noqa: BLE001 — retry
                        if time.time() > end:
                            res = {"ok": False, "err": type(e).__name__}
                            break
                    time.sleep(0.05)
            elif cmd["op"] == "exit":
                _write_atomic(f"{workdir}/res-{rid}-{n}.json", {"ok": True})
                nh.close()
                return
        except Exception as e:  # noqa: BLE001 — report, keep serving
            res = {"ok": False, "err": repr(e)}
        _write_atomic(f"{workdir}/res-{rid}-{n}.json", res)
        n += 1


if __name__ == "__main__":
    main()
