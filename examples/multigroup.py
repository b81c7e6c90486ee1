"""Multi-group + on-disk state machines on one NodeHost trio.

reference: the lni/dragonboat-example multigroup + ondisk examples [U].
Three NodeHosts in one process host TWO raft shards each: shard 1 is an
in-memory KV, shard 2 an on-disk KV that persists itself and reports its
applied index at open (only the log tail replays).  Run:

    python examples/multigroup.py

NOTE on the client pattern: this example drives NodeHost RAW (resolve
the leader by hand, ``sync_propose``/``sync_read`` per call) to keep
the SM-tier mechanics in focus.  For the production client path —
session handles, leader routing, admission control, lease reads — see
examples/kv_gateway.py and docs/GATEWAY.md.

NOTE on the device launch pipeline: when these NodeHosts share a
``ColocatedEngineGroup`` (the product device path), generations are
double-buffered — the merge tail runs one generation behind the
device so a readback's latency overlaps the next launch — and routable
generations fuse three consecutive consensus rounds device-side: a
quiet-path proposal commits in ONE launch + ONE readback window.  The
engine keywords ``pipeline_depth=1`` and ``fused_rounds=1`` are the
serial single-round loop tests compare against; ``sync_floor_ms`` is a
simulated link latency for tests (0, the default, is the real machine).
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
    IOnDiskStateMachine,
    IStateMachine,
    NodeHost,
    NodeHostConfig,
    Result,
)

ADDRS = {1: "mg-1", 2: "mg-2", 3: "mg-3"}


class MemKV(IStateMachine):
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

    def close(self):
        pass


class DiskKV(IOnDiskStateMachine):
    """Owns its own durability: a json file + applied-index marker."""

    def __init__(self, shard_id, replica_id):
        self.path = f"/tmp/mg-diskkv-{shard_id}-{replica_id}.json"
        self.d = {}
        self.applied = 0

    def open(self, stop_event) -> int:
        if os.path.exists(self.path):
            with open(self.path) as f:
                blob = json.load(f)
            self.d, self.applied = blob["d"], blob["applied"]
        return self.applied

    def update(self, entries):
        results = []
        for e in entries:
            k, v = e.cmd.decode().split("=", 1)
            self.d[k] = v
            self.applied = e.index
            results.append(Result(value=len(self.d)))
        return results

    def lookup(self, q):
        return self.d.get(q)

    def sync(self):
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"d": self.d, "applied": self.applied}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)

    def prepare_snapshot(self):
        return dict(self.d)

    def save_snapshot(self, ctx, w, files, done):
        w.write(json.dumps(ctx).encode())

    def recover_from_snapshot(self, r, files, done):
        self.d = json.loads(r.read(-1).decode())

    def close(self):
        pass


def main() -> None:
    for rid in ADDRS:
        shutil.rmtree(f"/tmp/nh-mg-{rid}", ignore_errors=True)
    for p in os.listdir("/tmp"):
        if p.startswith("mg-diskkv-"):
            os.unlink(f"/tmp/{p}")

    nhs = {
        rid: NodeHost(
            NodeHostConfig(
                nodehost_dir=f"/tmp/nh-mg-{rid}",
                rtt_millisecond=10,
                raft_address=ADDRS[rid],
                expert=ExpertConfig(
                    engine=EngineConfig(exec_shards=2, apply_shards=2)
                ),
            )
        )
        for rid in ADDRS
    }
    try:
        for rid, nh in nhs.items():
            nh.start_replica(
                ADDRS, False, MemKV,
                Config(shard_id=1, replica_id=rid, election_rtt=10),
            )
            nh.start_replica(
                ADDRS, False, DiskKV,
                Config(shard_id=2, replica_id=rid, election_rtt=10,
                       snapshot_entries=50),
            )

        def leader(shard):
            while True:
                for nh in nhs.values():
                    lid, ok = nh.get_leader_id(shard)
                    if ok and lid:
                        return nhs[lid]
                time.sleep(0.05)

        for shard in (1, 2):
            nh = leader(shard)
            s = nh.get_noop_session(shard)
            for i in range(5):
                while True:
                    try:
                        nh.sync_propose(
                            s, f"k{i}=s{shard}v{i}".encode(), timeout=2.0
                        )
                        break
                    except Exception:
                        time.sleep(0.05)
            print(f"shard {shard}: k0 =", nh.sync_read(shard, "k0"))

        # restart host 1: the on-disk SM reopens at its applied index and
        # only the log tail replays
        nhs[1].close()
        nhs[1] = NodeHost(
            NodeHostConfig(
                nodehost_dir="/tmp/nh-mg-1",
                rtt_millisecond=10,
                raft_address=ADDRS[1],
            )
        )
        nhs[1].start_replica(
            ADDRS, False, MemKV, Config(shard_id=1, replica_id=1, election_rtt=10)
        )
        nhs[1].start_replica(
            ADDRS, False, DiskKV,
            Config(shard_id=2, replica_id=1, election_rtt=10),
        )
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                if nhs[1].stale_read(2, "k4") == "s2v4":
                    break
            except Exception:
                pass
            time.sleep(0.05)
        print("restarted host 1, on-disk shard k4 =", nhs[1].stale_read(2, "k4"))
        print("ok")
    finally:
        for nh in nhs.values():
            nh.close()


if __name__ == "__main__":
    main()
