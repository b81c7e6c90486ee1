"""The program with its timed path broken underneath (``--fault NAME``).

A fault is planted in the built deployment, after the election and
before any load.  It is how the upper reading of a limit is taken on
the chip at a cell's own size, and what ``tests/test_correct.py`` sees
``correct`` come out false on; no measurement runs with one.

  host-plan   the device planner refuses every row of one shard in ten
              that carries input, so the host engine steps them: the
              device path lost for a tenth of the load.  (Refused for
              every shard, the program cannot hold its leaders at 1,000
              shards: the host path runs under the engine's one lock.)
"""
from __future__ import annotations


def host_plan(system) -> None:
    core = system.group.core
    real = core._plan_device

    def plan(node, si, mirror_leader, g):
        if node.shard_id % 10 == 1:
            return None
        return real(node, si, mirror_leader, g)

    core._plan_device = plan


FAULTS = {"host-plan": host_plan}
