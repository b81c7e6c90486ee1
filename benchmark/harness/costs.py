"""Bytes a device program has to move, from the configuration's geometry.

Kept with the benchmark so that a roofline share is always the same
arithmetic.  ``tests/test_costs.py`` holds each count against the live
arrays' ``nbytes`` at a small capacity.
"""
from __future__ import annotations

import json
import os

I32 = 4
STATE_ROW_FIELDS = 21      # DeviceState: [G] fields
STATE_PEER_FIELDS = 8      # [G, P]
STATE_RING_FIELDS = 2      # [G, W]
INBOX_SLOT_FIELDS = 10     # Inbox: [G, M]
INBOX_ENTRY_FIELDS = 2     # [G, M, E]
OUT_MSG_FIELDS = 11        # DeviceOut.buf: [G, O, N_FIELDS]


def state_bytes(capacity: int, P: int, W: int, **_) -> int:
    return I32 * capacity * (STATE_ROW_FIELDS + STATE_PEER_FIELDS * P
                             + STATE_RING_FIELDS * W)


def inbox_bytes(capacity: int, slots: int, E: int) -> int:
    return I32 * capacity * slots * (INBOX_SLOT_FIELDS
                                     + INBOX_ENTRY_FIELDS * E)


def out_bytes(capacity: int, P: int, slots: int, E: int, O: int) -> int:
    # buf, count, escalate, need_snapshot, slot_base, slot_term, ent_drop,
    # append_lo, barrier_idx, barrier_term
    return I32 * capacity * (O * OUT_MSG_FIELDS + 2 + P + 2 * slots
                             + slots * E + 3)


def colocated_step_bytes(capacity: int, P: int, W: int, M: int, E: int,
                         O: int, budget: int, **_) -> int:
    """Least traffic of one ``_assemble_and_step``: the state read once and
    written once, the host inbox (M slots) and the routed inbox (P*budget
    slots) read once, the [G, 4] upload lanes, the outbox written once."""
    slots = M + P * budget
    return (2 * state_bytes(capacity, P, W)
            + inbox_bytes(capacity, M, E)
            + inbox_bytes(capacity, P * budget, E)
            + I32 * capacity * 4
            + out_bytes(capacity, P, slots, E, O))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]
