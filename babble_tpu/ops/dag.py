"""Host-side assembly of an event DAG into integer arrays.

Converts a topologically-ordered list of Events (hashes, pubkeys,
timestamps) into the integer-id layout IncrementalEngine.append_batch
takes. Strings never reach the device: events become ids in insertion
order, and creators become participant ids (the reference's
sorted-pubkey fake ids, cmd/babble/main.go:215-221).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

from ..hashgraph.event import Event
from ..hashgraph.graph import middle_bit


@dataclass
class DagTensors:
    """Structure-of-arrays DAG, one row per event in insertion order."""

    n: int  # participants
    e: int  # event count
    # [E] int32; parents are event ids, -1 = root / none
    self_parent: np.ndarray
    other_parent: np.ndarray
    creator: np.ndarray  # [E] int32 participant ids
    index: np.ndarray  # [E] int32 creator-sequence index
    coin: np.ndarray  # [E] int8 middleBit of the event hash
    ts_ns: np.ndarray  # [E] int64 event timestamps
    hexes: List[str]  # id -> event hex
    events: List[Event]  # id -> Event


def build_dag(events: Sequence[Event],
              participants: Dict[str, int]) -> DagTensors:
    """`events` must be in insertion (topological) order — the same
    order the engine receives them."""
    e = len(events)
    hexes = [ev.hex() for ev in events]
    hex_to_id = {h: i for i, h in enumerate(hexes)}
    self_parent = np.full(e, -1, dtype=np.int32)
    other_parent = np.full(e, -1, dtype=np.int32)
    creator = np.zeros(e, dtype=np.int32)
    index = np.zeros(e, dtype=np.int32)
    coin = np.zeros(e, dtype=np.int8)
    ts_ns = np.zeros(e, dtype=np.int64)
    for i, ev in enumerate(events):
        sp, op = ev.self_parent(), ev.other_parent()
        if sp:
            self_parent[i] = hex_to_id[sp]
        if op:
            other_parent[i] = hex_to_id[op]
        creator[i] = participants[ev.creator()]
        index[i] = ev.index()
        coin[i] = 1 if middle_bit(ev.hex()) else 0
        ts_ns[i] = ev.body.timestamp.ns
    return DagTensors(len(participants), e, self_parent, other_parent,
                      creator, index, coin, ts_ns, hexes, list(events))


def synthetic_dag(n: int, e: int, seed: int = 0):
    """Generate a random-gossip DAG directly as arrays (no crypto, no
    Event objects): each step a random creator records a sync from a
    random other peer, exactly the event pattern the gossip runtime
    produces (reference node/node.go:315-487). Timestamps are the
    event ids.

    Returns (DagTensors, s_rank[E] int64) where s_rank stands in for
    the raw big-int signature-S tiebreak of the final sort."""
    if e < n or n < 2:
        raise ValueError("need n >= 2 and at least one event per participant")
    rng = np.random.default_rng(seed)
    self_parent = np.full(e, -1, dtype=np.int32)
    other_parent = np.full(e, -1, dtype=np.int32)
    creator = np.zeros(e, dtype=np.int32)
    index = np.zeros(e, dtype=np.int32)

    heads = np.full(n, -1, dtype=np.int64)
    seqs = np.full(n, -1, dtype=np.int64)
    creators = np.concatenate(
        [np.arange(n, dtype=np.int64), rng.integers(0, n, size=e - n)]
    )
    others = rng.integers(1, n, size=e)  # offset, so other != creator
    for i in range(e):
        c = int(creators[i])
        if i >= n:
            j = (c + int(others[i])) % n
            other_parent[i] = heads[j]
        self_parent[i] = heads[c]
        seqs[c] += 1
        creator[i] = c
        index[i] = seqs[c]
        heads[c] = i

    coin = rng.integers(0, 2, size=e, dtype=np.int8)
    s_rank = rng.integers(0, 2**62, size=e, dtype=np.int64)
    dag = DagTensors(n, e, self_parent, other_parent, creator, index, coin,
                     np.arange(e, dtype=np.int64), hexes=[], events=[])
    return dag, s_rank
