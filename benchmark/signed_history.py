"""Signed gossip history for a validator that starts late: the events
its peers made before it joined, signed, in the sync batches a peer's
`Core.to_wire_batch` would serve it.

The DAG is `random_gossip(validators - 1, events, dag_seed)` relabelled
by `seed` (benchmark/gossip.py); the last validator is the local one
and is silent in the history. Keys come from `seed`; participant ids
are the validators' public keys in sorted order, as a Node numbers its
peers. Event i carries one `tx_bytes`-byte transaction (its number,
then bytes drawn from `seed`) and the timestamp `T0_NS` + i ms.
Signatures use RFC 6979 nonces, so the same arguments give the same
bytes."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

from benchmark.gossip import random_gossip, relabelled

T0_NS = 1_600_000_000 * 10**9
TICK_NS = 10**6


@dataclass
class History:
    validators: int
    participants: Dict[str, int]  # public key hex -> participant id
    local_id: int
    local_key: object  # the active crypto backend's private key
    events: int  # history events, the local validator's not counted
    batches: list  # ColumnarEvents sync batches, in creation order
    parents: Dict[str, Tuple[str, str]]  # event hex -> its parents' hexes


def _signers(seeds: List[int]):
    """Deterministic P-256 signing for each key seed, with the scalar
    crypto.key_from_seed derives from it."""
    from babble_tpu.crypto import _fallback, _openssl

    scalars = [s % (_fallback.N - 1) + 1 for s in seeds]
    if _openssl.available():
        return [lambda dg, d=d: _openssl.sign(d, dg) for d in scalars]
    keys = [_fallback.PrivateKey.from_scalar(d) for d in scalars]
    return [lambda dg, k=k: _fallback.sign(k, dg) for k in keys]


def generate(validators: int, events: int, dag_seed: int, seed: int,
             sync: int, tx_bytes: int = 64) -> History:
    from babble_tpu import crypto
    from babble_tpu.gojson import BigInt, Timestamp
    from babble_tpu.hashgraph.event import Event
    from babble_tpu.net.columnar import ColumnarEvents

    active = validators - 1
    dag = relabelled(random_gossip(active, events, dag_seed), seed)
    seeds = [seed * validators + v + 1 for v in range(validators)]
    pubs = [crypto.pub_key_bytes(crypto.key_from_seed(s)) for s in seeds]
    hexes = ["0x" + p.hex().upper() for p in pubs]
    pid = {v: i for i, v in enumerate(sorted(range(validators),
                                             key=hexes.__getitem__))}
    sign = _signers(seeds[:active])
    rng = np.random.default_rng(seed)
    blob = rng.bytes((tx_bytes - 8) * events)
    w = tx_bytes - 8

    made: List[Event] = []
    for i in range(events):
        v, s, o = int(dag.creator[i]), int(dag.sp[i]), int(dag.op[i])
        ix = int(dag.index[i])
        tx = i.to_bytes(8, "big") + blob[w * i:w * (i + 1)]
        ev = Event.new([tx], [made[s].hex() if s >= 0 else "",
                              made[o].hex() if o >= 0 else ""],
                       pubs[v], ix, Timestamp(T0_NS + i * TICK_NS))
        ev.set_wire_info(
            ix - 1 if s >= 0 else -1,
            pid[int(dag.creator[o])] if o >= 0 else -1,
            int(dag.index[o]) if o >= 0 else -1,
            pid[v])
        r, sig_s = sign[v](ev.body.hash())
        ev.r, ev.s = BigInt(r), BigInt(sig_s)
        ev.invalidate(body=False)
        made.append(ev)
    # Columnar, the wire format a peer at Config()'s defaults serves.
    batches = [ColumnarEvents.from_events(made[k:k + sync])
               for k in range(0, events, sync)]
    return History(
        validators=validators,
        participants={hexes[v]: pid[v] for v in range(validators)},
        local_id=pid[active], local_key=crypto.key_from_seed(seeds[active]),
        events=events, batches=batches,
        parents={ev.hex(): (ev.self_parent(), ev.other_parent())
                 for ev in made})
