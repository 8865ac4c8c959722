"""Hand-drawn DAG fixtures used as the consensus-parity oracle.

These re-create the reference's test graphs (reference
hashgraph/hashgraph_test.go: initHashgraph:80, initRoundHashgraph:383,
initConsensusHashgraph:912, initFunkyHashgraph:1464) via a `play` DSL:
each play appends one event (creator, creator-index, named self/other
parents, payload) to the graph in insertion order.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from babble_tpu import crypto
from babble_tpu.gojson import Timestamp
from babble_tpu.hashgraph import Event, Hashgraph, InmemStore

CACHE_SIZE = 100


@dataclass
class SimNode:
    id: int
    key: object
    pub: bytes
    pub_hex: str
    events: List[Event] = field(default_factory=list)


def make_nodes(n: int, seed_base: int = 1000) -> List[SimNode]:
    nodes = []
    for i in range(n):
        key = crypto.key_from_seed(seed_base + i)
        pub = crypto.pub_key_bytes(key)
        nodes.append(SimNode(id=i, key=key, pub=pub, pub_hex="0x" + pub.hex().upper()))
    return nodes


@dataclass
class Play:
    to: int
    index: int
    self_parent: str
    other_parent: str
    name: str
    payload: Optional[List[bytes]] = None  # None -> empty list (Go [][]byte{})


class GraphBuilder:
    """Builds events from plays; timestamps increase monotonically so
    median-timestamp consensus ordering is deterministic across runs."""

    def __init__(self, n: int, seed_base: int = 1000):
        self.nodes = make_nodes(n, seed_base)
        self.index: Dict[str, str] = {}
        self.ordered_events: List[Event] = []
        self._clock = 1_600_000_000_000_000_000  # arbitrary fixed epoch ns

    def _next_ts(self) -> Timestamp:
        self._clock += 1_000_000  # 1ms
        return Timestamp(self._clock)

    def add_initial(self, name: str, node_i: int, payload: Optional[List[bytes]] = None):
        node = self.nodes[node_i]
        ev = Event.new(
            payload if payload is not None else [],
            ["", ""],
            node.pub,
            0,
            timestamp=self._next_ts(),
        )
        ev.sign(node.key)
        node.events.append(ev)
        self.index[name] = ev.hex()
        self.ordered_events.append(ev)
        return ev

    def play(self, p: Play):
        node = self.nodes[p.to]
        ev = Event.new(
            p.payload if p.payload is not None else [],
            [self.index.get(p.self_parent, ""), self.index.get(p.other_parent, "")],
            node.pub,
            p.index,
            timestamp=self._next_ts(),
        )
        ev.sign(node.key)
        node.events.append(ev)
        self.index[p.name] = ev.hex()
        self.ordered_events.append(ev)
        return ev

    def participants(self) -> Dict[str, int]:
        return {node.pub_hex: node.id for node in self.nodes}

    def make_hashgraph(self, store=None) -> Hashgraph:
        participants = self.participants()
        if store is None:
            store = InmemStore(participants, CACHE_SIZE)
        return Hashgraph(participants, store)

    def get_name(self, hash_: str) -> str:
        for name, h in self.index.items():
            if h == hash_:
                return name
        return ""


def build_basic_graph() -> Tuple[Hashgraph, GraphBuilder]:
    """Ancestry fixture — reference hashgraph_test.go:66-133.

    |  e12  |
    |   | \\ |
    |  s10   e20
    |   | / |
    |   /   |
    | / |   |
    s00 |  s20
    |   |   |
    e01 |   |
    | \\ |   |
    e0  e1  e2
    0   1   2

    Events are installed without the insert pipeline (coordinates +
    store + first-descendant update only), as the reference does.
    """
    b = GraphBuilder(3)
    for i in range(3):
        b.add_initial(f"e{i}", i)
    for p in [
        Play(0, 1, "e0", "e1", "e01"),
        Play(2, 1, "e2", "", "s20"),
        Play(1, 1, "e1", "", "s10"),
        Play(0, 2, "e01", "", "s00"),
        Play(2, 2, "s20", "s00", "e20"),
        Play(1, 2, "s10", "e20", "e12"),
    ]:
        b.play(p)

    h = b.make_hashgraph()
    for ev in b.ordered_events:
        h._init_event_coordinates(ev)
        h.store.set_event(ev)
        h._update_ancestor_first_descendant(ev)
    return h, b


def build_round_graph() -> Tuple[Hashgraph, GraphBuilder]:
    """Rounds/witness fixture — reference hashgraph_test.go:365-427.

    |  s11  |
    |   |   |
    |   f1  |
    |  /|   |
    | / s10 |
    |/  |   |
    e02 |   |
    | \\ |   |
    |   \\   |
    |   | \\ |
    s00 |  e21
    |   | / |
    |  e10  s20
    | / |   |
    e0  e1  e2
    0   1    2
    """
    b = GraphBuilder(3)
    for i in range(3):
        b.add_initial(f"e{i}", i)
    for p in [
        Play(1, 1, "e1", "e0", "e10"),
        Play(2, 1, "e2", "", "s20"),
        Play(0, 1, "e0", "", "s00"),
        Play(2, 2, "s20", "e10", "e21"),
        Play(0, 2, "s00", "e21", "e02"),
        Play(1, 2, "e10", "", "s10"),
        Play(1, 3, "s10", "e02", "f1"),
        Play(1, 4, "f1", "", "s11", [b"abc"]),
    ]:
        b.play(p)

    h = b.make_hashgraph()
    for ev in b.ordered_events:
        h.insert_event(ev, True)
    return h, b


CONSENSUS_PLAYS = [
    Play(1, 1, "e1", "e0", "e10"),
    Play(2, 1, "e2", "e10", "e21", [b"e21"]),
    Play(2, 2, "e21", "", "e21b"),
    Play(0, 1, "e0", "e21b", "e02"),
    Play(1, 2, "e10", "e02", "f1"),
    Play(1, 3, "f1", "", "f1b", [b"f1b"]),
    Play(0, 2, "e02", "f1b", "f0"),
    Play(2, 3, "e21b", "f1b", "f2"),
    Play(1, 4, "f1b", "f0", "f10"),
    Play(2, 4, "f2", "f10", "f21"),
    Play(0, 3, "f0", "f21", "f02"),
    Play(0, 4, "f02", "", "f02b", [b"e21"]),
    Play(1, 5, "f10", "f02b", "g1"),
    Play(0, 5, "f02b", "g1", "g0"),
    Play(2, 5, "f21", "g1", "g2"),
    Play(1, 6, "g1", "g0", "g10"),
    Play(0, 6, "g0", "f21", "o02"),
    Play(2, 6, "g2", "g10", "g21"),
    Play(0, 7, "o02", "g21", "g02"),
    Play(1, 7, "g10", "g02", "h1"),
    Play(0, 8, "g02", "h1", "h0"),
    Play(2, 7, "g21", "h1", "h2"),
]


def build_consensus_graph(store=None) -> Tuple[Hashgraph, GraphBuilder]:
    """Fame/order fixture (25 events / 3 nodes) — reference
    hashgraph_test.go:866-983."""
    b = GraphBuilder(3)
    for i in range(3):
        b.add_initial(f"e{i}", i)
    for p in CONSENSUS_PLAYS:
        b.play(p)

    h = b.make_hashgraph(store=store)
    for ev in b.ordered_events:
        h.insert_event(ev, True)
    return h, b


FUNKY_PLAYS = [
    Play(2, 1, "w02", "w03", "a23", [b"a23"]),
    Play(1, 1, "w01", "a23", "a12", [b"a12"]),
    Play(0, 1, "w00", "", "a00", [b"a00"]),
    Play(1, 2, "a12", "a00", "a10", [b"a10"]),
    Play(2, 2, "a23", "a12", "a21", [b"a21"]),
    Play(3, 1, "w03", "a21", "w13", [b"w13"]),
    Play(2, 3, "a21", "w13", "w12", [b"w12"]),
    Play(1, 3, "a10", "w12", "w11", [b"w11"]),
    Play(0, 2, "a00", "w11", "w10", [b"w10"]),
    Play(2, 4, "w12", "w11", "b21", [b"b21"]),
    Play(3, 2, "w13", "b21", "w23", [b"w23"]),
    Play(1, 4, "w11", "w23", "w21", [b"w21"]),
    Play(0, 3, "w10", "", "b00", [b"b00"]),
    Play(1, 5, "w21", "b00", "c10", [b"c10"]),
    Play(2, 5, "b21", "c10", "w22", [b"w22"]),
    Play(0, 4, "b00", "w22", "w20", [b"w20"]),
    Play(1, 6, "c10", "w20", "w31", [b"w31"]),
    Play(2, 6, "w22", "w31", "w32", [b"w32"]),
    Play(0, 5, "w20", "w32", "w30", [b"w30"]),
    Play(3, 3, "w23", "w32", "w33", [b"w33"]),
    Play(1, 7, "w31", "w33", "d13", [b"d13"]),
    Play(0, 6, "w30", "d13", "w40", [b"w40"]),
    Play(1, 8, "d13", "w40", "w41", [b"w41"]),
    Play(2, 7, "w32", "w41", "w42", [b"w42"]),
    Play(3, 4, "w33", "w42", "w43", [b"w43"]),
    Play(2, 8, "w42", "w43", "e23", [b"e23"]),
    Play(1, 9, "w41", "e23", "w51", [b"w51"]),
]


def build_funky_graph() -> Tuple[Hashgraph, GraphBuilder]:
    """Irregular-rounds fixture (4 nodes / 32 events) incl. a coin round —
    reference hashgraph_test.go:1407-1533."""
    b = GraphBuilder(4)
    for i in range(4):
        b.add_initial(f"w0{i}", i, [f"w0{i}".encode()])
    for p in FUNKY_PLAYS:
        b.play(p)

    h = b.make_hashgraph()
    for ev in b.ordered_events:
        h.insert_event(ev, True)
    return h, b


def build_coin_graph(extra_rounds: int = 3) -> GraphBuilder:
    """The funky graph extended with a gossip ring so the coin round
    RESOLVES: w00's fame cannot be decided by round 4 (the normal
    rounds stay split), so round-4 witnesses cast coin votes
    (diff % n == 0, reference hashgraph.go:703-709), and the round-5
    tally decides from those coin-influenced votes. With the coin
    forced to 1 the graph decides w00 famous; forced to 0 it stays
    undecided forever (the hashgraph coin-round liveness hole) — both
    outcomes are topology-deterministic, which is what makes this
    testable even though real coin bits depend on event signatures.

    Returns the builder only (no consensus run): callers choose the
    engine and the coin regime."""
    b = GraphBuilder(4)
    for i in range(4):
        b.add_initial(f"w0{i}", i, [f"w0{i}".encode()])
    heads = {0: "w00", 1: "w01", 2: "w02", 3: "w03"}
    idx = {0: 0, 1: 0, 2: 0, 3: 0}
    for p in FUNKY_PLAYS:
        b.play(p)
        heads[p.to] = p.name
        idx[p.to] = p.index
    k = 0
    for _ in range(extra_rounds):
        for c, p in ((3, 1), (1, 3), (0, 2), (2, 0)):
            idx[c] += 1
            name = f"z{k}"
            b.play(Play(c, idx[c], heads[c], heads[p], name,
                        [name.encode()]))
            heads[c] = name
            k += 1
    return b


# ------------------------------------------ the engine against the reference


def feed_engine(eng, dag, bs: int):
    """Append `dag` (ops/dag.py) to an IncrementalEngine in batches of
    `bs` events with a pass after each; returns the engine, closed."""
    for k in range(0, dag.e, bs):
        hi = min(k + bs, dag.e)
        eng.append_batch(dag.self_parent[k:hi], dag.other_parent[k:hi],
                         dag.creator[k:hi], dag.index[k:hi], dag.coin[k:hi],
                         dag.ts_ns[k:hi])
        eng.run()
    eng.close()
    return eng


def reference_of(dag):
    """The plain reference's consensus (benchmark/reference.py) of a
    whole DAG."""
    from benchmark import reference

    return reference.consensus(dag.n, dag.self_parent, dag.other_parent,
                               dag.creator, dag.index, dag.coin, dag.ts_ns)


def reference_witness_table(ref, creator, rows: int):
    """The reference's witnesses as a [rows, n] table of event ids by
    (round, creator), -1 empty."""
    wt = np.full((rows, ref.famous.shape[1]), -1, np.int32)
    w = np.flatnonzero(ref.witness)
    wt[ref.rounds[w], creator[w]] = w
    return wt


def assert_engine_matches_reference(eng, dag):
    """An engine fed all of `dag` holds the reference's rounds,
    witnesses, witness table, fame, round received, and consensus
    timestamps where decided. Returns the reference's result."""
    ref = reference_of(dag)
    e = dag.e
    assert (eng.rounds[:e] == ref.rounds).all()
    assert (eng.witness[:e] == ref.witness).all()
    assert (eng.rr[:e] == ref.rr).all()
    dec = ref.rr >= 0
    assert (eng.cts_ns[:e][dec] == ref.cts[dec]).all()
    ref_wt = reference_witness_table(ref, dag.creator, len(ref.famous))
    for mine, theirs, empty in ((eng.witness_table(), ref_wt, -1),
                                (eng.famous, ref.famous, 0)):
        r = min(len(mine), len(theirs))
        assert (mine[:r] == theirs[:r]).all()
        assert (mine[r:] == empty).all() and (theirs[r:] == empty).all()
    return ref
