"""Node runtime tests: core-pair syncs (reference node/core_test.go) and
multi-node gossip with checkGossip prefix equality (reference
node/node_test.go:396-599), over both the inmem and TCP transports."""

from __future__ import annotations

import threading
import time

import pytest

from babble_tpu import crypto
from babble_tpu.hashgraph import InmemStore
from babble_tpu.net import InmemTransport, Peer, TCPTransport
from babble_tpu.net.inmem_transport import connect_all
from babble_tpu.node import Core, Node
from babble_tpu.node.config import test_config as fast_config
from babble_tpu.node.state import StateMachine
from babble_tpu.proxy import InmemAppProxy

CACHE = 10000


def make_keyed_peers(n, seed_base=5000, addr_fn=None):
    keys = [crypto.key_from_seed(seed_base + i) for i in range(n)]
    entries = []
    for i, k in enumerate(keys):
        pub_hex = "0x" + crypto.pub_key_bytes(k).hex().upper()
        addr = addr_fn(i) if addr_fn else f"peer{i}"
        entries.append((k, Peer(addr, pub_hex)))
    # canonical id assignment: sorted pubkey order (cmd/babble/main.go:215-225)
    entries.sort(key=lambda e: e[1].pub_key_hex)
    return entries


def init_cores(n):
    entries = make_keyed_peers(n)
    participants = {p.pub_key_hex: i for i, (_, p) in enumerate(entries)}
    cores = []
    for i, (key, _) in enumerate(entries):
        core = Core(i, key, participants, InmemStore(participants, CACHE))
        core.init()
        cores.append(core)
    return cores


def synchronize_cores(cores, frm, to, payload=()):
    known_by_to = cores[to].known()
    unknown = cores[frm].diff(known_by_to)
    wire = cores[frm].to_wire(unknown)
    cores[to].add_transactions(list(payload))
    cores[to].sync(wire)


def sync_and_run_consensus(cores, frm, to, payload=()):
    synchronize_cores(cores, frm, to, payload)
    cores[to].run_consensus()


# ---------------------------------------------------------------- cores


def test_core_init_heads():
    cores = init_cores(3)
    for c in cores:
        assert c.seq == 0
        assert c.head != ""
        head = c.get_head()
        assert head.creator() == c.hex_id()


def test_core_sync_pair():
    cores = init_cores(2)
    # 0 -> 1: 1 learns 0's initial event and creates a new head
    synchronize_cores(cores, 0, 1, [b"hello"])
    assert cores[1].seq == 1
    known = cores[1].known()
    assert sorted(known.values()) == [0, 1]
    # back: 0 learns 1's two events
    synchronize_cores(cores, 1, 0)
    assert cores[0].seq == 1
    assert all(v == 1 for v in cores[0].known().values())


def test_core_sync_tolerates_duplicates():
    """A sync batch computed against a stale known-map overlaps events
    the receiver already has (pulls and pushes run concurrently in the
    live node); already-known events are skipped, the rest of the batch
    lands, and the receiver's state matches a duplicate-free sync —
    aborting on the first duplicate wedged nodes permanently."""
    cores = init_cores(2)
    synchronize_cores(cores, 0, 1, [b"a"])
    # A stale diff: everything core 0 has, including what core 1
    # already knows (known-map of a fresh peer).
    stale_known = {pid: -1 for pid in cores[1].known()}
    overlap = cores[0].to_wire(cores[0].diff(stale_known))
    assert len(overlap) >= 1
    before = cores[1].known()
    cores[1].sync(overlap)  # must not raise
    after = cores[1].known()
    # Only core 1's own new head event was added; core 0's events were
    # all duplicates and silently skipped.
    for pid, idx in before.items():
        assert after[pid] >= idx
    assert sum(after.values()) == sum(before.values()) + 1
    # State remains insertable: a clean follow-up round-trip works.
    synchronize_cores(cores, 1, 0)
    synchronize_cores(cores, 0, 1)


def test_core_consensus_identical_order():
    """Scripted gossip between 3 cores converges to identical consensus
    order — reference core_test.go TestConsensus:354."""
    cores = init_cores(3)
    playbook = [
        (0, 1, [b"tx one"]),
        (1, 2, []),
        (2, 0, [b"tx two"]),
        (0, 1, []),
        (1, 2, [b"tx three"]),
        (2, 0, []),
        (0, 1, [b"tx four"]),
        (1, 2, []),
        (2, 0, []),
        (0, 1, []),
        (1, 2, []),
        (2, 0, []),
    ]
    for frm, to, payload in playbook:
        sync_and_run_consensus(cores, frm, to, payload)

    lens = [len(c.get_consensus_events()) for c in cores]
    assert max(lens) > 0, "no consensus reached"
    ref = cores[0].get_consensus_events()
    for c in cores[1:]:
        other = c.get_consensus_events()
        m = min(len(ref), len(other))
        assert ref[:m] == other[:m]


def test_core_over_sync_limit():
    cores = init_cores(2)
    for _ in range(5):
        synchronize_cores(cores, 0, 1, [b"x"])
        synchronize_cores(cores, 1, 0)
    known_zero = {i: -1 for i in cores[0].known()}
    assert cores[0].over_sync_limit(known_zero, 5)
    assert not cores[0].over_sync_limit(cores[0].known(), 5)


# ---------------------------------------------------------------- nodes


def make_nodes(n, transport, engine="host", engine_mesh=0):
    if transport == "tcp":
        transports = [
            TCPTransport("127.0.0.1:0", timeout=2.0) for _ in range(n)
        ]
        addrs = [t.local_addr() for t in transports]
        entries = make_keyed_peers(n, addr_fn=lambda i: addrs[i])
    else:
        transports = [InmemTransport(f"addr{i}", timeout=2.0) for i in range(n)]
        connect_all(transports)
        entries = make_keyed_peers(n, addr_fn=lambda i: f"addr{i}")

    # transports were created in creation order; map them to sorted order
    by_addr = {t.local_addr(): t for t in transports}
    peers = [p for _, p in entries]
    participants = {p.pub_key_hex: i for i, p in enumerate(peers)}

    nodes = []
    for i, (key, peer) in enumerate(entries):
        conf = fast_config(heartbeat=0.01 if transport == "inmem" else 0.05)
        conf.engine = engine
        conf.engine_mesh = engine_mesh
        if engine == "tpu":
            # Production cadence (cli.py default): a dedicated
            # consensus worker batching syncs per device pass, with
            # the core lock released around the device wait — the
            # unlocked seam must be exercised by gossip, not only by
            # the deterministic interleave unit test.
            conf.consensus_interval = 0.05
        store = InmemStore(participants, CACHE)
        proxy = InmemAppProxy()
        node = Node(conf, i, key, peers, store, by_addr[peer.net_addr], proxy)
        node.init()
        nodes.append(node)
    return nodes


def run_gossip(nodes, target_round, timeout=60.0, shutdown=True):
    """Run all nodes and bombard them with transactions until every
    node reaches target_round — the reference's gossip/bombardAndWait
    driver (node_test.go:507-545,601-617). Continuous submission
    matters: nodes go quiescent by design when nothing is pending.
    shutdown=False leaves the testnet running (reference gossip()'s
    shutdown flag, node_test.go:507)."""
    for node in nodes:
        node.run_async(gossip=True)
    submitted = []
    deadline = time.monotonic() + timeout
    i = 0
    try:
        while time.monotonic() < deadline:
            tx = f"node{i % len(nodes)} transaction {i}".encode()
            nodes[i % len(nodes)].submit_tx(tx)
            submitted.append(tx)
            i += 1
            done = all(
                (n.core.get_last_consensus_round_index() or 0) >= target_round
                for n in nodes
            )
            if done:
                return submitted
            time.sleep(0.02)
        rounds = [n.core.get_last_consensus_round_index() for n in nodes]
        raise AssertionError(f"timeout: consensus rounds {rounds} < {target_round}")
    finally:
        if shutdown:
            for node in nodes:
                node.shutdown()


def check_gossip(nodes):
    cons_events = {n.id: n.core.get_consensus_events() for n in nodes}
    cons_txs = {n.id: n.core.get_consensus_transactions() for n in nodes}

    min_e = min(len(v) for v in cons_events.values())
    min_t = min(len(v) for v in cons_txs.values())
    assert min_e > 0, "no consensus events"

    ref_e = cons_events[nodes[0].id]
    ref_t = cons_txs[nodes[0].id]
    for n in nodes[1:]:
        assert cons_events[n.id][:min_e] == ref_e[:min_e], (
            f"consensus event mismatch vs node {n.id}"
        )
        assert cons_txs[n.id][:min_t] == ref_t[:min_t], (
            f"consensus tx mismatch vs node {n.id}"
        )


@pytest.mark.parametrize("transport", ["inmem", "tcp"])
def test_gossip(transport):
    # inmem runs to round 50 like the reference's TestGossip
    # (node_test.go:396-407); tcp keeps a shallower target so the
    # socket path stays covered without doubling suite time.
    target = 50 if transport == "inmem" else 10
    nodes = make_nodes(4, transport)
    run_gossip(nodes, target_round=target, timeout=180.0)
    check_gossip(nodes)


def test_gossip_consensus_interval():
    """Rate-limited consensus (consensus_interval > 0): gossip inserts
    at wire speed, consensus passes batch several syncs, and the
    network still converges to the same order (the trailing heartbeat
    pass drains the backlog when gossip quiesces)."""
    nodes = make_nodes(4, "inmem")
    for node in nodes:
        node.conf.consensus_interval = 0.05
    run_gossip(nodes, target_round=10)
    check_gossip(nodes)


def test_missing_node_gossip():
    """Gossip converges even when one node never participates —
    reference node_test.go:409-420."""
    nodes = make_nodes(4, "inmem")
    try:
        for node in nodes[1:]:
            node.run_async(gossip=True)
        deadline = time.monotonic() + 60.0
        i = 0
        while time.monotonic() < deadline:
            nodes[1 + i % 3].submit_tx(f"tx {i}".encode())
            i += 1
            if all(
                (n.core.get_last_consensus_round_index() or 0) >= 5
                for n in nodes[1:]
            ):
                break
            time.sleep(0.02)
        else:
            raise AssertionError("timeout")
    finally:
        for node in nodes:
            node.shutdown()
    check_gossip(nodes[1:])


def test_stats():
    nodes = make_nodes(4, "inmem")
    run_gossip(nodes, target_round=3)
    stats = nodes[0].get_stats()
    base = {
        "last_consensus_round", "consensus_events", "consensus_transactions",
        "undetermined_events", "transaction_pool", "num_peers", "sync_rate",
        "events_per_second", "rounds_per_second", "round_events", "id", "state",
    }
    assert base <= set(stats)
    assert int(stats["last_consensus_round"]) >= 3
    assert int(stats["num_peers"]) == 3
    assert float(stats["events_per_second"]) > 0
    # per-phase ns timers (reference node/core.go:277-296 phase logging)
    for phase in ("diff", "sync", "run_consensus"):
        last, avg = stats[f"time_{phase}_ns"].split(";avg=")
        assert int(last) > 0 and int(avg) > 0


def test_committed_transactions_reach_proxy():
    nodes = make_nodes(4, "inmem")
    submitted = run_gossip(nodes, target_round=8)
    # every node's app proxy saw a prefix-consistent committed tx stream
    time.sleep(0.2)
    committed = [n.proxy.committed_transactions() for n in nodes]
    assert any(len(c) > 0 for c in committed), "nothing committed to apps"
    for c in committed:
        for tx in c:
            assert tx in submitted


def test_sync_limit():
    """A SyncRequest whose known map trails by more than sync_limit gets
    SyncLimit=true instead of a diff, and the requester passes through
    CatchingUp (whose fast-forward is a reference-parity stub that drops
    back to Babbling) — reference node_test.go:422-459."""
    from babble_tpu.net.transport import SyncRequest
    from babble_tpu.node.state import NodeState

    nodes = make_nodes(2, "inmem")
    try:
        # node 1 serves RPCs but does not gossip; node 0 stays un-run so
        # its state transitions can be observed synchronously.
        nodes[1].run_async(gossip=False)
        for k in range(8):  # node 1 builds a backlog beyond the limit
            nodes[1].core.add_transactions([f"tx {k}".encode()])
            nodes[1].core.add_self_event()

        # Serve-side: an empty-known request gets SyncLimit=true and no
        # events once the backlog exceeds the limit.
        nodes[1].conf.sync_limit = 5
        behind = {i: -1 for i in range(2)}
        resp = nodes[0].trans.sync(
            nodes[1].local_addr, SyncRequest(nodes[0].id, behind))
        assert resp.sync_limit, "expected SyncLimit=true for a lagging peer"
        assert not resp.events

        # Request-side: a pull that hits the limit drives the node into
        # CatchingUp; the run loop's fast-forward (reference-parity
        # stub, node/node.go:432-441) drops back to Babbling.
        nodes[0].conf.sync_limit = 5
        nodes[0]._gossip(nodes[1].local_addr)
        assert nodes[0].state.get_state() == NodeState.CATCHING_UP
        nodes[0]._fast_forward()
        assert nodes[0].state.get_state() == NodeState.BABBLING
    finally:
        for node in nodes:
            node.shutdown()


def test_not_ready_rpc_matches_request_type():
    """A node that is not BABBLING must answer each RPC with the
    response type its caller expects — a SyncResponse to an EagerSync
    caller dies on the response-type check instead of surfacing the
    real 'not ready' error."""
    from babble_tpu.net.transport import (
        EagerSyncRequest,
        EagerSyncResponse,
        FastForwardRequest,
        FastForwardResponse,
        RPC,
        SyncRequest,
        SyncResponse,
    )
    from babble_tpu.node.state import NodeState

    nodes = make_nodes(2, "inmem")
    try:
        nodes[0].state.set_state(NodeState.CATCHING_UP)
        for cmd, expected in (
            (SyncRequest(1, {}), SyncResponse),
            (EagerSyncRequest(1, []), EagerSyncResponse),
            (FastForwardRequest(1), FastForwardResponse),
        ):
            rpc = RPC(cmd)
            nodes[0]._process_rpc(rpc)
            out = rpc.resp_chan.get(timeout=1.0)
            assert isinstance(out.response, expected), (
                f"{type(cmd).__name__} answered with "
                f"{type(out.response).__name__}")
            assert out.error is not None and "not ready" in str(out.error)
    finally:
        for node in nodes:
            node.shutdown()


def test_fast_forward_failure_drops_back_to_babbling():
    """CatchingUp resilience: a garbage frame from the peer, or the
    transport raising mid fast-forward, must drop the node back to
    BABBLING with gossip still functional — not wedge it in
    CatchingUp."""
    from babble_tpu.net.transport import FastForwardResponse, TransportError
    from babble_tpu.node.state import NodeState

    nodes = make_nodes(2, "inmem")
    try:
        nodes[1].run_async(gossip=False)  # serves RPCs

        # Peer returns a garbage frame: deserialization blows up.
        nodes[0].trans.fast_forward = lambda target, args: \
            FastForwardResponse(1, roots={}, events=[{"garbage": 1}])
        nodes[0].state.set_state(NodeState.CATCHING_UP)
        nodes[0]._fast_forward()
        assert nodes[0].state.get_state() == NodeState.BABBLING
        assert nodes[0].fast_forwards == 0

        # Transport raises mid-flight.
        def raising_ff(target, args):
            raise TransportError("injected mid-flight failure")

        nodes[0].trans.fast_forward = raising_ff
        nodes[0].state.set_state(NodeState.CATCHING_UP)
        nodes[0]._fast_forward()
        assert nodes[0].state.get_state() == NodeState.BABBLING

        # Still fully functional: a normal gossip round succeeds.
        before = nodes[0].sync_requests
        nodes[0]._gossip(nodes[1].local_addr)
        assert nodes[0].sync_requests > before
        assert nodes[0].state.get_state() == NodeState.BABBLING
    finally:
        for node in nodes:
            node.shutdown()


def test_shutdown():
    """Shutting a node down closes its transport (peers' syncs fail) and
    the second shutdown is idempotent — reference node_test.go:461-475."""
    from babble_tpu.net.transport import SyncRequest
    from babble_tpu.node.state import NodeState

    nodes = make_nodes(2, "inmem")
    try:
        for node in nodes:
            node.run_async(gossip=True)
        time.sleep(0.2)
        nodes[0].shutdown()
        assert nodes[0].state.get_state() == NodeState.SHUTDOWN

        with pytest.raises(Exception):
            nodes[1].trans.sync(
                nodes[0].local_addr, SyncRequest(nodes[1].id, {0: -1, 1: -1}))

        nodes[1].shutdown()
        assert nodes[1].state.get_state() == NodeState.SHUTDOWN
        nodes[1].shutdown()  # idempotent
    finally:
        for node in nodes:
            node.shutdown()


def test_wait_routines_while_routines_start():
    """A node's shutdown joins its routines while others may be starting
    (a gossip handler spawned mid-shutdown): wait_routines never sees a
    thread it cannot join yet."""
    state = StateMachine()
    stop = threading.Event()

    def spawn():
        while not stop.is_set():
            state.go_func(lambda: time.sleep(0.001))

    spawner = threading.Thread(target=spawn)
    spawner.start()
    try:
        t_end = time.monotonic() + 1.0
        while time.monotonic() < t_end:
            state.wait_routines(timeout=0.05)
    finally:
        stop.set()
        spawner.join()
