"""Fork / equivocation detection end-to-end (docs/observability.md
"Consensus health"): the insert path surfaces two-signed-events-at-
one-index as ForkError + persisted evidence + the babble_forks_total
counter; the chaos transport's equivocation injector proves detection
fires within one gossip round in a live net while the honest nodes'
consensus order stays byte-identical; FileStore evidence survives
restart."""

from __future__ import annotations

import os
import time

import pytest

from babble_tpu import crypto
from babble_tpu.hashgraph import Event, FileStore, ForkError, InmemStore
from babble_tpu.net import FaultyTransport, InmemTransport
from babble_tpu.net.inmem_transport import connect_all
from babble_tpu.node import Core, Node
from babble_tpu.node.config import test_config as fast_config
from babble_tpu.proxy import InmemAppProxy

from test_node import check_gossip, init_cores, make_keyed_peers, \
    synchronize_cores

CACHE = 10000


def _forge_at_head(core, key):
    """A signed conflicting event at the creator's CURRENT head index:
    same creator, same index, same self-parent, different payload —
    textbook equivocation, provable by the two signatures."""
    head = core.get_head()
    assert head.index() >= 1, "forge below the initial event"
    forged = Event.new([b"equivocation payload"],
                       [head.self_parent(), ""],
                       core.pub_key(), head.index())
    forged.sign(key)
    assert forged.hex() != head.hex()
    forged.set_wire_info(
        head.index() - 1, -1, -1,
        core.participants[core.hex_id()])
    return head, forged


# ---------------------------------------------------------------- unit


def test_insert_path_detects_fork_and_records_evidence():
    cores = init_cores(2)
    keys = [crypto.key_from_seed(5000 + i) for i in range(2)]
    # init_cores sorts by pubkey: map keys to cores by hex id.
    by_hex = {"0x" + crypto.pub_key_bytes(k).hex().upper(): k
              for k in keys}
    synchronize_cores(cores, 0, 1, [b"a"])  # core1 head now index 1
    synchronize_cores(cores, 1, 0)          # core0 learns core1's chain

    victim_key = by_hex[cores[1].hex_id()]
    head, forged = _forge_at_head(cores[1], victim_key)

    with pytest.raises(ForkError, match="equivocation"):
        cores[0].hg.insert_event(forged, False)

    evidence = cores[0].fork_evidence()
    assert len(evidence) == 1
    rec = evidence[0]
    assert rec["creator"] == cores[1].hex_id()
    assert rec["index"] == head.index()
    assert rec["existing"] == head.hex()
    assert rec["forged"] == forged.hex()
    assert cores[0].forks_detected() == 1
    # Evidence carries the full signed proof: it re-parses and its
    # signature verifies.
    import json

    from babble_tpu.hashgraph.event import event_from_json_obj

    proof = event_from_json_obj(json.loads(rec["event_json"]))
    assert proof.verify() and proof.hex() == forged.hex()

    # A replayed forgery re-raises but dedupes: one record, one count.
    with pytest.raises(ForkError):
        cores[0].hg.insert_event(forged, False)
    assert len(cores[0].fork_evidence()) == 1
    assert cores[0].forks_detected() == 1


def test_benign_insert_failures_record_no_evidence():
    cores = init_cores(2)
    synchronize_cores(cores, 0, 1, [b"a"])
    # An unsigned event at a taken index proves nothing about the
    # creator: rejected, but NOT fork evidence.
    head = cores[1].get_head()
    fake = Event.new([b"junk"], [head.self_parent(), ""],
                     cores[1].pub_key(), head.index())
    wrong_key = crypto.key_from_seed(999)
    fake.sign(wrong_key)
    with pytest.raises(Exception):
        cores[1].hg.insert_event(fake, False)
    assert cores[1].fork_evidence() == []
    assert cores[1].forks_detected() == 0


def test_fork_evidence_survives_filestore_restart(tmp_path):
    path = str(tmp_path / "forks.db")
    entries = make_keyed_peers(2)
    participants = {p.pub_key_hex: i for i, (_k, p) in enumerate(entries)}
    store = FileStore(participants, 100, path)
    cores = [Core(i, key, participants, InmemStore(participants, CACHE))
             for i, (key, _p) in enumerate(entries)]
    for c in cores:
        c.init()
    synchronize_cores(cores, 0, 1, [b"a"])
    head, forged = _forge_at_head(cores[1], entries[1][0])
    from babble_tpu.hashgraph.health import fork_evidence_record

    rec = fork_evidence_record(head.hex(), forged)
    assert store.add_fork_evidence(rec) is True
    assert store.add_fork_evidence(rec) is False  # deduped
    store.close()

    reopened = FileStore.load(100, path)
    try:
        got = reopened.fork_evidence()
        assert len(got) == 1
        assert got[0]["forged"] == forged.hex()
        assert got[0]["creator"] == cores[1].hex_id()
    finally:
        reopened.close()
    os.remove(path)


# ------------------------------------------------------------- live e2e


def test_equivocation_injected_via_chaos_transport_detected_live():
    """Acceptance: the chaos transport delivers a forged conflicting
    event as an extra push; the receiving node detects the fork within
    one gossip round (counter + persisted evidence), the network keeps
    committing, and the honest nodes' consensus order stays
    byte-identical."""
    n = 3
    inner = [InmemTransport(f"addr{i}", timeout=2.0) for i in range(n)]
    connect_all(inner)
    wrapped = {t.local_addr(): FaultyTransport(t, seed=3) for t in inner}
    entries = make_keyed_peers(n, addr_fn=lambda i: f"addr{i}")
    peers = [p for _, p in entries]
    participants = {p.pub_key_hex: i for i, p in enumerate(peers)}
    nodes = []
    for i, (key, peer) in enumerate(entries):
        conf = fast_config(heartbeat=0.01)
        store = InmemStore(participants, CACHE)
        node = Node(conf, i, key, peers, store,
                    wrapped[peer.net_addr], InmemAppProxy())
        node.init()
        nodes.append(node)
    victim_key = entries[0][0]
    victim = nodes[0]
    try:
        for nd in nodes:
            nd.run_async(gossip=True)
        deadline = time.monotonic() + 90.0
        i = 0
        while time.monotonic() < deadline:
            nodes[i % n].submit_tx(f"pre tx {i}".encode())
            i += 1
            if all((nd.core.get_last_consensus_round_index() or 0) >= 1
                   for nd in nodes) and victim.core.seq >= 1:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("warmup timeout")

        # Forge at a head every honest node already holds: the evidence
        # names the copy that arrives second as forged, so a node that
        # got the forged copy first would name the real head instead.
        vid = victim.core.participants[victim.core.hex_id()]
        deadline = time.monotonic() + 30.0
        while True:
            with victim.core_lock:
                head, forged = _forge_at_head(victim.core, victim_key)
            if all(nd.core.known().get(vid, -1) >= head.index()
                   for nd in nodes[1:]):
                break
            assert time.monotonic() < deadline, "victim head never spread"
            time.sleep(0.02)
        wrapped[victim.local_addr].inject_equivocation(
            [forged.to_wire()])

        def fork_seen():
            return any(nd.core.forks_detected() > 0
                       for nd in nodes[1:])

        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and not fork_seen():
            nodes[i % n].submit_tx(f"mid tx {i}".encode())
            i += 1
            time.sleep(0.02)
        assert fork_seen(), "equivocation was never detected"
        assert sum(f.injected["equivocate"]
                   for f in wrapped.values()) == 1
        detector = next(nd for nd in nodes[1:]
                        if nd.core.forks_detected() > 0)
        (rec,) = detector.core.fork_evidence()
        assert rec["creator"] == victim.core.hex_id()
        assert rec["index"] == head.index()
        assert rec["forged"] == forged.hex()
        # /debug/consensus surfaces it.
        health = detector.get_consensus_health()
        assert health["forks"]["detected"] >= 1
        assert health["forks"]["evidence"][0]["forged"] == forged.hex()

        # The net keeps deciding rounds after the attack...
        target = max((nd.core.get_last_consensus_round_index() or 0)
                     for nd in nodes) + 2
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            nodes[i % n].submit_tx(f"post tx {i}".encode())
            i += 1
            if all((nd.core.get_last_consensus_round_index() or 0)
                   >= target for nd in nodes):
                break
            time.sleep(0.02)
        else:
            raise AssertionError("net stopped deciding after the fork")
    finally:
        for nd in nodes:
            nd.shutdown()
    # ...and the honest order never diverged: the forged event was
    # rejected everywhere, the block streams agree, zero divergence
    # sentinel alarms.
    check_gossip(nodes)
    for nd in nodes:
        assert nd.sentinel.divergence_count() == 0, nd.sentinel.reports
