#!/usr/bin/env python
"""Chip smoke: drive the served consensus path once on a TPU, checked.

    python chip_smoke.py               # one chip, phases 1-4
    python chip_smoke.py --four-chips  # four chips: the mesh engine only

One process, the only one to touch JAX; JAX_PLATFORMS is never set, so
JAX takes the chip or fails. Each phase prints one JSON line: the
phase, its seconds, events, committed events and the device bytes in
use (`memory_stats()`). A failed check raises; the last line,
`{"ok": true, "device": {...}}`, is printed only when every phase
passed.

1. device     platform, device_kind, count; not a TPU -> exit 1.
2. testnet    BASELINE.json config 1: 4 validators, InmemTransport +
              InmemStore, 1000 tx through the app proxy, nodes built
              from `cli run` flags (Config -> Node -> Core ->
              TpuHashgraph) with failover off. Blocks byte-identical
              across nodes and against a host-engine replay of node 0's
              events in a different valid order.
3. n64        BASELINE.json config 2: 64 validators, uniform-random peer
              selection, 10k signed events through TpuHashgraph and the
              host Hashgraph: identical consensus order and blocks.
4. northstar  n=1024, 100k events: IncrementalEngine presized as a node
              presizes it, 4096-event syncs, packed pull every sync;
              rounds, witnesses, fame, round received and consensus
              timestamps equal to the plain reference
              (benchmark/reference.py, the benchmark's `correct` gate)
              on the same DAG.

--four-chips: at n=1024 (100k events) the mesh-resident engine over
four chips is bit-identical to the same engine on device 0 alone; at
n=4096 (BASELINE.json config 5, 80 events per validator, enough for
rounds to be decided) the mesh engine runs alone, must commit events,
and prints its bytes per device. Both meshes are built as a node builds
its engine's (node.core.engine_mesh). The n=4096 phase is not yet
verified on the chip: it failed before the round-received sweep was
chunked, and has not been run since (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SYNC = 4096  # events per engine sync in phases 4 and --four-chips


def _bytes_in_use(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(int(stats.get("bytes_in_use", 0)))
    return out


def emit(phase: str, t0: float, devices, **fields) -> None:
    row = {"phase": phase, "seconds": round(time.monotonic() - t0, 3)}
    row.update(fields)
    used = _bytes_in_use(devices)
    row["device_bytes_in_use"] = used[0] if len(used) == 1 else used
    print(json.dumps(row), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------- testnet


def testnet(n_nodes: int = 4, n_tx: int = 1000, deadline_s: float = 600.0,
            platform: str = "tpu"):
    """The upstream testnet on the served path; returns the phase
    fields. Settings are the reference's (docs/usage.rst): heartbeat
    10 ms, sync_limit 500, cache_size 50000, tcp_timeout 200 ms."""
    from babble_tpu import cli, crypto
    from babble_tpu.hashgraph import Hashgraph, InmemStore
    from babble_tpu.net import InmemTransport
    from babble_tpu.net.inmem_transport import connect_all
    from babble_tpu.net.peer import Peer
    from babble_tpu.node import Node
    from babble_tpu.proxy import InmemAppProxy

    args = cli.build_parser().parse_args([
        "run", "--engine", "tpu", "--no_failover", "--no_client",
        "--heartbeat", "10", "--sync_limit", "500",
        "--cache_size", "50000", "--tcp_timeout", "200"])
    keys = [crypto.key_from_seed(7000 + i) for i in range(n_nodes)]
    peers = sorted(
        ((Peer(f"node{i}", "0x" + crypto.pub_key_bytes(k).hex().upper()), k)
         for i, k in enumerate(keys)), key=lambda pk: pk[0].pub_key_hex)
    participants = {p.pub_key_hex: i for i, (p, _) in enumerate(peers)}
    transports = {p.net_addr: InmemTransport(p.net_addr, timeout=2.0)
                  for p, _ in peers}
    connect_all(list(transports.values()))

    class BlockProxy(InmemAppProxy):
        """The app: keeps each committed block's bytes."""

        def __init__(self):
            super().__init__()
            self.blocks = []

        def commit_block(self, block):
            super().commit_block(block)
            self.blocks.append(block.marshal())

    nodes, proxies = [], []
    for i, (peer, key) in enumerate(peers):
        conf = cli.config_from_args(args)
        proxy = BlockProxy()
        node = Node(conf, i, key, [p for p, _ in peers],
                    InmemStore(participants, conf.cache_size),
                    transports[peer.net_addr], proxy)
        node.init()
        nodes.append(node)
        proxies.append(proxy)

    txs = [f"chip smoke tx {i}".encode() for i in range(n_tx)]
    try:
        for node in nodes:
            node.run_async(gossip=True)
        for i, tx in enumerate(txs):
            proxies[i % n_nodes].submit_tx(tx)
        deadline = time.monotonic() + deadline_s
        want = set(txs)
        while True:
            done = [len(want.intersection(p.committed_transactions()))
                    for p in proxies]
            if min(done) == n_tx:
                break
            check(time.monotonic() < deadline,
                  f"testnet: committed {done} of {n_tx} tx by the deadline")
            time.sleep(0.1)
    finally:
        for node in nodes:
            node.shutdown()

    stats = [node.get_stats() for node in nodes]
    for s in stats:
        check(s["engine_state"] == "device",
              f"testnet: engine_state {s['engine_state']}")
        check(s["engine_platform"] == platform,
              f"testnet: engine on {s['engine_platform']!r}")
        check(s["engine_failovers"] == "0", "testnet: an engine failed over")
        check(s["consensus_pass_failures"] == "0",
              f"testnet: {s['consensus_pass_failures']} passes raised")
    for p in proxies[1:]:
        check(p.blocks == proxies[0].blocks,
              "testnet: blocks differ between nodes")
    for p in proxies:
        check(sorted(p.committed_transactions()) == sorted(txs),
              "testnet: a tx was lost or committed twice")

    # Host oracle: node 0's events into the host engine in another
    # valid order (the latest creator first, as far as parents allow).
    store0 = nodes[0].core.hg.store
    chains = [list(store0.participant_events(pk, -1))
              for pk in sorted(participants, key=participants.get)]
    events = replay_order(store0, chains)
    blocks = []
    host = Hashgraph(participants, InmemStore(participants, 4 * len(events)),
                     lambda b: blocks.append(b.marshal()))
    for ev in events:
        host.insert_event(host.read_wire_info(ev.to_wire()), False)
    host.run_consensus()
    ours = nodes[0].core.get_consensus_events()
    check(len(ours) > 0, "testnet: no consensus events")
    check(host.consensus_events()[:len(ours)] == ours,
          "testnet: node 0's consensus order != host replay")
    check(blocks[:len(proxies[0].blocks)] == proxies[0].blocks,
          "testnet: node 0's blocks != host replay")
    return {
        "events": len(events), "committed": len(ours),
        "blocks": len(proxies[0].blocks), "tx": n_tx,
        "platform": stats[0]["engine_platform"],
        "device_kind": stats[0]["engine_device_kind"],
        "failovers": 0, "failed_passes": 0,
    }


def replay_order(store, chains):
    """Every event of `chains` (per-creator hex lists), parents first,
    taking from the highest creator id that is ready."""
    pos = [0] * len(chains)
    seen = set()
    out = []
    while True:
        moved = False
        for c in reversed(range(len(chains))):
            while pos[c] < len(chains[c]):
                ev = store.get_event(chains[c][pos[c]])
                op = ev.other_parent()
                if op and op not in seen:
                    break
                out.append(ev)
                seen.add(ev.hex())
                pos[c] += 1
                moved = True
        if not moved:
            break
    check(all(p == len(ch) for p, ch in zip(pos, chains)),
          "replay: events with unknown other-parents")
    return out


# ---------------------------------------------------------------- n64


def signed_gossip(n: int, e: int, seed: int):
    """`e` signed events from uniform-random peer selection (the
    reference RandomPeerSelector): the participants map and the events
    in creation order."""
    from babble_tpu import crypto
    from babble_tpu.gojson import Timestamp
    from babble_tpu.hashgraph import Event
    from babble_tpu.ops.sim import gossip_schedule

    keys = [crypto.key_from_seed(9000 + i) for i in range(n)]
    pubs = [crypto.pub_key_bytes(k) for k in keys]
    participants = {"0x" + p.hex().upper(): i for i, p in
                    enumerate(sorted(pubs, key=lambda p: p.hex()))}
    creators, targets = gossip_schedule(n, e - n, seed=seed)
    heads, seqs, events = [""] * n, [-1] * n, []
    clock = 1_700_000_000_000_000_000

    def make(c, other, payload):
        nonlocal clock
        clock += 1_000_000
        seqs[c] += 1
        ev = Event.new(payload, [heads[c], other], pubs[c], seqs[c],
                       timestamp=Timestamp(clock))
        ev.sign(keys[c])
        heads[c] = ev.hex()
        events.append(ev)

    for c in range(n):
        make(c, "", [f"init {c}".encode()])
    for t, (c, j) in enumerate(zip(creators, targets)):
        make(int(c), heads[int(j)], [f"tx {t}".encode()])
    return participants, events


def n64(n: int = 64, e: int = 10_000, seed: int = 0, sync: int = 500):
    """Config 2 through the device engine and the host engine."""
    from babble_tpu.hashgraph import Hashgraph, InmemStore
    from babble_tpu.hashgraph.tpu_graph import TpuHashgraph
    from babble_tpu.node.core import engine_sizes

    participants, events = signed_gossip(n, e, seed)
    host_blocks, dev_blocks = [], []
    host = Hashgraph(participants, InmemStore(participants, 2 * e),
                     lambda b: host_blocks.append(b.marshal()))
    dev = TpuHashgraph(participants, InmemStore(participants, 2 * e),
                       lambda b: dev_blocks.append(b.marshal()),
                       prewarm=True, **engine_sizes(n))
    for k, ev in enumerate(events):
        host.insert_event(ev, True)
        dev.insert_event(ev, True)
        if (k + 1) % sync == 0:
            dev.run_consensus()
    dev.run_consensus()
    host.run_consensus()
    cons = host.consensus_events()
    check(len(cons) > 0, "n64: no consensus events")
    check(dev.consensus_events() == cons, "n64: consensus order != host")
    check(dev_blocks == host_blocks, "n64: blocks != host")
    return {"events": e, "committed": len(cons), "blocks": len(host_blocks),
            "n": n}


# ---------------------------------------------------------------- engine


def drive(eng, dag, sync: int = SYNC):
    """Feed `dag` to an IncrementalEngine in `sync`-event syncs, as the
    node's consensus worker does (IncrementalEngine.feed)."""
    for _ in eng.feed(dag, sync):
        pass
    return eng


def results(eng, e: int) -> dict:
    return {"rounds": eng.rounds[:e], "witness": eng.witness[:e],
            "rr": eng.rr[:e], "cts": eng.cts_ns[:e], "famous": eng.famous}


def same(a: dict, b: dict, what: str) -> None:
    import numpy as np

    for k in a:
        check(a[k].shape == b[k].shape and np.array_equal(a[k], b[k]),
              f"{what}: {k} differs")


def against_reference(eng, dag, what: str) -> int:
    """Check the engine fed `dag` (timestamps = event ids, as
    IncrementalEngine.feed stamps them) against benchmark.reference over
    the same DAG: rounds, witnesses, fame, round received, and consensus
    timestamps where decided. Returns the decided event count."""
    import numpy as np

    from benchmark import reference

    e = dag.e
    ref = reference.consensus(dag.n, dag.self_parent, dag.other_parent,
                              dag.creator, dag.index, dag.coin,
                              np.arange(e))
    got = results(eng, e)
    dec = ref.rr >= 0
    r = min(len(got["famous"]), len(ref.famous))
    same({"rounds": got["rounds"], "witness": got["witness"],
          "rr": got["rr"], "cts": got["cts"][dec],
          "famous": got["famous"][:r]},
         {"rounds": ref.rounds, "witness": ref.witness, "rr": ref.rr,
          "cts": ref.cts[dec], "famous": ref.famous[:r]},
         f"{what}: engine vs reference")
    check(not got["famous"][r:].any() and not ref.famous[r:].any(),
          f"{what}: fame rows past the other's rounds")
    return int(dec.sum())


def northstar(n: int = 1024, e: int = 100_000, seed: int = 0):
    """The live engine at north-star size against the plain reference
    on the same DAG."""
    from babble_tpu.node.core import engine_sizes
    from babble_tpu.ops.dag import synthetic_dag
    from babble_tpu.ops.incremental import IncrementalEngine

    t0 = time.monotonic()
    dag, _ = synthetic_dag(n, e, seed=seed)
    t1 = time.monotonic()
    eng = drive(IncrementalEngine(n, **engine_sizes(n)), dag)
    t2 = time.monotonic()
    resident = eng.device_memory_stats()
    committed = against_reference(eng, dag, "northstar")
    t3 = time.monotonic()
    check(committed > 0, "northstar: no consensus events")
    return {"events": e, "committed": committed, "n": n,
            "max_round": int(eng.rounds[:e].max()),
            "dag_s": round(t1 - t0, 3), "engine_s": round(t2 - t1, 3),
            "reference_s": round(t3 - t2, 3),
            "resident_device_bytes": resident["device_bytes"],
            "capacity": resident["capacity"],
            "chain_capacity": resident["chain_capacity"]}


def per_device_bytes(eng) -> dict:
    """Bytes of the engine's resident carries on each device."""
    import jax

    out: dict = {}
    for v in vars(eng).values():
        if isinstance(v, jax.Array):
            for s in v.addressable_shards:
                key = str(s.device.id)
                out[key] = out.get(key, 0) + int(s.data.nbytes)
    return out


def mesh_engines(devices, n: int = 1024, e: int = 100_000,
                 n_big: int = 4096, per_big: int = 80, seed: int = 0):
    """--four-chips: mesh vs device 0 at n, then the mesh alone at
    n_big. Yields (phase, t0, fields)."""
    import numpy as np

    from babble_tpu.node.core import engine_mesh, engine_sizes
    from babble_tpu.ops.dag import synthetic_dag
    from babble_tpu.ops.incremental import IncrementalEngine

    mesh = engine_mesh(devices, n, len(devices))
    t0 = time.monotonic()
    dag, _ = synthetic_dag(n, e, seed=seed)
    t1 = time.monotonic()
    one = drive(IncrementalEngine(n, **engine_sizes(n)), dag)
    ref = results(one, e)
    del one
    t2 = time.monotonic()
    meshed = drive(IncrementalEngine(n, mesh=mesh, **engine_sizes(n)), dag)
    t3 = time.monotonic()
    same(results(meshed, e), ref, f"mesh n={n}: mesh vs device 0")
    committed = int((ref["rr"] >= 0).sum())
    check(committed > 0, f"mesh n={n}: no consensus events")
    yield f"mesh_n{n}", t0, {
        "events": e, "committed": committed, "n": n,
        "device0_s": round(t2 - t1, 3), "mesh_s": round(t3 - t2, 3),
        "per_device_bytes": per_device_bytes(meshed)}
    del meshed

    # Random gossip first commits at about 60 events per validator at
    # n=1024 and n=2048 (CPU one-shot runs, PR 21); 80 leaves a margin.
    # The engine is presized for the run through engine_opts, as a node
    # is (Core(engine_opts=...)): growth steps would only add compiles.
    # A sync is 4 events per validator, as at n=1024. An undecided
    # window past 262144 events before the first decision lands in the
    # 1M-lane bucket, which the fused epilogue's round-received sweep
    # walks in chunks split over the chips (ops/incremental.py
    # _rr_chunked).
    t0 = time.monotonic()
    e_big = n_big * per_big
    dag, _ = synthetic_dag(n_big, e_big, seed=seed)
    k_max = int(np.bincount(dag.creator[:e_big]).max())
    sizes = engine_sizes(n_big, {
        "capacity": 1 << e_big.bit_length(),
        "k_capacity": 1 << k_max.bit_length()})
    t1 = time.monotonic()
    mesh = engine_mesh(devices, n_big, len(devices))
    big = drive(IncrementalEngine(n_big, mesh=mesh, **sizes), dag,
                sync=4 * n_big)
    t2 = time.monotonic()
    rounds = big.rounds[:e_big]
    committed = int((big.rr[:e_big] >= 0).sum())
    check(bool((rounds >= 0).all()), f"mesh n={n_big}: undetermined rounds")
    check(int(big.witness[:e_big].sum()) >= n_big,
          f"mesh n={n_big}: a validator without a witness")
    check(int(rounds.max()) >= 1, f"mesh n={n_big}: no round advanced")
    check(committed > 0, f"mesh n={n_big}: no consensus events")
    yield f"mesh_n{n_big}", t0, {
        "events": e_big, "committed": committed, "n": n_big,
        "max_round": int(rounds.max()), "engine_s": round(t2 - t1, 3),
        "capacity": sizes["capacity"], "chain_capacity": sizes["k_capacity"],
        "per_device_bytes": per_device_bytes(big)}


# ---------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh engine and its comparison, "
                         "on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # The repo first: a lone copy of this script fails before any output.
    from babble_tpu.devices import ensure_compile_cache
    import jax

    t0 = time.monotonic()
    devices = jax.devices()
    want = 4 if args.four_chips else 1
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices)}
    emit("device", t0, devices[:want], **device)
    if dev0.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev0.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < want:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    ensure_compile_cache()
    if args.four_chips:
        for phase, t0, fields in mesh_engines(devices[:4], seed=args.seed):
            emit(phase, t0, devices[:4], **fields)
    else:
        for phase, fn in (("testnet", testnet), ("n64", n64),
                          ("northstar", northstar)):
            t0 = time.monotonic()
            kw = {} if fn is testnet else {"seed": args.seed}
            emit(phase, t0, devices[:1], **fn(**kw))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 - any failure is exit 1
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # The script starts no process; _exit also ends any thread a failed
    # phase left behind, so a failure never hangs the chip.
    os._exit(code)
