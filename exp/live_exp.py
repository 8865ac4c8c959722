"""Instrumented live-testnet experiment: like bench.node_testnet_events_per_sec
but dumps per-node phase breakdowns so we can see where the one core goes."""
import os, sys, time, threading, json
sys.path.insert(0, "/root/repo")
if os.environ.get("SWITCH_IV"):
    sys.setswitchinterval(float(os.environ["SWITCH_IV"]))

def main(engine="tpu", n_nodes=4, warm_s=150.0, window_s=45.0, interval=1.0,
         gate=1500):
    from babble_tpu.devices import ensure_compile_cache
    ensure_compile_cache()

    from babble_tpu import crypto
    from babble_tpu.hashgraph import InmemStore
    from babble_tpu.net import InmemTransport, Peer
    from babble_tpu.net.inmem_transport import connect_all
    from babble_tpu.node import Node
    from babble_tpu.node.config import test_config

    from babble_tpu.proxy import InmemAppProxy

    keys = [crypto.key_from_seed(9000 + i) for i in range(n_nodes)]
    entries = []
    for i, k in enumerate(keys):
        pub_hex = "0x" + crypto.pub_key_bytes(k).hex().upper()
        entries.append((k, Peer(f"addr{i}", pub_hex)))
    entries.sort(key=lambda kp: kp[1].pub_key_hex)
    transports = [InmemTransport(p.net_addr, timeout=2.0) for _, p in entries]
    connect_all(transports)
    peers = [p for _, p in entries]
    participants = {p.pub_key_hex: i for i, p in enumerate(peers)}
    nodes = []
    for i, (key, peer) in enumerate(entries):
        conf = test_config(heartbeat=0.01, cache_size=100000)
        conf.engine = engine
        conf.consensus_interval = interval
        node = Node(conf, i, key, peers, InmemStore(participants, 100000),
                    transports[i], InmemAppProxy())
        node.init()
        nodes.append(node)

    stop = threading.Event()
    def bombard():
        i = 0
        while not stop.is_set():
            try:
                nodes[i % n_nodes].submit_tx(f"bench tx {i}".encode())
            except Exception:
                pass
            i += 1
            time.sleep(0.002)

    committed = lambda: min(len(nd.core.get_consensus_events()) for nd in nodes)
    t_start = time.monotonic()
    try:
        for nd in nodes:
            nd.run_async(gossip=True)
        bomber = threading.Thread(target=bombard, daemon=True)
        bomber.start()
        deadline = time.monotonic() + warm_s
        while time.monotonic() < deadline and committed() < gate:
            time.sleep(0.5)
        print(f"[exp] warm done at +{time.monotonic()-t_start:.1f}s committed={committed()}", flush=True)
        # snapshot phase counters
        snap0 = [dict((k, list(v)) for k, v in list(nd.core.phase_ns.items())) for nd in nodes]
        c0, t0 = committed(), time.monotonic()
        time.sleep(window_s)
        c1, t1 = committed(), time.monotonic()
        snap1 = [dict((k, list(v)) for k, v in list(nd.core.phase_ns.items())) for nd in nodes]
    finally:
        stop.set()
        for nd in nodes:
            nd.shutdown()
    dt = t1 - t0
    eps = (c1 - c0) / dt
    print(f"[exp] engine={engine} n={n_nodes} interval={interval}: {eps:.1f} ev/s ({c1-c0} in {dt:.1f}s)")
    # aggregate per-phase deltas across nodes
    agg = {}
    for s0, s1 in zip(snap0, snap1):
        for ph, v1 in s1.items():
            v0 = s0.get(ph, [0, 0, 0])
            agg.setdefault(ph, [0.0, 0])
            agg[ph][0] += (v1[1] - v0[1]) / 1e9
            agg[ph][1] += v1[2] - v0[2]
    print(f"[exp] phase totals over {dt:.1f}s window (all {n_nodes} nodes), core-seconds:")
    for ph, (secs, calls) in sorted(agg.items(), key=lambda kv: -kv[1][0]):
        print(f"  {ph:24s} {secs:7.2f}s  calls={calls:6d}  ({secs/dt*100:5.1f}% of wall)")
    ins = sum(nd.core.hg.topological_index for nd in nodes)
    print(f"[exp] total events inserted (all nodes, lifetime): {ins}")
    for i, nd in enumerate(nodes):
        eng = getattr(nd.core.hg, "engine", None)
        if eng is not None:
            print(f"[exp] node{i} windows: {getattr(eng, '_dbg_windows', None)} "
                  f"e={eng.e} und={int((eng.rr[:eng.e] < 0).sum())} "
                  f"rounds={len(eng._fr_table)}+{eng.rho_min}")
    return eps

if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="tpu")
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--warm", type=float, default=150.0)
    ap.add_argument("--window", type=float, default=45.0)
    ap.add_argument("--interval", type=float, default=1.0)
    ap.add_argument("--gate", type=int, default=1500)
    a = ap.parse_args()
    main(a.engine, a.n, a.warm, a.window, a.interval, a.gate)
