#!/usr/bin/env python
"""Benchmark: consensus throughput of the batched TPU engine, on the chip.

Measures in this one process, which is the only one to touch JAX, and
refuses to run anywhere but a TPU: when JAX finds no TPU, or a stage
fails, it exits non-zero. After every completed stage it prints the
payload so far as one JSON line on stdout:
  {"metric": ..., "value": N, "unit": "events/s", "vs_baseline": N, ...}
The last line carries the device it ran on (`backend`, `device_kind`).

Metric: events/sec to full consensus order (device pipeline + host
final sort) at N=64 peers over a 50k-event synthetic random-gossip DAG
— the event pattern the gossip runtime produces (reference
node/node.go:315-487). `vs_baseline` is the honest like-for-like
multiple: this repo's own reference-semantics host engine on the same
topology (real signed events, ECDSA verify on insert, same gossip
pattern). The reference's published 4-node docker steady state
(265.53-268.27 ev/s, reference docs/usage.rst:31-34) is reported
separately as `ref_docker_events_per_s` — an indicative, not
like-for-like, anchor.

Stages (each emits a partial JSON line; later stages refine):
  smoke     n=8    e=256     proves the pipeline end-to-end
  headline  n=64   e=50_000  the reported metric
  northstar n=1024 e=100_000 BASELINE.md driver target size
  host      n=64   same topology subset -> vs_baseline denominator

The other modes (--node-smoke, --soak, ...) measure host paths and run
anywhere.
"""

import json
import os
import subprocess
import sys
import time

BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "1500"))
REPO = os.path.dirname(os.path.abspath(__file__))

_T0 = time.monotonic()


def log(msg):
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def main() -> int:
    """The chip measurement, in this process, or exit 1."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"JAX found no TPU (platform {dev.platform!r}); this bench "
            "measures the chip only")
        return 1
    measure()
    return 0


# --------------------------------------------------------------------------
# The measurement. Emits a (partial) JSON line after every completed stage
# so a run cut by its budget still leaves the best result so far.
# --------------------------------------------------------------------------


def _emit(payload):
    print(json.dumps(payload), flush=True)


def _budget_left():
    return BUDGET_S - (time.monotonic() - _T0) - 30.0


def time_pipeline(dag, s_rank, warm=1, reps=3, engine="auto"):
    """Times `reps` full runs; returns (best, median, times, n_consensus,
    max_round). The median with its spread is the number; best is
    reported alongside, never alone (run-to-run spread on the chip is
    not measured yet)."""
    import numpy as np

    from babble_tpu.ops.pipeline import run_pipeline

    t0 = time.monotonic()
    for _ in range(warm):
        out = run_pipeline(dag, engine=engine)
        np.asarray(out[0])
    log(f"  [{engine}] compile+warmup {time.monotonic() - t0:.1f}s")
    times = []
    n_consensus = 0
    max_round = 0
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run_pipeline(dag, engine=engine)
        rounds, wit, wt, famous, rr, cts = [np.asarray(x) for x in out]
        # host finish: the consensus total order (rr, ts, S-tiebreak)
        mask = rr >= 0
        np.lexsort((s_rank[mask], cts[mask], rr[mask]))
        times.append(time.perf_counter() - t0)
        n_consensus = int(mask.sum())
        max_round = int(rounds.max())
    return (min(times), float(np.median(times)), times, n_consensus,
            max_round)


def tune_engine(dag, s_rank):
    """Time both pipeline engines once and return the faster — the
    closure/frontier path is built for the MXU, the wavefront for
    dispatch-cheap backends; measuring beats guessing on an unknown
    chip."""
    results = {}
    for engine in ("closure", "wavefront"):
        if _budget_left() < 60:
            break
        best, _, _, _, _ = time_pipeline(dag, s_rank, warm=1, reps=1,
                                         engine=engine)
        results[engine] = best
        log(f"  tune: {engine} {best * 1e3:.1f} ms")
    if not results:
        return "auto"
    return min(results, key=results.get)


def host_engine_events_per_sec(n_peers, n_events, seed=7):
    """This repo's reference-semantics host engine on real signed
    events with the same gossip topology — the honest like-for-like
    baseline."""
    from babble_tpu import crypto
    from babble_tpu.gojson import Timestamp
    from babble_tpu.hashgraph import Event, Hashgraph, InmemStore
    import numpy as np

    rng = np.random.default_rng(seed)
    keys = [crypto.key_from_seed(3000 + i) for i in range(n_peers)]
    pubs = [crypto.pub_key_bytes(k) for k in keys]
    participants = {"0x" + p.hex().upper(): i for i, p in enumerate(pubs)}
    clock = 1_700_000_000_000_000_000
    heads = [""] * n_peers
    seqs = [-1] * n_peers
    events = []

    creators = np.concatenate(
        [np.arange(n_peers), rng.integers(0, n_peers, size=n_events - n_peers)]
    )
    others = rng.integers(1, n_peers, size=n_events)
    for i in range(n_events):
        c = int(creators[i])
        op = heads[(c + int(others[i])) % n_peers] if i >= n_peers else ""
        clock += 1_000_000
        seqs[c] += 1
        ev = Event.new([b"tx"], [heads[c], op], pubs[c], seqs[c],
                       timestamp=Timestamp(clock))
        ev.sign(keys[c])
        heads[c] = ev.hex()
        events.append(ev)

    h = Hashgraph(participants, InmemStore(participants, 2 * n_events))
    t0 = time.perf_counter()
    for ev in events:
        h.insert_event(ev, True)
    h.run_consensus()
    dt = time.perf_counter() - t0
    return len(h.consensus_events()) / dt, len(h.consensus_events()), dt


def _audit_metrics_scrape(node, phases, file_store=False):
    """Scrape a live node's /metrics over real HTTP, run it through
    the exposition parser, and FAIL (raise) when a core series is
    missing — the CI node-smoke job runs this so a telemetry
    regression breaks the build, not the next incident. Also loads
    /debug/trace and checks it is valid Chrome trace JSON."""
    import urllib.request

    from babble_tpu.service import Service
    from babble_tpu.telemetry import promtext

    svc = Service("127.0.0.1:0", node)
    svc.serve_async()
    try:
        with urllib.request.urlopen(
                f"http://{svc.addr}/metrics", timeout=10) as r:
            text = r.read().decode()
        samples, _types = promtext.parse(text)  # raises on bad format
        required = [
            "babble_commit_latency_seconds",
            "babble_gossip_rtt_seconds",
            "babble_breaker_state",
            "babble_engine_pass_seconds",
            "babble_sync_requests_total",
            "babble_phase_seconds",
            # Consensus health plane (docs/observability.md
            # "Consensus health"): divergence/fork counters exist (at
            # zero) from boot, progress + stall gauges refresh at
            # scrape, the trace ring reports drops.
            "babble_divergence_total",
            "babble_forks_total",
            "babble_round_lag",
            "babble_undecided_witnesses",
            "babble_last_decided_fame_round",
            "babble_consensus_stalled",
            "babble_chain_index",
            "babble_trace_dropped_total",
            # Gossip efficiency observatory (docs/observability.md
            # "Gossip efficiency"): redundancy accounting counters and
            # the propagation-latency histogram — aggregate children
            # exist (at zero) from boot, per-peer ones as soon as a
            # sync lands.
            "babble_gossip_offered_events_total",
            "babble_gossip_new_events_total",
            "babble_gossip_duplicate_events_total",
            "babble_gossip_stale_events_total",
            "babble_gossip_syncs_total",
            "babble_gossip_payload_bytes_total",
            "babble_propagation_latency_seconds",
            # Saturation observatory (docs/observability.md
            # "Saturation"): every bounded buffer exports depth/
            # capacity/wait/drops from boot, and the thread CPU
            # attribution gauges refresh at scrape.
            "babble_queue_depth",
            "babble_queue_capacity",
            "babble_queue_wait_seconds",
            "babble_queue_dropped_total",
            "babble_thread_cpu_seconds_total",
            "babble_cpu_utilization_cores",
            "babble_cpu_saturation_ratio",
            # Crypto plane (docs/observability.md "Crypto plane"):
            # the backend info gauge and the per-call batch-size
            # histogram exist as soon as the first sync batch is
            # ECDSA-checked; verified-event totals from boot.
            "babble_verify_backend",
            "babble_verify_batch_size",
            "babble_verify_events_total",
            # Ingress armor (docs/ingress.md): admission counters
            # exist (at zero) from boot, and the intake queue reports
            # through the standard queue families.
            "babble_ingress_admitted_total",
            "babble_ingress_shed_total",
            "babble_ingress_quota_rejected_total",
            'babble_queue_depth{queue="intake"}',
            # Capacity observatory (docs/observability.md "Capacity"):
            # per-subsystem retained bytes, the process RSS ground
            # truth, cache efficiency, and the cardinality self-audit
            # all refresh at scrape.
            "babble_mem_bytes",
            'babble_mem_bytes{component="store_event_log"}',
            "babble_process_rss_bytes",
            "babble_mem_budget_bytes",
            'babble_cache_hits_total{cache="store_events"}',
            "babble_telemetry_series",
            "babble_telemetry_series_total",
        ]
        if file_store:
            required.append("babble_store_fsync_seconds")
            required.append('babble_store_bytes{file="wal"}')
        missing = promtext.check_series(samples, required)
        if missing:
            raise RuntimeError(
                f"/metrics scrape is missing core series: {missing}")
        with urllib.request.urlopen(
                f"http://{svc.addr}/debug/trace", timeout=10) as r:
            trace = json.loads(r.read())
        if not trace.get("traceEvents"):
            raise RuntimeError("/debug/trace has no traceEvents")
        phases["metrics_scrape"] = {
            "families": len(samples),
            "trace_events": len(trace["traceEvents"]),
        }
    finally:
        svc.close()


def _runtime_arg() -> str:
    """`--runtime threads|procs` / BENCH_RUNTIME: the execution
    runtime every bench testnet is built with (docs/runtime.md)."""
    if "--runtime" in sys.argv:
        try:
            return sys.argv[sys.argv.index("--runtime") + 1]
        except IndexError:
            pass
    return os.environ.get("BENCH_RUNTIME", "threads")


def _cpus_effective():
    """Cores this process may actually run on (None where the
    platform has no affinity API). Recorded in every soak ledger
    entry so a 1-core container's numbers are machine-distinguishable
    from a real multicore run — bench_compare auto-skips the
    multicore-only gates on it."""
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return None


def build_host_testnet(n_nodes, engine="host", interval=0.0,
                       heartbeat=0.0015, store="inmem",
                       store_sync="batch", trace_sample=0.0,
                       wire_format="columnar", transport="inmem",
                       health=True, observatory=True, plumtree=True,
                       profile_hz=0.0, admission=True, quota_rate=0.0,
                       ingress_target=0.2, runtime=None,
                       capacity=True):
    """Construct (but do not start) a localhost testnet of N real
    nodes: signed keys, fully-meshed transports, per-node stores and
    app proxies — the shared builder behind the throughput smoke, the
    overhead A/Bs, and the gossip soak (one construction path, so a
    config knob added here is measured everywhere). Returns the node
    list; callers own run_async/shutdown."""
    import tempfile

    from babble_tpu import crypto
    from babble_tpu.hashgraph import FileStore, InmemStore
    from babble_tpu.net import InmemTransport, Peer
    from babble_tpu.net.inmem_transport import connect_all
    from babble_tpu.node import Node
    from babble_tpu.node.config import test_config
    from babble_tpu.proxy import InmemAppProxy

    keys = [crypto.key_from_seed(9000 + i) for i in range(n_nodes)]
    keyed = sorted(
        ((k, "0x" + crypto.pub_key_bytes(k).hex().upper()) for k in keys),
        key=lambda kp: kp[1])
    if transport == "tcp":
        # Real localhost sockets: the configuration where the wire
        # format actually serializes (binary columnar frames vs
        # base64-inside-JSON-inside-readline) instead of passing
        # payload objects by reference.
        from babble_tpu.net import TCPTransport

        transports = [
            TCPTransport("127.0.0.1:0", timeout=2.0,
                         wire_format=wire_format, consumer_buffer=64)
            for _ in keyed]
        entries = [(k, Peer(t.local_addr(), pub))
                   for (k, pub), t in zip(keyed, transports)]
    else:
        entries = [(k, Peer(f"addr{i}", pub))
                   for i, (k, pub) in enumerate(keyed)]
        transports = [InmemTransport(p.net_addr, timeout=2.0)
                      for _, p in entries]
        connect_all(transports)
    peers = [p for _, p in entries]
    participants = {p.pub_key_hex: i for i, p in enumerate(peers)}
    rt = runtime or _runtime_arg()
    nodes = []
    for i, (key, peer) in enumerate(entries):
        conf = test_config(heartbeat=heartbeat, cache_size=100000)
        conf.engine = engine
        conf.wire_format = wire_format
        # Execution runtime (docs/runtime.md): procs moves the verify
        # plane to worker processes. The pool only engages above the
        # min batch AND workers > 1 — auto would resolve to 1 on a
        # 1-core box, so the procs leg pins a real pool size (the
        # point of the leg is measuring the off-GIL path).
        conf.runtime = rt
        if rt == "procs":
            conf.verify_workers = max(2, min(8, os.cpu_count() or 1))
        # Compile the engine's kernel ladder at construction (first
        # node pays; jit caches are process-global) — this is what
        # retired the old 6000-event warm gate.
        conf.engine_prewarm = engine == "tpu"
        conf.consensus_interval = interval
        # End-to-end tx tracing sample rate (docs/observability.md) —
        # 0 keeps the stamping/flow paths as no-ops; the trace-overhead
        # A/B drives this.
        conf.trace_sample = trace_sample
        # Consensus health plane (docs/observability.md "Consensus
        # health"): sentinel + stall watchdog are the product default;
        # health=False is the baseline leg of the --health-overhead
        # A/B (no chain hashing, no piggyback, no watchdog thread).
        conf.divergence_sentinel = health
        conf.stall_timeout = 30.0 if health else 0.0
        # Gossip efficiency observatory (docs/observability.md "Gossip
        # efficiency"): redundancy accounting + creation-stamp sidecar
        # + propagation histogram; observatory=False is the baseline
        # leg of the --gossip-overhead A/B.
        conf.gossip_observatory = observatory
        # Epidemic broadcast tree (docs/gossip.md): the product default
        # since the plumtree PR; plumtree=False is the pull-only
        # baseline (the committed pre-plumtree SOAK ledger's shape).
        conf.plumtree = plumtree
        # In-process flame profiler (docs/observability.md
        # "Saturation"): 0 keeps the sampler thread unspawned — the
        # --profile-overhead A/B drives this.
        conf.profile_hz = profile_hz
        # Ingress armor (docs/ingress.md): the admission plane is the
        # product default; admission=False is the bare-intake baseline
        # leg of the --ingress-overhead A/B. quota_rate exercises the
        # per-client token buckets (the --loadgen leg drives this).
        conf.admission = admission
        conf.quota_rate = quota_rate
        conf.ingress_target_delay = ingress_target
        # Capacity observatory (docs/observability.md "Capacity"): the
        # product default; capacity=False is the baseline leg of the
        # --capacity-overhead A/B (no sizers, no growth model, hot-path
        # carry counters still incremented — they are the cheap part
        # the A/B exists to bound).
        conf.capacity = capacity
        if store == "file":
            # Durable-path A/B (docs/robustness.md "Crash recovery"):
            # same testnet over WAL-backed FileStores, so the
            # store_commit_share below measures the transactional
            # overhead against the in-mem baseline.
            sdir = tempfile.mkdtemp(prefix="bench-store-")
            node_store = FileStore(
                participants, 100000,
                os.path.join(sdir, f"node{i}.db"), sync=store_sync)
        else:
            node_store = InmemStore(participants, 100000)
        node = Node(conf, i, key, peers, node_store,
                    transports[i], InmemAppProxy())
        node.init()
        nodes.append(node)
    return nodes


def node_testnet_events_per_sec(engine="tpu", n_nodes=4, warm_s=60.0,
                                window_s=30.0, interval=None,
                                warm_gate_events=1500, windows=1,
                                store="inmem", store_sync="batch",
                                metrics_scrape=False, trace_sample=0.0,
                                wire_format="columnar", heartbeat=None,
                                transport="inmem", health=True,
                                observatory=True, profile_hz=0.0,
                                capacity=True, scrape_hz=0.0):
    """Throughput of a live localhost testnet: N real nodes (threads,
    inmem transport, signed events, full sync protocol) bombarded with
    transactions; returns (committed consensus events/sec during a
    steady-state window after a warmup, per-phase breakdown dict) —
    the breakdown aggregates every node's Core.phase_ns so a
    regression in this stage is attributable to a phase (the sustained
    stage alone had this before). The reference's counterpart is the
    4-node docker demo steady state (reference docs/usage.rst:31-34)."""
    import threading

    if heartbeat is None:
        # Host-engine gossip is bounded by round cadence once ingest is
        # cheap (columnar wire + libcrypto ECDSA): each round yields ~2
        # events, so the heartbeat IS the throughput ceiling. 1.5 ms
        # keeps the cluster comfortably inside what the ingest path
        # sustains (A/B'd 0.01 -> 0.0015: 433 -> 794 ev/s on a 1-core
        # runner); the tpu engine keeps the 10 ms cadence that paces
        # its device passes.
        heartbeat = 0.01 if engine == "tpu" else 0.0015
    # Batch many syncs per consensus pass. For the tpu engine each
    # pass costs a device round trip and the nodes share one chip
    # (the cadence's effect on the chip is not measured). For the
    # 16-node host testnet the same batching amortizes the
    # undecided-round rescan (A/B 52 vs 78 ev/s); the 4-node host
    # testnet keeps the reference's per-sync cadence.
    if interval is None:
        # tpu: the FLOOR of the adaptive cadence (the worker
        # tracks ~3x its measured pass wall, see node.py
        # _consensus_loop).
        interval = 0.25 if engine == "tpu" else 0.0
    nodes = build_host_testnet(
        n_nodes, engine=engine, interval=interval, heartbeat=heartbeat,
        store=store, store_sync=store_sync, trace_sample=trace_sample,
        wire_format=wire_format, transport=transport, health=health,
        observatory=observatory, profile_hz=profile_hz,
        capacity=capacity)

    stop = threading.Event()
    # One process, dozens of pure-Python threads: the default 5 ms GIL
    # switch interval thrashes caches (A/B at 16 nodes: 78 -> 102 ev/s
    # at 100 ms). Restored in the finally below.
    import sys as _sys
    old_switch = _sys.getswitchinterval()
    _sys.setswitchinterval(0.1)

    def bombard():
        i = 0
        while not stop.is_set():
            try:
                nodes[i % n_nodes].submit_tx(f"bench tx {i}".encode())
            except Exception:  # noqa: BLE001
                pass
            i += 1
            time.sleep(0.002)

    def scraper():
        # Simulated Prometheus: refresh every scrape-time gauge at a
        # fixed cadence so an A/B leg pays what a scraped production
        # node pays (the capacity sizers only run when scraped).
        while not stop.is_set():
            try:
                nodes[0].get_stats()
            except Exception:  # noqa: BLE001
                pass
            stop.wait(1.0 / scrape_hz)

    committed = lambda: min(  # noqa: E731
        len(nd.core.get_consensus_events()) for nd in nodes)
    try:
        for nd in nodes:
            nd.run_async(gossip=True)
        bomber = threading.Thread(target=bombard, daemon=True)
        bomber.start()
        if scrape_hz > 0:
            threading.Thread(target=scraper, daemon=True).start()
        # Warmup gate: each engine shape compiles once per process
        # (the persistent cache covers restarts), and the first
        # post-compile minutes can still hit window-growth compiles —
        # so the gate requires enough committed events to prove MATURE
        # steady state, under a generous cap.
        deadline = time.monotonic() + warm_s
        while time.monotonic() < deadline and committed() < warm_gate_events:
            time.sleep(0.5)
        # Commit-latency snapshot at window start: the p50/p99 below is
        # a DELTA over the measurement windows (warmup samples — cold
        # caches, first compiles — would otherwise poison the tail),
        # merged across every node's submit->commit histogram.
        lat0 = [nd._m_commit_latency.snapshot() for nd in nodes]
        # Median over `windows` measurement windows: a single window is
        # at the mercy of a transient stall.
        rates = []
        for _ in range(windows):
            c0, t0 = committed(), time.monotonic()
            time.sleep(window_s)
            c1, t1 = committed(), time.monotonic()
            if c1 > c0:
                rates.append((c1 - c0) / (t1 - t0))
            # c1 <= c0: a lagging node fast-forwarded (store reset,
            # node.py _fast_forward) or the chip stalled — skip the
            # window.
        lat = None
        for nd, before in zip(nodes, lat0):
            delta = nd._m_commit_latency.snapshot() - before
            lat = delta if lat is None else lat.merge(delta)
        # Per-phase breakdown (harvested before shutdown): node-level
        # phases and, for the device engine, its sub-phases. The
        # engine_* entries are subsets of consensus_dispatch/collect
        # wall, so they get their own share denominator; engine_overlap
        # is not host wall at all (device compute that overlapped
        # ingest) and rides along in seconds.
        tot: dict = {}
        for nd in nodes:
            for ph, ent in list(nd.core.phase_ns.items()):
                tot[ph] = tot.get(ph, 0) + ent[1]
        phases: dict = {}
        # The ingest stages (docs/ingest.md) are sub-spans of `sync`,
        # so they get their own share denominator (the sync wall) and
        # stay out of the top-level split, like the engine_* subset of
        # consensus_dispatch/collect.
        # wire_unpack is a sub-span of from_wire (columnar batches
        # only); wire_pack is the outbound marshal on the diff/serve
        # side and stays top-level (docs/ingest.md "marshal split").
        ingest = {ph: v for ph, v in tot.items()
                  if ph in ("from_wire", "wire_unpack", "verify",
                            "insert")}
        # verify_<backend> re-records the verify interval keyed by the
        # crypto backend (docs/ingest.md "Crypto plane") — keep it out
        # of every share denominator or verify wall counts twice.
        top = {ph: v for ph, v in tot.items()
               if not ph.startswith("engine_") and ph not in ingest
               and not ph.startswith("verify_")
               and ph != "store_commit"}
        if top:
            s = sum(top.values())
            phases["phase_share"] = {
                ph: round(v / s, 3) for ph, v in sorted(top.items())}
        if ingest and tot.get("sync"):
            phases["ingest_phase_share"] = {
                ph: round(v / tot["sync"], 3)
                for ph, v in sorted(ingest.items())}
        # c_pull_wait/c_pull_xfer are sub-spans of c_pull (the wait/
        # transfer split) — they ride along as their own ratio and stay
        # out of the share denominator, which would double-count them.
        eng_t = {ph[len("engine_"):]: v for ph, v in tot.items()
                 if ph.startswith("engine_") and ph != "engine_overlap"
                 and not ph.startswith("engine_c_pull_")}
        if eng_t:
            es = sum(eng_t.values())
            phases["engine_phase_share"] = {
                ph: round(v / es, 3) for ph, v in sorted(eng_t.items())}
            phases["engine_pull_share"] = round(
                (eng_t.get("c_pull", 0) + eng_t.get("coords", 0)
                 + eng_t.get("fd_fold", 0)) / es, 3)
            if tot.get("engine_c_pull"):
                phases["engine_c_pull_split"] = {
                    "wait": round(tot.get("engine_c_pull_wait", 0)
                                  / tot["engine_c_pull"], 3),
                    "xfer": round(tot.get("engine_c_pull_xfer", 0)
                                  / tot["engine_c_pull"], 3)}
        if "engine_overlap" in tot:
            phases["engine_overlap_s"] = round(
                tot["engine_overlap"] / 1e9, 2)
        if "store_commit" in tot and top:
            # Durable-commit wall (sqlite COMMIT = WAL write + fsync,
            # a sub-span of sync/run_consensus) as a share of the
            # top-level phase wall: what the durable path costs vs
            # in-mem.
            phases["store_commit_share"] = round(
                tot["store_commit"] / sum(top.values()), 3)
        if lat is not None and lat.count > 0:
            # End-to-end submit->commit latency over the measurement
            # windows, cross-node (docs/observability.md).
            phases["commit_latency_p50_ms"] = round(
                lat.quantile(0.5) * 1000.0, 2)
            phases["commit_latency_p99_ms"] = round(
                lat.quantile(0.99) * 1000.0, 2)
            phases["commit_latency_samples"] = lat.count
        if metrics_scrape:
            _audit_metrics_scrape(nodes[0], phases,
                                  file_store=(store == "file"))
    finally:
        _sys.setswitchinterval(old_switch)
        stop.set()
        for nd in nodes:
            nd.shutdown()
    if not rates:
        raise RuntimeError(
            "testnet made no valid measurement window (fast-forward "
            "resets or stalls)")
    rates.sort()
    m = len(rates)
    # true median: even counts average the middle pair (an
    # upper-middle pick would report the best window after a skip).
    if m % 2:
        return rates[m // 2], phases
    return (rates[m // 2 - 1] + rates[m // 2]) / 2.0, phases


def wire_ingest_microbench(target_events=1500):
    """Columnar-vs-legacy wire A/B on the batch shape where marshal
    actually matters: one big sync diff (catch-up / eager-push shape),
    measured end to end on one core — sender pack+serialize, then
    receiver deserialize + `Core.sync` (materialize, ECDSA verify,
    insert). Live 3-node testnets at steady state move 2-4 events per
    batch, where syscalls and round-trip pacing dominate and the two
    forms tie; this is the payload-bound regime the columnar wire was
    built for (docs/ingest.md "Wire layout")."""
    import json as _json

    from babble_tpu import crypto
    from babble_tpu.hashgraph.inmem_store import InmemStore
    from babble_tpu.net.columnar import ColumnarEvents
    from babble_tpu.net.transport import SyncResponse
    from babble_tpu.node.core import Core

    keys = sorted(
        (crypto.key_from_seed(9000 + i) for i in range(3)),
        key=lambda k: crypto.pub_key_bytes(k).hex().upper())
    parts = {"0x" + crypto.pub_key_bytes(k).hex().upper(): i
             for i, k in enumerate(keys)}

    donors = [Core(i, k, parts, InmemStore(parts, 100000))
              for i, k in enumerate(keys)]
    for c in donors:
        c.init()
    import itertools

    pairs = list(itertools.permutations(range(3), 2))
    i = 0
    while sum(donors[0].known().values()) < target_events:
        a, b = pairs[i % len(pairs)]
        diff = donors[b].diff(donors[a].known())
        donors[a].add_transactions([b"wire bench tx %d" % i])
        donors[a].sync(donors[b].to_wire_batch(diff, "columnar"))
        i += 1
    diff = donors[0].diff({i: -1 for i in range(3)})

    out = {"batch_events": len(diff)}

    def fresh():
        return Core(9, keys[0], parts, InmemStore(parts, 100000))

    # The timed windows are ~200 ms; a generational GC pass over the
    # garbage a preceding testnet leg left behind would eat half a
    # window (observed 2.8x swings inside the full smoke). Collect
    # now, then keep the collector out of the measurement.
    import gc

    gc.collect()
    gc.disable()
    try:
        t0 = time.perf_counter()
        buf = donors[0].to_wire_batch(diff, "columnar").encode()
        out["pack_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        out["bytes"] = len(buf)
        c = fresh()
        t0 = time.perf_counter()
        c.sync(ColumnarEvents.decode(buf))
        dt = time.perf_counter() - t0
        out["events_per_s"] = round(len(diff) / dt, 1)

        from babble_tpu.net.tcp_transport import _b64_bytes

        t0 = time.perf_counter()
        resp = SyncResponse(
            1, events=donors[0].to_wire_batch(diff, "gojson"))
        data = _json.dumps(resp.to_dict(), default=_b64_bytes).encode()
        out["legacy_pack_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        out["legacy_bytes"] = len(data)
        c = fresh()
        t0 = time.perf_counter()
        c.sync(SyncResponse.from_dict(_json.loads(data)).events)
        dt = time.perf_counter() - t0
        out["legacy_events_per_s"] = round(len(diff) / dt, 1)
        out["bytes_ratio"] = round(out["legacy_bytes"] / out["bytes"], 2)
    finally:
        gc.enable()
    return out


def node_smoke():
    """Host-ingest microbench for CI: a 3-node in-mem host-engine
    gossip testnet (fixed seeds, no TPU, no JAX import) measured for
    ~20s, emitting one JSON line with `node_events_per_s` so host-path
    regressions are visible per-PR in the job log. The raw exit code
    is 0 whenever a measurement was made; the hard gate is
    bench_compare.py, which diffs this payload against the committed
    ledger (BENCH_SMOKE.json / BENCH_r*.json) with the
    `host_events_per_s` machine-speed calibration below normalizing
    out runner differences."""
    payload = {
        "metric": "node_events_per_s_smoke",
        "unit": "events/s",
        "nodes": 3,
        "engine": "host",
    }
    try:
        # Machine-speed calibration: the SAME pinned single-thread
        # host-engine run (n=64, e=5000, seed 7) the full bench
        # records as host_events_per_s — the shared yardstick
        # bench_compare.py uses to normalize throughput/latency
        # across machines before gating.
        calib_eps, _, _ = host_engine_events_per_sec(64, 5000)
        payload["host_events_per_s"] = round(calib_eps, 1)
        payload["host_events"] = 5000
    except Exception as exc:  # noqa: BLE001
        payload["calibration_error"] = str(exc)
    try:
        eps, phases = node_testnet_events_per_sec(
            engine="host", n_nodes=3, warm_s=8.0, window_s=12.0,
            interval=0.03, warm_gate_events=200, windows=1,
            metrics_scrape=True)
        payload["node_events_per_s"] = round(eps, 1)
        payload["node_phase_share"] = phases.get("phase_share")
        payload["node_ingest_phase_share"] = phases.get(
            "ingest_phase_share")
        payload["wire_format"] = "columnar"
        # End-to-end submit->commit latency over the measurement
        # window (docs/observability.md) — the headline observability
        # numbers next to throughput.
        payload["commit_latency_p50_ms"] = phases.get(
            "commit_latency_p50_ms")
        payload["commit_latency_p99_ms"] = phases.get(
            "commit_latency_p99_ms")
        payload["metrics_scrape"] = phases.get("metrics_scrape")
    except Exception as exc:  # noqa: BLE001
        payload["error"] = str(exc)
        _emit(payload)
        return 1
    try:
        # Columnar-vs-legacy wire A/B (docs/ingest.md): the same
        # testnet pinned to the Go-JSON event-dict payload. The delta
        # is the marshal/materialize share the packed wire removes —
        # recorded so the interop-preserving legacy path's cost stays
        # visible per-PR.
        leps, _ = node_testnet_events_per_sec(
            engine="host", n_nodes=3, warm_s=6.0, window_s=8.0,
            interval=0.03, warm_gate_events=150, windows=1,
            wire_format="gojson")
        payload["node_legacy_events_per_s"] = round(leps, 1)
        payload["wire_ab_speedup"] = round(eps / leps, 3) if leps else None
    except Exception as exc:  # noqa: BLE001
        payload["legacy_wire_error"] = str(exc)
    try:
        # Big-batch wire A/B: the payload-bound regime (catch-up /
        # eager-push diffs) where the columnar form pays — steady-state
        # testnet batches are 2-4 events, where the two forms tie.
        payload["wire_ingest"] = wire_ingest_microbench()
        payload["wire_ingest_events_per_s"] = payload["wire_ingest"][
            "events_per_s"]
    except Exception as exc:  # noqa: BLE001
        payload["wire_ingest_error"] = str(exc)
    try:
        # Cluster-scaling leg: the 16-node testnet in the same smoke,
        # so the node{3,16} trend is machine-tracked per PR (the full
        # bench records it too; this keeps the trend visible on CI
        # runners). Consensus batching per the 16-node A/B note in
        # node_testnet_events_per_sec.
        seps, _ = node_testnet_events_per_sec(
            engine="host", n_nodes=16, warm_s=8.0, window_s=12.0,
            interval=0.5, warm_gate_events=150, windows=1)
        payload["node16_events_per_s"] = round(seps, 1)
        payload["node_scaling_events_per_s"] = {
            "3": payload["node_events_per_s"], "16": round(seps, 1)}
    except Exception as exc:  # noqa: BLE001
        payload["node16_error"] = str(exc)
    try:
        # Durable-path leg: the same smoke over WAL-backed FileStores.
        # store_commit_share is the fraction of node phase wall spent
        # in sqlite COMMITs; the events/s delta against the in-mem leg
        # above is the full durable-path overhead (record in BENCH).
        # The scrape audit runs here too: the file leg must expose the
        # fsync-latency histogram on top of the core series.
        feps, fphases = node_testnet_events_per_sec(
            engine="host", n_nodes=3, warm_s=8.0, window_s=12.0,
            interval=0.0, warm_gate_events=200, windows=1,
            store="file", metrics_scrape=True)
        payload["node_file_events_per_s"] = round(feps, 1)
        payload["store_commit_share"] = fphases.get("store_commit_share")
        payload["file_commit_latency_p50_ms"] = fphases.get(
            "commit_latency_p50_ms")
        payload["file_commit_latency_p99_ms"] = fphases.get(
            "commit_latency_p99_ms")
    except Exception as exc:  # noqa: BLE001
        payload["file_store_error"] = str(exc)
    _emit(payload)
    return 0


def trace_overhead(reps=4, bar=0.05):
    """Interleaved A/B of the end-to-end tracing path (same protocol
    PR 5 used for the telemetry registry): `reps` back-to-back pairs
    of the 3-node host smoke, one leg with trace_sample=0 (stamping
    and flow emission must compile down to a falsy check) and one with
    tracing ON at a rate high enough to actually exercise the flow
    paths every window (0.05 — 50x the documented production default
    of 0.001, so the measurement bounds the real overhead from above).
    Interleaving absorbs machine drift; the medians must agree within
    `bar` (5%) or the exit code fails the CI job."""
    on_rate = 0.05
    off_rates, on_rates = [], []
    payload = {
        "metric": "trace_overhead_ab",
        "nodes": 3,
        "engine": "host",
        "trace_sample_on": on_rate,
        "reps": reps,
    }
    try:
        for rep in range(reps):
            for label, rate, acc in (("off", 0.0, off_rates),
                                     ("on", on_rate, on_rates)):
                eps, _ = node_testnet_events_per_sec(
                    engine="host", n_nodes=3, warm_s=6.0, window_s=8.0,
                    interval=0.0, warm_gate_events=150, windows=1,
                    trace_sample=rate)
                acc.append(eps)
                log(f"  rep {rep} {label}: {eps:,.1f} ev/s")
    except Exception as exc:  # noqa: BLE001
        payload["error"] = str(exc)
        _emit(payload)
        return 1
    off_rates.sort()
    on_rates.sort()
    med = lambda xs: (xs[len(xs) // 2] if len(xs) % 2  # noqa: E731
                      else (xs[len(xs) // 2 - 1] + xs[len(xs) // 2]) / 2)
    off_med, on_med = med(off_rates), med(on_rates)
    overhead = 1.0 - on_med / off_med if off_med > 0 else 0.0
    payload["off_events_per_s"] = [round(x, 1) for x in off_rates]
    payload["on_events_per_s"] = [round(x, 1) for x in on_rates]
    payload["off_median"] = round(off_med, 1)
    payload["on_median"] = round(on_med, 1)
    payload["overhead_pct"] = round(overhead * 100.0, 2)
    payload["bar_pct"] = bar * 100.0
    payload["within_bar"] = overhead <= bar
    _emit(payload)
    if overhead > bar:
        log(f"trace overhead {overhead:.1%} exceeds the {bar:.0%} bar")
        return 1
    return 0


def health_overhead(reps=4, bar=0.05):
    """Interleaved A/B of the consensus health plane (same protocol as
    trace_overhead): `reps` back-to-back pairs of the 3-node host
    smoke with the divergence sentinel + stall watchdog + progress
    gauges ON (the product default — chain hash per committed block,
    health sidecar on every gossip pull, watchdog thread polling) vs
    OFF. The medians must agree within `bar` (5%) or the exit code
    fails the CI job."""
    on_rates, off_rates = [], []
    payload = {
        "metric": "health_overhead_ab",
        "nodes": 3,
        "engine": "host",
        "reps": reps,
    }
    try:
        for rep in range(reps):
            for label, health, acc in (("off", False, off_rates),
                                       ("on", True, on_rates)):
                eps, _ = node_testnet_events_per_sec(
                    engine="host", n_nodes=3, warm_s=6.0, window_s=8.0,
                    interval=0.0, warm_gate_events=150, windows=1,
                    health=health)
                acc.append(eps)
                log(f"  rep {rep} health {label}: {eps:,.1f} ev/s")
    except Exception as exc:  # noqa: BLE001
        payload["error"] = str(exc)
        _emit(payload)
        return 1
    off_rates.sort()
    on_rates.sort()
    med = lambda xs: (xs[len(xs) // 2] if len(xs) % 2  # noqa: E731
                      else (xs[len(xs) // 2 - 1] + xs[len(xs) // 2]) / 2)
    off_med, on_med = med(off_rates), med(on_rates)
    overhead = 1.0 - on_med / off_med if off_med > 0 else 0.0
    payload["off_events_per_s"] = [round(x, 1) for x in off_rates]
    payload["on_events_per_s"] = [round(x, 1) for x in on_rates]
    payload["off_median"] = round(off_med, 1)
    payload["on_median"] = round(on_med, 1)
    payload["overhead_pct"] = round(overhead * 100.0, 2)
    payload["bar_pct"] = bar * 100.0
    payload["within_bar"] = overhead <= bar
    _emit(payload)
    if overhead > bar:
        log(f"health overhead {overhead:.1%} exceeds the {bar:.0%} bar")
        return 1
    return 0


def gossip_overhead(reps=4, bar=0.05):
    """Interleaved A/B of the gossip efficiency observatory (same
    protocol as trace/health_overhead): `reps` back-to-back pairs of
    the 3-node host smoke with the observatory ON (the product default
    — per-sync redundancy classification, the known-map snapshot, the
    creation-stamp sidecar on every self-event, the propagation
    histogram) vs OFF. The measurement plane that exists to find waste
    must not itself be waste: medians must agree within `bar` (5%) or
    the exit code fails the CI job."""
    on_rates, off_rates = [], []
    payload = {
        "metric": "gossip_overhead_ab",
        "nodes": 3,
        "engine": "host",
        "reps": reps,
    }
    try:
        for rep in range(reps):
            for label, obs, acc in (("off", False, off_rates),
                                    ("on", True, on_rates)):
                eps, _ = node_testnet_events_per_sec(
                    engine="host", n_nodes=3, warm_s=6.0, window_s=8.0,
                    interval=0.0, warm_gate_events=150, windows=1,
                    observatory=obs)
                acc.append(eps)
                log(f"  rep {rep} observatory {label}: {eps:,.1f} ev/s")
    except Exception as exc:  # noqa: BLE001
        payload["error"] = str(exc)
        _emit(payload)
        return 1
    off_rates.sort()
    on_rates.sort()
    med = lambda xs: (xs[len(xs) // 2] if len(xs) % 2  # noqa: E731
                      else (xs[len(xs) // 2 - 1] + xs[len(xs) // 2]) / 2)
    off_med, on_med = med(off_rates), med(on_rates)
    overhead = 1.0 - on_med / off_med if off_med > 0 else 0.0
    payload["off_events_per_s"] = [round(x, 1) for x in off_rates]
    payload["on_events_per_s"] = [round(x, 1) for x in on_rates]
    payload["off_median"] = round(off_med, 1)
    payload["on_median"] = round(on_med, 1)
    payload["overhead_pct"] = round(overhead * 100.0, 2)
    payload["bar_pct"] = bar * 100.0
    payload["within_bar"] = overhead <= bar
    _emit(payload)
    if overhead > bar:
        log(f"gossip overhead {overhead:.1%} exceeds the {bar:.0%} bar")
        return 1
    return 0


def capacity_overhead(reps=4, bar=0.05):
    """Interleaved A/B of the capacity observatory (same protocol as
    trace/health/gossip_overhead): `reps` back-to-back pairs of the
    3-node host smoke with the capacity plane ON (the product default
    — scrape-time sizers, the growth model, the cardinality audit) vs
    OFF, both legs scraped at 1 Hz so the on leg pays what a
    Prometheus-watched production node pays. The hot-path carries
    (cache hit/miss ints) are unconditional in both legs — the A/B
    bounds the scrape-time plane. Medians must agree within `bar` (5%)
    or the exit code fails the CI job."""
    on_rates, off_rates = [], []
    payload = {
        "metric": "capacity_overhead_ab",
        "nodes": 3,
        "engine": "host",
        "scrape_hz": 1.0,
        "reps": reps,
    }
    try:
        for rep in range(reps):
            for label, cap_on, acc in (("off", False, off_rates),
                                       ("on", True, on_rates)):
                eps, _ = node_testnet_events_per_sec(
                    engine="host", n_nodes=3, warm_s=6.0, window_s=8.0,
                    interval=0.0, warm_gate_events=150, windows=1,
                    capacity=cap_on, scrape_hz=1.0)
                acc.append(eps)
                log(f"  rep {rep} capacity {label}: {eps:,.1f} ev/s")
    except Exception as exc:  # noqa: BLE001
        payload["error"] = str(exc)
        _emit(payload)
        return 1
    off_rates.sort()
    on_rates.sort()
    med = lambda xs: (xs[len(xs) // 2] if len(xs) % 2  # noqa: E731
                      else (xs[len(xs) // 2 - 1] + xs[len(xs) // 2]) / 2)
    off_med, on_med = med(off_rates), med(on_rates)
    overhead = 1.0 - on_med / off_med if off_med > 0 else 0.0
    payload["off_events_per_s"] = [round(x, 1) for x in off_rates]
    payload["on_events_per_s"] = [round(x, 1) for x in on_rates]
    payload["off_median"] = round(off_med, 1)
    payload["on_median"] = round(on_med, 1)
    payload["overhead_pct"] = round(overhead * 100.0, 2)
    payload["bar_pct"] = bar * 100.0
    payload["within_bar"] = overhead <= bar
    _emit(payload)
    if overhead > bar:
        log(f"capacity overhead {overhead:.1%} exceeds the {bar:.0%} "
            f"bar")
        return 1
    return 0


# --------------------------------------------------------------------------
# Cluster-scaling gossip soak (docs/observability.md "Gossip efficiency"):
# the instrument the epidemic-broadcast rewrite will be accepted
# against. For each n it runs a live host testnet for a fixed wall,
# scrapes /metrics on an interval into a JSONL time-series ledger, and
# summarizes the per-n efficiency curves — per-node ev/s, redundancy
# ratio, duplicate share, propagation p50/p99, coverage time, and the
# known-map bookkeeping share the O(n) hypothesis blames.
# --------------------------------------------------------------------------


def profile_overhead(reps=4, bar=0.05):
    """Interleaved A/B of the in-process flame profiler (same protocol
    as trace/health/gossip_overhead): `reps` back-to-back pairs of the
    3-node host smoke, one leg with profile_hz=0 (the sampler thread
    must never be spawned — sampling-off is a strict no-op) and one at
    the documented production rate of 99 Hz, where the sampler walks
    sys._current_frames() under the GIL ~99 times a second. The
    medians must agree within `bar` (5%) or the exit code fails the CI
    job."""
    on_hz = 99.0
    off_rates, on_rates = [], []
    payload = {
        "metric": "profile_overhead_ab",
        "nodes": 3,
        "engine": "host",
        "profile_hz_on": on_hz,
        "reps": reps,
    }
    try:
        for rep in range(reps):
            for label, hz, acc in (("off", 0.0, off_rates),
                                   ("on", on_hz, on_rates)):
                eps, _ = node_testnet_events_per_sec(
                    engine="host", n_nodes=3, warm_s=6.0, window_s=8.0,
                    interval=0.0, warm_gate_events=150, windows=1,
                    profile_hz=hz)
                acc.append(eps)
                log(f"  rep {rep} profiler {label}: {eps:,.1f} ev/s")
    except Exception as exc:  # noqa: BLE001
        payload["error"] = str(exc)
        _emit(payload)
        return 1
    off_rates.sort()
    on_rates.sort()
    med = lambda xs: (xs[len(xs) // 2] if len(xs) % 2  # noqa: E731
                      else (xs[len(xs) // 2 - 1] + xs[len(xs) // 2]) / 2)
    off_med, on_med = med(off_rates), med(on_rates)
    overhead = 1.0 - on_med / off_med if off_med > 0 else 0.0
    payload["off_events_per_s"] = [round(x, 1) for x in off_rates]
    payload["on_events_per_s"] = [round(x, 1) for x in on_rates]
    payload["off_median"] = round(off_med, 1)
    payload["on_median"] = round(on_med, 1)
    payload["overhead_pct"] = round(overhead * 100.0, 2)
    payload["bar_pct"] = bar * 100.0
    payload["within_bar"] = overhead <= bar
    _emit(payload)
    if overhead > bar:
        log(f"profiler overhead {overhead:.1%} exceeds the {bar:.0%} bar")
        return 1
    return 0


def verify_bench(sizes=(1, 8, 64, 512), device_budget_s=150.0):
    """Crypto-plane microbenchmark (docs/ingest.md "Crypto plane"):
    per-backend serial vs batch vs device µs/event at batch sizes
    {1,8,64,512}, emitted as one JSON payload (metric `verify_bench`)
    whose headline keys bench_compare gates against the committed
    VERIFY_BENCH.json — a crypto regression fails CI like any other.

    Backends: the active host backend (`crypto.BACKEND`), the
    pure-python fallback when it is not already active, and the
    ops/p256.py device kernel when JAX is importable. Serial parses
    creator keys once outside the timer — the ingest path's
    `pub_key_from_bytes_cached` amortizes exactly that. The device leg
    respects `device_budget_s` and records any sizes it skipped (no
    silent caps; on a CPU-fallback runner the 512-lane kernel alone can
    cost minutes of XLA compile + run)."""
    import hashlib

    from babble_tpu import crypto
    from babble_tpu.crypto import _fallback as fb

    payload = {"metric": "verify_bench", "sizes": list(sizes),
               "backend_active": crypto.BACKEND}
    max_n = max(sizes)
    seeds = (1, 2, 3, 5)
    keys = [fb.key_from_seed(s) for s in seeds]
    pubs_b = [fb.pub_key_bytes(k) for k in keys]
    log(f"signing {max_n}-event corpus ({len(keys)} creators, "
        f"backend {crypto.BACKEND})")
    pubs, digests, sigs = [], [], []
    for i in range(max_n):
        d = hashlib.sha256(b"verify-bench-%d" % i).digest()
        pubs.append(pubs_b[i % len(keys)])
        digests.append(d)
        sigs.append(crypto.sign(keys[i % len(keys)], d))

    def _serial_host(name, verify_fn, key_of):
        cache = {p: key_of(p) for p in pubs_b}
        for s in sizes:
            reps = max(1, min(8, 256 // s))
            t0 = time.perf_counter()
            for _ in range(reps):
                for i in range(s):
                    verify_fn(cache[pubs[i]], digests[i], *sigs[i])
            us = (time.perf_counter() - t0) / (reps * s) * 1e6
            payload[f"verify_{name}_serial_us_{s}"] = round(us, 2)
            log(f"  {name} serial n={s}: {us:,.1f} us/ev")

    def _batch(name, batch_fn, budget_s=None):
        t_leg = time.monotonic()
        for s in sizes:
            if budget_s is not None and \
                    time.monotonic() - t_leg > budget_s:
                skipped = [x for x in sizes if x >= s]
                payload[f"verify_{name}_sizes_skipped"] = skipped
                log(f"  {name} batch: budget exhausted, "
                    f"skipping sizes {skipped}")
                break
            reps = max(1, min(8, 256 // s))
            batch_fn(pubs[:s], digests[:s], sigs[:s])  # warm (compile)
            t0 = time.perf_counter()
            for _ in range(reps):
                batch_fn(pubs[:s], digests[:s], sigs[:s])
            us = (time.perf_counter() - t0) / (reps * s) * 1e6
            payload[f"verify_{name}_batch_us_{s}"] = round(us, 2)
            log(f"  {name} batch n={s}: {us:,.1f} us/ev")

    _serial_host(crypto.BACKEND, crypto.verify,
                 crypto.pub_key_from_bytes)
    _batch(crypto.BACKEND, crypto.verify_batch)
    if crypto.BACKEND != "pure-python":
        _serial_host("pure-python", fb.verify, fb.pub_key_from_bytes)
        _batch("pure-python", fb.verify_batch)

    try:
        from babble_tpu.ops import p256
        device_ok = p256.available()
    except Exception:  # noqa: BLE001
        device_ok = False
    if device_ok:
        _batch("device-p256", p256.verify_batch,
               budget_s=device_budget_s)
    else:
        payload["device_skipped"] = "jax unavailable"
        log("  device-p256: skipped (jax unavailable)")

    _emit(payload)
    return 0


def _http_testnet(n_nodes, admission, quota_rate=0.0,
                  ingress_target=0.2, heartbeat=0.0015, interval=0.0):
    """A host testnet with a Service per node — the real HTTP intake
    path (docs/ingress.md). Returns (nodes, services); callers own
    run_async/shutdown/close."""
    from babble_tpu.service import Service

    nodes = build_host_testnet(
        n_nodes, engine="host", interval=interval, heartbeat=heartbeat,
        admission=admission, quota_rate=quota_rate,
        ingress_target=ingress_target)
    services = [Service("127.0.0.1:0", nd) for nd in nodes]
    for svc in services:
        svc.serve_async()
    return nodes, services


def _ingress_eps(admission, rate=400, batch=40, warm_s=6.0,
                 window_s=8.0):
    """Committed ev/s of a 3-node host testnet driven through the
    real HTTP batch-submit path at a fixed sub-capacity open-loop
    rate — the measured leg of the --ingress-overhead A/B. Admission
    ON routes tx intake through quota -> CoDel -> intake queue;
    OFF is the bare pre-ingress path (submit_ch direct)."""
    import threading
    import urllib.request

    from babble_tpu.service.ingress import encode_tx_batch

    nodes, services = _http_testnet(3, admission)
    stop = threading.Event()
    seq = [0]

    def bombard():
        i = 0
        period = batch / rate
        nxt = time.monotonic()
        while not stop.is_set():
            txs = []
            for _ in range(batch):
                txs.append(b"ingress tx %d" % seq[0])
                seq[0] += 1
            req = urllib.request.Request(
                f"http://{services[i % 3].addr}/submit/batch",
                data=encode_tx_batch(txs), method="POST")
            try:
                urllib.request.urlopen(req, timeout=5).read()
            except Exception:  # noqa: BLE001
                pass
            i += 1
            nxt += period
            delay = nxt - time.monotonic()
            if delay > 0:
                stop.wait(delay)
            else:
                # Fixed offered rate for the A/B: don't accumulate
                # scheduling debt into a burst.
                nxt = time.monotonic()

    committed = lambda: min(  # noqa: E731
        len(nd.core.get_consensus_events()) for nd in nodes)
    import sys as _sys
    old_switch = _sys.getswitchinterval()
    _sys.setswitchinterval(0.1)
    try:
        for nd in nodes:
            nd.run_async(gossip=True)
        threading.Thread(target=bombard, daemon=True).start()
        deadline = time.monotonic() + warm_s
        while time.monotonic() < deadline and committed() < 150:
            time.sleep(0.25)
        c0, t0 = committed(), time.monotonic()
        time.sleep(window_s)
        c1, t1 = committed(), time.monotonic()
        return (c1 - c0) / (t1 - t0)
    finally:
        _sys.setswitchinterval(old_switch)
        stop.set()
        for svc in services:
            svc.close()
        for nd in nodes:
            nd.shutdown()


def ingress_overhead(reps=4, bar=0.05):
    """Interleaved A/B of the ingress admission plane (same protocol
    as trace/health/gossip_overhead): `reps` back-to-back pairs of a
    3-node host testnet bombarded through the REAL HTTP batch-submit
    path at a fixed sub-capacity rate, one leg with the admission
    plane ON (per-client quota lookup, CoDel controller, bounded
    intake queue + coalesced pool inserts — the product default) and
    one with --no_admission (bare submit_ch intake). Under
    non-overload load the armor must be free: medians within `bar`
    (5%) or the exit code fails the CI job."""
    on_rates, off_rates = [], []
    payload = {
        "metric": "ingress_overhead_ab",
        "nodes": 3,
        "engine": "host",
        "reps": reps,
        "offered_tx_per_s": 400,
    }
    try:
        for rep in range(reps):
            for label, admission, acc in (("off", False, off_rates),
                                          ("on", True, on_rates)):
                eps = _ingress_eps(admission)
                acc.append(eps)
                log(f"  rep {rep} admission {label}: {eps:,.1f} ev/s")
    except Exception as exc:  # noqa: BLE001
        payload["error"] = str(exc)
        _emit(payload)
        return 1
    off_rates.sort()
    on_rates.sort()
    med = lambda xs: (xs[len(xs) // 2] if len(xs) % 2  # noqa: E731
                      else (xs[len(xs) // 2 - 1] + xs[len(xs) // 2]) / 2)
    off_med, on_med = med(off_rates), med(on_rates)
    overhead = 1.0 - on_med / off_med if off_med > 0 else 0.0
    payload["off_events_per_s"] = [round(x, 1) for x in off_rates]
    payload["on_events_per_s"] = [round(x, 1) for x in on_rates]
    payload["off_median"] = round(off_med, 1)
    payload["on_median"] = round(on_med, 1)
    payload["overhead_pct"] = round(overhead * 100.0, 2)
    payload["bar_pct"] = bar * 100.0
    payload["within_bar"] = overhead <= bar
    _emit(payload)
    if overhead > bar:
        log(f"ingress overhead {overhead:.1%} exceeds the {bar:.0%} bar")
        return 1
    return 0


def loadgen():
    """Load-generator mode (docs/ingress.md): drive >= 100k open
    client transactions (open-loop arrival — each client schedules
    sends by wall clock, never by response) from many quota'd clients
    through the real HTTP batch-submit path against a host testnet,
    then assert the overload contract straight from /metrics:

    - `babble_ingress_shed_total` > 0 and quota rejections > 0 (the
      offered rate is sized >= 2x the cluster's commit capacity, and
      a slice of clients is greedy past its bucket),
    - `babble_queue_dropped_total{queue="commit"}` == 0 — shedding
      happens at the FRONT door, the commit path never drops,
    - every ADMITTED transaction commits, byte-identically ordered
      across nodes,
    - the admitted-tx p99 commit latency (scraped, cross-node-merged
      histogram) meets the SLO.

    Emits one JSON payload (loadgen_* keys) gated by bench_compare
    against the committed LOADGEN_SMOKE.json. Env knobs:
    LOADGEN_NODES/TXS/RATE/CLIENTS/BATCH/SLO_MS/QUOTA."""
    import threading
    import urllib.request
    from urllib.error import HTTPError

    from babble_tpu.service.ingress import encode_tx_batch
    from babble_tpu.telemetry import promtext

    n_nodes = int(os.environ.get("LOADGEN_NODES", "3"))
    total_txs = int(os.environ.get("LOADGEN_TXS", "100000"))
    rate = float(os.environ.get("LOADGEN_RATE", "2500"))
    n_clients = int(os.environ.get("LOADGEN_CLIENTS", "24"))
    batch = int(os.environ.get("LOADGEN_BATCH", "100"))
    slo_ms = float(os.environ.get("LOADGEN_SLO_MS", "10000"))
    fair = rate / n_clients
    # Per-client quota at 2x fair share: in-contract clients never see
    # the bucket; every 6th client offers 4x fair share and MUST get
    # quota-rejected — the quota plane exercised, not just configured.
    quota_rate = float(os.environ.get("LOADGEN_QUOTA", str(2.0 * fair)))
    payload = {
        "metric": "loadgen",
        "nodes": n_nodes,
        "engine": "host",
        "loadgen_offered_target": total_txs,
        "loadgen_rate_tx_per_s": rate,
        "loadgen_clients": n_clients,
        "loadgen_quota_tx_per_s": round(quota_rate, 1),
        "loadgen_slo_ms": slo_ms,
    }
    try:
        calib_eps, _, _ = host_engine_events_per_sec(64, 5000)
        payload["host_events_per_s"] = round(calib_eps, 1)
    except Exception as exc:  # noqa: BLE001
        payload["calibration_error"] = str(exc)

    nodes, services = _http_testnet(
        n_nodes, admission=True, quota_rate=quota_rate, interval=0.03)
    lock = threading.Lock()
    counts = {"offered": 0, "accepted": 0, "shed": 0,
              "quota_rejected": 0, "http_429": 0, "errors": 0}
    admitted: set = set()
    stop = threading.Event()

    def client(idx):
        greedy = idx % 6 == 0
        my_rate = fair * (4.0 if greedy else 1.0)
        period = batch / my_rate
        svc = services[idx % n_nodes]
        url = f"http://{svc.addr}/submit/batch"
        nxt = time.monotonic()
        i = 0
        while not stop.is_set():
            with lock:
                if counts["offered"] >= total_txs:
                    return
                base = counts["offered"]
                counts["offered"] += batch
            txs = [b"lg %d %d %d" % (idx, i, base + k)
                   for k in range(batch)]
            i += 1
            req = urllib.request.Request(
                url, data=encode_tx_batch(txs), method="POST",
                headers={"X-Babble-Client": f"lg-{idx}"})
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    doc = json.loads(r.read())
            except HTTPError as e:
                # 429 = the whole batch was rejected; the body still
                # carries the shed/quota split.
                try:
                    doc = json.loads(e.read())
                except Exception:  # noqa: BLE001
                    doc = {}
                with lock:
                    counts["http_429"] += 1
                    counts["shed"] += int(doc.get("shed", 0))
                    counts["quota_rejected"] += int(
                        doc.get("quota_rejected", batch))
                doc = None
            except Exception:  # noqa: BLE001
                with lock:
                    counts["errors"] += 1
                doc = None
            if doc is not None:
                with lock:
                    counts["accepted"] += int(doc.get("submitted", 0))
                    counts["shed"] += int(doc.get("shed", 0))
                    counts["quota_rejected"] += int(
                        doc.get("quota_rejected", 0))
                    for tx, st in zip(txs, doc.get("statuses", [])):
                        if st == "accepted":
                            admitted.add(tx)
            # Open-loop arrival: the next send is scheduled by wall
            # clock from the PREVIOUS schedule point, not from when
            # the response came back.
            nxt += period
            delay = nxt - time.monotonic()
            if delay > 0:
                stop.wait(delay)

    committed_txs = lambda nd: nd.core.get_consensus_transactions()  # noqa: E731
    import sys as _sys
    old_switch = _sys.getswitchinterval()
    _sys.setswitchinterval(0.1)
    t0 = time.monotonic()
    try:
        for nd in nodes:
            nd.run_async(gossip=True)
        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        # Progress log while the offered load drains out.
        while any(t.is_alive() for t in threads):
            time.sleep(2.0)
            with lock:
                snap = dict(counts)
            log(f"  offered {snap['offered']:,} accepted "
                f"{snap['accepted']:,} shed {snap['shed']:,} quota "
                f"{snap['quota_rejected']:,}")
        offered_wall = time.monotonic() - t0
        # Drain: every admitted tx must land in every node's committed
        # stream (the front door shed instead of the commit path
        # dropping — nothing admitted may be lost).
        drain_deadline = time.monotonic() + max(
            120.0, 30.0 * n_nodes)
        pending = len(nodes)
        while time.monotonic() < drain_deadline:
            pending = sum(
                1 for nd in nodes
                if not admitted.issubset(set(committed_txs(nd))))
            if pending == 0:
                break
            time.sleep(1.0)
        wall = time.monotonic() - t0
        with lock:
            snap = dict(counts)
        payload.update({
            "loadgen_offered": snap["offered"],
            "loadgen_admitted": snap["accepted"],
            "loadgen_shed": snap["shed"],
            "loadgen_quota_rejected": snap["quota_rejected"],
            "loadgen_http_429": snap["http_429"],
            "loadgen_errors": snap["errors"],
            "loadgen_offered_wall_s": round(offered_wall, 1),
            "loadgen_wall_s": round(wall, 1),
            "loadgen_admitted_per_s": round(
                snap["accepted"] / offered_wall, 1),
            "loadgen_shed_share": round(
                snap["shed"] / max(snap["offered"], 1), 3),
        })
        # The /metrics-side contract: scrape every node's service,
        # merge the commit-latency histograms, sum the shed/drop
        # counters — the same bytes a Prometheus server would see.
        lat_snap = None
        shed_total = 0.0
        quota_total = 0.0
        commit_drops = 0.0
        for svc in services:
            with urllib.request.urlopen(
                    f"http://{svc.addr}/metrics", timeout=10) as r:
                samples, _ = promtext.parse(r.read().decode())
            h = promtext.histogram_snapshot(
                samples, "babble_commit_latency_seconds")
            lat_snap = h if lat_snap is None else lat_snap.merge(h)
            shed_total += sum(
                v for _lb, v in samples.get(
                    "babble_ingress_shed_total", []))
            quota_total += sum(
                v for _lb, v in samples.get(
                    "babble_ingress_quota_rejected_total", []))
            commit_drops += sum(
                v for lb, v in samples.get(
                    "babble_queue_dropped_total", [])
                if lb.get("queue") == "commit")
        p99_ms = round(lat_snap.quantile(0.99) * 1000.0, 1)
        p50_ms = round(lat_snap.quantile(0.5) * 1000.0, 1)
        payload["loadgen_commit_latency_p99_ms"] = p99_ms
        payload["loadgen_commit_latency_p50_ms"] = p50_ms
        payload["loadgen_scraped_shed_total"] = int(shed_total)
        payload["loadgen_scraped_quota_rejected"] = int(quota_total)
        payload["loadgen_commit_drops"] = int(commit_drops)
        # Byte-identical order across nodes over the common prefix.
        streams = [committed_txs(nd) for nd in nodes]
        prefix = min(len(s) for s in streams)
        order_ok = all(s[:prefix] == streams[0][:prefix]
                       for s in streams)
        payload["loadgen_committed_txs"] = prefix
        failures = []
        if snap["offered"] < total_txs:
            failures.append(
                f"offered {snap['offered']} < target {total_txs}")
        if shed_total + quota_total <= 0:
            failures.append("no sheds or quota rejections under a "
                            ">=2x-capacity firehose")
        if quota_total <= 0:
            failures.append("greedy clients never hit their quota")
        if commit_drops > 0:
            failures.append(f"commit_ch dropped {int(commit_drops)}")
        if pending > 0:
            failures.append(
                f"{pending} node(s) missing admitted txs after drain")
        if not order_ok:
            failures.append("committed tx order diverged across nodes")
        if p99_ms > slo_ms:
            failures.append(
                f"admitted p99 {p99_ms}ms exceeds SLO {slo_ms}ms")
        payload["loadgen_pass"] = not failures
        if failures:
            payload["error"] = "; ".join(failures)
        _emit(payload)
        return 1 if failures else 0
    except Exception as exc:  # noqa: BLE001
        payload["error"] = str(exc)
        _emit(payload)
        return 1
    finally:
        _sys.setswitchinterval(old_switch)
        stop.set()
        for svc in services:
            svc.close()
        for nd in nodes:
            nd.shutdown()


def _soak_coverage_probe(nodes, timeout=15.0):
    """Coverage time: wall seconds for node 0's NEXT self-event to
    become known to every node (the known maps are read through the
    raw store path so the probe does not inflate the `known` phase it
    is measuring). None when the net is too stalled to measure."""
    n0 = nodes[0]
    pid0 = n0.core.participants[n0.core.hex_id()]
    target = n0.core.seq + 1
    deadline = time.monotonic() + timeout
    while n0.core.seq < target:
        if time.monotonic() > deadline:
            return None
        time.sleep(0.001)
    t0 = time.monotonic()
    remaining = set(range(1, len(nodes)))
    while remaining:
        if time.monotonic() > deadline:
            return None
        for i in list(remaining):
            if nodes[i].core.hg.known().get(pid0, -1) >= target:
                remaining.discard(i)
        time.sleep(0.002)
    return time.monotonic() - t0


def gossip_soak_leg(n, wall_s, scrape_s, ts_file, probes=5):
    """One soak leg: n in-process host nodes under continuous load for
    `wall_s` of measurement, /metrics scraped over real HTTP every
    `scrape_s` (parse-validated) with per-node counter rows appended
    to the JSONL ledger `ts_file`. Returns the leg summary dict."""
    import threading
    import urllib.request

    from babble_tpu.service import Service
    from babble_tpu.telemetry import promtext

    # n >= 16 batches several syncs per consensus pass, matching the
    # node16 smoke leg (amortizes the undecided-round rescan).
    interval = 0.5 if n >= 16 else 0.0
    nodes = build_host_testnet(n, engine="host", interval=interval,
                               heartbeat=0.0015)
    svc = Service("127.0.0.1:0", nodes[0])
    svc.serve_async()
    stop = threading.Event()
    coverage: list = []

    def bombard():
        i = 0
        while not stop.is_set():
            try:
                nodes[i % n].submit_tx(f"soak tx {i}".encode())
            except Exception:  # noqa: BLE001
                pass
            i += 1
            time.sleep(0.002)

    def probe_loop():
        gap = max(wall_s / (probes + 1), 0.5)
        while not stop.is_set() and len(coverage) < probes:
            c = _soak_coverage_probe(nodes)
            if c is not None:
                coverage.append(c)
            if stop.wait(gap):
                return

    committed = lambda: min(  # noqa: E731
        len(nd.core.get_consensus_events()) for nd in nodes)
    agg_snap = lambda nd: {  # noqa: E731
        k: c.value for k, c in nd._m_gossip_agg.items()}

    def sat_agg():
        # Queue saturation across ALL nodes, folded by queue family
        # (per-peer plumtree_push:<addr> entries collapse into one
        # row): wait p99 takes the max (the bottleneck criterion),
        # drops sum, depth/capacity report the worst occupant.
        out: dict = {}
        for nd in nodes:
            for name, s in nd.saturation_stats().items():
                fam = name.split(":", 1)[0]
                row = out.setdefault(fam, {
                    "depth": 0, "capacity": 0, "wait_p99_ms": 0.0,
                    "dropped": 0, "waits": 0})
                row["depth"] = max(row["depth"], s.get("depth", 0))
                row["capacity"] = max(row["capacity"],
                                      s.get("capacity", 0))
                if s.get("wait_p99_ms") is not None:
                    row["wait_p99_ms"] = max(row["wait_p99_ms"],
                                             s["wait_p99_ms"])
                row["dropped"] += int(s.get("dropped", 0))
                row["waits"] += int(s.get("waits", 0))
        return out

    def cpu_from_samples(samples):
        # Thread CPU folded by role (babble-worker-3 -> babble-worker,
        # Thread-42 (handle) -> Thread (handle)) so the curve stays
        # n-independent; the utilization gauge rides along.
        import re as _re

        by_role: dict = {}
        for lb, v in samples.get("babble_thread_cpu_seconds_total", []):
            role = _re.sub(r"-\d+", "", lb.get("thread", "?"))
            by_role[role] = round(by_role.get(role, 0.0) + v, 3)
        util = samples.get("babble_cpu_utilization_cores", [])
        return by_role, (round(util[0][1], 3) if util else None)

    def plumtree_snap():
        out = {"grafts": 0, "prunes": 0, "shed": 0}
        for nd in nodes:
            pt = nd.plumtree
            if pt is None:
                continue
            out["grafts"] += int(pt._m_graft["tx"].value)
            out["prunes"] += int(pt._m_prune["tx"].value)
            out["shed"] += int(pt._m_shed.value)
        return out

    def leg_snap():
        # Cluster totals per ingest leg (eager / lazy_pull / graft /
        # pull / push_in): the acceptance split for the tree rewrite.
        out: dict = {}
        for nd in nodes:
            for (_p, leg), ch in list(nd._gossip_children.items()):
                row = out.setdefault(leg, {"new": 0, "duplicate": 0})
                row["new"] += int(ch["new"].value)
                row["duplicate"] += int(ch["duplicate"].value)
        return out

    import sys as _sys
    old_switch = _sys.getswitchinterval()
    _sys.setswitchinterval(0.1)
    rows_written = 0
    try:
        for nd in nodes:
            nd.run_async(gossip=True)
        threading.Thread(target=bombard, daemon=True).start()
        # Warmup: first commits prove the net is live before the
        # measurement window opens. The cap scales with n — at n=32
        # the first rounds take ~60 s to decide (round cadence is the
        # cluster's end-to-end propagation time, not CPU), and opening
        # the window during that ramp measures the ramp, not the
        # steady state.
        deadline = time.monotonic() + max(6.0, wall_s / 3.0, 3.0 * n)
        while time.monotonic() < deadline and committed() < 100:
            time.sleep(0.25)
        threading.Thread(target=probe_loop, daemon=True).start()

        c0, t0 = committed(), time.monotonic()
        g0 = [agg_snap(nd) for nd in nodes]
        p0 = [nd.core._m_propagation.snapshot() for nd in nodes]
        pt0 = plumtree_snap()
        legs0 = leg_snap()
        phase0: dict = {}
        for nd in nodes:
            for ph, ent in list(nd.core.phase_ns.items()):
                phase0[ph] = phase0.get(ph, 0) + ent[1]
        with open(ts_file, "a") as ts:
            while time.monotonic() - t0 < wall_s:
                time.sleep(scrape_s)
                now = round(time.monotonic() - t0, 2)
                # Real HTTP scrape of node 0 — parse-validated, the
                # same bytes a Prometheus server would ingest.
                with urllib.request.urlopen(
                        f"http://{svc.addr}/metrics", timeout=10) as r:
                    samples, _ = promtext.parse(r.read().decode())
                scraped = {
                    kind: sum(
                        v for lb, v in samples.get(
                            f"babble_gossip_{kind}_events_total", [])
                        if lb.get("node") == "0" and "peer" not in lb)
                    for kind in ("offered", "new", "duplicate")}
                ts.write(json.dumps(
                    {"t": now, "n": n, "node": "scrape0"} | scraped)
                    + "\n")
                rows_written += 1
                # Saturation curves (docs/observability.md
                # "Saturation"): per-family queue depth/wait and the
                # role-folded thread CPU totals, one row each per
                # scrape tick.
                ts.write(json.dumps({
                    "t": now, "n": n, "node": "sat",
                    "queues": {
                        fam: {"depth": r["depth"],
                              "wait_p99_ms": r["wait_p99_ms"],
                              "dropped": r["dropped"]}
                        for fam, r in sat_agg().items()},
                }) + "\n")
                by_role, util = cpu_from_samples(samples)
                ts.write(json.dumps({
                    "t": now, "n": n, "node": "cpu",
                    "thread_cpu_s": by_role,
                    "utilization_cores": util,
                }) + "\n")
                rows_written += 2
                for i, nd in enumerate(nodes):
                    snap = agg_snap(nd)
                    ts.write(json.dumps({
                        "t": now, "n": n, "node": i,
                        "consensus_events":
                            len(nd.core.get_consensus_events()),
                        **{k: int(v) for k, v in snap.items()},
                    }) + "\n")
                    rows_written += 1
        wall = time.monotonic() - t0
        c1 = committed()
        g1 = [agg_snap(nd) for nd in nodes]
        pt1 = plumtree_snap()
        legs1 = leg_snap()
        prop = None
        for nd, before in zip(nodes, p0):
            delta = nd.core._m_propagation.snapshot() - before
            prop = delta if prop is None else prop.merge(delta)
        phase1: dict = {}
        for nd in nodes:
            for ph, ent in list(nd.core.phase_ns.items()):
                phase1[ph] = phase1.get(ph, 0) + ent[1]
        # End-of-leg saturation summary, harvested while the nodes are
        # still alive (saturation_stats reads live queue instruments).
        sat1 = sat_agg()
        try:
            with urllib.request.urlopen(
                    f"http://{svc.addr}/metrics", timeout=10) as r:
                fsamples, _ = promtext.parse(r.read().decode())
            cpu_roles, cpu_util = cpu_from_samples(fsamples)
        except Exception:  # noqa: BLE001
            cpu_roles, cpu_util = {}, None
    finally:
        _sys.setswitchinterval(old_switch)
        stop.set()
        for nd in nodes:
            nd.shutdown()
        svc.close()

    tot = {k: sum(b[k] - a[k] for a, b in zip(g0, g1))
           for k in g0[0]} if g0 else {}
    plumtree_counters = ({k: pt1[k] - pt0[k] for k in pt1}
                         if any(nd.plumtree is not None for nd in nodes)
                         else {})
    leg_totals = {}
    for lg, row1 in legs1.items():
        row0 = legs0.get(lg, {"new": 0, "duplicate": 0})
        lnew = row1["new"] - row0["new"]
        ldup = row1["duplicate"] - row0["duplicate"]
        if lnew or ldup:
            leg_totals[lg] = {
                "new": lnew, "duplicate": ldup,
                "redundancy_ratio": (round(ldup / lnew, 3)
                                     if lnew else None)}
    offered = tot.get("offered", 0)
    new = tot.get("new", 0)
    dup = tot.get("duplicate", 0)
    # Pacing/bookkeeping attribution over the window (same share
    # denominators as node_testnet_events_per_sec).
    dphase = {ph: phase1.get(ph, 0) - phase0.get(ph, 0) for ph in phase1}
    ingest = ("from_wire", "wire_unpack", "verify", "insert")
    # verify_<backend> is the same interval as verify under a
    # backend-keyed name — excluded so the verify wall isn't counted
    # twice in the pacing denominator.
    top = {ph: v for ph, v in dphase.items()
           if not ph.startswith("engine_") and ph not in ingest
           and not ph.startswith("verify_")
           and ph != "store_commit" and v > 0}
    top_sum = sum(top.values())
    leg = {
        "n": n,
        "wall_s": round(wall, 1),
        # Core budget + runtime stamped on EVERY ledger entry: the
        # machine-readable honesty note. bench_compare auto-skips
        # multicore-only gates when either side ran on < 2 cores.
        "cpus_effective": _cpus_effective(),
        "runtime": _runtime_arg(),
        "events_per_s": round((c1 - c0) / wall, 1),
        "offered_events": int(offered),
        "new_events": int(new),
        "duplicate_events": int(dup),
        "stale_events": int(tot.get("stale", 0)),
        "payload_bytes": int(tot.get("bytes", 0)),
        # duplicates per NEW event: the gossip amplification waste
        # (0 = perfect); duplicate_share is the same waste as a
        # fraction of everything offered (bounded [0, 1]).
        "redundancy_ratio": round(dup / new, 3) if new else None,
        "duplicate_share": round(dup / offered, 3) if offered else None,
        "bytes_per_new_event": round(tot.get("bytes", 0) / new, 1)
        if new else None,
        "coverage_ms": (round(
            1e3 * sorted(coverage)[len(coverage) // 2], 1)
            if coverage else None),
        "coverage_probes": len(coverage),
        "timeseries_rows": rows_written,
    }
    if prop is not None and prop.count:
        leg["propagation_p50_ms"] = round(prop.quantile(0.5) * 1e3, 2)
        leg["propagation_p99_ms"] = round(prop.quantile(0.99) * 1e3, 2)
        leg["propagation_samples"] = prop.count
    # Epidemic broadcast tree churn (docs/gossip.md): graft/prune
    # totals over the window — a settled tree shows ~0 churn per
    # second, repair storms show up immediately.
    if plumtree_counters:
        for k, v in plumtree_counters.items():
            leg[k] = v
        leg["grafts_per_s"] = round(
            plumtree_counters.get("grafts", 0) / wall, 2)
        leg["prunes_per_s"] = round(
            plumtree_counters.get("prunes", 0) / wall, 2)
    # Per-leg redundancy split (eager plane vs anti-entropy backstop):
    # the acceptance view — eager should carry nearly all new events
    # at low duplicate cost once the tree settles.
    if leg_totals:
        leg["legs"] = leg_totals
    # Saturation summary (USE-method: which queue is the bottleneck,
    # where did the CPU-seconds go). wait p99 is the bottleneck
    # criterion — the queue where enqueued work waited longest.
    if sat1:
        leg["queues"] = sat1
        bq = max(sat1.items(), key=lambda kv: kv[1]["wait_p99_ms"])
        leg["bottleneck_queue"] = bq[0]
        leg["queue_wait_p99_ms"] = round(bq[1]["wait_p99_ms"], 2)
    if cpu_roles:
        leg["thread_cpu_s"] = cpu_roles
    if cpu_util is not None:
        leg["cpu_utilization_cores"] = cpu_util
    if top_sum:
        leg["phase_share"] = {ph: round(v / top_sum, 3)
                              for ph, v in sorted(top.items())}
        # The suspected O(n) term: known-map walks + diff merges as a
        # share of the top-level phase wall.
        leg["bookkeeping_share"] = round(
            (dphase.get("known", 0) + dphase.get("diff", 0)) / top_sum,
            3)
    if dphase.get("sync"):
        # Inside the sync wall (docs/ingest.md): materialize / verify /
        # insert split — when `sync` dominates the leg, this names the
        # stage that grew with n.
        leg["ingest_phase_share"] = {
            ph: round(dphase.get(ph, 0) / dphase["sync"], 3)
            for ph in ingest if dphase.get(ph)}
    return leg


def gossip_soak():
    """`bench.py --soak`: the cluster-scaling soak ledger. Legs and
    wall come from SOAK_NS / SOAK_WALL_S / SOAK_SCRAPE_S (defaults
    n∈{3,8,16,32}, 45 s, 2 s) so CI can run a {3,8} smoke against the
    same committed SOAK_SMOKE.json baseline (bench_compare gates the
    keys both payloads carry). Emits one JSON payload; the raw
    time-series JSONL lands in SOAK_OUT_DIR."""
    import tempfile

    ns = [int(x) for x in os.environ.get(
        "SOAK_NS", "3,8,16,32").split(",") if x.strip()]
    wall_s = float(os.environ.get("SOAK_WALL_S", "45"))
    scrape_s = float(os.environ.get("SOAK_SCRAPE_S", "2.0"))
    out_dir = os.environ.get("SOAK_OUT_DIR") or tempfile.mkdtemp(
        prefix="babble-soak-")
    os.makedirs(out_dir, exist_ok=True)
    ts_file = os.path.join(out_dir, "soak_timeseries.jsonl")
    # Multicore leg (`--cpus K` / SOAK_CPUS): pin the whole testnet
    # process to K cores so thread CPU attribution and the queue
    # curves are measured under a known core budget. Pinning is
    # best-effort — the host may expose fewer cores than asked
    # (cpus_effective records what the run actually had, and the
    # ledger keeps both so a 1-core container's numbers are never
    # mistaken for a 2-core result).
    cpus_req = None
    if "--cpus" in sys.argv:
        try:
            cpus_req = int(sys.argv[sys.argv.index("--cpus") + 1])
        except (IndexError, ValueError):
            log("--cpus needs an integer argument")
            return 1
    elif os.environ.get("SOAK_CPUS"):
        cpus_req = int(os.environ["SOAK_CPUS"])
    payload = {
        "metric": "gossip_soak_multicore" if cpus_req else "gossip_soak",
        "unit": "events/s",
        "engine": "host",
        "runtime": _runtime_arg(),
        "wall_s_per_leg": wall_s,
        "timeseries_jsonl": ts_file,
        "legs": {},
    }
    if cpus_req:
        payload["cpus_requested"] = cpus_req
        if hasattr(os, "sched_setaffinity"):
            avail = sorted(os.sched_getaffinity(0))
            os.sched_setaffinity(0, set(avail[:cpus_req]))
    # Recorded UNCONDITIONALLY (post-pinning), not just on --cpus
    # runs: every soak ledger carries its real core budget, so
    # bench_compare can machine-skip multicore-only gates instead of
    # relying on a hand-written honest note.
    payload["cpus_effective"] = _cpus_effective()
    if cpus_req:
        log(f"soak multicore: requested {cpus_req} cpus, "
            f"effective {payload['cpus_effective']}")
    # 1->2 core scaling factor (ROADMAP multicore gate): when
    # SOAK_BASELINE_JSON names a prior soak payload (the 1-core
    # reference leg), each leg's throughput is expressed as a factor
    # over the baseline's same-n leg — the `soak{n}_scaling_x`
    # headline bench_compare gates as a raw factor (no machine
    # normalization: both runs happened on THIS machine).
    base_eps: dict = {}
    bp = os.environ.get("SOAK_BASELINE_JSON")
    if bp and os.path.exists(bp):
        try:
            with open(bp) as f:
                bj = json.load(f)
            base_eps = {k: v for k, v in bj.items()
                        if k.endswith("_events_per_s")
                        and isinstance(v, (int, float))}
            payload["scaling_baseline"] = bp
        except Exception as exc:  # noqa: BLE001
            log(f"scaling baseline unreadable: {exc}")
    try:
        # The shared machine-speed yardstick (see bench_compare.py).
        calib_eps, _, _ = host_engine_events_per_sec(64, 5000)
        payload["host_events_per_s"] = round(calib_eps, 1)
        payload["host_events"] = 5000
    except Exception as exc:  # noqa: BLE001
        payload["calibration_error"] = str(exc)
    failures = 0
    for n in ns:
        log(f"soak leg n={n}: {wall_s:.0f}s wall, "
            f"scrape every {scrape_s:.1f}s")
        try:
            leg = gossip_soak_leg(n, wall_s, scrape_s, ts_file)
        except Exception as exc:  # noqa: BLE001
            payload[f"soak{n}_error"] = str(exc)
            failures += 1
            _emit(payload)
            continue
        payload["legs"][str(n)] = leg
        payload[f"soak{n}_events_per_s"] = leg["events_per_s"]
        for k in ("redundancy_ratio", "duplicate_share",
                  "bytes_per_new_event", "propagation_p50_ms",
                  "propagation_p99_ms", "coverage_ms",
                  "bookkeeping_share", "grafts_per_s", "prunes_per_s",
                  "queue_wait_p99_ms", "cpu_utilization_cores"):
            if leg.get(k) is not None:
                payload[f"soak{n}_{k}"] = leg[k]
        # Per-leg redundancy (docs/gossip.md): the eager plane is the
        # headline — a settled tree delivers ~once per event there.
        eager = (leg.get("legs") or {}).get("eager") or {}
        if eager.get("redundancy_ratio") is not None:
            payload[f"soak{n}_eager_redundancy_ratio"] = \
                eager["redundancy_ratio"]
        # Crypto-plane multicore gate (ROADMAP "verify share < 0.3"):
        # verify's share of the sync wall, a multicore-only headline —
        # bench_compare skips it when cpus_effective < 2.
        ing = leg.get("ingest_phase_share") or {}
        if ing.get("verify") is not None:
            payload[f"soak{n}_verify_share"] = ing["verify"]
        base = base_eps.get(f"soak{n}_events_per_s")
        if base:
            payload[f"soak{n}_scaling_x"] = round(
                leg["events_per_s"] / base, 2)
        log(f"  n={n}: {leg['events_per_s']:,.1f} ev/s, redundancy "
            f"{leg['redundancy_ratio']}, dup share "
            f"{leg['duplicate_share']}, propagation p99 "
            f"{leg.get('propagation_p99_ms')} ms, bookkeeping share "
            f"{leg.get('bookkeeping_share')}")
        _emit(payload)
    payload["node_scaling_events_per_s"] = {
        str(n): payload[f"soak{n}_events_per_s"]
        for n in ns if f"soak{n}_events_per_s" in payload}
    _emit(payload)
    return 1 if failures else 0


# --------------------------------------------------------------------------
# Retention soak (docs/observability.md "Capacity"): the state-growth
# ledger the checkpoint/compaction work will be accepted against. Each
# leg runs a WAL-backed host testnet under fixed load, samples the
# capacity families over real HTTP on an interval, and fits
# bytes-per-committed-event slopes for total retained state, the
# process RSS, and the WAL — plus the named top-growing component from
# /debug/capacity. bench_compare gates the slopes against the
# committed RETENTION_SMOKE.json.
# --------------------------------------------------------------------------


def retention_leg(n, wall_s, scrape_s, ts_file):
    """One retention leg: n host nodes over WAL-backed FileStores
    under continuous load for `wall_s`, capacity families scraped over
    real HTTP every `scrape_s` into the JSONL ledger `ts_file`.
    Returns the leg summary with the fitted growth slopes."""
    import threading
    import urllib.request

    from babble_tpu.service import Service
    from babble_tpu.telemetry import promtext
    from babble_tpu.telemetry.capacity import GrowthTracker

    interval = 0.5 if n >= 16 else 0.0
    nodes = build_host_testnet(n, engine="host", interval=interval,
                               heartbeat=0.0015, store="file")
    svc = Service("127.0.0.1:0", nodes[0])
    svc.serve_async()
    stop = threading.Event()

    def bombard():
        i = 0
        while not stop.is_set():
            try:
                nodes[i % n].submit_tx(f"retention tx {i}".encode())
            except Exception:  # noqa: BLE001
                pass
            i += 1
            time.sleep(0.002)

    committed = lambda: min(  # noqa: E731
        len(nd.core.get_consensus_events()) for nd in nodes)

    # The slope fitter the node itself uses — one model, two callers.
    growth = GrowthTracker(window=4096)
    samples_taken = 0
    import sys as _sys
    old_switch = _sys.getswitchinterval()
    _sys.setswitchinterval(0.1)
    try:
        for nd in nodes:
            nd.run_async(gossip=True)
        threading.Thread(target=bombard, daemon=True).start()
        deadline = time.monotonic() + max(6.0, wall_s / 3.0, 3.0 * n)
        while time.monotonic() < deadline and committed() < 100:
            time.sleep(0.25)
        c0, t0 = committed(), time.monotonic()
        with open(ts_file, "a") as ts:
            while time.monotonic() - t0 < wall_s:
                time.sleep(scrape_s)
                now = round(time.monotonic() - t0, 2)
                ev = committed()
                # Real HTTP scrape — the same bytes Prometheus would
                # ingest, parse-validated.
                with urllib.request.urlopen(
                        f"http://{svc.addr}/metrics", timeout=10) as r:
                    samples, _ = promtext.parse(r.read().decode())
                node0 = lambda fam: {  # noqa: E731
                    lb.get("component") or lb.get("file") or "": v
                    for lb, v in samples.get(fam, [])
                    if lb.get("node", "0") == "0"}
                mem = node0("babble_mem_bytes")
                files = node0("babble_store_bytes")
                rss_rows = samples.get("babble_process_rss_bytes", [])
                rss = rss_rows[0][1] if rss_rows else 0
                mem_total = sum(mem.values())
                # x = committed events: the slopes read directly as
                # bytes per committed event.
                growth.observe("mem_total", ev, mem_total)
                growth.observe("rss", ev, rss)
                if "wal" in files:
                    growth.observe("wal", ev, files["wal"])
                if "journal" in files:
                    growth.observe("journal", ev, files["journal"])
                for comp, b in mem.items():
                    growth.observe(f"mem:{comp}", ev, b)
                ts.write(json.dumps({
                    "t": now, "n": n, "node": "capacity",
                    "committed_events": ev,
                    "mem_total_bytes": int(mem_total),
                    "rss_bytes": int(rss),
                    "files": {k: int(v) for k, v in files.items()},
                    "components": {k: int(v) for k, v in mem.items()},
                }) + "\n")
                samples_taken += 1
        wall = time.monotonic() - t0
        c1 = committed()
        # Final /debug/capacity read while the net is live: the ranked
        # top-growers table names the verdict component.
        try:
            with urllib.request.urlopen(
                    f"http://{svc.addr}/debug/capacity", timeout=10) \
                    as r:
                cap_dbg = json.loads(r.read())
        except Exception:  # noqa: BLE001
            cap_dbg = {}
    finally:
        _sys.setswitchinterval(old_switch)
        stop.set()
        for nd in nodes:
            nd.shutdown()
        svc.close()

    sl = lambda s: growth.slope(s)  # noqa: E731
    rnd = lambda v: None if v is None else round(v, 2)  # noqa: E731
    # Top grower by fitted slope across the per-component series (the
    # node's own /debug/capacity table rides along as a cross-check).
    comp_slopes = {s[len("mem:"):]: v for s, v in growth.slopes().items()
                   if s.startswith("mem:") and v is not None}
    top = max(comp_slopes.items(), key=lambda kv: kv[1]) \
        if comp_slopes else (None, None)
    leg = {
        "n": n,
        "wall_s": round(wall, 1),
        "cpus_effective": _cpus_effective(),
        "runtime": _runtime_arg(),
        "events_per_s": round((c1 - c0) / wall, 1),
        "committed_events": c1,
        "samples": samples_taken,
        "bytes_per_event": rnd(sl("mem_total")),
        "rss_slope_bytes_per_event": rnd(sl("rss")),
        "wal_slope_bytes_per_event": rnd(sl("wal")),
        "journal_slope_bytes_per_event": rnd(sl("journal")),
        "mem_total_bytes": (int(growth.last("mem_total"))
                           if growth.last("mem_total") else 0),
        "rss_bytes": (int(growth.last("rss"))
                      if growth.last("rss") else 0),
        "top_grower": top[0],
        "top_grower_bytes_per_event": rnd(top[1]),
        "component_slopes": {k: round(v, 2)
                             for k, v in sorted(
                                 comp_slopes.items(),
                                 key=lambda kv: -kv[1])},
        "debug_top_growers": (cap_dbg.get("top_growers") or [])[:5],
    }
    return leg


def retention():
    """`bench.py --retention`: the retention soak ledger. Legs and
    wall come from RETENTION_NS / RETENTION_WALL_S /
    RETENTION_SCRAPE_S (defaults n∈{3,8}, 60 s, 2 s) so CI can run the
    same shape it gates against the committed RETENTION_SMOKE.json.
    Emits one JSON payload; raw per-scrape rows land in
    RETENTION_OUT_DIR."""
    import tempfile

    ns = [int(x) for x in os.environ.get(
        "RETENTION_NS", "3,8").split(",") if x.strip()]
    wall_s = float(os.environ.get("RETENTION_WALL_S", "60"))
    scrape_s = float(os.environ.get("RETENTION_SCRAPE_S", "2.0"))
    out_dir = os.environ.get("RETENTION_OUT_DIR") or tempfile.mkdtemp(
        prefix="babble-retention-")
    os.makedirs(out_dir, exist_ok=True)
    ts_file = os.path.join(out_dir, "retention_timeseries.jsonl")
    payload = {
        "metric": "retention_soak",
        "unit": "bytes/event",
        "engine": "host",
        "store": "file",
        "runtime": _runtime_arg(),
        "wall_s_per_leg": wall_s,
        "timeseries_jsonl": ts_file,
        "cpus_effective": _cpus_effective(),
        "legs": {},
    }
    try:
        # The shared machine-speed yardstick (see bench_compare.py) —
        # only the ev/s context rows normalize by it; the byte slopes
        # are machine-independent ratios.
        calib_eps, _, _ = host_engine_events_per_sec(64, 5000)
        payload["host_events_per_s"] = round(calib_eps, 1)
        payload["host_events"] = 5000
    except Exception as exc:  # noqa: BLE001
        payload["calibration_error"] = str(exc)
    failures = 0
    for n in ns:
        log(f"retention leg n={n}: {wall_s:.0f}s wall, scrape every "
            f"{scrape_s:.1f}s")
        try:
            leg = retention_leg(n, wall_s, scrape_s, ts_file)
        except Exception as exc:  # noqa: BLE001
            payload[f"retention{n}_error"] = str(exc)
            failures += 1
            _emit(payload)
            continue
        payload["legs"][str(n)] = leg
        for k in ("events_per_s", "bytes_per_event",
                  "rss_slope_bytes_per_event",
                  "wal_slope_bytes_per_event", "top_grower"):
            if leg.get(k) is not None:
                payload[f"retention{n}_{k}"] = leg[k]
        log(f"  n={n}: {leg['events_per_s']:,.1f} ev/s, "
            f"{leg['bytes_per_event']} bytes/event, rss slope "
            f"{leg['rss_slope_bytes_per_event']}, wal slope "
            f"{leg['wal_slope_bytes_per_event']}, top grower "
            f"{leg['top_grower']}")
        _emit(payload)
    _emit(payload)
    return 1 if failures else 0


def measure():
    import jax

    from babble_tpu.devices import ensure_compile_cache
    from babble_tpu.ops.dag import synthetic_dag

    ensure_compile_cache()
    devs = jax.devices()
    log(f"backend={devs[0].platform} devices={len(devs)}x "
        f"{devs[0].device_kind}")

    ref_docker = 266.9  # reference docs/usage.rst:31-34 midpoint
    payload = {
        "metric": "consensus_events_per_s_n64",
        "value": 0.0,
        "unit": "events/s",
        "vs_baseline": 0.0,
        "baseline": "repo host engine, same topology (see host_* fields)",
        "ref_docker_events_per_s": ref_docker,
        "backend": devs[0].platform,
        "device_kind": devs[0].device_kind,
    }

    profile_dir = os.environ.get("BENCH_PROFILE_DIR")

    # -- stage 0: smoke ----------------------------------------------------
    log("stage smoke: n=8 e=256")
    dag, s_rank = synthetic_dag(8, 256, seed=0)
    best, _, _, n_cons, _ = time_pipeline(dag, s_rank, warm=1, reps=2)
    log(f"  smoke ok: {best * 1e3:.1f} ms, {n_cons} consensus events")
    payload["smoke_events_per_s"] = round(n_cons / best, 1)
    _emit(payload)

    # -- stage 1: headline n=64 e=50k -------------------------------------
    engine = "auto"
    if _budget_left() > 60:
        n, e = 64, 50_000
        log(f"stage headline: n={n} e={e}")
        t0 = time.monotonic()
        dag, s_rank = synthetic_dag(n, e, seed=1)
        log(f"  DAG gen {time.monotonic() - t0:.1f}s, "
            f"levels={dag.levels.shape}")
        engine = tune_engine(dag, s_rank)
        log(f"  tuned engine: {engine}")
        if profile_dir:
            jax.profiler.start_trace(profile_dir)
        best, med, times, n_cons, max_round = time_pipeline(
            dag, s_rank, reps=5, engine=engine)
        if profile_dir:
            jax.profiler.stop_trace()
        eps = n_cons / med
        log(f"  headline: median {med * 1e3:.1f} ms (best {best * 1e3:.1f}, "
            f"spread {min(times) * 1e3:.0f}-{max(times) * 1e3:.0f} ms) -> "
            f"{n_cons} consensus events ({eps:,.0f} ev/s median), "
            f"last round {max_round}")
        # The headline metric is the MEDIAN of 5 runs; best and the full
        # spread ride along.
        payload["value"] = round(eps, 1)
        payload["engine"] = engine
        payload["headline_ms"] = round(med * 1e3, 2)
        payload["headline_best_ms"] = round(best * 1e3, 2)
        payload["headline_best_events_per_s"] = round(n_cons / best, 1)
        payload["headline_spread_ms"] = [round(t * 1e3, 1) for t in times]
        payload["headline_consensus_events"] = n_cons
        _emit(payload)

    # -- stage 2: host-engine baseline, same topology ---------------------
    if _budget_left() > 60:
        # Size SWEEP: the device headline runs at e=50k but the host
        # engine would take minutes there, so the sweep measures how the
        # host's per-event cost moves with size — evidence for (not an
        # assumption of) the cross-size vs_baseline ratio. e must be
        # large enough that fame decides at n=64 (a round is ~700
        # events at this fan-out).
        sweep = {}
        for host_n_events in (2500, 5000, 10000):
            if sweep and _budget_left() < 2.5 * host_n_events / max(
                    min(sweep.values()), 1):
                break
            log(f"stage host baseline: n=64 e={host_n_events} "
                "(same topology family)")
            host_eps, host_done, _ = host_engine_events_per_sec(
                64, host_n_events)
            log(f"  host engine: {host_eps:,.0f} ev/s "
                f"({host_done} consensus)")
            sweep[host_n_events] = round(host_eps, 1)
        # vs_baseline stays pinned to the fixed e=5000 run so the ratio
        # is comparable across rounds; the sweep rides along as
        # evidence of how host cost moves with size.
        host_n_events = 5000 if 5000 in sweep else max(sweep)
        host_eps = sweep[host_n_events]
        payload["host_events_per_s"] = host_eps
        payload["host_events"] = host_n_events
        payload["host_sweep_events_per_s"] = sweep
        if payload["value"] and host_eps > 0:
            payload["vs_baseline"] = round(payload["value"] / host_eps, 1)
        _emit(payload)

    # -- stage 2b: sustained incremental ingest ---------------------------
    # The live-node metric: events arrive in sync-sized batches and each
    # batch re-runs consensus over the undecided tip (ops/incremental.py)
    # — the counterpart of the reference's per-sync RunConsensus
    # (node/core.go:277-296) rather than a one-shot full-DAG recompute.
    if _budget_left() > 120:
        from babble_tpu.ops.incremental import IncrementalEngine

        n, e_sus, bs = 64, 50_000, 4096
        log(f"stage sustained: n={n} e={e_sus} batch={bs} (pipelined)")
        dag_s, _ = synthetic_dag(n, e_sus, seed=3)
        eng = IncrementalEngine(
            n, capacity=65536, block=512, k_capacity=1024)
        import numpy as _np

        # PIPELINED driving — the same dispatch/collect overlap the
        # live node's consensus worker uses: append batch k+1 while
        # pass k computes on device, then collect k's commit delta and
        # dispatch k+1. Per-batch time is the HOST-BLOCKING wall
        # (append + collect wait + dispatch staging); the device
        # compute that overlapped the append no longer counts, which
        # is exactly the production hot path.
        phase_tot: dict = {}
        overlap_ns = 0
        prof_from = 3  # skip compile-warmup batches in the phase split

        def _harvest(b_i):
            nonlocal overlap_ns
            if b_i >= prof_from:
                for ph, ns in eng.phase_ns.items():
                    phase_tot[ph] = phase_tot.get(ph, 0) + ns
                overlap_ns += eng.last_overlap_ns

        t0 = time.perf_counter()
        per_batch = []
        pending = None
        b_i = 0
        k = 0
        while k < e_sus:
            hi = min(k + bs, e_sus)
            tb = time.perf_counter()
            eng.append_batch(
                dag_s.self_parent[k:hi], dag_s.other_parent[k:hi],
                dag_s.creator[k:hi], dag_s.index[k:hi], dag_s.coin[k:hi],
                _np.arange(k, hi))
            if pending is not None:
                eng.collect(pending)
                _harvest(b_i)
            pending = eng.dispatch()
            per_batch.append(time.perf_counter() - tb)
            b_i += 1
            k = hi
        if pending is not None:
            eng.collect(pending)
            _harvest(b_i)
        # Drain appends staged during the final in-flight pass.
        pending = eng.dispatch()
        if pending is not None:
            eng.collect(pending)
        total = time.perf_counter() - t0
        if e_sus % bs:  # final partial batch would skew the per-batch rate
            per_batch = per_batch[:-1]
        half = per_batch[len(per_batch) // 2:]
        steady = float(_np.median(half))
        log(f"  sustained: {total:.1f}s total ({e_sus / total:,.0f} ev/s), "
            f"steady {bs / steady:,.0f} ev/s "
            f"(per-batch spread {min(half):.2f}-{max(half):.2f}s), "
            f"{int((eng.rr[:e_sus] >= 0).sum())} consensus")
        payload["sustained_events_per_s"] = round(e_sus / total, 1)
        payload["sustained_steady_events_per_s"] = round(bs / steady, 1)
        payload["sustained_steady_spread_s"] = [
            round(min(half), 3), round(max(half), 3)]
        payload["sustained_batch"] = bs

        # Phase split of the pipelined loop: host-blocking ns per
        # phase (timers NOT synced — async dispatches only charge
        # their enqueue). The device->host trio (c_pull + coords +
        # fd_fold) is the share the tentpole targets: with the delta
        # pull overlapped it should be a small minority of pass wall.
        if phase_tot:
            # c_pull_wait/xfer are a SPLIT of c_pull, not siblings —
            # keep them out of the share denominator.
            _sub = ("c_pull_wait", "c_pull_xfer")
            top_t = {ph: ns for ph, ns in phase_tot.items()
                     if ph not in _sub}
            tot_ns = sum(top_t.values())
            shares = {ph: round(ns / tot_ns, 3)
                      for ph, ns in sorted(top_t.items())}
            bounding = max(top_t, key=top_t.get)
            pull_share = (shares.get("c_pull", 0) + shares.get("coords", 0)
                          + shares.get("fd_fold", 0))
            log(f"  phase split: " + ", ".join(
                f"{ph} {sh:.0%}" for ph, sh in shares.items())
                + f" -> bounded by {bounding}; "
                f"pull share {pull_share:.0%}, "
                f"overlap {overlap_ns / 1e9:.1f}s")
            payload["sustained_phase_share"] = shares
            payload["sustained_bounding_phase"] = bounding
            payload["sustained_pull_share"] = round(pull_share, 3)
            payload["sustained_overlap_s"] = round(overlap_ns / 1e9, 2)
            if phase_tot.get("c_pull"):
                # Wait (device still computing) vs xfer (D2H copy) —
                # the attribution that says whether c_pull needs a
                # faster kernel or a smaller pull.
                payload["sustained_c_pull_split"] = {
                    "wait": round(phase_tot.get("c_pull_wait", 0)
                                  / phase_tot["c_pull"], 3),
                    "xfer": round(phase_tot.get("c_pull_xfer", 0)
                                  / phase_tot["c_pull"], 3)}

        _emit(payload)

    # -- stage 2c: the real gossiping node --------------------------------
    # 4 live nodes (threads, inmem transport, per-event ECDSA, the full
    # sync protocol) — the apples-to-apples number against the
    # reference's 4-node docker steady state (265.53-268.27 ev/s,
    # reference docs/usage.rst:31-34). Two rows: the host engine (the
    # like-for-like configuration — 4 independent consensus engines on
    # one machine, as the reference runs), and the TPU engine, where
    # all 4 nodes time-share ONE chip — honest, but hardware-limited in
    # a way a per-validator accelerator deployment is not.
    if os.environ.get("BENCH_SKIP_NODE") != "1":
        if _budget_left() > 180:
            node_eps, node_ph = node_testnet_events_per_sec(
                engine="host", warm_s=30.0, window_s=30.0)
            log(f"  4-node --engine host testnet: {node_eps:,.1f} "
                f"committed events/s (ref docker: {ref_docker})")
            payload["node_events_per_s"] = round(node_eps, 1)
            payload["node_vs_ref_docker"] = round(
                node_eps / ref_docker, 2)
            payload["node_phase_share"] = node_ph.get("phase_share")
            payload["node_ingest_phase_share"] = node_ph.get(
                "ingest_phase_share")
            payload["commit_latency_p50_ms"] = node_ph.get(
                "commit_latency_p50_ms")
            payload["commit_latency_p99_ms"] = node_ph.get(
                "commit_latency_p99_ms")
            _emit(payload)
        if _budget_left() > 150:
            # Durable-path A/B: the same host testnet on WAL-backed
            # FileStores. store_commit_share = fraction of node
            # phase wall inside sqlite COMMITs; the events/s delta
            # vs node_events_per_s is the full durable overhead.
            file_eps, file_ph = node_testnet_events_per_sec(
                engine="host", warm_s=30.0, window_s=30.0,
                store="file")
            log(f"  4-node --engine host --store file testnet: "
                f"{file_eps:,.1f} committed events/s "
                f"(store_commit_share "
                f"{file_ph.get('store_commit_share')})")
            payload["node_file_events_per_s"] = round(file_eps, 1)
            payload["store_commit_share"] = file_ph.get(
                "store_commit_share")
            _emit(payload)
        if _budget_left() > 520:
            # The warm gate shrank 6000 -> 2500 committed events:
            # engine prewarm compiles the kernel ladder at node
            # construction and the persistent compile cache covers
            # restarts, so the old multi-thousand-event drift of
            # window-shape compiles is mostly gone.
            node_eps, node_ph = node_testnet_events_per_sec(
                engine="tpu", warm_s=180.0, window_s=40.0,
                warm_gate_events=2500, windows=3)
            log(f"  4-node --engine tpu testnet (one chip): "
                f"{node_eps:,.1f} committed events/s; "
                f"phases {node_ph}")
            payload["node_tpu_events_per_s"] = round(node_eps, 1)
            payload["node_tpu_phase_share"] = node_ph.get(
                "phase_share")
            payload["node_tpu_engine_phase_share"] = node_ph.get(
                "engine_phase_share")
            payload["node_tpu_engine_pull_share"] = node_ph.get(
                "engine_pull_share")
            payload["node_tpu_engine_overlap_s"] = node_ph.get(
                "engine_overlap_s")
            _emit(payload)
        # 16 validators on one machine — 4x the reference's published
        # deployment size, host engine (16 independent engines).
        if _budget_left() > 150:
            node_eps, _ = node_testnet_events_per_sec(
                engine="host", n_nodes=16, warm_s=45.0, window_s=30.0,
                interval=1.0)
            log(f"  16-node --engine host testnet: {node_eps:,.1f} "
                f"committed events/s")
            payload["node16_events_per_s"] = round(node_eps, 1)
            # Machine-tracked cluster-scaling trend (node{4,16}
            # here, node{3,16} in the smoke payload): the ledger
            # charts whether per-node throughput scales out or
            # collapses with cluster size.
            if "node_events_per_s" in payload:
                payload["node_scaling_events_per_s"] = {
                    "4": payload["node_events_per_s"],
                    "16": round(node_eps, 1)}
            _emit(payload)

    # -- stage 3: north star n=1024 e=100k --------------------------------
    if _budget_left() > 300:
        n, e = 1024, 100_000
        log(f"stage northstar: n={n} e={e}")
        t0 = time.monotonic()
        dag, s_rank = synthetic_dag(n, e, seed=2)
        log(f"  DAG gen {time.monotonic() - t0:.1f}s, "
            f"levels={dag.levels.shape}")
        # Engine choice flips with n (the frontier sweep's trip
        # count is the round count, which shrinks as n grows), so
        # re-tune at this size instead of reusing the headline's.
        engine_ns = tune_engine(dag, s_rank)
        log(f"  tuned northstar engine: {engine_ns}")
        best, med, times, n_cons, max_round = time_pipeline(
            dag, s_rank, warm=1, reps=3, engine=engine_ns)
        eps = n_cons / med
        log(f"  northstar: median {med * 1e3:.1f} ms "
            f"(spread {min(times) * 1e3:.0f}-{max(times) * 1e3:.0f}) -> "
            f"{n_cons} consensus ({eps:,.0f} ev/s), "
            f"last round {max_round}")
        payload["northstar_events_per_s"] = round(eps, 1)
        payload["northstar_best_events_per_s"] = round(n_cons / best, 1)
        payload["northstar_spread_ms"] = [
            round(t * 1e3, 1) for t in times]
        payload["northstar_n"] = n
        payload["northstar_events"] = e
        _emit(payload)

        # North-star INCREMENTAL: the engine a live `--engine tpu`
        # node actually drives (ops/incremental.py), fed the same
        # DAG in sync-sized batches — the validated at-scale number
        # VERDICT r3 asked for (run on the real chip, value-pulling
        # every sync).
        if _budget_left() > 240:
            from babble_tpu.node.core import engine_sizes
            from babble_tpu.ops.incremental import IncrementalEngine
            import numpy as _np

            bs_ns = 4096
            log(f"stage northstar incremental: n={n} e={e} "
                f"batch={bs_ns}")
            # Presized and fed as a node presizes and feeds it; the
            # loop chip_smoke.py checks.
            eng = IncrementalEngine(n, **engine_sizes(n))
            t0 = time.perf_counter()
            per_b = []
            tb = t0
            for _ in eng.feed(dag, bs_ns):
                now = time.perf_counter()
                per_b.append(now - tb)
                tb = now
            total_ns = time.perf_counter() - t0
            half = per_b[len(per_b) // 2:]
            steady_ns = float(_np.median(half))
            n_cons_inc = int((eng.rr[:e] >= 0).sum())
            log(f"  northstar incremental: {total_ns:.1f}s "
                f"({e / total_ns:,.0f} ev/s), steady "
                f"{bs_ns / steady_ns:,.0f} ev/s, "
                f"{n_cons_inc} consensus")
            payload["northstar_incremental_events_per_s"] = round(
                e / total_ns, 1)
            payload["northstar_incremental_steady_events_per_s"] = (
                round(bs_ns / steady_ns, 1))
            _emit(payload)

        # Honest wall-clock multiple at this scale (BASELINE.md
        # driver target: >=100x at n=1024/100k): the host engine
        # reaches no consensus below ~3n events per round, so its
        # per-event processing rate (insert + consensus pass) over
        # a 2k-event prefix is measured and extrapolated to the
        # full run — labeled as such.
        if _budget_left() > 120:
            host_e = 2000
            log(f"stage northstar host extrapolation: n={n} e={host_e}")
            # only the insert+consensus span counts (key generation
            # and event signing setup are excluded on both sides)
            _, _, host_dt = host_engine_events_per_sec(n, host_e)
            host_rate = host_e / host_dt
            extrapolated = e / host_rate
            payload["northstar_host_rate_events_per_s"] = round(
                host_rate, 1)
            payload["northstar_host_wall_extrapolated_s"] = round(
                extrapolated, 1)
            payload["northstar_wall_speedup_vs_host"] = round(
                extrapolated / best, 1)
            log(f"  host rate {host_rate:.1f} ev/s -> extrapolated "
                f"{extrapolated:,.0f}s vs device {best:.1f}s "
                f"({extrapolated / best:,.0f}x)")
            _emit(payload)

        # vs-Go calibration (BASELINE.json's target names Go, not
        # Python): build and run the C++ conservative stand-in for
        # the reference engine's data path (cpp/ref_model_bench.cc
        # — flat int-indexed storage, no GC, no signatures, fame
        # and FindOrder omitted; every choice makes it FASTER than
        # real Go), and report the resulting LOWER bound on the
        # device-vs-Go wall-clock multiple. The Python-host
        # extrapolation above brackets it from the other side.
        if _budget_left() > 120:
            src = os.path.join(REPO, "cpp", "ref_model_bench.cc")
            binp = os.path.join(REPO, "cpp", "ref_model_bench")
            stale = (not os.path.exists(binp)
                     or os.path.getmtime(binp)
                     < os.path.getmtime(src))
            if stale:
                subprocess.run(
                    ["g++", "-O3", "-march=native", "-o", binp,
                     src], check=True, timeout=120)
            out = subprocess.run(
                [binp, str(n), str(e)], capture_output=True,
                timeout=1200, check=True)
            model = json.loads(out.stdout)
            model_wall = float(model["wall_s"])
            vs_go_min = model_wall / best
            payload["vs_go_model_wall_s"] = round(model_wall, 2)
            payload["vs_go_estimated_min"] = round(vs_go_min, 1)
            payload["vs_go_basis"] = (
                "lower bound: wall of a C++ reimplementation "
                "of the reference insert+DivideRounds data "
                "path (cpp/ref_model_bench.cc), strictly "
                "faster than Go (no GC/strings/signatures, "
                "fame+order omitted), vs the device one-shot")
            log(f"  vs-Go: C++ model {model_wall:,.1f}s vs "
                f"device {best:.1f}s -> >= {vs_go_min:,.0f}x "
                f"(conservative lower bound)")
            _emit(payload)

    payload["wall_s"] = round(time.monotonic() - _T0, 1)
    _emit(payload)


if __name__ == "__main__":
    if "--node-smoke" in sys.argv:
        sys.exit(node_smoke())
    elif "--trace-overhead" in sys.argv:
        sys.exit(trace_overhead())
    elif "--health-overhead" in sys.argv:
        sys.exit(health_overhead())
    elif "--gossip-overhead" in sys.argv:
        sys.exit(gossip_overhead())
    elif "--profile-overhead" in sys.argv:
        sys.exit(profile_overhead())
    elif "--verify-bench" in sys.argv:
        sys.exit(verify_bench())
    elif "--ingress-overhead" in sys.argv:
        sys.exit(ingress_overhead())
    elif "--loadgen" in sys.argv:
        sys.exit(loadgen())
    elif "--soak" in sys.argv:
        sys.exit(gossip_soak())
    elif "--capacity-overhead" in sys.argv:
        sys.exit(capacity_overhead())
    elif "--retention" in sys.argv:
        sys.exit(retention())
    else:
        sys.exit(main())
