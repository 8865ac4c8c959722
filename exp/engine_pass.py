"""Standalone engine pass-cost probe at live-node shapes.
Feeds a realistic n-node gossip DAG to IncrementalEngine in sync-sized
batches and reports synced per-phase costs per pass, for different
k_capacity presizes and batch sizes."""
import os, sys, time
sys.path.insert(0, "/root/repo")
import numpy as np

def main(n=4, e_tot=20000, bs=256, cap=65536, kcap=65536, timers=True):
    from babble_tpu.devices import ensure_compile_cache
    ensure_compile_cache()
    from babble_tpu.ops.dag import synthetic_dag
    from babble_tpu.ops.incremental import IncrementalEngine
    dag, _ = synthetic_dag(n, e_tot, seed=5)
    if timers:
        os.environ["BABBLE_ENGINE_TIMERS"] = "1"
    eng = IncrementalEngine(n, capacity=cap, block=512, k_capacity=kcap)
    k = 0
    per = []
    while k < e_tot:
        hi = min(k + bs, e_tot)
        eng.append_batch(dag.self_parent[k:hi], dag.other_parent[k:hi],
                         dag.creator[k:hi], dag.index[k:hi], dag.coin[k:hi],
                         np.arange(k, hi, dtype=np.int64) * 1000 + 1_700_000_000_000_000_000)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        per.append((dt, dict(eng.phase_ns)))
        k = hi
    total = sum(d for d, _ in per)
    print(f"   total run-pass wall: {total:.1f}s")
    # steady state = last half
    half = per[len(per) // 2:]
    med = np.median([d for d, _ in half])
    print(f"[n={n} cap={cap} kcap={kcap} bs={bs}] passes={len(per)} "
          f"steady median {med*1e3:.1f} ms/pass -> {bs/med:,.0f} ev/s")
    agg = {}
    for _, ph in half:
        for name, ns in ph.items():
            agg.setdefault(name, []).append(ns / 1e6)
    for name, vals in sorted(agg.items(), key=lambda kv: -np.median(kv[1])):
        print(f"   {name:12s} median {np.median(vals):7.1f} ms  max {max(vals):7.1f}")
    cons = int((eng.rr[:e_tot] >= 0).sum())
    print(f"   consensus events: {cons}")

if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--e", type=int, default=20000)
    ap.add_argument("--bs", type=int, default=256)
    ap.add_argument("--cap", type=int, default=65536)
    ap.add_argument("--kcap", type=int, default=65536)
    ap.add_argument("--no-timers", action="store_true")
    a = ap.parse_args()
    main(a.n, a.e, a.bs, a.cap, a.kcap, not a.no_timers)
