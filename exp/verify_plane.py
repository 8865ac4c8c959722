"""Verify-plane probe: microseconds per event of the device ECDSA kernel
behind `device_verify` (ops/p256.verify_batch) against the host verify
pool (node.ingest.verify_events), on the signed sync batches of the
v1024-ingest cell (benchmark/signed_history.py), and the compiles each
batch size costs the kernel.

    python3 exp/verify_plane.py [--validators 1024] [--batches 4] [--sync 500]

Prints one JSON line. Run it on the chip: off a TPU the kernel runs on
the CPU backend, which says nothing of the chip."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Sizes a 500-event sync hands verify_events: the whole sync, or what
# dedup-before-verify leaves of it.
SIZES = (500, 499, 300, 64, 63, 8, 1)


def _time(fn, events) -> float:
    for ev in events:
        ev._sig_ok = None
    t0 = time.perf_counter()
    fn(events)
    dt = time.perf_counter() - t0
    assert all(ev._sig_ok for ev in events), "a good signature failed"
    return dt / len(events) * 1e6


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--validators", type=int, default=1024)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--sync", type=int, default=500)
    ap.add_argument("--seed", type=int, default=2718281828)
    args = ap.parse_args()

    import jax
    from jax import monitoring

    from babble_tpu import crypto
    from babble_tpu.devices import ensure_compile_cache
    from babble_tpu.hashgraph import Hashgraph, InmemStore
    from babble_tpu.node.ingest import resolve_verify_workers, verify_events
    from babble_tpu.ops import p256
    from benchmark.signed_history import generate

    ensure_compile_cache()
    compiles = [0]

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            compiles[0] += 1

    def on_duration(name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    hist = generate(args.validators, max(args.validators, args.batches
                                         * args.sync), 0, args.seed,
                    args.sync)
    hg = Hashgraph(hist.participants, InmemStore(hist.participants, 1 << 20))
    workers = resolve_verify_workers(-1)
    out = {"device": str(jax.devices()[0].device_kind),
           "backend": crypto.BACKEND, "cpu_count": os.cpu_count(),
           "pool_workers": workers, "host_pool_us_per_event": [],
           "host_inline_us_per_event": [], "device_us_per_event": [],
           "compiles_by_size": {}}
    for k, batch in enumerate(hist.batches[:args.batches]):
        events = hg.read_wire_batch(batch)
        if k == 0:
            for m in SIZES:
                part = events[:m]
                c0, t0 = compiles[0], time.perf_counter()
                p256.verify_batch([ev.body.creator for ev in part],
                                  [ev.body.hash() for ev in part],
                                  [(int(ev.r), int(ev.s)) for ev in part])
                out["compiles_by_size"][m] = [compiles[0] - c0,
                                              time.perf_counter() - t0]
        out["host_pool_us_per_event"].append(
            _time(lambda evs: verify_events(evs, workers), events))
        out["host_inline_us_per_event"].append(
            _time(lambda evs: verify_events(evs, 1), events))
        out["device_us_per_event"].append(
            _time(lambda evs: verify_events(evs, workers, True), events))
        for ev in events:
            hg.insert_event(ev, False)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
