"""Device-idle time per pass attributed to the driver's `bench_sync`
span (trace_reduce: each idle gap goes to the `bench_*` span that
overlaps it most): the chip waiting on Core.sync's wire decode, verify
and insert."""


def read(run):
    ts, passes = run.trace_summary, run.counters.get("passes")
    if ts is None or not passes:
        return None
    return ts.gaps_s.get("bench_sync", 0.0) / passes * 1e3
