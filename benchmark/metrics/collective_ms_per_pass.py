"""Device time of the collectives (all-gather, all-reduce,
reduce-scatter, collective-permute, all-to-all) per pass, the union of
their intervals on each device plane, averaged over the planes
(benchmark/collectives.py). Reads the kept trace of a traced run."""

from benchmark import collectives


def read(run):
    passes = run.counters.get("passes")
    total = collectives.of_run(run).get("total")
    if not passes or total is None:
        return None
    return total / passes * 1e3
