"""Chunks of the fused kernel's round-received sweep per pass, from the
engine's per-pass counts (IncrementalEngine.pass_counts, summed by the
mesh engine driver): 1 while the undecided window fits one chunk, as
in every pass of the v4096-mesh-replay job; it rises only if a window
outgrows the chunk."""


def read(run):
    passes, chunks = run.counters.get("passes"), run.counters.get("rr_chunks")
    if not passes or chunks is None:
        return None
    return chunks / passes
