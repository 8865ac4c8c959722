"""Fused end-to-end consensus pipeline: one jitted function from raw
DAG tensors to (rounds, witness flags, witness table, fame, round
received, consensus timestamps). This is the framework's flagship
compiled step — XLA fuses across the five kernels and a single dispatch
covers the whole reference pipeline DivideRounds -> DecideFame ->
FindOrder (reference node/core.go:277-296, hashgraph.go:616-858)."""

from __future__ import annotations

import functools

import jax

from . import kernels


@functools.partial(jax.jit, static_argnames=("n", "sm", "r"))
def _coordinates_and_rounds(
    self_parent, other_parent, creator, index, levels, chain, chain_len,
    root_round, valid_mask=None, *, n, sm, r,
):
    la = kernels.compute_last_ancestors(
        self_parent, other_parent, creator, index, levels, n=n
    )
    fd = kernels.compute_first_descendants(la, creator, index, chain, chain_len, n=n)
    rounds, wit, wt = kernels.compute_rounds(
        self_parent, other_parent, creator, index, la, fd, levels, root_round,
        valid_mask, n=n, sm=sm, r=r,
    )
    return la, fd, rounds, wit, wt


@functools.partial(jax.jit, static_argnames=("n", "sm", "r"))
def _fame_and_order(wt, la, fd, rounds, creator, index, coin, chain_rank,
                    valid_mask=None, *, n, sm, r):
    famous = kernels.decide_fame(wt, la, fd, index, coin, n=n, sm=sm, r=r)
    rr, cts = kernels.decide_round_received(
        rounds, wt, famous, la, fd, creator, index, chain_rank, valid_mask,
        n=n, r=r,
    )
    return famous, rr, cts


@functools.partial(jax.jit, static_argnames=("n", "sm", "r"))
def consensus_pipeline(
    self_parent,
    other_parent,
    creator,
    index,
    coin,
    levels,
    root_round,
    chain,
    chain_len,
    chain_rank,
    valid_mask=None,
    *,
    n: int,
    sm: int,
    r: int,
):
    la, fd, rounds, wit, wt = _coordinates_and_rounds(
        self_parent, other_parent, creator, index, levels, chain, chain_len,
        root_round, valid_mask, n=n, sm=sm, r=r,
    )
    famous, rr, cts = _fame_and_order(
        wt, la, fd, rounds, creator, index, coin, chain_rank, valid_mask,
        n=n, sm=sm, r=r,
    )
    return rounds, wit, wt, famous, rr, cts


def _round_bucket(max_round: int, bound: int) -> int:
    """Static round capacity for stage 2: next power of two above the
    observed max round (+2 headroom), bucketed to bound recompiles."""
    need = max_round + 3
    r = 8
    while r < need:
        r *= 2
    return min(r, bound)


def tight_round_bucket(rounds, bound: int) -> int:
    """The fame/round-received round capacity from observed rounds (one
    host round-trip): votes are O(r^2), so the observed max round — not
    the depth-derived static bound — sets the real cost. Shared by the
    one-shot engines, the factored view sim, and the sharded pipeline."""
    import numpy as np

    arr = np.asarray(rounds)
    max_round = int(arr.max()) if arr.size else 0
    return _round_bucket(max_round, bound)


def pad_famous(famous_small, bound: int, n: int):
    """Restore the [bound, n] famous-table contract: rounds beyond the
    tight bucket have no witnesses and stay UNDEFINED (== 0, which is
    what the zero padding encodes)."""
    import numpy as np

    famous = np.zeros((bound, n), dtype=np.int32)
    famous[: np.asarray(famous_small).shape[0]] = np.asarray(famous_small)
    return famous


def run_pipeline_wavefront(dag):
    """The original depth-sequential driver (one dispatch step per DAG
    level) — kept as a second oracle for kernel cross-validation."""
    import numpy as np

    n, sm, r_bound = dag.n, dag.super_majority, dag.max_rounds
    la, fd, rounds, wit, wt = _coordinates_and_rounds(
        dag.self_parent, dag.other_parent, dag.creator, dag.index, dag.levels,
        dag.chain, dag.chain_len, dag.root_round, n=n, sm=sm, r=r_bound,
    )
    r_small = tight_round_bucket(rounds if dag.e else np.zeros(0), r_bound)
    famous_small, rr, cts = _fame_and_order(
        wt[:r_small], la, fd, rounds, dag.creator, dag.index, dag.coin,
        dag.chain_rank, n=n, sm=sm, r=r_small,
    )
    return rounds, wit, wt, pad_famous(famous_small, r_bound, n), rr, cts


def _default_engine(n: int) -> str:
    """Hardware-adaptive default: the block-closure/round-frontier path
    trades FLOPs (dense boolean matmuls) for sequential trip count —
    the right trade on a TPU MXU, the wrong one on a host CPU where
    dispatch is cheap and FLOPs are scarce. The CPU therefore keeps the
    wavefront, as does n > 256 on TPU: on the v5e chip the wavefront is
    what chip_smoke.py checks at n=1024 against the live engine, and the
    closure engine at n=64; the closure engine at n=1024 has not been
    run on the chip."""
    import jax

    if jax.default_backend() in ("cpu",) or n > 256:
        return "wavefront"
    return "closure"


def run_pipeline(dag, block: int = 512, engine: str = "auto"):
    """Consensus pipeline driver over a DagTensors.

    engine="closure": trip counts scale with E/block + number-of-rounds,
    not DAG depth — coordinates from the block-closure kernel
    (ops/closure.py), rounds from the witness-frontier sweep
    (ops/frontier.py, one step per round), then fame / round-received at
    a tight round bound read from the frontier. engine="wavefront": the
    depth-sequential drivers. engine="auto" picks by backend
    (_default_engine). Output contracts are identical."""
    import numpy as np

    from . import closure, frontier

    if engine == "auto":
        engine = _default_engine(dag.n)
    if engine == "wavefront":
        return run_pipeline_wavefront(dag)

    n, sm = dag.n, dag.super_majority
    block = min(block, max(64, 1 << (dag.e - 1).bit_length())) if dag.e else 64
    la, rbase = closure.coordinates(dag, block=block)
    fd = kernels.compute_first_descendants(
        la, dag.creator, dag.index, dag.chain, dag.chain_len, n=n)
    wt_np, fr_rel, rho_min = frontier.compute_frontier(
        la, rbase, fd, dag.chain, dag.chain_len, dag.root_round, n=n, sm=sm)
    e = dag.e
    rounds, wit = frontier.rounds_from_frontier(
        fr_rel, dag.creator[:e], dag.index[:e], dag.self_parent[:e],
        rho_min, n=n)
    max_round = wt_np.shape[0] - 1
    r_bound = max(dag.max_rounds, max_round + 1)
    r_small = _round_bucket(max_round, r_bound)
    wt_small = np.full((r_small, n), -1, dtype=np.int32)
    wt_small[: min(r_small, wt_np.shape[0])] = wt_np[:r_small]
    famous_small, rr, cts = _fame_and_order(
        wt_small, la, fd, rounds, dag.creator, dag.index, dag.coin,
        dag.chain_rank, n=n, sm=sm, r=r_small,
    )
    wt = np.full((r_bound, n), -1, dtype=np.int32)
    wt[: wt_np.shape[0]] = wt_np
    return rounds, wit, wt, pad_famous(famous_small, r_bound, n), rr, cts
