"""Round assignment as a frontier sweep — sequential in the number of
consensus rounds, not DAG depth.

Replaces kernels.compute_rounds' per-level wavefront (2,709 sequential
levels at n=64/e=50k) with one step per round (~72 at the same size,
~E/(3n) in general): round numbers are determined by witness frontiers.

Theory (mirrors reference hashgraph.go:211-339, DivideRounds 616-646):
round(x) = max over ancestors-incl-self y of local(y), where
local(y) = root_round[creator(y)]+1 when y has a missing parent
(Root fallback + RoundInc's pr_root branch), and local(y) = q+1 when y
strongly sees >= supermajority witnesses of round q. Because
lastAncestors are monotone along descent, strongly-seeing is inherited
by descendants, which gives the exact frontier recurrence proved in
the docstrings below:

  round(x) >= rho  <=>  rbase(x) >= rho  OR  x strongly sees >= sm
                        witnesses of round rho-1

with rbase the ancestor-max of the root contribution (computed by
ops/closure.py). Along each creator chain both conditions are monotone
in chain position, so the first position with round >= rho is a closed
form: a compare-and-count for rbase, and for strongly-see a vectorized
binary search over positions (the per-position strongly-seen-witness
count is monotone along chains because chain lastAncestors are
sorted — see make_round_step). A one-shot skip-correction then removes
candidates whose round
exceeds rho (round skips happen when a peer rejoins after missing
rounds): a candidate y is round->rho iff it neither carries
rbase >= rho+1 nor strongly sees >= sm of the candidate row itself —
exact because a true round-rho witness cannot strongly see any
higher-round candidate (that would lift its own round), and a
higher-round candidate strongly sees >= sm true round-rho witnesses
(all of which are candidates).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .kernels import INT32_MAX

# Working-set bound for the per-round [chains, coords, witnesses]
# searchsorted cube: chains are processed in chunks so each materialized
# [cc, n, n] block stays under ~64M elements (sized to trade kernel
# count for VMEM pressure; the full cube would be 4.3 GB at n=1024).
# Whether fewer, larger kernels win on the v5e chip is not measured.
_CUBE_ELEMS = 1 << 26


def _chain_chunks(n: int) -> int:
    cc = max(min(_CUBE_ELEMS // max(n * n, 1), n), 1)
    while n % cc:
        cc -= 1
    return n // cc


@functools.partial(jax.jit, static_argnames=("n",))
def build_chain_tables(la, rbase, chain, *, n):
    """chain_la[c, k, i] = la[chain[c, k], i] (INT32_MAX beyond the
    chain, so searchsorted targets land past real entries);
    chain_rbase[c, k] likewise. chain: [n, K] event ids, -1 pad."""
    valid = chain >= 0
    safe = jnp.where(valid, chain, 0)
    chain_la = jnp.where(valid[:, :, None], la[safe], INT32_MAX)
    chain_rbase = jnp.where(valid, rbase[safe], INT32_MAX)
    return chain_la, chain_rbase


def make_round_step(chain_la, chain_rbase, chain_len, la, fd, rbase, chain,
                    *, n, sm):
    """One frontier round: step(rho, wt_prev, fr_prev) ->
    (wt_row, fr_unclamped, fr_clamped, any_candidate). Shared by the
    chunked host driver below and the single-dispatch while-loop sweep
    (used by ops/incremental.py).

    k2 is a vectorized binary search: because per-witness strongly-see
    indicators are monotone along a chain, "sm-th smallest over w of
    the per-w first position" equals "first position whose event
    strongly sees >= sm witnesses" — so log2(K) probe steps, each one
    dense compare-and-count over a [cc, w, i] chunked cube, replace the
    earlier per-(c, i, w) lookup + double sort (1M length-K sorts per
    round at n=1024, and an XLA fusion of the gather+sort composition
    that once faulted on a TPU)."""
    k_cap = chain_la.shape[1]
    cc = n // _chain_chunks(n)
    probes = max(int(np.ceil(np.log2(max(k_cap, 2)))), 1) + 1

    def step(rho, wt_prev, fr_prev):
        # k1: first chain position whose propagated root contribution
        # reaches rho = #{k : chain_rbase[c, k] < rho} (monotone along
        # the chain; pads are INT32_MAX and never count).
        k1 = (chain_rbase < rho).sum(1, dtype=jnp.int32)

        # k2: first position strongly seeing >= sm of wt_prev.
        wt_valid = wt_prev >= 0
        fdw = fd[jnp.where(wt_valid, wt_prev, 0)]  # [w, i]
        fdw_row = jnp.where(wt_valid[:, None], fdw, INT32_MAX)

        def sees_sm(mid):
            """ok[c] = chain_la[c, mid[c]] strongly sees >= sm valid
            witnesses (positions beyond the chain are INT32_MAX rows
            and are guarded by the callers' chain_len clamp)."""
            x_row = chain_la[jnp.arange(n), jnp.clip(mid, 0, k_cap - 1)]

            def chunk(g, acc):
                c0 = g * cc
                x_g = lax.dynamic_slice(x_row, (c0, 0), (cc, n))
                ss = (x_g[:, None, :] >= fdw_row[None, :, :]).sum(-1) >= sm
                cnt = ss.sum(-1, dtype=jnp.int32)  # [cc]
                return lax.dynamic_update_slice(acc, cnt, (c0,))

            cnt = lax.fori_loop(
                0, n // cc, chunk, jnp.zeros((n,), dtype=jnp.int32))
            return cnt >= sm

        def probe(_, carry):
            lo, hi = carry
            mid = (lo + hi) // 2
            ok = sees_sm(mid) & (mid < hi)
            hi = jnp.where(ok, mid, hi)
            lo = jnp.where(ok | (lo >= hi), lo, mid + 1)
            return lo, hi

        # search in [0, chain_len]; hi == chain_len means no position
        lo0 = jnp.zeros((n,), jnp.int32)
        _, k2 = lax.fori_loop(0, probes, probe, (lo0, chain_len))
        k2 = jnp.where(k2 < chain_len, k2, INT32_MAX)

        fr = jnp.maximum(jnp.minimum(k1, k2), fr_prev)
        cand_valid = fr < chain_len
        fr_c = jnp.where(cand_valid, fr, k_cap)
        cand = jnp.where(
            cand_valid, chain[jnp.arange(n), jnp.clip(fr, 0, k_cap - 1)], -1)

        # Skip correction: candidate's true round exceeds rho?
        safe = jnp.where(cand_valid, cand, 0)
        la_c = la[safe]
        fd_c = fd[safe]
        ss_cc = ((la_c[:, None, :] >= fd_c[None, :, :]).sum(-1) >= sm)
        ss_cc = ss_cc & cand_valid[None, :] & cand_valid[:, None]
        rb_c = jnp.where(cand_valid, rbase[safe], -1)
        skip = (rb_c >= rho + 1) | (ss_cc.sum(-1) >= sm)
        wt_row = jnp.where(cand_valid & ~skip, cand, -1)
        return wt_row, fr, fr_c, cand_valid.any()

    return step


@functools.partial(jax.jit, static_argnames=("n", "sm", "rc"))
def frontier_chunk(chain_la, chain_rbase, chain_len, la, fd, rbase, chain,
                   wt_prev, fr_prev, rho0, *, n, sm, rc):
    """Advance the witness frontier by `rc` rounds starting at rho0.

    wt_prev: [n] witness event ids of round rho0-1 (-1 none);
    fr_prev: [n] first chain position with round >= rho0-1.
    Returns (wt_out[rc, n], fr_out[rc, n], active[rc], wt_last, fr_last).
    """
    k_cap = chain_la.shape[1]
    step = make_round_step(chain_la, chain_rbase, chain_len, la, fd, rbase,
                           chain, n=n, sm=sm)

    def round_step(t, carry):
        wt_prev, fr_prev, wt_out, fr_out, act_out = carry
        wt_row, fr, fr_c, any_cand = step(rho0 + t, wt_prev, fr_prev)
        wt_out = wt_out.at[t].set(wt_row)
        fr_out = fr_out.at[t].set(fr_c)
        act_out = act_out.at[t].set(any_cand)
        return wt_row, fr, wt_out, fr_out, act_out

    wt_out = jnp.full((rc, n), -1, dtype=jnp.int32)
    fr_out = jnp.full((rc, n), k_cap, dtype=jnp.int32)
    act_out = jnp.zeros((rc,), dtype=jnp.bool_)
    wt_last, fr_last, wt_out, fr_out, act_out = lax.fori_loop(
        0, rc, round_step, (wt_prev, fr_prev, wt_out, fr_out, act_out))
    return wt_out, fr_out, act_out, wt_last, fr_last


def frontier_sweep_impl(chain_la, chain_rbase, chain_len, la, fd, rbase,
                        chain, wt_tab, fr_tab, wt_prev, fr_prev, t0,
                        rho_min, *, n, sm, rcap):
    """Single-dispatch frontier: run rounds rho_min+t for t in [t0, rcap)
    under a device while-loop until no chain has a candidate, writing
    into the [rcap, n] tables (rows >= t0 are overwritten; rows < t0 are
    the frozen warm-start prefix). Returns (wt_tab, fr_tab, t_end);
    t_end == rcap with activity still pending means the caller must
    re-run with a larger bucket.

    Unjitted so callers already inside a trace can pass a lazy row-view
    `fd` (any object supporting fd[ids] -> [len(ids), n], e.g.
    incremental._FdRows) instead of a dense [E, n] array."""
    k_cap = chain_la.shape[1]
    step = make_round_step(chain_la, chain_rbase, chain_len, la, fd, rbase,
                           chain, n=n, sm=sm)

    def cond(carry):
        t, active, *_ = carry
        return (t < rcap) & active

    def body(carry):
        t, _, wt_prev, fr_prev, wt_tab, fr_tab = carry
        wt_row, fr, fr_c, any_cand = step(rho_min + t, wt_prev, fr_prev)
        wt_tab = lax.dynamic_update_slice(wt_tab, wt_row[None], (t, 0))
        fr_tab = lax.dynamic_update_slice(fr_tab, fr_c[None], (t, 0))
        return t + 1, any_cand, wt_row, fr, wt_tab, fr_tab

    t_end, _, _, _, wt_tab, fr_tab = lax.while_loop(
        cond, body, (t0, jnp.bool_(True), wt_prev, fr_prev, wt_tab, fr_tab))
    return wt_tab, fr_tab, t_end


frontier_sweep = functools.partial(jax.jit, static_argnames=(
    "n", "sm", "rcap"))(frontier_sweep_impl)


@functools.partial(jax.jit, static_argnames=("n",))
def rounds_from_frontier(frontier, creator, index, self_parent, rho_min, *, n):
    """Per-event rounds + witness flags from the frontier table.

    round(chain[c, k]) = rho_min - 1 + #{rows with frontier[., c] <= k};
    witness(x) = sits-on-root or round > round(self-parent)
    (reference hashgraph.go:265-282). creator/index/self_parent: [E]."""
    e = creator.shape[0]
    rows = (frontier[:, creator] <= index[None, :]).sum(0)  # [E]
    rounds = rho_min - 1 + rows.astype(jnp.int32)
    sp_safe = jnp.where(self_parent >= 0, self_parent, 0)
    wit = (self_parent < 0) | (rounds > rounds[sp_safe])
    return rounds, wit


def compute_frontier(la, rbase, fd, chain, chain_len, root_round,
                     *, n: int, sm: int, rc: int = 64,
                     view_chain_len: Optional[np.ndarray] = None,
                     ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Host driver: sweep rounds in chunks of rc until the frontier
    passes every chain's end. `view_chain_len` restricts to an
    ancestry-closed prefix view (per-peer simulation): coordinates from
    the full DAG stay exact for any closed view, so only the chain
    lengths change. Returns (wt[R, n] absolute-round-indexed,
    frontier[R', n], rho_min)."""
    chain_len_eff = chain_len if view_chain_len is None else view_chain_len
    chain_la, chain_rbase = build_chain_tables(la, rbase, chain, n=n)
    rho_min = int(root_round.min()) + 1

    wt_prev = jnp.full((n,), -1, dtype=jnp.int32)
    fr_prev = jnp.zeros((n,), dtype=jnp.int32)
    wt_rows, fr_rows = [], []
    rho0 = rho_min
    while True:
        wt_o, fr_o, act, wt_prev, fr_prev = frontier_chunk(
            chain_la, chain_rbase, chain_len_eff, la, fd, rbase, chain,
            wt_prev, fr_prev, jnp.int32(rho0), n=n, sm=sm, rc=rc)
        act_np = np.asarray(act)
        wt_rows.append(np.asarray(wt_o))
        fr_rows.append(np.asarray(fr_o))
        if not bool(act_np[-1]):
            break
        rho0 += rc
    wt_rel = np.concatenate(wt_rows, axis=0)
    fr_rel = np.concatenate(fr_rows, axis=0)
    active = (fr_rel < np.asarray(chain_len_eff)[None, :]).any(axis=1)
    # highest round with any event = last active row
    n_rounds = int(np.nonzero(active)[0][-1]) + 1 if active.any() else 0
    wt_rel = wt_rel[:n_rounds]
    fr_rel = fr_rel[:n_rounds]

    # Absolute-round-indexed witness table (rows 0..rho_min-1 empty),
    # matching the old kernels' contract for fame / round-received.
    r_abs = rho_min + n_rounds
    wt = np.full((max(r_abs, 1), n), -1, dtype=np.int32)
    if n_rounds:
        wt[rho_min:r_abs] = wt_rel
    return wt, fr_rel, rho_min
