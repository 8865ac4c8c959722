"""Round assignment as a frontier sweep — sequential in the number of
consensus rounds, not DAG depth: one step per round (~72 at n=64/e=50k
against 2,709 DAG levels, ~E/(3n) in general), round numbers being
determined by witness frontiers.

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


def make_round_step(chain_la, chain_rbase, chain_len, la, fd, rbase, chain,
                    *, n, sm):
    """One frontier round: step(rho, wt_prev, fr_prev) ->
    (wt_row, fr_unclamped, fr_clamped, any_candidate), run by the
    single-dispatch while-loop sweep below.

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


def frontier_sweep_impl(chain_la, chain_rbase, chain_len, la, fd, rbase,
                        chain, wt_tab, fr_tab, wt_prev, fr_prev, t0,
                        rho_min, *, n, sm, rcap):
    """Single-dispatch frontier: run rounds rho_min+t for t in [t0, rcap)
    under a device while-loop until no chain has a candidate, writing
    into the [rcap, n] tables (rows >= t0 are overwritten; rows < t0 are
    the frozen warm-start prefix). Returns (wt_tab, fr_tab, t_end);
    t_end == rcap with activity still pending means the caller must
    re-run with a larger bucket.

    Unjitted: the fused kernel (ops/incremental.py) calls it inside its
    trace with a lazy row view `fd` (incremental._FdRows: fd[ids] ->
    [len(ids), n]) instead of a dense [E, n] array."""
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
