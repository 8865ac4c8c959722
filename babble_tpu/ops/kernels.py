"""The fame stage of the fused consensus kernel (ops/incremental.py).

Pure functions over int32 tensors: a [r, n] witness table (event ids,
-1 empty) and per-event coordinate rows. Semantics mirror reference
hashgraph/hashgraph.go DecideFame (649-730); the computation is a
vote-matrix contraction over voting rounds instead of nested vote
loops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

INT32_MAX = 2**31 - 1
FAME_UNDEFINED = 0
FAME_TRUE = 1
FAME_FALSE = 2


def chunk_width(w: int, row_elems: int, budget: int) -> int:
    """Width of a processing chunk such that chunk*row_elems stays
    under `budget` elements. Callers iterate ceil(w/wc) chunks with
    CLAMPED dynamic slices (the final chunk re-reads/rewrites a few
    overlapping rows, which is idempotent) — no divisibility demanded,
    so a prime width cannot collapse the chunk to 1."""
    return max(min(budget // max(row_elems, 1), w), 1)


def _bcast_budget() -> int:
    """Chunk budget for broadcast-COMPARE intermediates: the CPU
    backend materializes them (tight budget), the TPU streams the
    fused compare+reduce (loose budget, fewer sequential steps)."""
    return (1 << 26) if jax.default_backend() == "cpu" else (1 << 28)


def strongly_see_counts_chunked(la_rows, fd_p, *, n):
    """ss_cnt[y, x] = #{k : la_rows[y, k] >= fd_p[x, k]} — the pairwise
    strongly-see tally, chunked over the voter axis so the [Y, n, n]
    broadcast stays bounded where the backend materializes it."""
    y_n = la_rows.shape[0]
    yc = chunk_width(y_n, n * n, _bcast_budget())

    def ss_yc(g, acc):
        y0 = g * yc  # clamped on the final chunk (idempotent)
        la_g = lax.dynamic_slice(la_rows, (y0, 0), (yc, n))
        cnt_g = (la_g[:, None, :] >= fd_p[None, :, :]).sum(
            -1, dtype=jnp.int32)
        return lax.dynamic_update_slice(acc, cnt_g, (y0, 0))

    return lax.fori_loop(
        0, -(-y_n // yc), ss_yc, jnp.zeros((y_n, n), jnp.int32))


def fame_steps(wt):
    """The vote steps decide_fame_impl runs over the window `wt` [r, n]:
    one per voting row j in [1, last], `last` the window's last row that
    holds a witness (0 when none past row 0 does). A step whose voter
    row is empty leaves `famous` as it was, so the steps past `last`
    decide nothing; an empty row below `last` (a round skip) still
    zeroes the next row's tallies, so the sweep runs through it."""
    live = (wt >= 0).any(1)
    return jnp.max(jnp.where(live, jnp.arange(wt.shape[0]), 0))


def decide_fame_impl(wt, la, fd, index, coin, *, n, sm, r):
    """Virtual voting — reference DecideFame (hashgraph.go:649-730).

    One sweep over voting rounds j, up to the window's last row that
    holds a witness (fame_steps): round-j witnesses vote on every
    earlier witness slot (rx, cx). First-round votes are plain `see`
    (ancestry); later rounds take the majority over the round-(j-1)
    witnesses they strongly see, deciding fame on a >= 2n/3+1 tally in
    normal rounds and flipping the precomputed middle-bit coin in coin
    rounds (diff % n == 0, hashgraph.go:695-709,1039-1048). Decisions
    are consistent across deciders (two 2n/3+1 tallies cannot
    disagree), so the sweep decides without the reference's early-break
    bookkeeping; votes on already-decided slots are computed but gated
    out of the fame table, matching the reference where such votes are
    never read.

    Returns famous[r, n] trilean (0 undefined / 1 true / 2 false).
    """
    wt_valid = wt >= 0
    wt_safe = jnp.where(wt_valid, wt, 0)
    idx_x = jnp.where(wt_valid, index[wt_safe], -1)  # [r, n]
    rx = jnp.broadcast_to(jnp.arange(r)[:, None], (r, n))
    famous0 = jnp.zeros((r, n), dtype=jnp.int32)
    votes0 = jnp.zeros((n, r, n), dtype=jnp.bool_)

    def step(j, carry):
        famous, v_prev = carry
        y = wt[j]
        y_valid = y >= 0
        ys = jnp.where(y_valid, y, 0)
        la_y = la[ys]  # [n, n]
        see_v = la_y[:, None, :] >= idx_x[None, :, :]  # [n(y), r, n(cx)]
        wp = wt[j - 1]
        wp_valid = wp >= 0
        fd_p = fd[jnp.where(wp_valid, wp, 0)]  # [n, n]
        # The [n, n, n] pairwise compare is the per-round hot op;
        # chunked where the backend materializes the broadcast.
        ss_cnt = strongly_see_counts_chunked(la_y, fd_p, n=n)
        ss = (ss_cnt >= sm) & wp_valid[None, :]
        # f32 contraction rides the MXU; tallies are <= n < 2^24 so
        # float32 arithmetic is exact.
        yays = (
            (ss.astype(jnp.float32) @ v_prev.reshape(n, r * n).astype(jnp.float32))
            .astype(jnp.int32)
            .reshape(n, r, n)
        )
        tot = ss.sum(-1).astype(jnp.int32)[:, None, None]
        nays = tot - yays
        v = yays >= nays
        t = jnp.maximum(yays, nays)
        diff = j - rx  # [r, n]
        is_first = (diff == 1)[None]
        normal = ((diff % n) != 0)[None]
        coin_vote = jnp.broadcast_to(
            coin[ys].astype(jnp.bool_)[:, None, None], see_v.shape
        )
        vote = jnp.where(
            is_first, see_v, jnp.where(normal | (t >= sm), v, coin_vote)
        )
        active = y_valid[:, None, None] & wt_valid[None] & (rx < j)[None]
        vote = vote & active
        decide_now = active & ~is_first & normal & (t >= sm)
        dec_any = decide_now.any(0)
        dec_val = (decide_now & v).any(0)
        undecided = (famous == FAME_UNDEFINED) & wt_valid
        famous = jnp.where(
            undecided & dec_any,
            jnp.where(dec_val, FAME_TRUE, FAME_FALSE),
            famous,
        )
        return famous, vote

    famous, _ = lax.fori_loop(1, fame_steps(wt) + 1, step,
                              (famous0, votes0))
    return famous
