"""The jitted consensus kernels.

All kernels are pure functions over int32/int8 SoA tensors (see
dag.DagTensors). Shapes: E = events (+1 sentinel pad row where noted),
N = participants, R = static round bound, L x W = wavefront levels,
K = longest creator chain.

Semantics mirror reference hashgraph/hashgraph.go exactly (anchors on
each kernel); the *computation* is restructured for the TPU: wavefront
sweeps instead of per-event recursion, a [R, N] witness table instead
of round LRUs, batched searchsorted instead of chain walking, and a
vote-matrix contraction instead of nested vote loops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

INT32_MAX = 2**31 - 1
# Device stand-in for Go's zero time (reference hashgraph.go:860-868);
# smaller than every real timestamp rank (>= 0).
ZERO_TS_RANK = -1

FAME_UNDEFINED = 0
FAME_TRUE = 1
FAME_FALSE = 2

# BABBLE_PALLAS=1 swaps the strongly-see contraction in decide_fame for
# the opt-in pallas kernel. Read ONCE at import and fixed for the
# process lifetime: decide_fame is jitted, so a mid-process toggle
# would silently keep serving whichever variant was compiled first for
# a given shape (the jit cache does not key on the environment).
import os as _os  # noqa: E402

_PALLAS = _os.environ.get("BABBLE_PALLAS") == "1"


@functools.partial(jax.jit, static_argnames=("n",))
def compute_last_ancestors(self_parent, other_parent, creator, index, levels, *, n):
    """last_anc[x, i] = index of x's latest ancestor created by i, -1 if
    none — the coordinate init of reference hashgraph.go:448-499
    (elementwise max of parent rows, own slot = own index), swept one
    DAG depth level at a time.

    Per-event inputs are [E+1] with a sentinel pad row at id E; returns
    la[E, n].
    """
    e = self_parent.shape[0] - 1
    w = levels.shape[1]
    la = jnp.full((e + 1, n), -1, dtype=jnp.int32)

    def step(l, la):
        ids = levels[l]  # [W]
        valid = ids >= 0
        sids = jnp.where(valid, ids, e)  # pad lanes hit the sentinel row
        sp = self_parent[sids]
        op = other_parent[sids]
        sp_rows = jnp.where((sp >= 0)[:, None], la[jnp.where(sp >= 0, sp, e)], -1)
        op_rows = jnp.where((op >= 0)[:, None], la[jnp.where(op >= 0, op, e)], -1)
        rows = jnp.maximum(sp_rows, op_rows)
        rows = rows.at[jnp.arange(w), creator[sids]].set(index[sids])
        return la.at[sids].set(jnp.where(valid[:, None], rows, -1))

    la = lax.fori_loop(0, levels.shape[0], step, la)
    return la[:e]


def chunk_width(w: int, row_elems: int, budget: int = 1 << 26) -> int:
    """Width of a processing chunk such that chunk*row_elems stays
    under `budget` elements. Callers iterate ceil(w/wc) chunks with
    CLAMPED dynamic slices (the final chunk re-reads/rewrites a few
    overlapping rows, which is idempotent) — no divisibility demanded,
    so a prime width cannot collapse the chunk to 1."""
    return max(min(budget // max(row_elems, 1), w), 1)


def _bcast_budget() -> int:
    """Chunk budget for broadcast-COMPARE intermediates: the CPU
    backend materializes them (tight budget), the TPU streams the
    fused compare+reduce (loose budget, fewer sequential steps). Only
    for broadcasts — gather-bounded chunks (which materialize on every
    backend) keep the default tight budget."""
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


@functools.partial(jax.jit, static_argnames=("n",))
def first_descendant_cube(la, chain, chain_len, *, n):
    """pos2k[c, i, t] = first position k on creator c's chain whose
    event descends from chain i's position t (INT32_MAX when no such
    position) — the closed form of the reference's first-descendant
    chain walk (hashgraph.go:490-530): within chain c,
    last_anc[chain[c, k], i] is monotone nondecreasing in k, so the
    answer is one searchsorted per (c, i) column.

    Per-event first descendants gather from the cube (fd_from_cube)."""
    k = chain.shape[1]
    chain_valid = chain >= 0
    # [n, K, n]; pad slots sort to the top so searchsorted lands on them
    # only when no real descendant exists.
    chain_la = jnp.where(
        chain_valid[:, :, None],
        la[jnp.where(chain_valid, chain, 0)],
        INT32_MAX,
    )
    # ranks[c, i, t] = #{k : chain_la[c, k, i] < t} — the searchsorted
    # closed form, computed as dense chunked compare-and-count (VPU
    # work) instead of vmapped binary search (gather-bound on TPU).
    # Chunked over targets to bound the [n, K, n, tc] compare cube; the
    # rank table is padded to a chunk multiple so arbitrary K (the
    # one-shot path's K = max index + 1) keeps full-width chunks.
    tc = min(max((1 << 27) // max(n * n * k, 1), 1), k)
    nchunks = (k + tc - 1) // tc
    k_pad = nchunks * tc

    def tchunk(g, acc):
        t0 = g * tc
        ts = t0 + jnp.arange(tc, dtype=jnp.int32)
        cnt = (chain_la[:, :, :, None] < ts[None, None, None, :]).sum(
            1, dtype=jnp.int32)  # [n(c), n(i), tc]
        return lax.dynamic_update_slice(acc, cnt, (0, 0, t0))

    ranks = lax.fori_loop(
        0, nchunks, tchunk, jnp.zeros((n, n, k_pad), dtype=jnp.int32))[
        :, :, :k]
    return jnp.where(ranks < chain_len[:, None, None], ranks, INT32_MAX)


@functools.partial(jax.jit, static_argnames=("n",))
def fd_from_cube(cube, creator, index, *, n):
    """fd[a, c] from the pos2k cube: event a = chain[creator_a,
    index_a], so fd[a, c] = cube[c, creator_a, index_a] — a gather
    (a scatter would serialize on TPU). Pad rows (index < 0) stay at
    INT32_MAX."""
    e = creator.shape[0] - 1
    k = cube.shape[2]
    ca = creator[:e]
    ia = jnp.clip(index[:e], 0, k - 1)
    fd = cube[:, ca, ia].T  # [E, n]
    return jnp.where((index[:e] >= 0)[:, None], fd, INT32_MAX)


@functools.partial(jax.jit, static_argnames=("n",))
def compute_first_descendants(la, creator, index, chain, chain_len, *, n):
    """first_desc[a, c] = index of the earliest event by creator c that
    descends from a, INT32_MAX if none — reference
    hashgraph.go:490-530. la: [E, n]; creator/index: [E+1] padded;
    chain: [n, K]; returns fd[E, n]."""
    cube = first_descendant_cube(la, chain, chain_len, n=n)
    return fd_from_cube(cube, creator, index, n=n)


@functools.partial(jax.jit, static_argnames=("n", "sm", "r"))
def compute_rounds(
    self_parent,
    other_parent,
    creator,
    index,
    la,
    fd,
    levels,
    root_round,
    valid_mask=None,
    *,
    n,
    sm,
    r,
):
    """Round numbers, witness flags, and the witness table — reference
    DivideRounds / Round / RoundInc / Witness (hashgraph.go:211-339,
    616-646), swept per DAG level.

    stronglySee(x, w) (hashgraph.go:179-198) is evaluated only against
    the <= n candidate witnesses of x's parent round (rounds are
    monotone along self-parent chains, so each creator contributes at
    most one witness per round) — [W, n, n] compares per level instead
    of anything E x E.

    `valid_mask` [E+1] restricts consensus to an ancestry-closed
    subgraph (a simulated peer's partial view): coordinates computed on
    the full DAG stay exact for any closed view (descendants along a
    creator chain form a suffix, so la[x] >= fd[w] agrees with the
    view-local comparison for every valid x), leaving the witness table
    as the only place masking is required. This is what makes the
    per-peer batched simulation one vmap over masks.

    Returns (rounds[E], witness[E] bool, wt[r, n] event ids, -1 empty).
    """
    e = la.shape[0]
    if valid_mask is None:
        valid_mask = jnp.ones((e + 1,), dtype=jnp.bool_)
    la_p = jnp.concatenate([la, jnp.full((1, n), -1, jnp.int32)], axis=0)
    rounds = jnp.full((e + 1,), -1, dtype=jnp.int32)
    wit = jnp.zeros((e + 1,), dtype=jnp.bool_)
    wt = jnp.full((r + 1, n), -1, dtype=jnp.int32)  # row r = scatter dump

    def step(l, carry):
        rounds, wit, wt = carry
        ids = levels[l]
        valid = ids >= 0
        sids = jnp.where(valid, ids, e)
        sp = self_parent[sids]
        op = other_parent[sids]
        cr = creator[sids]
        rnd_sp_raw = jnp.where(sp >= 0, rounds[jnp.where(sp >= 0, sp, e)], -1)
        # parentRound with Root fallback (hashgraph.go:211-262): a
        # missing parent means the base Root (X = Y = ""), whose round
        # comes from root_round.
        sp_round = jnp.where(sp >= 0, rnd_sp_raw, root_round[cr])
        op_round = jnp.where(op >= 0, rounds[jnp.where(op >= 0, op, e)], root_round[cr])
        use_op = sp_round < op_round
        pr = jnp.where(use_op, op_round, sp_round)
        pr_root = jnp.where(use_op, op < 0, sp < 0)
        # roundInc: count parent-round witnesses strongly seen.
        # Chunked over the level width: the [W, n, n] candidate-fd
        # gather is the kernel's peak transient, and a full-width
        # level at n=4096 would materialize n^3 ints.
        cand = wt[jnp.clip(pr, 0, r - 1)]  # [W, n]
        cand_valid = cand >= 0
        la_x = la_p[sids]  # [W, n]
        w = la_x.shape[0]
        wc = chunk_width(w, n * n)

        def ss_chunk(g, cnt):
            w0 = g * wc  # clamped by dynamic_slice on the final chunk
            la_g = lax.dynamic_slice(la_x, (w0, 0), (wc, n))
            cand_g = lax.dynamic_slice(cand, (w0, 0), (wc, n))
            cv_g = cand_g >= 0
            fd_g = fd[jnp.where(cv_g, cand_g, 0)]  # [wc, n, n]
            ss_g = ((la_g[:, None, :] >= fd_g).sum(-1) >= sm) & cv_g
            return lax.dynamic_update_slice(
                cnt, ss_g.sum(-1, dtype=jnp.int32), (w0,))

        ss_cnt = lax.fori_loop(0, -(-w // wc), ss_chunk,
                               jnp.zeros((w,), jnp.int32))
        inc = pr_root | (ss_cnt >= sm)
        r_new = pr + inc.astype(jnp.int32)
        # witness: sits on the Root, or exceeds the self-parent's round
        # (hashgraph.go:265-282).
        w_new = ((sp < 0) & (op < 0)) | (r_new > rnd_sp_raw)
        rounds = rounds.at[sids].set(jnp.where(valid, r_new, -1))
        wit = wit.at[sids].set(jnp.where(valid, w_new, False))
        upd = valid & w_new & valid_mask[sids]
        r_idx = jnp.where(upd, jnp.clip(r_new, 0, r - 1), r)
        wt = wt.at[r_idx, cr].set(jnp.where(upd, sids, -1))
        return rounds, wit, wt

    rounds, wit, wt = lax.fori_loop(0, levels.shape[0], step, (rounds, wit, wt))
    return rounds[:e], wit[:e], wt[:r]


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

    # Opt-in pallas path for the pairwise strongly-see contraction (the
    # per-round hot op at large n); the XLA broadcast-compare-reduce is
    # the bit-identical default. The pallas module is only imported when
    # the flag is set, so the default path never depends on it.
    pallas_ss = _PALLAS
    if pallas_ss:
        from .pallas_kernels import strongly_see_counts_auto

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
        if pallas_ss:
            ss_cnt = strongly_see_counts_auto(la_y, fd_p)
        else:
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


decide_fame = functools.partial(jax.jit, static_argnames=(
    "n", "sm", "r"))(decide_fame_impl)


@functools.partial(jax.jit, static_argnames=("n", "r"))
def decide_round_received(
    rounds, wt, famous, la, fd, creator, index, chain_rank, valid_mask=None, *, n, r
):
    """Round-received + median consensus timestamps — reference
    DecideRoundReceived / MedianTimestamp / OldestSelfAncestorToSee
    (hashgraph.go:753-799,860-868,141-167).

    For each event x and candidate round i (fully decided, with every
    earlier round decided too), x is received at the first i where a
    strict majority of i's famous witnesses see it. Its consensus
    timestamp is the median over those witnesses of the timestamp of
    x's first descendant on each witness's own chain (Go substitutes
    the zero time when that descendant doesn't reach the witness;
    device rank -1 plays that role).

    Two phases: a cheap sweep over candidate rounds finds each event's
    receiving round; one vectorized pass then computes the medians
    against only the deciding round's witnesses (the reference
    recomputes per (event, round) pair; the result is identical because
    only the first qualifying round's witnesses contribute).

    Returns (round_received[E] int32, -1 undecided;
             cts_rank[E] int32 timestamp rank, -1 = zero time).
    """
    e = rounds.shape[0]
    k = chain_rank.shape[1]
    if valid_mask is None:
        in_view = jnp.ones((e,), dtype=jnp.bool_)
    else:
        in_view = valid_mask[:e]
    wt_valid = wt >= 0
    wt_safe = jnp.where(wt_valid, wt, 0)
    has_undec = ((famous == FAME_UNDEFINED) & wt_valid).any(1)  # [r]
    min_undec = jnp.min(jnp.where(has_undec, jnp.arange(r), r))
    fmask = (famous == FAME_TRUE) & wt_valid  # [r, n]
    fcnt = fmask.sum(1)
    idx_w = jnp.where(wt_valid, index[wt_safe], -1)  # [r, n]
    creator_e = creator[:e]
    index_e = index[:e]

    # Phase 1: first qualifying round per event.
    rr0 = jnp.full((e,), -1, dtype=jnp.int32)

    def step(i, rr):
        eligible = ~has_undec[i] & (min_undec > i)
        la_w = la[wt_safe[i]]  # [n(w), n]
        see_wx = la_w[:, creator_e] >= index_e[None, :]  # [n(w), E]
        s_cnt = (see_wx & fmask[i][:, None]).sum(0)
        ok = eligible & (s_cnt > fcnt[i] // 2) & (i > rounds) & (rr < 0) & in_view
        return jnp.where(ok, i, rr)

    rr = lax.fori_loop(0, r, step, rr0)

    # Phase 2: medians against each event's own receiving round.
    rr_safe = jnp.clip(rr, 0, r - 1)
    w_sel = wt_safe[rr_safe]  # [E, n] witness ids of the receiving round
    fm_sel = fmask[rr_safe]  # [E, n]
    idxw_sel = idx_w[rr_safe]  # [E, n]
    see_sel = la[w_sel, creator_e[:, None]] >= index_e[:, None]  # [E, n]
    s_mask = see_sel & fm_sel
    s_cnt = s_mask.sum(1)
    kk = fd  # [E, n]: first descendant of x on each witness creator's chain
    valid_t = kk <= idxw_sel  # descendant reaches the witness
    ts_fd = chain_rank[jnp.arange(n)[None, :], jnp.clip(kk, 0, k - 1)]  # [E, n]
    tsv = jnp.where(valid_t, ts_fd, ZERO_TS_RANK)
    tvals = jnp.where(s_mask, tsv, INT32_MAX)
    sorted_t = jnp.sort(tvals, axis=1)
    med = jnp.take_along_axis(sorted_t, (s_cnt // 2)[:, None], axis=1)[:, 0]
    cts = jnp.where(rr >= 0, med, ZERO_TS_RANK)
    return rr, cts
