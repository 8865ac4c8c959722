"""Incremental device-backed consensus: append events, re-run only the
undecided tip.

The reference inserts one event at a time and re-runs
DivideRounds/DecideFame/FindOrder over its undetermined queue
(reference hashgraph/hashgraph.go:356-401, 616-858). This module is the
TPU-native equivalent — an append-only device DAG with amortized
per-sync work instead of full-DAG recompute:

  coordinates   the frozen prefix stays resident in HBM; only new
                closure blocks run (ops/closure.py block body over a
                donated carry), so per-sync cost scales with the new
                events, not E.
  rounds        the witness frontier (ops/frontier.py) restarts at the
                first round that can still gain members. Rows below are
                provably frozen: chain positions only append, and
                strongly-see of an existing event is stable under new
                descendants (a new first-descendant index always
                exceeds the old event's last-ancestor index).
  fame          kernels.decide_fame_impl over a round window starting at
                the first undecided round, voting through its last row
                that holds a witness. Window-relative round numbers
                preserve the vote/coin semantics exactly: diff = j - rx
                is shift-invariant, so first-round votes and coin
                rounds (diff % n) land identically.
  round recv    a windowed sweep over candidate rounds, gated by a
                host-maintained eligibility mask that mirrors the
                reference's undecided-rounds bookkeeping — including
                the straggler quirk: a witness discovered in a round
                already removed from the undecided list stays
                UNDEFINED forever and poisons that round's
                witnesses_decided (hashgraph.go:629-637, 762-764).

Capacity, chain length, and round windows are bucketed to powers of two
so steady-state syncs never recompile.

Frame reset (reference hashgraph.go:879-898): the engine is
position-based internally — coordinates, frontier positions, and the fd
rank cube all index chain POSITIONS, not Go event indexes — so offset
chain bases (Root.index != -1) reduce to a per-creator `index_base`
subtracted at append time, and offset round bases (Root.round != -1)
ride the existing per-participant `root_round` vector the closure
propagates as rbase. A reset engine starts with an empty
undecided-rounds queue (the host mirror's reset() does the same;
rounds re-queue as replayed events land, graph.py divide_rounds).
"""

from __future__ import annotations

import bisect
import functools
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..telemetry.trace import SpanRing
from . import closure, frontier, kernels
from .kernels import FAME_TRUE, FAME_UNDEFINED, INT32_MAX

# Go's zero time (0001-01-01T00:00:00Z) in ns — the value MedianTimestamp
# substitutes for unreached witnesses (reference hashgraph.go:860-868).
# It overflows int64, so device/host arrays store CTS_SENTINEL (which
# still sorts below every real timestamp) and the Python-level RunDelta
# carries the true value.
ZERO_TIME_NS = -62135596800 * 1_000_000_000
CTS_SENTINEL = np.iinfo(np.int64).min

# Device timestamps ride as a lexicographic (hi, lo) int32 pair:
# hi = ns >> 32 (arithmetic), lo = (ns & 0xFFFFFFFF) - 2^31, so signed
# (hi, lo) order == int64 ns order for EVERY int64. ZERO_TIME (whose ns
# overflows int64, see above) is the pair (INT32_MIN, 0) — it sorts
# below any real wall-clock timestamp (a real hi of INT32_MIN would
# need ns < -2^62, i.e. ~146 billion years before 1970).
ZERO_TS_HI = -(2**31)

# Lanes per step of _consensus_fused's timestamp loop: a dispatch runs
# ceil(newly_count / chunk) steps of [chunk, n] gathers, where chunk is
# this or the bucket cb if smaller. It equals cb's floor, so a pass that
# receives no more than the floor bucket holds runs one step.
TS_CHUNK = 1024

# Bytes per device of the round-received sweep's [witnesses, lanes]
# compare plane. The sweep walks the undecided window in chunks of
# _rr_lanes(n, devices) lanes, split over a mesh's devices. At n=1024
# on one chip, and at n=4096 on four, a chunk is 262,144 lanes, so
# every window no wider than that is one chunk; a 1M-lane window at
# n=4096 on four chips would be four.
RR_PLANE_BYTES = 1 << 30


def _rr_lanes(n: int, devices: int) -> int:
    """Lanes per chunk of the round-received sweep: the largest power of
    two whose [n, lanes] int32 plane, split over `devices`, stays within
    RR_PLANE_BYTES on each device."""
    lanes = max(RR_PLANE_BYTES * devices // (4 * n), devices)
    return 1 << (lanes.bit_length() - 1)


def _ts_split(ts: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Split int64 ns into order-preserving (hi, lo) int32 planes."""
    ts = np.asarray(ts, np.int64)
    hi = (ts >> 32).astype(np.int32)
    lo = ((ts & 0xFFFFFFFF) - 2**31).astype(np.int32)
    return hi, lo


def _ts_join(hi: int, lo: int) -> int:
    """Inverse of _ts_split for one pair (host-side, Python ints)."""
    return (int(hi) << 32) | ((int(lo) + 2**31) & 0xFFFFFFFF)


def _pow2(x: int, floor: int = 8) -> int:
    p = floor
    while p < x:
        p *= 2
    return p


def _pow4(x: int, floor: int) -> int:
    """Coarser bucket (x4 steps): every distinct static shape is a
    compile that stalls a pass — a 4x bucket costs a few padded KB per
    dispatch and quarters the shape space."""
    p = floor
    while p < x:
        p *= 4
    return p


@functools.partial(
    jax.jit, static_argnames=("n", "block"), donate_argnums=(0, 1)
)
def _closure_update(la, rb, self_parent, other_parent, creator, index,
                    root_base, b0, b1, *, n, block):
    """Run the closure block body over blocks [b0, b1) against donated
    coordinate carries la [cap+1, n] / rb [cap+1]."""
    body = closure.make_block_body(
        self_parent, other_parent, creator, index, root_base,
        n=n, block=block)
    return lax.fori_loop(b0, b1, body, (la, rb))


@functools.partial(jax.jit, static_argnames=("rows", "fill"))
def _pad_rows(a, *, rows, fill):
    """Grow a device carry by `rows` fill-rows along axis 0. NOT
    donated: the output buffer is strictly larger than the input, so
    XLA can never alias them — a donate_argnums here only buys the
    "donated buffers were not usable" warning on every growth step."""
    pad_shape = (rows,) + a.shape[1:]
    return jnp.concatenate([a, jnp.full(pad_shape, fill, a.dtype)], axis=0)


@functools.partial(jax.jit, static_argnames=("cols", "fill", "axis"))
def _pad_cols(a, *, cols, fill, axis=-1):
    """Grow a device carry by `cols` fill-slices along `axis`. NOT
    donated (see _pad_rows: growth outputs can never alias)."""
    axis = axis % a.ndim
    pad_shape = a.shape[:axis] + (cols,) + a.shape[axis + 1:]
    return jnp.concatenate([a, jnp.full(pad_shape, fill, a.dtype)],
                           axis=axis)


@functools.partial(jax.jit, static_argnames=("cols",))
def _pad_ranks(ranks, len_counted, *, cols):
    """Grow the fd rank cube [n, n, K] -> [n, n, K+cols]. Every counted
    la value is a chain position < K <= t for the new thresholds t, so
    the new columns are exactly the per-chain counted length."""
    n = ranks.shape[0]
    pad = jnp.broadcast_to(len_counted[:, None, None], (n, n, cols))
    return jnp.concatenate([ranks, pad.astype(ranks.dtype)], axis=2)


@functools.partial(jax.jit, static_argnames=("bp",),
                   donate_argnums=(0, 1, 2, 3, 4, 5))
def _ingest(sp_d, op_d, cr_d, idx_d, coin_d, rb0_d,
            sp_b, op_b, cr_b, idx_b, coin_b, rb0_b, e0, *, bp):
    """Write one appended batch (host slices padded to bp) into the
    device-resident event arrays at offset e0. Pad lanes carry the init
    fill values, so rows beyond the true batch stay inert until a later
    batch overwrites them."""
    out = []
    for arr, b in ((sp_d, sp_b), (op_d, op_b), (cr_d, cr_b),
                   (idx_d, idx_b), (coin_d, coin_b), (rb0_d, rb0_b)):
        out.append(lax.dynamic_update_slice(arr, b.astype(arr.dtype), (e0,)))
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("n", "m"),
                   donate_argnums=(0, 1, 2))
def _chain_ingest(chain_d, chain_th, chain_tl, newtab, newpos,
                  newhi, newlo, *, n, m):
    """Scatter the batch's per-creator new events ([n, m] id table, -1
    pad; newpos the matching chain positions) into the resident chain
    table and the resident timestamp planes. Pad lanes scatter out of
    bounds and are dropped."""
    k = chain_d.shape[1]
    valid = newtab >= 0
    pos = jnp.where(valid, newpos, k)  # OOB -> dropped
    crows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, m))
    return (chain_d.at[crows, pos].set(newtab, mode="drop"),
            chain_th.at[crows, pos].set(newhi, mode="drop"),
            chain_tl.at[crows, pos].set(newlo, mode="drop"))


# Working-set bound for the incremental fd-rank update's histogram +
# cumsum transients (sized to trade kernel count for VMEM pressure;
# the trade on the v5e chip is not measured).
_FD_CHUNK_ELEMS = 1 << 26


def _tables_chain_write(chain_la, chain_rb, la, rb, newtab, newpos,
                        *, n, m, k):
    """Shared prologue of the fd-fold variants: write the batch rows
    into the resident chain_la/chain_rb tables and return the
    effective la rows (INT32_MAX in pad lanes)."""
    cap1 = la.shape[0]
    valid = newtab >= 0
    ids = jnp.where(valid, newtab, cap1 - 1)  # sentinel row, masked below
    la_new = la[ids]  # [n, m, n]
    rb_new = rb[ids]  # [n, m]
    pos = jnp.where(valid, newpos, k)  # OOB -> dropped
    crows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, m))
    chain_la = chain_la.at[crows, pos].set(
        jnp.where(valid[:, :, None], la_new, INT32_MAX), mode="drop")
    chain_rb = chain_rb.at[crows, pos].set(
        jnp.where(valid, rb_new, INT32_MAX), mode="drop")
    la_eff = jnp.where(valid[:, :, None], la_new, INT32_MAX)  # [n, m, n]
    return chain_la, chain_rb, la_eff


@functools.partial(jax.jit, static_argnames=("n", "m"),
                   donate_argnums=(0, 1, 2))
def _tables_update_hist(ranks, chain_la, chain_rb, la, rb, newtab,
                        newpos, *, n, m):
    """Histogram + cumulative-sum form of the fd-rank fold —
    O(batch·n scatter + n^2·K cumsum) work instead of the broadcast
    form's O(batch·n^2·K) compares. The scatter-add serializes on TPU
    (which is why _tables_update exists), but on CPU/GPU backends it
    is the difference between milliseconds and seconds per pass; the
    engine picks per backend at construction.

    Bucketing: la = -1 counts for every t >= 0 (bucket 0), la = v >= 0
    counts for t > v (bucket v+1), pad lanes (INT32_MAX) clip to
    bucket K and never land inside the cumsum's [0, K) window — the
    exact contract of the broadcast form's clip(la+1, 0, k)."""
    k = ranks.shape[2]
    chain_la, chain_rb, la_eff = _tables_chain_write(
        chain_la, chain_rb, la, rb, newtab, newpos, n=n, m=m, k=k)
    # Clip BEFORE the +1: pad lanes are INT32_MAX and la_eff + 1 would
    # wrap to INT32_MIN, landing them in bucket 0 (= counted for every
    # t) instead of the never-counted bucket k.
    b = jnp.clip(la_eff, -1, k - 1) + 1  # [n, m, n] buckets
    h = jnp.zeros((n, n, k + 1), jnp.int32)
    crows = jnp.arange(n)[:, None, None]
    icols = jnp.arange(n)[None, None, :]
    h = h.at[crows, icols, b].add(1)
    ranks = ranks + jnp.cumsum(h, axis=2)[:, :, :k]
    return ranks, chain_la, chain_rb


@functools.partial(jax.jit, static_argnames=("n", "m"),
                   donate_argnums=(0, 1, 2))
def _tables_update(ranks, chain_la, chain_rb, la, rb, newtab, newpos,
                   *, n, m):
    """Fold one appended batch into the resident per-chain tables:

    chain_la[c, k, i] / chain_rb[c, k]  new rows written from the
        batch events' (frozen) coordinates;
    ranks[c, i, t] += #{new events on chain c : la[., i] < t}  — the
        first-descendant rank cube (ranks[c, i, t] = first position on
        chain c whose event descends from chain i's position t; la
        columns are monotone along a chain, so the position is a
        count), kept incrementally: old events' la rows never change,
        so the count over a chain only grows by the new suffix
        contributions (reference semantics hashgraph.go:490-530). The
        count is a histogram over la values + a cumulative sum along the
        threshold axis — O(n^2·K + batch·n) work, replacing the dense
        [batch, K] compare cube's O(batch·n^2·K).
    """
    k = ranks.shape[2]
    chain_la, chain_rb, la_eff = _tables_chain_write(
        chain_la, chain_rb, la, rb, newtab, newpos, n=n, m=m, k=k)

    # Broadcast-compare-reduce: delta[c, i, t] = #{new j on chain c :
    # la_new[c, j, i] < t}. FLOP-wise this is O(batch·n·K) against the
    # histogram+cumsum's O(n^2·K), but it is pure compare+sum — XLA
    # fuses it into a stream with no scatter and no scan, and on TPU
    # the scatter-add histogram serialized into the per-sync bottleneck
    # (measured 347 ms/pass at n=1024 vs ~40 ms for this form; CPU/GPU
    # backends take _tables_update_hist instead).
    # Invalid lanes compare as INT32_MAX and never count; la = -1
    # counts for every t >= 0, matching clip(la+1, 0, k) bucketing.
    ic = max(min(_FD_CHUNK_ELEMS // max(m * k, 1), n), 1)
    while n % ic:
        ic -= 1
    nchunks = n // ic
    t_vec = jnp.arange(k, dtype=jnp.int32)

    def chunk(g, ranks):
        i0c = g * ic
        la_g = lax.dynamic_slice(la_eff, (0, 0, i0c), (n, m, ic))
        cmp = la_g[:, :, :, None] < t_vec  # [n, m, ic, k], fused
        delta = cmp.sum(1, dtype=jnp.int32)  # [n, ic, k]
        blk = lax.dynamic_slice(ranks, (0, i0c, 0), (n, ic, k)) + delta
        return lax.dynamic_update_slice(ranks, blk, (0, i0c, 0))

    ranks = lax.fori_loop(0, nchunks, chunk, ranks)
    return ranks, chain_la, chain_rb


# Non-donating twins of the same-shape carry-update kernels. Donation
# is a pure buffer-reuse optimization: on a single device the in-place
# update aliases cleanly, but under a mesh GSPMD may reshard the
# output, making the donated input unusable — XLA then warns "Some
# donated buffers were not usable" and copies anyway. Mesh-backed
# engines select these twins at construction (same pattern as the
# per-backend _tables_fn pick); single-device engines keep the
# donating forms and the in-place reuse. The growth kernels
# (_pad_rows/_pad_cols/_pad_ranks) never donate at all — see
# _pad_rows — so they need no twin.
_closure_update_nd = jax.jit(
    _closure_update.__wrapped__, static_argnames=("n", "block"))
_ingest_nd = jax.jit(_ingest.__wrapped__, static_argnames=("bp",))
_chain_ingest_nd = jax.jit(
    _chain_ingest.__wrapped__, static_argnames=("n", "m"))
_tables_update_hist_nd = jax.jit(
    _tables_update_hist.__wrapped__, static_argnames=("n", "m"))
_tables_update_nd = jax.jit(
    _tables_update.__wrapped__, static_argnames=("n", "m"))


class _FdRows:
    """Lazy row view of the first-descendant matrix, gathered straight
    from the resident rank cube: event a = chain[creator_a, index_a], so
    fd[a, c] = ranks[c, creator_a, index_a], INT32_MAX when the position
    is past chain c's end or a is a pad row. Every consumer of fd
    (frontier sweep, fame, consensus timestamps) reads row gathers only,
    so the dense [cap, n] materialization (512 MB/pass at the n=1024
    north star) is never built."""

    def __init__(self, ranks, chain_len, creator, index):
        self.ranks = ranks
        self.chain_len = chain_len
        self.creator = creator
        self.index = index
        self.k = ranks.shape[2]

    def __getitem__(self, ids):
        ca = self.creator[ids]
        ix = self.index[ids]
        ia = jnp.clip(ix, 0, self.k - 1)
        raw = jnp.moveaxis(self.ranks[:, ca, ia], 0, -1)  # [*S, n]
        fd = jnp.where(raw < self.chain_len, raw, INT32_MAX)
        return jnp.where((ix >= 0)[..., None], fd, INT32_MAX)


def _rr_steps(elig):
    """The steps the round-received sweep runs over the window's rounds
    [i0, i0 + iw): through the last eligible one, none when no round is
    eligible. A step whose round is not eligible assigns nothing."""
    return jnp.max(jnp.where(elig, jnp.arange(1, elig.shape[0] + 1), 0))


def _rr_chunked(rr_u0, i0, la, wt_safe, cr_u, ix_u, rnd_u, lane_ok, n_und,
                elig, fmask, fcnt, steps, *, rl, lanes):
    """The round-received sweep: in step t, lanes whose famous witnesses
    of round i0 + t see them by more than half receive that round. It
    runs `steps` steps (_rr_steps) and walks the undecided window in
    chunks of `rl` lanes, only as many as hold the n_und live lanes.
    The lanes lie as [chunks, rl] planes; on a mesh (`lanes`, the
    sharding of a lane vector) each chunk's lanes are split over the
    devices, which then each compare [n, rl / devices] against the
    witness rows, gathered whole once per round. No device holds an
    [n, au] plane."""
    au = rr_u0.shape[0]
    nch = au // rl

    def place(a, spec):
        if lanes is None:
            return a
        return lax.with_sharding_constraint(
            a, jax.sharding.NamedSharding(lanes.mesh, spec))

    lane_spec = jax.sharding.PartitionSpec(None, *lanes.spec) if lanes else None
    cr2, ix2, rnd2, ok2, rr2 = (place(a.reshape(nch, rl), lane_spec)
                                for a in (cr_u, ix_u, rnd_u, lane_ok, rr_u0))
    n_chunks = (n_und + rl - 1) // rl

    def step(t, rr2):
        i = i0 + t
        la_w = place(la[wt_safe[t]], jax.sharding.PartitionSpec())

        def chunk(j, rr2):
            rr = rr2[j]
            see_wx = la_w[:, cr2[j]] >= ix2[j][None, :]  # [n(w), rl]
            s_cnt = (see_wx & fmask[t][:, None]).sum(0)
            ok = (elig[t] & (s_cnt > fcnt[t] // 2) & (i > rnd2[j])
                  & (rr < 0) & ok2[j])
            return lax.dynamic_update_index_in_dim(
                rr2, jnp.where(ok, i, rr), j, 0)

        return lax.fori_loop(0, n_chunks, chunk, rr2)

    return lax.fori_loop(0, steps, step, rr2).reshape(au)


@functools.partial(
    jax.jit,
    static_argnames=("n", "sm", "rcap", "bp", "rw", "iw", "cb", "tc", "tw",
                     "rl", "lanes"))
def _consensus_fused(chain_la, chain_rb_tab, chain_len, la, ranks, rb_vec,
                     chain, wt_tab, fr_tab, wt_prev, fr_prev, t0, rho_min,
                     self_parent, creator, index, coin, e0, e1,
                     rounds_prev, rr_prev, fam_rel, in_list_rel,
                     chain_th, chain_tl, rx0, first_undec_prev, und_ids,
                     n_und, t_start,
                     *, n, sm, rcap, bp, rw, iw, cb, tc, tw, rl,
                     lanes=None):
    """The whole per-sync consensus tail in one dispatch — frontier
    sweep, new-event rounds, fame merge, round-received — returning a
    single packed int32 buffer so the host pays exactly ONE
    device->host round trip per sync (see also _fused_fame_rr's
    semantics which this kernel absorbs).

    Window geometry: the witness/frontier tables are rho_min-relative
    [rcap, n]; fame runs over the window [rx0, rho_min + rcap) and
    round-received over [i0, rho_min + rcap), where i0 is derived ON
    DEVICE from the new batch's rounds (i0 = min(first_undec_prev,
    min_new_round + 1)) — the host no longer needs an intermediate pull
    to build the windows. Host bookkeeping inputs (`fam_rel`,
    `in_list_rel`) are rho_min-relative round tables built
    from the PREVIOUS run's state; rows at or beyond this run's fame
    window take device-merged values exactly as the reference's
    DecideFame/DecideRoundReceived interleave (hashgraph.go:649-799).

    Packed layout (every plane is window-sized, never E- or cap-sized,
    so the pull stays small; its cost on the v5e chip is not measured):
    [t_end, newly_count, fame_steps, rr_steps, wt_win(tw*n),
    fr_win(tw*n), new_rounds(bp), new_wit(bp), famous_merged(rw*n),
    sel_l(cb), rr_sel(cb), cts_hi(cb), cts_lo(cb)] where fame_steps
    and rr_steps count the steps the fame vote loop and the
    round-received sweep ran (each stops at the window's last row that
    can hold work; W = rw = iw bounds the shapes, not the steps),
    wt/fr_win are the swept table rows
    [t_start, t_start+tw) (the only rows that can have changed) and the
    cb-compacted tail carries the newly-received lanes: sel_l[j] for
    j < newly_count is an undecided-window lane index, with its
    round-received and split-int64 consensus timestamp (see _ts_split)
    in the matching positions of the other three planes. The timestamps
    are computed `tc` lanes at a time for the first newly_count lanes
    only; past the last chunk the cts planes read 0, and they read 0
    throughout when newly_count > cb (the host redoes that dispatch).
    The round-received sweep walks the undecided window in chunks of
    `rl` lanes, only as many as hold its live lanes (_rr_chunked); on a
    mesh `lanes` splits each chunk's lanes over the devices.

    Besides the packed pull, the kernel returns updated `rounds` and
    `rr` DEVICE CARRIES (rounds_prev with the batch's rounds written,
    rr_prev with this sync's assignments scattered) — the host commits
    them after a successful pull so the next pass re-uploads neither.
    """
    k = chain_th.shape[1]
    fd = _FdRows(ranks, chain_len, creator, index)

    # 1. Witness frontier.
    with jax.named_scope("frontier"):
        wt_tab, fr_tab, t_end = frontier.frontier_sweep_impl(
            chain_la, chain_rb_tab, chain_len, la, fd, rb_vec, chain,
            wt_tab, fr_tab, wt_prev, fr_prev, t0, rho_min,
            n=n, sm=sm, rcap=rcap)

    # 2. Rounds + witness flags for the batch [e0, e1): round = rho_min
    # - 1 + #{frontier rows at or below the event's chain position}
    # (rows >= t_end keep the upload's kcap fill and never count).
    with jax.named_scope("rounds"):
        ids_b = e0 + jnp.arange(bp)
        valid_b = ids_b < e1
        cr_b = lax.dynamic_slice(creator, (e0,), (bp,))
        pos_b = lax.dynamic_slice(index, (e0,), (bp,))
        sp_b = lax.dynamic_slice(self_parent, (e0,), (bp,))
        cnt = (fr_tab[:, cr_b] <= pos_b[None, :]).sum(0, dtype=jnp.int32)
        rnd_b = jnp.where(valid_b, rho_min - 1 + cnt, -1)
        rounds_all = lax.dynamic_update_slice(rounds_prev, rnd_b, (e0,))
        sp_safe = jnp.where(sp_b >= 0, sp_b, 0)
        wit_b = valid_b & ((sp_b < 0) | (rnd_b > rounds_all[sp_safe]))
        big = jnp.iinfo(jnp.int32).max // 2
        min_new = jnp.min(jnp.where(valid_b, rnd_b, big))
        i0 = jnp.minimum(first_undec_prev, min_new + 1)

    # 3. Fame over the window [rx0, rho_min + rcap): rows gathered from
    # the swept table (mask instead of slice — rx0 is dynamic and
    # dynamic_slice would clamp), merged under the undecided-rounds
    # gating exactly as before.
    with jax.named_scope("fame"):
        t_w = rx0 - rho_min + jnp.arange(rw)
        row_ok = (t_w >= 0) & (t_w < rcap)
        t_wc = jnp.clip(t_w, 0, rcap - 1)
        wt_win = jnp.where(row_ok[:, None], wt_tab[t_wc], -1)
        famous_prev_win = jnp.where(row_ok[:, None], fam_rel[t_wc], 0)
        in_list_win = row_ok & in_list_rel[t_wc]

        famous_comp = kernels.decide_fame_impl(
            wt_win, la, fd, index, coin, n=n, sm=sm, r=rw)
        fame_steps = kernels.fame_steps(wt_win)
        wt_valid_f = wt_win >= 0
        mergeable = (
            in_list_win[:, None] & wt_valid_f
            & (famous_prev_win == FAME_UNDEFINED)
        )
        famous_merged = jnp.where(mergeable, famous_comp, famous_prev_win)
        undec_row = (wt_valid_f & (famous_merged == FAME_UNDEFINED)).any(1)
        still_listed = in_list_win & undec_row
        t_first = jnp.min(jnp.where(still_listed, jnp.arange(rw), big))
        first_undec = rx0 + t_first  # huge when the list empties

    # 4. Round received over [i0, rho_min + rcap): fame/eligibility from
    # the host tables below rx0, from this run's merge at and above it.
    with jax.named_scope("round_received"):
        i_vec = i0 + jnp.arange(iw)
        rel = i_vec - rho_min
        rel_ok = (rel >= 0) & (rel < rcap)
        rel_c = jnp.clip(rel, 0, rcap - 1)
        wt_rr = jnp.where(rel_ok[:, None], wt_tab[rel_c], -1)
        t2 = jnp.clip(i_vec - rx0, 0, rw - 1)
        in_fame_win = i_vec >= rx0
        fam_low = jnp.where(rel_ok[:, None], fam_rel[rel_c], 0)
        fam_rr = jnp.where(in_fame_win[:, None], famous_merged[t2], fam_low)
        # Decidedness below the fame window is derived from the POST-sweep
        # witness table, not host state: a straggler witness landing THIS
        # run in an already-removed round has UNDEFINED fame forever and
        # must poison the round's witnesses_decided (reference
        # hashgraph.go:629-637, 762-764).
        elig_low = rel_ok & ~(
            (wt_rr >= 0) & (fam_low == FAME_UNDEFINED)).any(1)
        decided_vec = jnp.where(in_fame_win, ~undec_row[t2], elig_low)
        elig = decided_vec & (first_undec > i_vec)

        wt_valid = wt_rr >= 0
        wt_safe = jnp.where(wt_valid, wt_rr, 0)
        fmask = (fam_rr == FAME_TRUE) & wt_valid
        fcnt = fmask.sum(1)
        idx_w = jnp.where(wt_valid, index[wt_safe], -1)

        # The sweep runs over the UNDECIDED window only (host-gathered ids
        # with rr < 0): decided events never change, so each of the iw
        # sequential steps compares [n, |undecided|] instead of [n, E] —
        # the dominant per-sync cost once the DAG is deep.
        au = und_ids.shape[0]
        lane_ok = jnp.arange(au) < n_und
        uid = jnp.where(lane_ok, und_ids, 0)
        cr_u = creator[uid]
        ix_u = index[uid]
        rnd_u = rounds_all[uid]
        # Pad lanes are never assigned.
        rr_u0 = jnp.where(lane_ok, rr_prev[uid], 0)

        rr_steps = _rr_steps(elig)
        rr_u = _rr_chunked(rr_u0, i0, la, wt_safe, cr_u, ix_u, rnd_u,
                           lane_ok, n_und, elig, fmask, fcnt, rr_steps,
                           rl=min(rl, au), lanes=lanes)
        newly_l = (rr_u >= 0) & (rr_u0 < 0) & lane_ok
        newly_count = newly_l.sum(dtype=jnp.int32)

    # Consensus timestamps only for the lanes that were JUST assigned.
    # argsort(~newly_l) is stable, so the first newly_count entries of
    # sel_l are exactly the newly-received lanes. The median machinery
    # (the [rows, n] gathers and the per-row sort) runs over those
    # lanes only, tc at a time, so it scales with this dispatch's
    # decisions, not with the static bucket cb. If the bucket overflows
    # (a late fame decision releasing a huge backlog), newly_count > cb
    # tells the host to redo with a bigger bucket, and no chunk runs.
    with jax.named_scope("timestamps"):
        order = jnp.argsort(~newly_l)
        sel_l = order[:cb]  # [cb] lanes, newly lanes first
        # The loop's fd gathers read a re-laid-out copy of the rank cube
        # (512 MiB at n=1024). Tied to newly_count, that copy is made
        # after the round-received sweep; otherwise the compiler shares
        # the frontier's copy and keeps it live through the sweep's peak.
        ranks_ts = lax.optimization_barrier((ranks, newly_count))[0]
        fd_ts = _FdRows(ranks_ts, chain_len, creator, index)

        def chunk(j, med):
            # When tc does not divide cb, the last chunk's slice and
            # update clamp to the same earlier start: lanes computed twice
            # get the same values.
            at = j * tc
            lanes = lax.dynamic_slice(sel_l, (at,), (tc,))
            sel_ids = uid[lanes]
            t_sel = jnp.clip(rr_u[lanes] - i0, 0, iw - 1)
            fm_sel = fmask[t_sel]  # [tc, n]
            idxw_sel = idx_w[t_sel]
            fd_sel = fd_ts[sel_ids]  # [tc, n]
            # "Famous witness w sees x" is read from x's first-descendant
            # row, not from la[w, creator_x] >= index_x: la[., i] never
            # decreases along a chain (a self-parent's ancestors are its
            # child's), and the rank cube is folded from this same la, so
            # the positions of w's chain that see x are exactly those at
            # or above fd[x, chain_w]. It rests on one event per (creator,
            # index): a forked chain would break the monotonicity, and
            # the engine accepts none. Do not restore the la gather.
            s_mask = (fd_sel <= idxw_sel) & fm_sel
            s_cnt = s_mask.sum(1)
            fd_pos = jnp.clip(fd_sel, 0, k - 1)
            rows_n = jnp.arange(n)[None, :]
            ts_hi = chain_th[rows_n, fd_pos]
            ts_lo = chain_tl[rows_n, fd_pos]
            # INT32_MAX pads the lanes of witnesses that are not famous or
            # do not see x to the end. Median by LEXICOGRAPHIC two-key
            # sort — signed (hi, lo) order equals int64 ns order
            # (_ts_split).
            hi_m = jnp.where(s_mask, ts_hi, INT32_MAX)
            lo_m = jnp.where(s_mask, ts_lo, INT32_MAX)
            s_hi, s_lo = lax.sort((hi_m, lo_m), dimension=1, num_keys=2)
            pick = (s_cnt // 2)[:, None]
            return tuple(
                lax.dynamic_update_slice(
                    m, jnp.take_along_axis(s, pick, axis=1)[:, 0], (at,))
                for m, s in zip(med, (s_hi, s_lo)))

        n_chunks = jnp.where(newly_count <= cb, (newly_count + tc - 1) // tc,
                             0)
        zero = jnp.zeros(cb, jnp.int32)
        med_hi, med_lo = lax.fori_loop(0, n_chunks, chunk, (zero, zero))
        # Results ride home cb-compacted: the pull carries [cb]
        # lanes+rr+cts instead of three au-wide planes — at n=1024 the
        # undecided window is tens of thousands of lanes and this saves
        # megabytes per pull.
        rr_sel = rr_u[sel_l]

    # Post-pass device carries: the batch's rounds and this sync's rr
    # assignments stay resident, so the next pass uploads neither. Pad
    # lanes scatter past the carry (NOT to row e, which may be a live
    # pad row a later append will occupy) and are dropped.
    with jax.named_scope("pack"):
        uid_scatter = jnp.where(lane_ok, uid, rr_prev.shape[0])
        rr_all = rr_prev.at[uid_scatter].set(rr_u, mode="drop")

        # Only rows [t_start, t_start + tw) of the frontier tables can have
        # changed this sync; the host reconstructs the rest from its copy.
        wt_ret = lax.dynamic_slice(wt_tab, (t_start, 0), (tw, n))
        fr_ret = lax.dynamic_slice(fr_tab, (t_start, 0), (tw, n))

        packed = jnp.concatenate([
            t_end[None].astype(jnp.int32), newly_count[None],
            fame_steps[None], rr_steps[None],
            wt_ret.ravel(), fr_ret.ravel(),
            rnd_b, wit_b.astype(jnp.int32), famous_merged.ravel(),
            sel_l.astype(jnp.int32), rr_sel, med_hi, med_lo,
        ])
    return packed, rounds_all, rr_all


# Length of the packed delta's header: t_end, newly_count, fame_steps,
# rr_steps (_consensus_fused).
_PACK_HEAD = 4


# Shape-keys already prewarmed this process (see
# IncrementalEngine.prewarm): jit caches are process-global, so one
# warm engine covers every same-shaped sibling (a localhost testnet's
# nodes, a reset engine, tests that rebuild graphs per fixture).
_PREWARM_DONE: set = set()


@dataclass
class RunDelta:
    """What one run() call newly decided — the exact shape of the
    reference's per-RunConsensus side effects (node/core.go:277-296)."""

    new_rounds: List[Tuple[int, int, bool]] = field(default_factory=list)
    # (round, eid, famous) in host decide_fame order
    fame_updates: List[Tuple[int, int, bool]] = field(default_factory=list)
    # (eid, round_received, consensus_ts_ns), unsorted
    new_received: List[Tuple[int, int, int]] = field(default_factory=list)
    newly_decided_rounds: List[int] = field(default_factory=list)
    last_consensus_round: Optional[int] = None
    last_commited_round_events: int = 0


class PendingPass:
    """One dispatched-but-uncollected consensus pass.

    Created by dispatch(), consumed exactly once by collect() (or
    abandon()). Carries the pass SNAPSHOT (batch ids, sizes, chain
    lengths), the staged device inputs the redo loop re-dispatches
    against, and the in-flight device result handles. Everything here
    is immutable from the engine's point of view until collect — the
    double-buffer contract: appends landing while the pass is in
    flight go to the engine's fresh staging list, never this one.

    `pass_id` numbers the engine's passes; every span of the pass, on
    either thread, carries it. `phase_ns` collects the pass's phase
    times, published as the engine's `phase_ns` when collect returns.
    """

    __slots__ = (
        "pass_id", "phase_ns",
        "new_ids", "e", "cap0", "k0", "chain_len0",
        "chain_len_d", "la", "rb", "cr_d", "idx_d", "coin_d",
        "t0", "wt_prev", "fr_prev", "rel_rows",
        "e0_b", "bp", "rounds_up", "rr_up",
        "und", "und_up", "n_und", "au",
        "undecided_set", "rx0",
        "w_floor", "tw_floor", "rw", "iw", "cb", "tc", "tw", "rcap",
        "tw_i", "t_start", "rl", "counts",
        "packed_dev", "rounds_out", "rr_out",
        "dispatched_ns",
        "ready", "error",
    )


def _axis_size(mesh, axis) -> int:
    """Devices along a mesh axis name or a tuple of them."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    d = 1
    for a in names:
        d *= mesh.shape[a]
    return d


class IncrementalEngine:
    """Growable device-resident DAG + amortized consensus pipeline.

    append()/append_batch() stage events on the host (numpy mirrors with
    capacity doubling). The pass itself is split for pipelining:
    dispatch() snapshots the staged batch, enqueues every device step
    (growth pads, ingest, closure, fd fold, and the fused consensus
    epilogue) WITHOUT any device->host round trip, and returns a
    PendingPass immediately; collect() blocks only on the packed
    commit-delta pull, applies the host mirrors, and returns a
    RunDelta. run() = dispatch + collect, the synchronous spelling.
    While a pass is in flight appends keep landing in a fresh staging
    list (double buffering), so ingest of pass k+1 overlaps device
    compute of pass k. Query helpers serve from the host mirrors of
    the last collected pass.
    """

    def __init__(self, n: int, root_round=None, *, capacity: int = 256,
                 block: int = 256, k_capacity: int = 64,
                 index_base=None, from_reset: bool = False,
                 mesh=None, mesh_axis="sp", trace: Optional[SpanRing] = None):
        if n < 1:
            raise ValueError("need at least one participant")
        self.n = n
        # Spans of each pass (docs/observability.md): on this ring, and
        # in any profiler session on the device trace's clock.
        self.trace = trace if trace is not None else SpanRing(0)
        self._passes = 0
        self.sm = 2 * n // 3 + 1
        self.block = block
        self.root_round = (
            np.full(n, -1, np.int32) if root_round is None
            else np.asarray(root_round, np.int32).copy()
        )
        # Chain-position offset per creator: a frame root with
        # Root.index = k means the creator's next event has Go index
        # k+1 but chain position 0 (reference hashgraph.go:879-898).
        self.index_base = (
            np.zeros(n, np.int32) if index_base is None
            else np.asarray(index_base, np.int32).copy()
        )
        self.rho_min = int(self.root_round.min()) + 1
        self.cap = max(_pow2(capacity, block), block)
        self.kcap = _pow2(k_capacity, 8)

        self.e = 0
        c1 = self.cap + 1
        self.self_parent = np.full(c1, -1, np.int32)
        self.other_parent = np.full(c1, -1, np.int32)
        self.creator = np.zeros(c1, np.int32)
        self.index = np.full(c1, -1, np.int32)
        self.coin = np.zeros(c1, np.int8)
        self.root_base = np.full(c1, -1, np.int32)
        self.ts_ns = np.zeros(self.cap, np.int64)
        self.chain = np.full((n, self.kcap), -1, np.int32)
        self.chain_len = np.zeros(n, np.int32)

        # Results (host mirrors, -1 = undetermined).
        self.rounds = np.zeros(self.cap, np.int32)
        self.witness = np.zeros(self.cap, np.bool_)
        self.rr = np.zeros(self.cap, np.int32)  # pad rows 0: never assigned
        self.cts_ns = np.zeros(self.cap, np.int64)

        # Multi-chip option: a jax.sharding.Mesh places the resident
        # carries with NamedSharding — the O(E·n) coordinate table
        # partitioned on the participant axis, the chain tables and the
        # fd rank cube on the chain axis — and GSPMD partitions the
        # same jitted kernels across the mesh (semantics-preserving;
        # the compiler inserts the collectives), so a node's DAG
        # capacity scales with its chips instead of one chip's HBM.
        # O(E) 1-D int vectors stay replicated: they are small beside
        # the [cap, n] table, and every kernel gathers from them.
        self._mesh = mesh
        self._mesh_axis = mesh_axis
        # Devices the participant axis is split over; the round-received
        # sweep's lanes split over them too (_rr_chunked).
        self.devices = 1
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.devices = daxes = _axis_size(mesh, mesh_axis)
            if n % daxes:
                raise ValueError(
                    f"participants {n} must divide over {daxes} devices")
            self._shard_cols = NamedSharding(mesh, P(None, mesh_axis))
            self._shard_ch = NamedSharding(mesh, P(mesh_axis))
        else:
            self._shard_cols = self._shard_ch = None

        # Device carries. Coordinates plus everything the per-sync
        # pipeline would otherwise re-upload or recompute from scratch:
        # the event arrays (ingested by batch slice), the chain tables
        # (new rows only), and the fd rank cube (incremental
        # compare-and-count; see _tables_update). On a mesh each sharded
        # carry is made shard by shard on its devices: made whole on one
        # device and then split, the n=4096 coordinate table (8.6 GB at
        # capacity 524288) did not fit one v5e chip.
        cols, ch = self._shard_cols, self._shard_ch
        t_init = time.perf_counter_ns()
        self._la = jnp.full((c1, n), -1, jnp.int32, device=cols)
        self._rb = jnp.full((c1,), -1, jnp.int32)
        self._frozen_blocks = 0
        self._sp_d = jnp.full((c1,), -1, jnp.int32)
        self._op_d = jnp.full((c1,), -1, jnp.int32)
        self._cr_d = jnp.zeros((c1,), jnp.int32)
        self._idx_d = jnp.full((c1,), -1, jnp.int32)
        self._coin_d = jnp.zeros((c1,), jnp.int8)
        self._rb0_d = jnp.full((c1,), -1, jnp.int32)
        self._chain_d = jnp.full((n, self.kcap), -1, jnp.int32, device=ch)
        # Resident split-int64 timestamp planes (see _ts_split): written
        # once per event at ingest, read by the fused kernel's median —
        # the host never re-uploads per-pass timestamp ranks.
        self._chain_th = jnp.zeros((n, self.kcap), jnp.int32, device=ch)
        self._chain_tl = jnp.zeros((n, self.kcap), jnp.int32, device=ch)
        # Resident consensus-result carries (committed post-pull; the
        # pad fill mirrors nothing — every row is written by the pass
        # that first covers it before any read).
        self._rounds_d = jnp.full((self.cap,), -1, jnp.int32)
        self._rr_d = jnp.full((self.cap,), -1, jnp.int32)
        self._ranks = jnp.zeros((n, n, self.kcap), jnp.int32, device=ch)
        # chain_la/chain_rb could be re-gathered per run from la/chain,
        # but the gather materializes this same [n, K, n] cube
        # transiently anyway (the frontier consumes it),
        # and at n=1024 it would re-read ~2 GB of HBM per sync; keeping
        # it resident costs the same peak memory and only writes the
        # new chain suffix rows.
        self._chain_la = jnp.full((n, self.kcap, n), INT32_MAX, jnp.int32,
                                  device=ch)
        self._chain_rb = jnp.full((n, self.kcap), INT32_MAX, jnp.int32,
                                  device=ch)
        self.trace.record("engine.init", t_init, time.perf_counter_ns(),
                          cat="engine.init", n=n, devices=self.devices,
                          capacity=self.cap, k_capacity=self.kcap)
        self._e_counted = 0
        self._len_counted = np.zeros(n, np.int32)

        # Frontier checkpoint: relative rows rho_min + t.
        self._fr_table = np.zeros((0, n), np.int32)
        self._wt_table = np.full((0, n), -1, np.int32)
        self._chain_len_prev = np.zeros(n, np.int32)

        # Fame / round-received bookkeeping (reference
        # hashgraph.go:629-637: queued-once, removed-once). A fresh
        # graph starts with round 0 queued (reference hashgraph.go
        # NewHashgraph); a frame-reset graph starts empty (the host
        # mirror's reset() clears the list) and re-queues rounds as
        # replayed events land.
        self.famous = np.zeros((0, n), np.int32)  # [r_total, n] trilean
        if from_reset:
            self.undecided_rounds: List[int] = []
            self._queued_rounds: set = set()
        else:
            self.undecided_rounds = [0]
            self._queued_rounds = {0}
        self._prev_first_undec = self.rho_min
        self._last_growth = 8  # rounds added by the previous run
        self._last_newly = 64  # round-received burst size of the last run
        self.last_consensus_round: Optional[int] = None

        self._new_since_run: List[int] = []
        self._empty_delta_ok = False  # True when state is at a fixpoint
        # The at-most-one in-flight pass (see PendingPass): dispatch
        # sets it, collect/abandon clear it. Pass k+1's window inputs
        # read pass k's committed carries, so two passes can never
        # overlap on device.
        self._inflight: Optional[PendingPass] = None
        # Staging worker (see dispatch()): device enqueues happen on a
        # dedicated thread because enqueue itself can block the caller
        # — the CPU client throttles at a fixed in-flight computation
        # count, and a TPU can block on transfer backpressure — and the
        # whole point of the async pipeline is that the host
        # never waits except at delta-fetch.
        self._stage_q: Optional[queue.Queue] = None
        self._stage_thread: Optional[threading.Thread] = None
        self._stage_lock = threading.Lock()
        # fd-rank fold variant: the broadcast compare-and-count streams
        # on the MXU; every other backend takes the histogram form
        # (FLOP count lower by the batch factor; scatter-add is fine
        # off-TPU).
        backend = jax.default_backend()
        # Kernel selection: donating forms on a single device (in-place
        # carry reuse), non-donating twins under a mesh where GSPMD's
        # resharded outputs make donation unusable (the XLA "donated
        # buffers were not usable" warning — ROADMAP item).
        donate = self._mesh is None
        if backend == "tpu":
            self._tables_fn = _tables_update if donate else _tables_update_nd
        else:
            self._tables_fn = (
                _tables_update_hist if donate else _tables_update_hist_nd)
        self._k_closure = _closure_update if donate else _closure_update_nd
        self._k_ingest = _ingest if donate else _ingest_nd
        self._k_chain_ingest = _chain_ingest if donate else _chain_ingest_nd
        # Window-floor ceiling: the big floors exist to collapse the
        # fused kernel's compile space on the TPU, where every distinct
        # static shape stalls the node for a compile (how long on the
        # v5e chip is not measured). Off-TPU a compile is a couple of
        # cached-persistent seconds,
        # and the fame/rr loops cost per SEQUENTIAL STEP — a 128-row
        # floor at n=64 runs ~10x more steps than the actual round
        # movement needs. Small floor => tight windows => the fused
        # kernel's step count tracks real work.
        self._w_floor_max = 256 if backend == "tpu" else 16
        # Overlap diagnostics of the last collected pass: wall between
        # dispatch return and collect entry (work the device did while
        # the host was free), and the blocking share of the pull.
        self.last_overlap_ns = 0

        # Host wall (ns) of the phases of the last collected pass, from
        # the stamps of its spans (node/core.go:278-296 logs the same
        # phases). Worker thread, around asynchronous enqueues: coords
        # (engine.ingest + engine.closure), fd_fold (engine.tables),
        # stage (engine.windows); c_dispatch sums engine.fused_enqueue
        # (worker and redos). Caller: c_stage_wait, c_pull_wait,
        # c_pull_xfer, c_pull (wait + xfer), apply (engine.apply), and
        # consensus: engine.collect outside those and the redos (the
        # redo decisions).
        self.phase_ns: dict = {}
        # Counts of the last collected pass, published with phase_ns:
        # au (the undecided-window bucket swept), n_und (its live
        # lanes), iw (the round window's rows), rr_chunks (chunks of
        # the round-received sweep), ts_chunks (chunks of the timestamp
        # loop), fame_steps and rr_steps (steps the fame vote loop and
        # the sweep ran, of iw - 1 and iw at most), all of the final
        # dispatch.
        self.pass_counts: dict = {}
        # Compiled-cost attribution (docs/observability.md "Device
        # profiling"): request_cost_report() arms a one-shot capture;
        # the next fused-epilogue dispatch AOT-lowers the kernel with
        # the pass's exact shapes and stores cost_analysis() FLOPs /
        # bytes here (served by /debug/profile?cost=1 and exported as
        # gauges). Off unless requested — lower+compile is a cache hit
        # in steady state but still not free.
        self.cost_report: Optional[dict] = None
        self._cost_requested = False
        # Bytes of the last commit-delta pull (the c_pull transfer).
        self.c_pull_bytes = 0
        # Redo dispatches over the engine's lifetime (window/cadence
        # tuning diagnostic; deliberately NOT in phase_ns, whose values
        # are nanoseconds).
        self.redo_count = 0

    # -- mesh placement -----------------------------------------------------

    def _put_cols(self, a):
        """Place a [cap, n] carry with its participant columns sharded."""
        if self._shard_cols is None:
            return a
        return jax.device_put(a, self._shard_cols)

    def _put_ch(self, a):
        """Place a chain-axis carry (axis 0 = creator)."""
        if self._shard_ch is None:
            return a
        return jax.device_put(a, self._shard_ch)

    def _constrain_carries(self) -> None:
        """Re-pin the resident carries to their mesh shardings. The
        jitted kernels usually propagate input shardings to the donated
        outputs, but GSPMD is free to choose otherwise; device_put is a
        no-op when the sharding already matches, so this only ever
        copies after an actual drift."""
        if self._mesh is None:
            return
        self._la = self._put_cols(self._la)
        self._chain_d = self._put_ch(self._chain_d)
        self._chain_th = self._put_ch(self._chain_th)
        self._chain_tl = self._put_ch(self._chain_tl)
        self._ranks = self._put_ch(self._ranks)
        self._chain_la = self._put_ch(self._chain_la)
        self._chain_rb = self._put_ch(self._chain_rb)

    # -- append ------------------------------------------------------------

    def append(self, sp: int, op: int, creator: int, index: int,
               coin: bool, ts_ns: int) -> int:
        """Append one event; parents are engine ids (-1 = root). Returns
        the event id. `index` is the event's Go index; the engine works
        in chain positions (index - index_base[creator]). Enforces the
        reference's insert discipline: index must extend the creator's
        chain contiguously (fork/foreign events are rejected upstream,
        hashgraph.go:404-445)."""
        index = index - int(self.index_base[creator])
        if index != int(self.chain_len[creator]):
            raise ValueError(
                f"non-contiguous position {index} for creator {creator} "
                f"(chain length {int(self.chain_len[creator])})"
            )
        expect_sp = self.chain[creator, index - 1] if index > 0 else -1
        if sp != int(expect_sp):
            raise ValueError("self-parent is not the creator's head")
        if self.e == self.cap:
            self._grow_capacity()
        if index == self.kcap:
            self._grow_chains()
        i = self.e
        self.self_parent[i] = sp
        self.other_parent[i] = op
        self.creator[i] = creator
        self.index[i] = index
        self.coin[i] = 1 if coin else 0
        self.root_base[i] = (
            self.root_round[creator] + 1 if (sp < 0 or op < 0) else -1
        )
        self.ts_ns[i] = ts_ns
        self.chain[creator, index] = i
        self.chain_len[creator] += 1
        self.rounds[i] = -1
        self.witness[i] = False
        self.rr[i] = -1
        self.cts_ns[i] = CTS_SENTINEL
        self.e += 1
        self._new_since_run.append(i)
        self._empty_delta_ok = False
        return i

    def append_batch(self, sp, op, creator, index, coin, ts_ns) -> int:
        """Vectorized append of a whole batch: one numpy slice
        assignment per staging column instead of a Python `append` per
        event — the device-direct ingest seam the columnar gossip wire
        lands on (docs/ingest.md). Semantics identical to the serial
        loop: per-creator contiguity and self-parent-is-head are
        enforced for every row, including rows whose parent is earlier
        in the same batch. Returns the first assigned event id (the
        batch occupies ids [first, first + len)); raises ValueError
        with NOTHING appended on an invalid batch — stricter than the
        serial loop's valid-prefix insert, and the host-side parent
        checks upstream (graph / tpu_graph) keep invalid rows from
        reaching here."""
        m = len(sp)
        if m == 0:
            return self.e
        if m == 1:
            return self.append(int(sp[0]), int(op[0]), int(creator[0]),
                               int(index[0]), bool(coin[0]), int(ts_ns[0]))
        sp = np.asarray(sp, np.int64)
        op = np.asarray(op, np.int64)
        cr = np.asarray(creator, np.int64)
        idx = np.asarray(index, np.int64)
        coin = np.asarray(coin)
        ts = np.asarray(ts_ns, np.int64)

        pos = idx - self.index_base[cr]
        # Occurrence rank of each row within its creator group (stable):
        # the j-th batch row of a creator must land at chain position
        # chain_len[creator] + j, exactly like j serial appends.
        order = np.argsort(cr, kind="stable")
        scr = cr[order]
        new_group = np.r_[True, scr[1:] != scr[:-1]]
        group_start = np.flatnonzero(new_group)
        group_sizes = np.diff(np.r_[group_start, m])
        occ_sorted = np.arange(m) - np.repeat(group_start, group_sizes)
        occ = np.empty(m, np.int64)
        occ[order] = occ_sorted
        expect_pos = self.chain_len[cr] + occ
        if not np.array_equal(pos, expect_pos):
            k = int(np.flatnonzero(pos != expect_pos)[0])
            raise ValueError(
                f"non-contiguous position {int(pos[k])} for creator "
                f"{int(cr[k])} (expected {int(expect_pos[k])})")

        # Grow BEFORE the head gather below (a chain position past the
        # current bucket size would otherwise read out of bounds).
        # Growing host mirrors is side-effect-free for a batch that
        # then fails validation: capacity is not observable state.
        while self.e + m > self.cap:
            self._grow_capacity()
        while int(pos.max()) >= self.kcap:
            self._grow_chains()

        # Self-parent must be the creator's head at that point: the
        # stored chain tip for a creator's first batch row, the
        # previous batch row's id (e0 + row) for later ones.
        e0 = self.e
        expect_sp = np.where(
            pos > 0, self.chain[cr, np.maximum(pos, 1) - 1], -1)
        prev_row = np.empty(m, np.int64)
        prev_row[order] = np.r_[-1, order[:-1]]
        in_batch = occ > 0
        expect_sp[in_batch] = e0 + prev_row[in_batch]
        if not np.array_equal(sp, expect_sp):
            raise ValueError("self-parent is not the creator's head")

        lo, hi = e0, e0 + m
        self.self_parent[lo:hi] = sp
        self.other_parent[lo:hi] = op
        self.creator[lo:hi] = cr
        self.index[lo:hi] = pos
        self.coin[lo:hi] = np.where(coin, 1, 0)
        self.root_base[lo:hi] = np.where(
            (sp < 0) | (op < 0), self.root_round[cr] + 1, -1)
        self.ts_ns[lo:hi] = ts
        self.chain[cr, pos] = np.arange(lo, hi, dtype=np.int32)
        np.add.at(self.chain_len, scr[new_group],
                  group_sizes.astype(np.int32))
        self.rounds[lo:hi] = -1
        self.witness[lo:hi] = False
        self.rr[lo:hi] = -1
        self.cts_ns[lo:hi] = CTS_SENTINEL
        self.e = hi
        self._new_since_run.extend(range(lo, hi))
        self._empty_delta_ok = False
        return e0

    def _grow_capacity(self) -> None:
        new_cap = self.cap * 2
        c1 = new_cap + 1

        def regrow(a, fill, dtype):
            out = np.full(c1, fill, dtype)
            out[: self.cap] = a[: self.cap]
            return out

        self.self_parent = regrow(self.self_parent, -1, np.int32)
        self.other_parent = regrow(self.other_parent, -1, np.int32)
        self.creator = regrow(self.creator, 0, np.int32)
        self.index = regrow(self.index, -1, np.int32)
        self.coin = regrow(self.coin, 0, np.int8)
        self.root_base = regrow(self.root_base, -1, np.int32)
        for name, fill, dtype in (
            ("ts_ns", 0, np.int64), ("rounds", 0, np.int32),
            ("witness", False, np.bool_), ("rr", 0, np.int32),
            ("cts_ns", 0, np.int64),
        ):
            out = np.full(new_cap, fill, dtype)
            out[: self.cap] = getattr(self, name)[: self.cap]
            setattr(self, name, out)
        # Device carries grow lazily at the next run() (_sync_device):
        # appends touch only host mirrors, so growth never costs a
        # device round trip here.
        self.cap = new_cap

    def _grow_chains(self) -> None:
        new_k = self.kcap * 2
        chain = np.full((self.n, new_k), -1, np.int32)
        chain[:, : self.kcap] = self.chain
        self.chain = chain
        self.kcap = new_k

    # -- the incremental pipeline -----------------------------------------

    @property
    def _cap_dev(self) -> int:
        """Device-side event capacity, derived from the carry shapes so
        it can never desynchronize from the buffers it describes."""
        return self._la.shape[0] - 1

    @property
    def _kcap_dev(self) -> int:
        return self._chain_d.shape[1]

    def _sync_device(self, cap_t: Optional[int] = None,
                     kcap_t: Optional[int] = None, pass_id: int = 0) -> None:
        """Bring the device carries up to the host mirrors' capacity and
        chain-bucket sizes (appends grow host state only). All growth is
        device-side concatenation — no device->host round trips.

        `cap_t`/`kcap_t` (default: the live fields) let a pass grow to
        its SNAPSHOT sizes: the pass may run outside the caller's lock,
        and a concurrent append crossing a growth boundary must not
        change this pass's kernel shapes mid-flight. Each doubling runs
        under an `engine.grow` span of the pass (`what`, `old`, `new`)."""
        if cap_t is None:
            cap_t = self.cap
        if kcap_t is None:
            kcap_t = self.kcap

        def grow(what, old):
            return self.trace.span("engine.grow", cat="engine",
                                   pass_id=pass_id, what=what, old=old,
                                   new=2 * old)

        while self._cap_dev < cap_t:
            rows = self._cap_dev  # double
            with grow("capacity", rows):
                self._la = _pad_rows(self._la, rows=rows, fill=-1)
                self._rb = _pad_rows(self._rb, rows=rows, fill=-1)
                self._sp_d = _pad_rows(self._sp_d, rows=rows, fill=-1)
                self._op_d = _pad_rows(self._op_d, rows=rows, fill=-1)
                self._cr_d = _pad_rows(self._cr_d, rows=rows, fill=0)
                self._idx_d = _pad_rows(self._idx_d, rows=rows, fill=-1)
                self._coin_d = _pad_rows(self._coin_d, rows=rows, fill=0)
                self._rb0_d = _pad_rows(self._rb0_d, rows=rows, fill=-1)
        while self._rounds_d.shape[0] < cap_t:
            rows = self._rounds_d.shape[0]  # double
            self._rounds_d = _pad_rows(self._rounds_d, rows=rows, fill=-1)
            self._rr_d = _pad_rows(self._rr_d, rows=rows, fill=-1)
        while self._kcap_dev < kcap_t:
            cols = self._kcap_dev  # double
            with grow("chains", cols):
                self._ranks = _pad_ranks(
                    self._ranks, jnp.asarray(self._len_counted), cols=cols)
                self._chain_la = _pad_cols(self._chain_la, cols=cols,
                                           fill=INT32_MAX, axis=1)
                self._chain_d = _pad_cols(self._chain_d, cols=cols, fill=-1)
                self._chain_th = _pad_cols(self._chain_th, cols=cols, fill=0)
                self._chain_tl = _pad_cols(self._chain_tl, cols=cols, fill=0)
                self._chain_rb = _pad_cols(self._chain_rb, cols=cols,
                                           fill=INT32_MAX)

    def _ingest_batch(self, e: int, chain_len0: np.ndarray):
        """Stage the events appended since the last run into the device
        carries: event-array slices at [e0, e), the per-creator new-event
        table into chain/coordinate tables, and the fd rank cube.

        `e`/`chain_len0` are the pass SNAPSHOT: this may run outside
        the caller's lock, and appends landing mid-call only ever touch
        rows at or beyond the snapshot, so every read below (local refs
        — the growth helpers replace the arrays rather than resizing
        them) sees stable values."""
        n = self.n
        sp_h, op_h = self.self_parent, self.other_parent
        cr_h, idx_h = self.creator, self.index
        coin_h, rb0_h = self.coin, self.root_base
        chain_h, ts_h = self.chain, self.ts_ns
        e0 = self._e_counted
        if e0 == e:
            return
        b = e - e0
        # Coarse floor-1024 x4 buckets: live-node syncs are small and
        # varied; collapsing them into few batch buckets avoids a
        # compile per distinct size (padding costs only KBs of upload).
        bp = _pow4(b, 1024)
        while e0 + bp > self._cap_dev + 1 and bp > b:
            bp //= 2
        if bp < b:
            bp = b  # rare near-capacity tail; exact-size compile

        def slc(a, fill, dtype):
            out = np.full(bp, fill, dtype)
            out[:b] = a[e0:e]
            return jnp.asarray(out)

        self._sp_d, self._op_d, self._cr_d, self._idx_d, self._coin_d, \
            self._rb0_d = self._k_ingest(
                self._sp_d, self._op_d, self._cr_d, self._idx_d,
                self._coin_d, self._rb0_d,
                slc(sp_h, -1, np.int32),
                slc(op_h, -1, np.int32),
                slc(cr_h, 0, np.int32),
                slc(idx_h, -1, np.int32),
                slc(coin_h, 0, np.int8),
                slc(rb0_h, -1, np.int32),
                jnp.int32(e0), bp=bp)

        # Per-creator new-event table: each creator's new events are the
        # suffix of its chain added since the last fold.
        new_lens = chain_len0 - self._len_counted
        # x4 buckets for the same compile-space reason as bp above.
        m = _pow4(int(new_lens.max()), 16)
        newtab = np.full((n, m), -1, np.int32)
        newpos = np.zeros((n, m), np.int32)
        newhi = np.zeros((n, m), np.int32)
        newlo = np.zeros((n, m), np.int32)
        for c in np.nonzero(new_lens)[0]:
            l0, l1 = int(self._len_counted[c]), int(chain_len0[c])
            ids = chain_h[c, l0:l1]
            newtab[c, : l1 - l0] = ids
            newpos[c, : l1 - l0] = np.arange(l0, l1)
            newhi[c, : l1 - l0], newlo[c, : l1 - l0] = _ts_split(
                ts_h[ids])
        self._newtab_d = jnp.asarray(newtab)
        self._newpos_d = jnp.asarray(newpos)
        self._new_m = m
        self._chain_d, self._chain_th, self._chain_tl = self._k_chain_ingest(
            self._chain_d, self._chain_th, self._chain_tl,
            self._newtab_d, self._newpos_d,
            jnp.asarray(newhi), jnp.asarray(newlo), n=n, m=m)

    def run(self, *, unlocked=None) -> RunDelta:
        """Run one synchronous incremental consensus pass:
        dispatch() + collect() back to back.

        `unlocked` (optional): a context manager factory. When given,
        the engine releases it around the device sections — a live
        node passes a core-lock release so gossip keeps inserting at
        wire speed while the chip computes. This is safe because the
        pass operates on a SNAPSHOT taken under the lock: the batch
        ids, e/cap/kcap, and chain lengths are captured before
        dispatch, every device input is uploaded before the wait, and
        the post-pull mirror section only touches state that
        concurrent append() never reads or writes.
        """
        pp = self.dispatch(unlocked=unlocked)
        if pp is None:
            return RunDelta(last_consensus_round=self.last_consensus_round)
        return self.collect(pp, unlocked=unlocked)

    # -- the async pipeline: dispatch / collect -----------------------------

    def dispatch(self, *, unlocked=None) -> Optional[PendingPass]:
        """Snapshot the appended batch and hand one full consensus
        pass — growth pads, ingest, closure, fd fold, and the fused
        commit-delta epilogue — to the staging worker thread, returning
        a PendingPass IMMEDIATELY. Returns None when there is nothing
        to do.

        The device enqueues happen off-thread because enqueue itself
        can block the caller (the CPU client throttles at a fixed
        in-flight computation count; a TPU can block on transfer
        backpressure), and the pipeline's contract is that the host
        waits only at delta-fetch. `unlocked` is accepted for API
        symmetry with collect() but unused — dispatch no longer does
        anything slow under the caller's lock.

        At most one pass may be in flight: the epilogue's window
        inputs read the previous pass's COMMITTED result carries, and
        commit happens in collect(). While the pass is in flight,
        append() keeps staging into a fresh list (double buffering),
        so ingest of pass k+1 overlaps device compute of pass k.
        """
        del unlocked
        if self._inflight is not None:
            raise RuntimeError("a consensus pass is already in flight")
        if self.e == 0 or (self._empty_delta_ok and not self._new_since_run):
            # No-op dispatches must not leave stale phase timings for
            # callers that aggregate them (node/core.py).
            self.phase_ns = {}
            return None
        new_ids = self._new_since_run
        self._new_since_run = []
        try:
            pp = PendingPass()
            self._passes += 1
            pp.pass_id = self._passes
            pp.phase_ns = {}
            pp.new_ids = new_ids
            # Snapshot (see run() docstring): the staging worker and
            # collect must use these, not the live fields, since
            # appends interleave with everything past this point.
            pp.e = self.e
            pp.cap0, pp.k0 = self.cap, self.kcap
            pp.chain_len0 = self.chain_len.copy()
            pp.ready = threading.Event()
            pp.error = None
            self._submit_stage(pp)
        except BaseException:
            # Retry safety: a transient failure must not orphan the
            # batch's host mirroring — restore the snapshot so the
            # next pass redoes it.
            self._new_since_run = new_ids + self._new_since_run
            raise
        self._inflight = pp
        return pp

    def _submit_stage(self, pp: PendingPass) -> None:
        with self._stage_lock:
            if self._stage_thread is None or not self._stage_thread.is_alive():
                self._stage_q = queue.Queue()
                self._stage_thread = threading.Thread(
                    target=self._stage_worker, args=(self._stage_q,),
                    daemon=True, name="babble-engine-stager")
                self._stage_thread.start()
            self._stage_q.put(pp)

    def _stage_worker(self, q: "queue.Queue") -> None:
        while True:
            try:
                pp = q.get(timeout=60.0)
            except queue.Empty:
                # Idle exit (bench/test engines come and go); the
                # submit path restarts a worker on demand. The lock
                # makes exit-vs-put atomic: a pass put while we decide
                # is either seen here or starts a fresh worker.
                with self._stage_lock:
                    if not q.empty():
                        continue
                    if self._stage_thread is threading.current_thread():
                        self._stage_thread = None
                    return
            if pp is None:
                return
            try:
                self._stage_pass(pp)
            except BaseException as exc:  # noqa: BLE001 - relayed to collect
                pp.error = exc
            finally:
                pp.ready.set()

    def close(self) -> None:
        """Stop the staging worker (idle workers also exit on their
        own). Safe to call repeatedly; a later dispatch restarts it."""
        with self._stage_lock:
            if self._stage_thread is not None and self._stage_q is not None:
                self._stage_q.put(None)
                self._stage_thread = None

    def collect(self, pp: Optional[PendingPass], *,
                unlocked=None) -> RunDelta:
        """Fetch the commit delta of an in-flight pass — the ONE
        blocking device->host wait of the pass — apply the host
        mirrors, commit the device result carries, and return the
        RunDelta. Window-overflow redos re-dispatch the fused epilogue
        from the snapshot still held by the PendingPass."""
        if pp is None:
            return RunDelta(last_consensus_round=self.last_consensus_round)
        if pp is not self._inflight:
            raise RuntimeError("collect() of a pass that is not in flight")
        self._inflight = None
        try:
            return self._collect_pass(pp, unlocked)
        except BaseException:
            self._new_since_run = pp.new_ids + self._new_since_run
            raise

    def abandon(self, pp: Optional[PendingPass]) -> None:
        """Drop an in-flight pass without applying it: the batch goes
        back to the staging list and the next pass redoes it — the same
        contract as the exception paths (result carries are only ever
        committed by a successful collect)."""
        if pp is None or pp is not self._inflight:
            return
        self._inflight = None
        self._new_since_run = pp.new_ids + self._new_since_run

    @property
    def inflight(self) -> bool:
        return self._inflight is not None

    def _stage_pass(self, pp: PendingPass) -> None:
        """The staging half of a pass, run on the worker thread: parts
        0-2 (device sync-up, ingest, closure, fd fold), the window
        derivation, and the fused-epilogue dispatch. Reads only the
        pass snapshot plus host state that collect alone mutates —
        concurrent append() is safe by the snapshot discipline (see
        run() docstring). Its phase times are host wall around
        asynchronous enqueues; the device's share of each stage is read
        from a profiler trace."""
        n, e, cap0 = self.n, pp.e, pp.cap0
        with self._span(pp, "engine.stage", events=len(pp.new_ids)):
            # 0. Device sync-up: lazy capacity growth, then ingest the
            # new batch into the resident event arrays and chain table.
            # All dispatches are async — nothing here round-trips. Under
            # a mesh, re-pin the carries first (growth concats and
            # kernel outputs may drift from the intended shardings).
            with self._span(pp, "engine.ingest") as ing:
                self._sync_device(cap0, pp.k0, pp.pass_id)
                self._constrain_carries()
                self._ingest_batch(e, pp.chain_len0)
                pp.chain_len_d = jnp.asarray(pp.chain_len0)
                pp.cr_d = self._cr_d
                pp.idx_d = self._idx_d
                pp.coin_d = self._coin_d

            # 1. Coordinates: only blocks the frozen prefix doesn't cover.
            with self._span(pp, "engine.closure") as clo:
                nb = (e + self.block - 1) // self.block
                self._la, self._rb = self._k_closure(
                    self._la, self._rb, self._sp_d, self._op_d, pp.cr_d,
                    pp.idx_d, self._rb0_d, jnp.int32(self._frozen_blocks),
                    jnp.int32(nb), n=n, block=self.block)
                self._frozen_blocks = e // self.block
                pp.la = self._la[:cap0]
                pp.rb = self._rb[:cap0]

            # 2. First descendants from the resident rank cube, folding
            # the batch first (incremental compare-and-count — per-sync
            # cost scales with the batch, not E; see _tables_update /
            # _tables_update_hist, picked per backend at construction).
            # fd is consumed as lazy row gathers from the rank cube
            # inside the fused kernel (_FdRows) — no [cap, n]
            # materialization.
            with self._span(pp, "engine.tables") as tab:
                if self._e_counted < e:
                    self._ranks, self._chain_la, self._chain_rb = (
                        self._tables_fn(
                            self._ranks, self._chain_la, self._chain_rb,
                            self._la, self._rb, self._newtab_d,
                            self._newpos_d, n=n, m=self._new_m))
                    self._e_counted = e
                    self._len_counted = pp.chain_len0.copy()

            with self._span(pp, "engine.windows") as win:
                self._derive_windows(pp)
            self._dispatch_fused(pp)
        pp.phase_ns.update(coords=clo.t1 - ing.t0, fd_fold=tab.t1 - tab.t0,
                           stage=win.t1 - win.t0)
        pp.dispatched_ns = time.perf_counter_ns()

    def _span(self, pp: PendingPass, name: str, **args):
        """A span of pass `pp` (docs/observability.md "The trace ring")."""
        return self.trace.span(name, cat="engine", pass_id=pp.pass_id,
                               **args)

    def _derive_windows(self, pp: PendingPass) -> None:
        """3-6. Frontier, new-event rounds, fame, and round-received run
        in ONE device dispatch with ONE packed pull (_consensus_fused):
        every device->host sync costs a full round trip, so the windows
        the host used to build between pulls are derived on device from
        host bookkeeping tables. This sets the pass's window geometry
        and inputs."""
        n, e, cap0 = self.n, pp.e, pp.cap0
        new_ids, chain_len0 = pp.new_ids, pp.chain_len0
        rel_rows = len(self._fr_table)
        if rel_rows:
            # A row can only change when a chain it is still waiting on
            # GROWS: frozen-row stability (module docstring) means old
            # positions never newly strongly-see, so row t is affected
            # only by chains c with fr[t, c] at/beyond the last-seen
            # end AND new events this sync. Without the `grew` mask a
            # single lagging peer marks every row past its head
            # permanently growable, and each pass re-sweeps hundreds of
            # rounds — a death spiral in a live testnet (slow passes ->
            # more lag -> longer sweeps). With it, the catch-up cost is
            # paid once, in the sync where the laggard's events arrive.
            grew = chain_len0 > self._chain_len_prev
            growable = (
                (self._fr_table >= self._chain_len_prev[None, :])
                & grew[None, :]
            ).any(axis=1)
            t0 = int(np.argmax(growable)) if growable.any() else rel_rows
        else:
            t0 = 0
        pp.rel_rows = rel_rows
        pp.t0 = t0
        if t0 > 0:
            pp.wt_prev = jnp.asarray(self._wt_table[t0 - 1])
            pp.fr_prev = jnp.asarray(self._fr_table[t0 - 1])
        else:
            pp.wt_prev = jnp.full((n,), -1, jnp.int32)
            pp.fr_prev = jnp.zeros((n,), jnp.int32)

        # Batch range for device-side round assignment (contiguous ids;
        # same coarse bucketing as _ingest_batch so live-node syncs
        # share one compile).
        e0_b = new_ids[0] if new_ids else e
        b_new = e - e0_b
        bp = _pow4(max(b_new, 1), 1024)
        # Bound by cap (not cap+1): the kernel's rounds/rr vectors are
        # cap long, and a clamped dynamic_update_slice would silently
        # shift every batch round one slot down.
        while e0_b + bp > cap0 and bp > b_new:
            bp //= 2
        if bp < max(b_new, 1):
            bp = max(b_new, 1)
        pp.e0_b = e0_b
        pp.bp = bp

        pp.undecided_set = set(self.undecided_rounds)
        # rounds/rr live on device (committed by the previous pass);
        # _sync_device grew them to self.cap = cap0 above.
        pp.rounds_up = self._rounds_d
        pp.rr_up = self._rr_d

        # Undecided-event window for the round-received sweep: decided
        # events never change, so the kernel's per-round pass compares
        # against this compacted id set instead of all E events.
        und = np.nonzero(self.rr[:e] < 0)[0].astype(np.int32)
        # x4 buckets: at the n=1024 north star the undecided window
        # grows monotonically to ~cap/2, and pow2 breathing would
        # recompile the fused kernel at every doubling.
        au = _pow4(len(und), 4096)
        und_p = np.zeros(au, np.int32)
        und_p[: len(und)] = und
        pp.und = und
        pp.au = au
        pp.und_up = jnp.asarray(und_p)
        pp.n_und = jnp.int32(len(und))

        # Fame/rr window widths: the spans actually needed, not the
        # table capacity. W bounds the shapes, not the steps: the fame
        # vote loop and the rr sweep stop at the window's last live or
        # eligible row, but each fame step's vote planes are [n, W, n]
        # and its matmul [n, n]@[n, W*n], so a wider window still costs
        # in every step. The widths are PREDICTED from the previous
        # run's observed round growth (doubled, so steady state never
        # redoes); the post-pull checks below are the safety net — a
        # misprediction or a straggler batch (i0 below the known
        # rounds) costs one redo dispatch, never correctness.
        growth = 2 * self._last_growth + 2
        # Empty-queue fallback: _prev_first_undec, NOT beyond the table —
        # an empty list means either a fresh reset (first undecided round
        # is rho_min) or a fixpoint (= r_total); in both cases rounds
        # discovered THIS run must land inside the fame window so fame
        # is decided in the same call, like the host's
        # divide_rounds->decide_fame sequence.
        rx0_known = (
            self.undecided_rounds[0]
            if self.undecided_rounds else self._prev_first_undec)
        i0_known = min(self._prev_first_undec, rx0_known)
        # ONE shared round-window size W for the fame span, the rr
        # span, and the returned table rows: they track the same
        # per-pass round movement, and collapsing them to a single
        # static dimension collapses the kernel's compile space
        # (observed live: 57 fused-kernel compiles per process with
        # independent dims, each stalling every node's dispatches).
        # n-scaled floors: at small n rounds arrive fast (a round
        # per ~n events), so the windows and the round table breathe
        # through many pow2 sizes — each a compile. The floors pin
        # them to their realistic ceiling where that is cheap (the
        # arrays scale with n) and stay tight at large n.
        # Large n => few, wide rounds: the fame step is a
        # [n, n]@[n, W*n] contraction per row, so an oversized W
        # floor multiplies real FLOPs in each step run; small n =>
        # fast, many rounds, where a big floor only pads cheap tiny
        # rows (the loops never step over the empty ones) but saves a
        # compile per pow2 step.
        w_floor = max(16, min(self._w_floor_max, (1 << 13) // n))
        pp.w_floor = w_floor
        pp.rw = pp.iw = _pow2(
            max(self.rho_min + rel_rows - rx0_known,
                self.rho_min + rel_rows - i0_known,
                rel_rows - t0, 1) + growth, w_floor)
        pp.rx0 = rx0_known
        # Consensus-timestamp bucket: syncs usually receive about a
        # batch worth of events; a late fame decision can release a
        # backlog, detected post-pull (newly_count) and redone bigger.
        # _last_newly keeps the bucket sticky across bursty stretches.
        # (cb never needs to exceed the undecided window: newly-received
        # events are a subset of it.)
        # (no 2*b_new term: batch-size breathing must not multiply
        # into the cb compile dimension; a burst costs one redo and
        # then sticks via _last_newly.)
        pp.cb = min(_pow2(max(self._last_newly, 1024)), cap0, au)
        # Returned frontier-table rows: their own pow2 size with a
        # large-n floor below W — at n=1024 the [tw, n] x2 planes
        # dominate the pull, and the actually-rewritten span is a
        # handful of rows; at small n the floor equals W's, so no
        # extra compile combo appears where W already breathes.
        pp.tw_floor = tw_floor = max(16, min(w_floor, (1 << 14) // n))
        pp.tw = min(pp.rw, _pow2(
            max(rel_rows - t0, 1) + growth, tw_floor))

        # Floor 64: each distinct rcap is a static shape of the fused
        # kernel, and a recompile stalls a sync — a long-running node would otherwise recompile at
        # every 16->32->64 table growth. The extra packed-pull bytes
        # (2*rcap*n int32) are sub-millisecond even at n=1024.
        pp.rcap = _pow2(rel_rows + 8,
                        max(64, min(2048, (1 << 16) // n)))

    def _dispatch_fused(self, pp: PendingPass) -> None:
        """Build the window tables from host bookkeeping and enqueue
        the fused consensus epilogue for the pass's CURRENT window
        sizes. Called once by dispatch() and again by collect() on a
        window-overflow redo; reads only host state that collect alone
        mutates, so a redo between dispatch and collect sees exactly
        the staging-time values."""
        with self._span(pp, "engine.fused_enqueue") as enq:
            fused_args, fused_kw = self._fused_inputs(pp)
            pp.packed_dev, pp.rounds_out, pp.rr_out = _consensus_fused(
                *fused_args, **fused_kw)
        pp.phase_ns["c_dispatch"] = (
            pp.phase_ns.get("c_dispatch", 0) + enq.t1 - enq.t0)
        if self._cost_requested:
            # One-shot: an overflow redo of the same pass re-arms only
            # if the operator asks again.
            self._cost_requested = False
            self.cost_report = self._analyze_cost(fused_args, fused_kw)

    def _fused_inputs(self, pp: PendingPass):
        """The fused epilogue's arguments and static sizes for the
        pass's current windows."""
        n, sm = self.n, self.sm
        rcap = pp.rcap
        wt_tab = np.full((rcap, n), -1, np.int32)
        fr_tab = np.full((rcap, n), pp.k0, np.int32)
        wt_tab[:pp.t0] = self._wt_table[:pp.t0]
        fr_tab[:pp.t0] = self._fr_table[:pp.t0]
        # rho_min-relative round bookkeeping from the PREVIOUS run:
        # fame trileans, queued state (rows beyond the known rounds
        # default to queued — a new round is queued when its first
        # event lands), and rr eligibility for already-decided
        # rounds (witnesses_decided, poisoned-straggler aware).
        fam_rel = np.zeros((rcap, n), np.int32)
        in_list_rel = np.ones(rcap, np.bool_)
        span = min(pp.rel_rows, rcap)
        for t in range(span):
            rho = self.rho_min + t
            fam_rel[t] = self.famous[rho]
            in_list_rel[t] = rho in pp.undecided_set
        # Clamp into pass-locals so an rcap-doubling redo reclamps
        # from the intact prediction instead of a stale bound.
        pp.tw_i = min(pp.tw, rcap)
        pp.t_start = min(pp.t0, rcap - pp.tw_i)
        pp.tc = min(TS_CHUNK, pp.cb)
        pp.rl = _rr_lanes(n, self.devices)
        fused_args = (
            self._chain_la, self._chain_rb, pp.chain_len_d, pp.la,
            self._ranks, pp.rb,
            self._chain_d, jnp.asarray(wt_tab), jnp.asarray(fr_tab),
            pp.wt_prev, pp.fr_prev, jnp.int32(pp.t0),
            jnp.int32(self.rho_min),
            self._sp_d, pp.cr_d, pp.idx_d, pp.coin_d,
            jnp.int32(pp.e0_b), jnp.int32(pp.e), pp.rounds_up, pp.rr_up,
            jnp.asarray(fam_rel), jnp.asarray(in_list_rel),
            self._chain_th, self._chain_tl, jnp.int32(pp.rx0),
            jnp.int32(self._prev_first_undec), pp.und_up, pp.n_und,
            jnp.int32(pp.t_start))
        fused_kw = dict(n=n, sm=sm, rcap=rcap, bp=pp.bp, rw=pp.rw,
                        iw=pp.iw, cb=pp.cb, tc=pp.tc, tw=pp.tw_i, rl=pp.rl,
                        lanes=self._shard_ch)
        return fused_args, fused_kw

    def request_cost_report(self) -> None:
        """Arm a one-shot compiled-cost capture: the next fused-
        epilogue dispatch records cost_analysis() FLOPs/bytes for its
        exact shapes into `self.cost_report`."""
        self.cost_report = None
        self._cost_requested = True

    def _analyze_cost(self, args, kw) -> dict:
        """AOT-lower the fused consensus kernel with the pass's exact
        inputs and pull the compiler's cost model. The kernel has no
        donated args, so lowering after the real dispatch is safe; the
        compile itself is a warm-cache hit for the shapes that just
        ran. Never raises — this is operator tooling riding the
        staging worker."""
        try:
            compiled = _consensus_fused.lower(*args, **kw).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            out = {
                "flops": float(ca.get("flops", 0.0) or 0.0),
                "bytes_accessed": float(
                    ca.get("bytes accessed", 0.0) or 0.0),
            }
            try:
                mem = compiled.memory_analysis()
                out["output_bytes"] = float(
                    getattr(mem, "output_size_in_bytes", 0) or 0)
                out["temp_bytes"] = float(
                    getattr(mem, "temp_size_in_bytes", 0) or 0)
            except Exception:  # noqa: BLE001 - backend-optional API
                pass
            return {"consensus_fused": out,
                    "shapes": {k: int(v) for k, v in kw.items()}}
        except Exception as exc:  # noqa: BLE001 - report, don't wedge
            return {"error": str(exc)}

    def device_memory_stats(self) -> dict:
        """Device-memory plane (docs/observability.md "Capacity"):
        live HBM bytes of the engine's resident carries (every
        jax.Array attribute), the host-mirror numpy bytes, the
        device-reported budget, and a projected-peers headroom
        estimate for the sharded northstar. Never raises — this runs
        inside a /metrics scrape."""
        import numpy as _np

        dev = host = 0
        try:
            import jax as _jax

            for v in vars(self).values():
                if isinstance(v, _jax.Array):
                    dev += int(getattr(v, "nbytes", 0))
                elif isinstance(v, _np.ndarray):
                    host += int(v.nbytes)
        except Exception:  # noqa: BLE001
            return {"device_bytes": 0, "host_mirror_bytes": 0}
        out = {
            "device_bytes": dev,
            "host_mirror_bytes": host,
            "events": self.e,
            "capacity": self.cap,
            "chain_capacity": self.kcap,
            "n": self.n,
        }
        try:
            mem = _jax.devices()[0].memory_stats() or {}
            budget = int(mem.get("bytes_limit", 0) or 0)
            if budget:
                out["hbm_budget_bytes"] = budget
                out["hbm_in_use_bytes"] = int(
                    mem.get("bytes_in_use", 0) or 0)
        except Exception:  # noqa: BLE001 - backend-optional API
            budget = 0
        if budget and dev > 0:
            # Headroom model: the dominant resident term is the
            # chain_la cube at O(n^2 * K) bytes, so usage scales
            # ~quadratically in participants at fixed window depth —
            # the largest n this budget supports at the current
            # per-peer footprint is n * sqrt(budget / device_bytes).
            # A mesh multiplies the budget by its device count (the
            # cube is sharded on the participant axis).
            devices = 1
            if self._mesh is not None:
                try:
                    devices = int(self._mesh.size)
                except Exception:  # noqa: BLE001
                    devices = 1
            out["projected_max_peers"] = int(
                self.n * ((budget * devices) / dev) ** 0.5)
        # Per-kernel compiled memory_analysis, when a cost capture has
        # run (/debug/profile?cost=1 arms it).
        report = self.cost_report
        if isinstance(report, dict):
            kernels = {}
            for kernel, d in report.items():
                if isinstance(d, dict) and (
                        "output_bytes" in d or "temp_bytes" in d):
                    kernels[kernel] = {
                        "output_bytes": d.get("output_bytes", 0.0),
                        "temp_bytes": d.get("temp_bytes", 0.0),
                    }
            if kernels:
                out["kernels"] = kernels
        return out

    def _collect_pass(self, pp: PendingPass, unlocked) -> RunDelta:
        ph = pp.phase_ns
        with self._span(pp, "engine.collect") as col:
            packed, newly_count, redo_ns = self._await_delta(pp, unlocked)
            counts = pp.counts
            with self._span(pp, "engine.apply", newly=newly_count,
                            ts_chunks=counts["ts_chunks"],
                            rr_chunks=counts["rr_chunks"],
                            au=counts["au"],
                            fame_steps=counts["fame_steps"],
                            rr_steps=counts["rr_steps"]) as app:
                delta = self._apply_delta(pp, packed, newly_count)
        ph["apply"] = app.t1 - app.t0
        # Collect's own host share: the redo decisions and the lock
        # hand-over, outside the waits, pulls, redos and the apply.
        ph["consensus"] = (col.t1 - col.t0 - ph["c_stage_wait"]
                           - ph["c_pull"] - redo_ns - ph["apply"])
        # One assignment of each finished dict: a reader after the next
        # dispatch sees this pass whole, never a part of the next one.
        self.phase_ns = ph
        self.pass_counts = counts
        # An append that slipped in during the unlocked wait means the
        # state is NOT at a fixpoint yet.
        self._empty_delta_ok = not self._new_since_run
        return delta

    def _await_delta(self, pp: PendingPass, unlocked):
        """The stage wait, the pull and the redo loop. Returns the
        final packed delta, its newly-received count, and the wall of
        the redo dispatches.

        This runs with the caller's lock RELEASED (the one blocking
        device->host wait of the pass): every input was uploaded at
        dispatch, and everything below uses the pass snapshot, so
        interleaved appends are safe (see run() docstring)."""
        ph = pp.phase_ns
        redo_ns = 0
        _uctx = unlocked() if unlocked is not None else None
        if _uctx is not None:
            _uctx.__enter__()
        try:
            # Wait for the staging worker (usually already done — the
            # wait is only non-zero when collect fires before staging
            # could enqueue everything, e.g. under compile stalls).
            with self._span(pp, "engine.stage_wait") as wait:
                pp.ready.wait()
            if pp.error is not None:
                raise pp.error
            ph["c_stage_wait"] = wait.t1 - wait.t0
            # Overlap diagnostic: wall between the staging worker's
            # last enqueue and collect entry — device compute the host
            # did NOT wait for (it was ingesting gossip instead).
            self.last_overlap_ns = max(wait.t1 - pp.dispatched_ns, 0)
            while True:
                # c_pull sub-phases (the bounding sustained phase —
                # BENCH_r05 put it at 0.44 share): `wait` is device
                # compute still finishing, `xfer` is the D2H copy of
                # the packed buffer. The split says whether to attack
                # the kernel (wait-bound) or the pull payload/transport
                # (xfer-bound); c_pull stays their sum for every
                # existing consumer.
                with self._span(pp, "engine.pull_wait") as pull:
                    try:
                        pp.packed_dev.block_until_ready()
                    except AttributeError:
                        pass  # non-array stand-ins in tests
                self.c_pull_bytes = int(getattr(pp.packed_dev, "nbytes", 0))
                with self._span(pp, "engine.pull_xfer",
                                bytes=self.c_pull_bytes) as xfer:
                    packed = np.asarray(pp.packed_dev)
                wait_ns, xfer_ns = pull.t1 - pull.t0, xfer.t1 - xfer.t0
                ph["c_pull_wait"] = ph.get("c_pull_wait", 0) + wait_ns
                ph["c_pull_xfer"] = ph.get("c_pull_xfer", 0) + xfer_ns
                ph["c_pull"] = ph.get("c_pull", 0) + wait_ns + xfer_ns
                t_end = int(packed[0])
                newly_count = int(packed[1])
                if t_end == pp.rcap:
                    # Frontier overflow: the fame/rr results were computed
                    # against a truncated table. They are a safe subset
                    # (eligibility is gated by the first undecided round, so
                    # no wrong or out-of-order assignment is possible) but
                    # incomplete — discard and redo at double capacity.
                    pp.rcap *= 2
                    reason = "rcap"
                else:
                    reason = self._resize_windows(pp, packed, t_end,
                                                  newly_count)
                if reason is not None:
                    self.redo_count += 1
                    with self._span(pp, "engine.redo", reason=reason) as redo:
                        self._dispatch_fused(pp)
                    redo_ns += redo.t1 - redo.t0
                    continue
                # The chunks the final dispatch ran (_consensus_fused):
                # the sweep's, as many as hold the window's live lanes,
                # and the timestamp loop's.
                n_und = len(pp.und)
                pp.counts = dict(
                    au=pp.au, n_und=n_und, iw=pp.iw,
                    rr_chunks=-(-n_und // min(pp.rl, pp.au)),
                    ts_chunks=-(-newly_count // pp.tc),
                    fame_steps=int(packed[2]), rr_steps=int(packed[3]))
                # Window-geometry diagnostics of the final dispatch.
                self._dbg_windows = dict(
                    rcap=pp.rcap, rw=pp.rw, cb=pp.cb, bp=pp.bp,
                    tw=pp.tw_i, t0=pp.t0, t_end=t_end,
                    rel_rows=pp.rel_rows, **pp.counts)
                return packed, newly_count, redo_ns
        finally:
            if _uctx is not None:
                _uctx.__exit__(None, None, None)

    def _resize_windows(self, pp: PendingPass, packed, t_end: int,
                        newly_count: int) -> Optional[str]:
        """Window overflow: in-window results are a valid subset
        (decisions are monotone in voting rounds; rr assignments
        outside the window simply stay unassigned) but rounds beyond
        the windows were never processed — redo with the exact spans
        now known from the pull. Likewise a timestamp-bucket overflow
        (a fame decision released more events than cb) redoes with the
        exact count. All overflow checks read the pulled buffer
        (offsets use the tw_i actually dispatched), so a sync
        overflowing several windows enlarges them all before ONE redo.
        Returns the redo's reason, `window` or `cb`, or None."""
        n = self.n
        reason = None
        if t_end > pp.t_start + pp.tw_i:
            # Returned-window overflow: the sweep advanced past the
            # predicted row window — redo with the exact span.
            pp.tw = _pow2(max(t_end - pp.t_start, pp.tw_i + 1),
                          pp.tw_floor)
            pp.rw = pp.iw = max(pp.rw, _pow2(pp.tw, pp.w_floor))
            reason = "window"
        off = _PACK_HEAD + 2 * pp.tw_i * n
        rnd_b = packed[off:off + pp.bp]
        valid_b = rnd_b >= 0
        min_new = int(rnd_b[valid_b].min()) if valid_b.any() else None
        r_hi = self.rho_min + t_end
        i0_true = self._prev_first_undec
        if min_new is not None:
            i0_true = min(i0_true, min_new + 1)
        wide = r_hi - pp.rx0 > pp.rw or r_hi - i0_true > pp.iw
        if wide or newly_count > pp.cb:
            pp.rw = pp.iw = _pow2(
                max(r_hi - pp.rx0, r_hi - i0_true, pp.rw), pp.w_floor)
            pp.cb = min(_pow2(max(newly_count, 1024)), pp.cap0, pp.au)
            reason = reason or ("window" if wide else "cb")
        return reason

    def _apply_delta(self, pp: PendingPass, packed,
                     newly_count: int) -> RunDelta:
        """Unpack the final delta, apply the host mirrors, commit the
        device result carries, and build the RunDelta."""
        n = self.n
        t_end = int(packed[0])
        e, chain_len0, new_ids = pp.e, pp.chain_len0, pp.new_ids
        tw_i, t_start, bp, rw, cb = pp.tw_i, pp.t_start, pp.bp, pp.rw, pp.cb
        rel_rows, rx0, und = pp.rel_rows, pp.rx0, pp.und
        off = _PACK_HEAD
        tabs = packed[off:off + 2 * tw_i * n].reshape(2, tw_i, n)
        off += 2 * tw_i * n
        span_w = t_end - t_start
        wt_all = np.concatenate(
            [self._wt_table[:t_start], tabs[0][:span_w]], axis=0)
        fr_all = np.concatenate(
            [self._fr_table[:t_start], tabs[1][:span_w]], axis=0)
        rnd_b = packed[off:off + bp]
        off += bp
        wit_b = packed[off:off + bp]
        off += bp
        famous_merged = packed[off:off + rw * n].reshape(rw, n)
        off += rw * n
        sel_np = packed[off:off + cb]
        off += cb
        rr_sel_np = packed[off:off + cb]
        off += cb
        cts_hi_np = packed[off:off + cb]
        off += cb
        cts_lo_np = packed[off:]

        active = (fr_all < chain_len0[None, :]).any(axis=1)
        n_rows = int(np.nonzero(active)[0][-1]) + 1 if active.any() else 0
        self._fr_table = fr_all[:n_rows]
        self._wt_table = wt_all[:n_rows]
        self._chain_len_prev = chain_len0.copy()
        self._last_growth = max(n_rows - rel_rows, 1)
        self._last_newly = max(newly_count, 64)
        r_total = self.rho_min + n_rows
        wt_abs = np.full((r_total, n), -1, np.int32)
        if n_rows:
            wt_abs[self.rho_min:] = self._wt_table
        if self.famous.shape[0] < r_total:
            grown = np.zeros((r_total, n), np.int32)
            grown[: self.famous.shape[0]] = self.famous
            self.famous = grown

        delta = RunDelta()

        # Host mirrors of the device-computed rounds (reference
        # DivideRounds bookkeeping, hashgraph.go:616-646).
        with self._span(pp, "engine.apply.rounds"):
            for j, i in enumerate(new_ids):
                rnd = int(rnd_b[j])
                wit = bool(wit_b[j])
                self.rounds[i] = rnd
                self.witness[i] = wit
                delta.new_rounds.append((i, rnd, wit))
                if rnd not in self._queued_rounds:
                    self._queued_rounds.add(rnd)
                    bisect.insort(self.undecided_rounds, rnd)

        # Host mirror of DecideFame's bookkeeping from the pulled
        # fame window (hashgraph.go:649-730).
        with self._span(pp, "engine.apply.fame"):
            for rho in list(self.undecided_rounds):
                if rho >= r_total:
                    continue
                t = rho - rx0
                row_decided = True
                for c in range(n):
                    if wt_abs[rho, c] < 0:
                        continue
                    if self.famous[rho, c] == FAME_UNDEFINED:
                        f = int(famous_merged[t, c])
                        if f != FAME_UNDEFINED:
                            self.famous[rho, c] = f
                            delta.fame_updates.append(
                                (rho, int(wt_abs[rho, c]), f == FAME_TRUE))
                    if self.famous[rho, c] == FAME_UNDEFINED:
                        row_decided = False
                if row_decided:
                    self.undecided_rounds.remove(rho)
                    delta.newly_decided_rounds.append(rho)
                    if (self.last_consensus_round is None
                            or rho > self.last_consensus_round):
                        self.last_consensus_round = rho
                        delta.last_commited_round_events = int(
                            (self.rounds[:e] == rho - 1).sum())

        # The cb-compacted tail: entries [0, newly_count) are the newly
        # received lanes in ascending lane (= event id) order — the
        # same order the au-wide scan used to produce.
        with self._span(pp, "engine.apply.received"):
            for j in range(newly_count):
                li = int(sel_np[j])
                i = int(und[li])
                rr_i = int(rr_sel_np[j])
                hi = int(cts_hi_np[j])
                self.rr[i] = rr_i
                if hi == ZERO_TS_HI:
                    self.cts_ns[i] = CTS_SENTINEL
                    ns = ZERO_TIME_NS
                else:
                    ns = _ts_join(hi, int(cts_lo_np[j]))
                    self.cts_ns[i] = ns
                delta.new_received.append((int(i), rr_i, ns))
        delta.last_consensus_round = self.last_consensus_round
        self._prev_first_undec = (
            self.undecided_rounds[0] if self.undecided_rounds else r_total)

        # Commit the device result carries only now that the host
        # mirrors are applied: a redo, a transient device failure, or an
        # exception anywhere above leaves the previous pass's carries
        # intact, so the retry recomputes against consistent state.
        self._rounds_d = pp.rounds_out
        self._rr_d = pp.rr_out
        return delta

    def feed(self, dag, sync: int = 4096):
        """Feed a whole `dag` (ops/dag.py) in `sync`-event syncs the way
        the node's consensus worker does: append sync k+1 while pass k
        is in flight, pull pass k's packed delta, dispatch k+1. Yields
        the events appended after each pull; closes the engine at the
        end. chip_smoke.py checks this loop and bench.py times it."""
        pending = None
        for k in range(0, dag.e, sync):
            hi = min(k + sync, dag.e)
            self.append_batch(
                dag.self_parent[k:hi], dag.other_parent[k:hi],
                dag.creator[k:hi], dag.index[k:hi], dag.coin[k:hi],
                np.arange(k, hi))
            if pending is not None:
                self.collect(pending)
                yield hi
            pending = self.dispatch()
        self.collect(pending)
        self.run()  # appends staged during the last in-flight pass
        self.close()
        yield dag.e

    # -- compile prewarm ----------------------------------------------------

    def prewarm(self, *, budget_bytes: int = 1 << 28) -> bool:
        """Compile the cold-start kernel ladder before live traffic.

        Builds a scratch sibling engine with the SAME static shapes
        (jit caches are process-global and shape-keyed), feeds it a
        small synthetic gossip DAG, and runs two passes — exactly the
        compiles a fresh live engine pays over its first syncs (ingest
        and fused-epilogue batch buckets are floor-padded, so any
        batch <= the floor shares these), moved to construction time.
        With a persistent compile cache (devices.ensure_compile_cache)
        the XLA artifacts also survive restarts, so a rebooted node
        skips even these. This is what retires the multi-thousand-event
        warm gate live nodes used to need before reaching steady state.

        Returns False (skipped) when the scratch carries would exceed
        `budget_bytes` — at large n the transient doubling of resident
        table memory is not worth it; those deployments rely on the
        persistent cache instead. Idempotent per shape-key per process.
        """
        key = (self.n, self.cap, self.kcap, self.block,
               id(self._mesh) if self._mesh is not None else None)
        if key in _PREWARM_DONE:
            return True
        n = self.n
        est = 4 * ((self.cap + 1) * n            # la
                   + 2 * n * n * self.kcap       # ranks + chain_la
                   + 5 * n * self.kcap           # chain id/ts/rb tables
                   + 8 * self.cap)               # 1-D event vectors
        if est > budget_bytes:
            return False
        scratch = IncrementalEngine(
            n, capacity=self.cap, block=self.block, k_capacity=self.kcap,
            mesh=self._mesh, mesh_axis=self._mesh_axis)
        heads = [-1] * n
        idx = [0] * n
        ts = 1_700_000_000_000_000_000

        def gossip_round(step: int) -> None:
            nonlocal ts
            for c in range(n):
                op = heads[(c + step) % n] if heads[c] >= 0 else -1
                ts += 1_000_000
                eid = scratch.append(
                    heads[c], op, c, idx[c], (idx[c] + c) % 2 == 1, ts)
                heads[c] = eid
                idx[c] += 1

        for step in (1, 2):
            gossip_round(step)
        scratch.run()
        for step in (3, 1):
            gossip_round(step)
        scratch.run()
        scratch.close()
        _PREWARM_DONE.add(key)
        return True

    # -- queries -----------------------------------------------------------

    def backlog(self) -> int:
        """Events appended but not yet folded by a pass — the node's
        ingest flow control gates on this (node/node.py
        _throttle_ingest), and it resets when run() snapshots its
        batch."""
        return len(self._new_since_run)

    def round_of(self, eid: int) -> int:
        return int(self.rounds[eid])

    def witness_table(self) -> np.ndarray:
        r_total = self.rho_min + len(self._wt_table)
        wt_abs = np.full((r_total, self.n), -1, np.int32)
        if len(self._wt_table):
            wt_abs[self.rho_min:] = self._wt_table
        return wt_abs
