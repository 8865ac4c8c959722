"""Multi-chip consensus with MEMORY sharding: d chips hold a d× DAG.

The pipeline shards the two O(E·n) tables — lastAncestor coordinates
and first descendants — across the mesh and keeps them sharded through
every stage; nothing event-sized is ever replicated except O(E) int32
vectors (parents, creators, rounds). This is the layout SURVEY.md §5
prescribes (shard the event axis, all-gathers for cross-shard
stronglySee), taken to its conclusion: the collectives move rows, the
resident state never un-shards.

  coordinates   chain-sharded [n/d, K, n]: device p owns the coordinate
                rows of its creators' chains. The wavefront sweep
                computes each level replicated (cheap [W, n] maxes) from
                parent rows fetched by masked-contribution + pmax (one
                [2W, n] collective per level) and each device writes
                back only its own creators' rows.
  fd            ranks are a pure chain-local compare-and-count (no
                collective at all — each device counts descendants on
                its own chains), then one all_to_all transposes the
                [E, n/d] chain columns into the event-sharded [E/d, n]
                table the round-received stage consumes.
  rounds        per level the candidate-witness strongly-see tally is
                sharded over candidate chains ([W, n/d, n] compares per
                device) and psum-reduced to the [W] count; witness
                coordinate/fd rows are accumulated into a chain-sharded
                [r, n/d, n] table as witnesses are discovered.
  fame          voting witnesses sharded exactly as before, but reading
                the prefetched [r_small, n, n] witness-row tables
                (bounded by rounds·n², not E·n) instead of replicated
                event tables; votes all-gathered per round, decisions
                psum-reduced.
  round recv    pure event-axis sharding: each device owns its block of
                the event-sharded fd table and decides round received +
                median timestamps against the replicated witness-row
                tables; no collective at all.

Every stage reproduces the single-device kernels bit-for-bit (asserted
by tests/test_sharded.py and the driver's dryrun_multichip). Semantics
anchors are the same as ops/kernels.py: reference hashgraph.go:211-339,
448-530, 616-858.

`axis` may be a tuple of mesh axis names — e.g. ("dcn", "ici") on a
hosts x chips mesh — in which case shards span both axes and every
collective rides the combined axes (XLA routes the intra-host part
over ICI and the cross-host part over DCN), the way the reference's
TCP backend spans processes and hosts alike (net/tcp_transport.go).
"""

from __future__ import annotations

import functools
from typing import Tuple, Union

MeshAxis = Union[str, Tuple[str, ...]]

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .kernels import (FAME_TRUE, FAME_FALSE, FAME_UNDEFINED, INT32_MAX,
                      ZERO_TS_RANK, chunk_width,
                      strongly_see_counts_chunked)


def _axis_size(mesh: Mesh, axis: MeshAxis) -> int:
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    d = 1
    for a in names:
        d *= mesh.shape[a]
    return d


def _axis_names(axis: MeshAxis) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _make_axis_index(mesh: Mesh, axis: MeshAxis):
    """Combined shard index along a (possibly composite) axis, matching
    shard_map's P((a, b)) partition order (a-major)."""
    names = _axis_names(axis)
    sizes = [mesh.shape[a] for a in names]

    def axis_index():
        idx = jnp.int32(0)
        for a, s in zip(names, sizes):
            idx = idx * s + lax.axis_index(a)
        return idx

    return axis_index


def _sharded(mesh, fn, in_specs, out_specs):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


# -- remote row fetches ---------------------------------------------------
#
# The resident tables are sharded; a row read is a masked local gather
# (the owner contributes the row, everyone else -1) followed by a pmax
# over the mesh axis. All real values are >= -1 (coordinates) or >= 0
# (fd ranks / INT32_MAX), so max-reduce with a -1 fill is exact.


def _fetch_by_chain(la_cs, cr, pos, off, n_loc, fill=-1):
    """Rows keyed by (creator, chain position) from the chain-sharded
    [n_loc, K, n] table. cr/pos: [m]; invalid entries (cr or pos < 0)
    fetch fill rows. Returns the LOCAL contribution [m, n]."""
    k = la_cs.shape[1]
    owned = (cr >= off) & (cr < off + n_loc) & (pos >= 0) & (pos < k)
    c_idx = jnp.clip(cr - off, 0, n_loc - 1)
    p_idx = jnp.clip(pos, 0, k - 1)
    return jnp.where(owned[:, None], la_cs[c_idx, p_idx], fill)


def _fetch_by_event(tbl_loc, ids, off, e_loc, fill=-1):
    """Rows keyed by event id from the event-sharded [e_loc, n] table.
    ids: [m]; invalid ids (< 0) fetch fill rows. Returns the LOCAL
    contribution [m, n]."""
    owned = (ids >= off) & (ids < off + e_loc)
    return jnp.where(
        owned[:, None], tbl_loc[jnp.clip(ids - off, 0, e_loc - 1)], fill)


# -- stage 1: lastAncestors, chain-sharded storage ------------------------


def make_last_ancestors(mesh: Mesh, *, n: int, k: int, axis: MeshAxis = "sp"):
    d = _axis_size(mesh, axis)
    if n % d:
        raise ValueError(f"participants {n} must divide over {d} devices")
    n_loc = n // d
    axis_index = _make_axis_index(mesh, axis)

    def la_sweep(self_parent, other_parent, creator, index, levels):
        w = levels.shape[1]
        la_cs = jnp.full((n_loc, k, n), -1, jnp.int32)
        off = axis_index() * n_loc
        rows_iota = jnp.arange(w)

        def step(l, la_cs):
            ids = levels[l]  # [W] replicated
            valid = ids >= 0
            sids = jnp.where(valid, ids, 0)
            sp = jnp.where(valid, self_parent[sids], -1)
            op = jnp.where(valid, other_parent[sids], -1)
            # Parent rows by (creator, position); one fused collective.
            both = jnp.concatenate([sp, op])
            safe = jnp.where(both >= 0, both, 0)
            cr_p = jnp.where(both >= 0, creator[safe], -1)
            pos_p = jnp.where(both >= 0, index[safe], -1)
            contrib = _fetch_by_chain(la_cs, cr_p, pos_p, off, n_loc)
            rows2 = lax.pmax(contrib, axis)  # [2W, n] replicated
            rows = jnp.maximum(rows2[:w], rows2[w:])
            cr_e = jnp.where(valid, creator[sids], -1)
            idx_e = index[sids]
            rows = rows.at[rows_iota, jnp.clip(cr_e, 0, n - 1)].set(
                jnp.where(valid, idx_e, -1))
            # Write back only this shard's creators (OOB lanes drop).
            owned = valid & (cr_e >= off) & (cr_e < off + n_loc)
            c_idx = jnp.where(owned, cr_e - off, n_loc)
            p_idx = jnp.where(owned, idx_e, k)
            return la_cs.at[c_idx, p_idx].set(rows, mode="drop")

        return lax.fori_loop(0, levels.shape[0], step, la_cs)

    return _sharded(
        mesh, la_sweep, (P(), P(), P(), P(), P()), P(axis))


# -- stage 2: first descendants, chain-local ranks + one all_to_all ------


def make_first_descendants(mesh: Mesh, *, n: int, axis: MeshAxis = "sp"):
    d = _axis_size(mesh, axis)
    if n % d:
        raise ValueError(f"participants {n} must divide over {d} devices")

    def fd_cols(la_cs, chain_len_loc, creator, index):
        # la_cs: [n/d, K, n] local chains' coordinate rows. Ranks are a
        # chain-local compare-and-count — zero communication.
        e_pad = creator.shape[0] - 1
        k = la_cs.shape[1]
        n_loc = la_cs.shape[0]
        tc = min(max((1 << 27) // max(n_loc * n * k, 1), 1), k)
        nchunks = (k + tc - 1) // tc
        k_cap = nchunks * tc
        # Positions beyond a chain's end carry the storage fill (-1) and
        # must not count (the one-shot kernel's chain_la uses INT32_MAX
        # there, kernels.first_descendant_cube).
        in_chain = (jnp.arange(k)[None, :] < chain_len_loc[:, None])

        def tchunk(g, acc):
            t0 = g * tc
            ts = t0 + jnp.arange(tc, dtype=jnp.int32)
            cnt = (
                (la_cs[:, :, :, None] < ts[None, None, None, :])
                & in_chain[:, :, None, None]
            ).sum(1, dtype=jnp.int32)
            return lax.dynamic_update_slice(acc, cnt, (0, 0, t0))

        ranks = lax.fori_loop(
            0, nchunks, tchunk,
            jnp.zeros((n_loc, n, k_cap), dtype=jnp.int32))[:, :, :k]
        cube = jnp.where(ranks < chain_len_loc[:, None, None], ranks,
                         INT32_MAX)
        ca = creator[:e_pad]
        ia = jnp.clip(index[:e_pad], 0, k - 1)
        fd_part = cube[:, ca, ia].T  # [E_pad, n/d] local chain columns
        fd_part = jnp.where(
            (index[:e_pad] >= 0)[:, None], fd_part, INT32_MAX)
        # Transpose chain-sharded columns into event-sharded rows.
        return lax.all_to_all(
            fd_part, axis, split_axis=0, concat_axis=1, tiled=True)

    return _sharded(
        mesh, fd_cols,
        (P(axis), P(axis), P(), P()),
        P(axis))


# -- stage 3: rounds + witness tables, candidate tally chain-sharded -----


def make_rounds(mesh: Mesh, *, n: int, sm: int, r: int, axis: MeshAxis = "sp"):
    d = _axis_size(mesh, axis)
    if n % d:
        raise ValueError(f"participants {n} must divide over {d} devices")
    n_loc = n // d
    axis_index = _make_axis_index(mesh, axis)

    def rounds_sweep(self_parent, other_parent, creator, index, levels,
                     root_round, la_cs, fd_es):
        e_pad = self_parent.shape[0] - 1
        e_loc = fd_es.shape[0]
        w = levels.shape[1]
        off = axis_index() * n_loc
        off_e = axis_index() * e_loc
        rounds = jnp.full((e_pad + 1,), -1, dtype=jnp.int32)
        wit = jnp.zeros((e_pad + 1,), dtype=jnp.bool_)
        wt = jnp.full((r + 1, n), -1, dtype=jnp.int32)
        # Chain-sharded fd rows of discovered witnesses: the candidate
        # tally below reads them per level without re-fetching.
        fd_wt = jnp.full((r + 1, n_loc, n), INT32_MAX, jnp.int32)

        def step(l, carry):
            rounds, wit, wt, fd_wt = carry
            ids = levels[l]
            valid = ids >= 0
            sids = jnp.where(valid, ids, 0)
            sp = jnp.where(valid, self_parent[sids], -1)
            op = jnp.where(valid, other_parent[sids], -1)
            cr = jnp.where(valid, creator[sids], 0)
            rnd_sp_raw = jnp.where(
                sp >= 0, rounds[jnp.where(sp >= 0, sp, 0)], -1)
            sp_round = jnp.where(sp >= 0, rnd_sp_raw, root_round[cr])
            op_round = jnp.where(
                op >= 0, rounds[jnp.where(op >= 0, op, 0)], root_round[cr])
            use_op = sp_round < op_round
            pr = jnp.where(use_op, op_round, sp_round)
            pr_root = jnp.where(use_op, op < 0, sp < 0)

            # lastAncestor rows of the level's events (one collective).
            pos_e = index[sids]
            la_x = lax.pmax(
                _fetch_by_chain(la_cs, jnp.where(valid, cr, -1), pos_e,
                                off, n_loc), axis)  # [W, n]

            # Candidate strongly-see tally, sharded over the candidate
            # chains: device p compares against fd rows of ITS creators'
            # candidate witnesses and the counts psum to the full tally.
            # Chunked over the level width: the [W, n/d, n] gather is
            # the pipeline's peak transient, and at n=4096 a full-width
            # level would materialize n^3/d ints per device.
            pr_c = jnp.clip(pr, 0, r - 1)
            cand = wt[pr_c]  # [W, n] replicated table
            cand_valid = cand >= 0
            cv_loc = _slice_cols(cand_valid, off, n_loc)  # [W, n/d]
            wc = chunk_width(w, n_loc * n)

            def tally_chunk(g, cnt_loc):
                w0 = g * wc  # clamped on the final chunk (idempotent)
                la_g = lax.dynamic_slice(la_x, (w0, 0), (wc, n))
                prc_g = lax.dynamic_slice(pr_c, (w0,), (wc,))
                cv_g = lax.dynamic_slice(cv_loc, (w0, 0), (wc, n_loc))
                fd_g = fd_wt[prc_g]  # [wc, n/d, n]
                ss_g = (la_g[:, None, :] >= fd_g).sum(-1) >= sm
                ss_g = ss_g & cv_g
                return lax.dynamic_update_slice(
                    cnt_loc, ss_g.sum(-1, dtype=jnp.int32), (w0,))

            cnt_loc = lax.fori_loop(
                0, -(-w // wc), tally_chunk, jnp.zeros((w,), jnp.int32))
            cnt = lax.psum(cnt_loc, axis)  # [W]

            inc = pr_root | (cnt >= sm)
            r_new = pr + inc.astype(jnp.int32)
            w_new = ((sp < 0) & (op < 0)) | (r_new > rnd_sp_raw)

            rounds = rounds.at[jnp.where(valid, sids, e_pad)].set(
                jnp.where(valid, r_new, -1), mode="drop")
            wit = wit.at[jnp.where(valid, sids, e_pad)].set(
                jnp.where(valid, w_new, False), mode="drop")
            upd = valid & w_new
            r_idx = jnp.where(upd, jnp.clip(r_new, 0, r - 1), r)
            wt = wt.at[r_idx, cr].set(jnp.where(upd, sids, -1))

            # fd rows of the new witnesses (one collective), written
            # into this shard's creator rows of the witness-fd table.
            fd_rows = lax.pmax(
                _fetch_by_event(fd_es, jnp.where(upd, sids, -1), off_e,
                                e_loc), axis)  # [W, n]
            owned = upd & (cr >= off) & (cr < off + n_loc)
            fd_wt = fd_wt.at[
                jnp.where(owned, r_idx, r), jnp.where(owned, cr - off, n_loc)
            ].set(fd_rows, mode="drop")
            return rounds, wit, wt, fd_wt

        rounds, wit, wt, fd_wt = lax.fori_loop(
            0, levels.shape[0], step, (rounds, wit, wt, fd_wt))
        return rounds[:e_pad], wit[:e_pad], wt[:r]

    return _sharded(
        mesh, rounds_sweep,
        (P(), P(), P(), P(), P(), P(), P(axis),
         P(axis)),
        (P(), P(), P()))


def _slice_cols(a, off, n_loc):
    """a[:, off:off+n_loc] with a traced offset."""
    return lax.dynamic_slice_in_dim(a, off, n_loc, axis=1)


# -- witness-row prefetch -------------------------------------------------


def make_wt_tables(mesh: Mesh, *, n: int, axis: MeshAxis = "sp"):
    """Fetch the lastAncestor and fd rows of every witness into
    replicated [r_small·n, n] tables — the only row tables the fame and
    round-received stages need, bounded by rounds·n², not E·n."""
    d = _axis_size(mesh, axis)
    if n % d:
        raise ValueError(f"participants {n} must divide over {d} devices")
    n_loc = n // d
    axis_index = _make_axis_index(mesh, axis)

    def fetch(wt_flat, creator, index, la_cs, fd_es):
        e_loc = fd_es.shape[0]
        off = axis_index() * n_loc
        off_e = axis_index() * e_loc
        safe = jnp.where(wt_flat >= 0, wt_flat, 0)
        cr = jnp.where(wt_flat >= 0, creator[safe], -1)
        pos = jnp.where(wt_flat >= 0, index[safe], -1)
        la_rows = lax.pmax(
            _fetch_by_chain(la_cs, cr, pos, off, n_loc), axis)
        fd_rows = lax.pmax(
            _fetch_by_event(fd_es, wt_flat, off_e, e_loc), axis)
        return la_rows, fd_rows

    return _sharded(
        mesh, fetch,
        (P(), P(), P(), P(axis), P(axis)),
        (P(), P()))


# -- stage 4: fame, voting witnesses sharded -----------------------------


def make_fame(mesh: Mesh, *, n: int, sm: int, r: int, axis: MeshAxis = "sp"):
    d = _axis_size(mesh, axis)
    if n % d:
        raise ValueError(f"participants {n} must divide over {d} devices")
    n_loc = n // d

    def fame_sweep(wt, la_wt, fd_wt, index, coin, y_off):
        # la_wt/fd_wt: [r, n, n] replicated witness rows (row (j, c) =
        # the coordinate/fd row of witness wt[j, c]; -1 rows for absent
        # witnesses are masked by wt validity below).
        wt_valid = wt >= 0
        wt_safe = jnp.where(wt_valid, wt, 0)
        idx_x = jnp.where(wt_valid, index[wt_safe], -1)  # [r, n]
        rx = jnp.broadcast_to(jnp.arange(r)[:, None], (r, n))
        famous0 = jnp.zeros((r, n), dtype=jnp.int32)
        votes0 = jnp.zeros((n_loc, r, n), dtype=jnp.bool_)

        def step(j, carry):
            famous, v_loc = carry
            y = lax.dynamic_slice(wt[j], (y_off[0],), (n_loc,))
            y_valid = y >= 0
            ys = jnp.where(y_valid, y, 0)
            la_y = lax.dynamic_slice(
                la_wt[j], (y_off[0], 0), (n_loc, n))  # [n/d, n]
            see_v = la_y[:, None, :] >= idx_x[None, :, :]
            wp_valid = wt[j - 1] >= 0
            fd_p = fd_wt[j - 1]  # [n, n]
            ss_cnt = strongly_see_counts_chunked(la_y, fd_p, n=n)
            ss = (ss_cnt >= sm) & wp_valid[None, :]
            # Round j-1's votes by ALL voters feed the tally.
            v_prev = lax.all_gather(v_loc, axis, axis=0, tiled=True)
            yays = (
                (ss.astype(jnp.float32)
                 @ v_prev.reshape(n, r * n).astype(jnp.float32))
                .astype(jnp.int32).reshape(n_loc, r, n)
            )
            tot = ss.sum(-1).astype(jnp.int32)[:, None, None]
            nays = tot - yays
            v = yays >= nays
            t = jnp.maximum(yays, nays)
            diff = j - rx
            is_first = (diff == 1)[None]
            normal = ((diff % n) != 0)[None]
            coin_vote = jnp.broadcast_to(
                coin[ys].astype(jnp.bool_)[:, None, None], see_v.shape)
            vote = jnp.where(
                is_first, see_v, jnp.where(normal | (t >= sm), v, coin_vote))
            active = y_valid[:, None, None] & wt_valid[None] & (rx < j)[None]
            vote = vote & active
            decide_now = active & ~is_first & normal & (t >= sm)
            dec_any = lax.psum(decide_now.any(0).astype(jnp.int32), axis) > 0
            dec_val = lax.psum(
                (decide_now & v).any(0).astype(jnp.int32), axis) > 0
            undecided = (famous == FAME_UNDEFINED) & wt_valid
            famous = jnp.where(
                undecided & dec_any,
                jnp.where(dec_val, FAME_TRUE, FAME_FALSE), famous)
            return famous, vote

        famous, _ = lax.fori_loop(1, r, step, (famous0, votes0))
        return famous

    return _sharded(
        mesh, fame_sweep,
        (P(), P(), P(), P(), P(), P(axis)), P())


# -- stage 5: round received, pure event sharding ------------------------


def make_round_received(mesh: Mesh, *, n: int, r: int, axis: MeshAxis = "sp"):
    def rr_block(rounds_loc, fd_loc, creator_loc, index_loc,
                 wt, famous, idx_w, la_wt, chain_rank, valid_loc):
        e_loc = rounds_loc.shape[0]
        k = chain_rank.shape[1]
        wt_valid = wt >= 0
        has_undec = ((famous == FAME_UNDEFINED) & wt_valid).any(1)
        min_undec = jnp.min(jnp.where(has_undec, jnp.arange(r), r))
        fmask = (famous == FAME_TRUE) & wt_valid
        fcnt = fmask.sum(1)

        rr0 = jnp.full((e_loc,), -1, dtype=jnp.int32)

        def step(i, rr):
            eligible = ~has_undec[i] & (min_undec > i)
            la_w = la_wt[i]  # [n(w), n] replicated witness coordinate rows
            see_wx = la_w[:, creator_loc] >= index_loc[None, :]
            s_cnt = (see_wx & fmask[i][:, None]).sum(0)
            ok = (eligible & (s_cnt > fcnt[i] // 2) & (i > rounds_loc)
                  & (rr < 0) & valid_loc)
            return jnp.where(ok, i, rr)

        rr = lax.fori_loop(0, r, step, rr0)

        rr_safe = jnp.clip(rr, 0, r - 1)
        fm_sel = fmask[rr_safe]
        idxw_sel = idx_w[rr_safe]
        la_w_sel = la_wt[rr_safe]  # [E/d, n, n]
        see_sel = jnp.take_along_axis(
            la_w_sel, creator_loc[:, None, None], axis=2)[:, :, 0]
        see_sel = see_sel >= index_loc[:, None]
        s_mask = see_sel & fm_sel
        s_cnt = s_mask.sum(1)
        valid_t = fd_loc <= idxw_sel
        ts_fd = chain_rank[jnp.arange(n)[None, :], jnp.clip(fd_loc, 0, k - 1)]
        tsv = jnp.where(valid_t, ts_fd, ZERO_TS_RANK)
        tvals = jnp.where(s_mask, tsv, INT32_MAX)
        sorted_t = jnp.sort(tvals, axis=1)
        med = jnp.take_along_axis(
            sorted_t, (s_cnt // 2)[:, None], axis=1)[:, 0]
        cts = jnp.where(rr >= 0, med, ZERO_TS_RANK)
        return rr, cts

    a = axis
    return _sharded(
        mesh, rr_block,
        (P(a), P(a), P(a), P(a), P(), P(), P(), P(), P(), P(a)),
        (P(a), P(a)))


# -- driver --------------------------------------------------------------


def sharded_pipeline(dag, mesh: Mesh, axis: MeshAxis = "sp") -> Tuple:
    """Run the full consensus pipeline sharded over `mesh` along
    `axis` (a mesh axis name or tuple of names for multi-host
    hierarchies). Output contract matches pipeline.run_pipeline — and
    matches it bit-for-bit (the parity oracle for the multi-chip
    path). The O(E·n) state stays sharded end to end, so d devices
    hold a d× larger DAG than one device can."""
    d = _axis_size(mesh, axis)
    n, e, sm = dag.n, dag.e, dag.super_majority
    r = dag.max_rounds
    if n % d:
        raise ValueError(f"participants {n} must divide over {d} devices")
    k = dag.chain.shape[1]
    e_pad = ((e + d - 1) // d) * d if e else d

    def padded(a, fill):
        out = np.full(e_pad + 1, fill, np.int32)
        out[:e] = np.asarray(a)[:e]
        return jnp.asarray(out)

    sp_p = padded(dag.self_parent, -1)
    op_p = padded(dag.other_parent, -1)
    cr_p = padded(dag.creator, 0)
    idx_p = padded(dag.index, -1)

    la_f = make_last_ancestors(mesh, n=n, k=k, axis=axis)
    la_cs = la_f(sp_p, op_p, cr_p, idx_p, jnp.asarray(dag.levels))

    fd_f = make_first_descendants(mesh, n=n, axis=axis)
    fd_es = fd_f(la_cs, jnp.asarray(dag.chain_len), cr_p, idx_p)

    rounds_f = make_rounds(mesh, n=n, sm=sm, r=r, axis=axis)
    rounds_p, wit_p, wt = rounds_f(
        sp_p, op_p, cr_p, idx_p, jnp.asarray(dag.levels),
        jnp.asarray(dag.root_round), la_cs, fd_es)
    rounds = np.asarray(rounds_p)[:e]
    wit = np.asarray(wit_p)[:e]

    from .pipeline import pad_famous, tight_round_bucket

    r_small = tight_round_bucket(rounds if e else np.zeros(0), r)
    wt_small = np.asarray(wt[:r_small])

    # Witness-row tables: the only row state fame / round-received
    # need, fetched once from the sharded tables.
    fetch_f = make_wt_tables(mesh, n=n, axis=axis)
    la_rows, fd_rows = fetch_f(
        jnp.asarray(wt_small.ravel()), cr_p, idx_p, la_cs, fd_es)
    la_wt = la_rows.reshape(r_small, n, n)
    fd_wt = fd_rows.reshape(r_small, n, n)

    y_off = np.arange(0, n, n // d, dtype=np.int32)
    fame_f = make_fame(mesh, n=n, sm=sm, r=r_small, axis=axis)
    famous_small = fame_f(jnp.asarray(wt_small), la_wt, fd_wt,
                          idx_p, jnp.asarray(dag.coin), jnp.asarray(y_off))

    wt_valid = wt_small >= 0
    wt_safe = np.where(wt_valid, wt_small, 0)
    idx_w = np.where(wt_valid, np.asarray(dag.index)[wt_safe], -1)

    rounds_pad = jnp.asarray(
        np.pad(rounds, (0, e_pad - e), constant_values=0))
    rr_f = make_round_received(mesh, n=n, r=r_small, axis=axis)
    rr_p, cts_p = rr_f(
        rounds_pad, fd_es, cr_p[:e_pad], idx_p[:e_pad],
        jnp.asarray(wt_small), famous_small, jnp.asarray(idx_w),
        la_wt, jnp.asarray(dag.chain_rank),
        jnp.asarray(np.arange(e_pad) < e))
    rr = np.asarray(rr_p)[:e]
    cts = np.asarray(cts_p)[:e]

    return rounds, wit, np.asarray(wt), pad_famous(famous_small, r, n), rr, cts
