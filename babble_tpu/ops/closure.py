"""Block-parallel ancestor coordinates via boolean closure matmuls.

A schedule whose trip count scales with E/block, not with DAG depth
(2,709 levels at n=64/e=50k): events are processed in
topological blocks of B; intra-block reachability is closed by log2(B)
boolean matrix squarings (MXU work), and each event's coordinates are
the masked max of the closure-selected base rows (VPU reduction, fused
by XLA — the [B, B, n] operand is never materialized; the reduction is
chunked over rows to bound the fusion working set).

Semantics mirror reference hashgraph.go:448-499 (InitEventCoordinates:
lastAncestors = elementwise max over parents' rows, own slot = own
index). Additionally propagates `rbase` — the max over ancestors of the
per-event root-round contribution (root_round[creator]+1 where a parent
is missing, reference hashgraph.go:211-262 Root fallback) — which the
round-frontier kernel (ops/frontier.py) consumes; it rides the same
closure at the cost of one extra column.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

# Fusion working-set bound for the closure-apply reduction: rows are
# processed in chunks so each fused [rows, B, n] select+max stays under
# ~64M elements (raised with the other chunk bounds; whether fewer,
# fatter kernels win on the v5e chip is not measured).
_APPLY_ELEMS = 1 << 26


def _apply_chunks(block: int, n: int) -> int:
    rows = max(_APPLY_ELEMS // (block * n), 1)
    chunks = (block + rows - 1) // rows
    # fori_loop needs equal chunks; round rows down to a divisor of block
    while block % chunks:
        chunks += 1
    return chunks


def make_block_body(self_parent, other_parent, creator, index, root_base,
                    *, n, block):
    """The per-block closure step over [cap+1]-shaped inputs, run by the
    engine's carry kernel (ops/incremental.py _closure_update). Inputs
    carry a sentinel row at id cap; pad events (sp=op=-1, index=-1,
    root_base=-1) produce inert rows. Returns body(b, (la, rb)) ->
    (la, rb)."""
    e_pad = self_parent.shape[0] - 1
    log2b = max(int(np.ceil(np.log2(block))), 1)
    chunks = _apply_chunks(block, n)
    rows_per_chunk = block // chunks
    eye = jnp.eye(block, dtype=jnp.float32)
    rows = jnp.arange(block)

    def body(b, carry):
        la, rb = carry
        s = b * block
        sp = lax.dynamic_slice(self_parent, (s,), (block,))
        op = lax.dynamic_slice(other_parent, (s,), (block,))
        cr = lax.dynamic_slice(creator, (s,), (block,))
        idx = lax.dynamic_slice(index, (s,), (block,))
        rb0 = lax.dynamic_slice(root_base, (s,), (block,))

        # Intra-block reachability closure: R[i, j] = 1 iff block event
        # i reaches block event j (topological order makes parents
        # strictly earlier, so log2(block) squarings close all paths).
        sp_int = sp >= s
        op_int = op >= s
        adj = jnp.zeros((block, block), dtype=jnp.float32)
        adj = adj.at[rows, jnp.where(sp_int, sp - s, 0)].max(
            sp_int.astype(jnp.float32))
        adj = adj.at[rows, jnp.where(op_int, op - s, 0)].max(
            op_int.astype(jnp.float32))
        reach = jnp.minimum(adj + eye, 1.0)

        def square(_, r):
            return jnp.minimum(r @ r, 1.0)

        reach = lax.fori_loop(0, log2b, square, reach) > 0.5

        # Base rows: external-parent coordinates + own slot.
        ext_sp = jnp.where(sp_int | (sp < 0), e_pad, sp)
        ext_op = jnp.where(op_int | (op < 0), e_pad, op)
        base = jnp.maximum(la[ext_sp], la[ext_op])
        base = base.at[rows, cr].max(idx)
        base_rb = jnp.maximum(jnp.maximum(rb[ext_sp], rb[ext_op]), rb0)

        # Apply the closure: out[i] = max over reached j of base[j].
        def apply_chunk(c, out):
            r0 = c * rows_per_chunk
            sel = lax.dynamic_slice(reach, (r0, 0), (rows_per_chunk, block))
            part = jnp.where(sel[:, :, None], base[None, :, :], -1).max(1)
            return lax.dynamic_update_slice(out, part, (r0, 0))

        out = lax.fori_loop(
            0, chunks, apply_chunk,
            jnp.full((block, n), -1, dtype=jnp.int32))
        out_rb = jnp.where(reach, base_rb[None, :], -1).max(1)

        la = lax.dynamic_update_slice(la, out, (s, 0))
        rb = lax.dynamic_update_slice(rb, out_rb, (s,))
        return la, rb

    return body
