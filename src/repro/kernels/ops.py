"""jit'd public wrappers around the Pallas kernels.

Off the TPU, ``interpret=None`` runs the kernel body in Python with
numpy semantics (correctness validation); on a TPU the same calls try
to compile to Mosaic.  The choice is made per call
(``apoz.default_interpret``), never at import.  Inputs are padded up
to block multiples here so the kernels themselves stay branch-free;
padding is score-neutral (zeros contribute nothing to squared norms,
padded entries are masked out of counts).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.kernels.apoz import apoz_counts_pallas, default_interpret
from repro.kernels.channel_norm import channel_norms_pallas
from repro.kernels.select_mask import (select_compact_pallas,
                                       select_mask_pallas)


def _pad2(x, bm, bn, value=0.0):
    m, n = x.shape
    pm = (-m) % bm
    pn = (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)), constant_values=value)
    return x, m, n


def channel_norms(g: jnp.ndarray, bm: int = 256, bn: int = 256,
                  interpret: bool = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row and column squared norms of g (M,N), fp32, via one fused pass."""
    interpret = default_interpret() if interpret is None else interpret
    bm = min(bm, max(8, g.shape[0]))
    bn = min(bn, max(8, g.shape[1]))
    gp, m, n = _pad2(g, bm, bn)
    row, col = channel_norms_pallas(gp, bm=bm, bn=bn, interpret=interpret)
    return row[:m], col[:n]


def select_mask(g: jnp.ndarray, row: jnp.ndarray, col: jnp.ndarray,
                threshold, bm: int = 256, bn: int = 256,
                interpret: bool = None) -> jnp.ndarray:
    """Masked gradient g̃ (keep where row[i]+col[j] > threshold)."""
    interpret = default_interpret() if interpret is None else interpret
    bm = min(bm, max(8, g.shape[0]))
    bn = min(bn, max(8, g.shape[1]))
    gp, m, n = _pad2(g, bm, bn)
    neg = jnp.float32(-jnp.inf)
    rowp = jnp.pad(row.astype(jnp.float32), (0, gp.shape[0] - m),
                   constant_values=neg)
    colp = jnp.pad(col.astype(jnp.float32), (0, gp.shape[1] - n),
                   constant_values=neg)
    out, _ = select_mask_pallas(gp, rowp, colp, threshold,
                                bm=bm, bn=bn, interpret=interpret)
    return out[:m, :n]


def scbf_select_fused(g: jnp.ndarray, row: jnp.ndarray, col: jnp.ndarray,
                      threshold, bm: int = 256, bn: int = 256,
                      interpret: bool = None):
    """(masked g̃, kept-entry count) in one kernel launch."""
    interpret = default_interpret() if interpret is None else interpret
    bm = min(bm, max(8, g.shape[0]))
    bn = min(bn, max(8, g.shape[1]))
    gp, m, n = _pad2(g, bm, bn)
    neg = jnp.float32(-jnp.inf)
    rowp = jnp.pad(row.astype(jnp.float32), (0, gp.shape[0] - m),
                   constant_values=neg)
    colp = jnp.pad(col.astype(jnp.float32), (0, gp.shape[1] - n),
                   constant_values=neg)
    out, cnt = select_mask_pallas(gp, rowp, colp, threshold,
                                  bm=bm, bn=bn, interpret=interpret)
    return out[:m, :n], cnt[0]


def select_compact(g: jnp.ndarray, row: jnp.ndarray, col: jnp.ndarray,
                   threshold, capacity: int = None, bm: int = 256,
                   interpret: bool = None):
    """Fused select-and-compact: one pass turns g (M,N) into COO upload
    buffers (idx (capacity,) int32, vals (capacity,) fp32, count int32),
    keeping entries where row[i]+col[j] > threshold, without
    materialising the mask or the dense masked gradient as separate
    arrays.  Default capacity is M*N (never truncates) — but the
    output buffers are revisited every grid step, so pass a capacity
    near the expected kept count (e.g. from the upload rate) on large
    inputs and compare ``count`` against it to detect dropped entries.

    The running-offset compaction needs the grid to execute
    sequentially, which only interpret mode guarantees on every
    backend, so this kernel defaults to interpret=True everywhere (the
    other kernels compile on TPU); pass interpret=False only on a
    backend whose grid is sequential.
    """
    interpret = True if interpret is None else interpret
    m, n = g.shape
    if capacity is None:
        capacity = m * n
    bm = min(bm, max(8, m))
    pm = (-m) % bm
    gp = jnp.pad(g, ((0, pm), (0, 0))) if pm else g
    # padded rows get -inf scores so they are never selected; columns are
    # not padded, so kernel flat indices are already g's flat indices
    rowp = jnp.pad(row.astype(jnp.float32), (0, pm),
                   constant_values=jnp.float32(-jnp.inf))
    idx, vals, cnt = select_compact_pallas(gp, rowp, col.astype(jnp.float32),
                                           threshold, bm=bm,
                                           capacity=capacity,
                                           interpret=interpret)
    return idx, vals, cnt[0]


def apoz_counts(acts: jnp.ndarray, bb: int = 512, bn: int = 256,
                interpret: bool = None) -> jnp.ndarray:
    """Zero counts per neuron over the batch; APoZ = counts / batch."""
    bb = min(bb, max(8, acts.shape[0]))
    bn = min(bn, max(8, acts.shape[1]))
    # pad batch rows with ones (non-zero → contribute no zero counts)
    ap, b, n = _pad2(acts, bb, bn, value=1.0)
    cnt = apoz_counts_pallas(ap, bb=bb, bn=bn, interpret=interpret)
    return cnt[:n]
