"""Channel selection + upload accounting — paper §2.1 "Sort Norms" /
"Process Gradients" / "Update Server" steps.

``select_gradients`` is the full paper pipeline for the MLP family:
layer scores → α-quantile threshold → exact edge masks → masked gradients.
``upload_stats`` turns masks into the paper's §3 communication numbers
(fraction of parameters revealed; bytes for dense vs. sparse encodings).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import wire
from repro.core import channels


@dataclass
class UploadStats:
    uploaded_params: int          # non-zero gradient entries uploaded
    total_params: int
    dense_bytes: int              # dense exchange (what FedAvg ships)
    sparse_bytes: int             # cheapest wire encoding (repro.comm.wire)
    upload_fraction: float

    @classmethod
    def from_masks(cls, masks: Sequence[dict]) -> "UploadStats":
        """Accounting from boolean masks; byte math delegates to
        ``repro.comm.wire`` so ``sparse_bytes <= dense_bytes`` holds by
        construction (cheapest of coo/bitmap/dense per mask array).
        ``None`` entries (e.g. bias masks of bias-free layers) cost
        nothing — they correspond to no transmitted tensor.
        """
        up, total, sparse = 0, 0, 0
        for m in masks:
            for v in m.values():
                if v is None:
                    continue
                # host numpy on purpose: masks arrive per client, and the
                # batched engine calls this K times per round — a device
                # reduction per mask would serialise the host loop
                nnz, size = int(np.count_nonzero(np.asarray(v))), int(v.size)
                up += nnz
                total += size
                sparse += wire.cheapest_bytes(nnz, size, itemsize=4)[1]
        dense = total * 4
        return cls(up, total, dense, sparse, up / max(total, 1))


def select_gradients(grads: Sequence[dict], upload_rate: float,
                     selection: str = "positive",
                     key: jax.Array | None = None,
                     score_norm: bool = False,
                     neuron_masks=None
                     ) -> Tuple[list, list, jnp.ndarray]:
    """The paper's channel-selection pipeline for MLP gradients.

    positive: upload channels with norm above the (1-α)-quantile (top α).
    negative: discard channels below the α-quantile (upload the top 1-α).

    ``neuron_masks`` (mask-mode SCBFwP): per-hidden-layer keep-masks.
    Pruned neurons score ``-inf`` (channels.layer_scores), the quantile
    ranks the effective channel population only, and the edge rule can
    never select an edge through a pruned neuron — all at static shape,
    so the selection of a masked-pruned model matches a
    physically-compacted one.

    Returns (masked_grads, masks, threshold).
    """
    scores = channels.layer_scores(grads, normalize=score_norm,
                                   neuron_masks=neuron_masks)
    thr = channels.channel_quantile(scores, upload_rate,
                                    selection=selection, key=key,
                                    masked=neuron_masks is not None)
    masked, masks = channels.apply_channel_mask(grads, scores, thr)
    return masked, masks, thr


def tree_sub(a, b):
    """Gradient pytree a - b (the paper's G = W_after - W_before)."""
    return jax.tree_util.tree_map(lambda x, y: x - y, a, b)


def tree_add(a, b):
    return jax.tree_util.tree_map(lambda x, y: x + y, a, b)


def tree_scale(a, c):
    return jax.tree_util.tree_map(lambda x: x * c, a)


def tree_zeros_like(a):
    return jax.tree_util.tree_map(jnp.zeros_like, a)
