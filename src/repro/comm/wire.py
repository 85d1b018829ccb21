"""Sparse channel-exchange wire formats — what SCBF actually ships.

The paper's §3 communication claim is that uploading only the top-α
channel gradients saves bytes versus FedAvg's full-weight exchange.  The
seed simulated that claim with a flat 8-bytes-per-nonzero model, which
*loses* to dense once the edge-union of selected channels passes 50% of
entries.  This module replaces the simulation with real payloads and is
the single source of truth for upload-byte accounting.

Three codecs per layer (leaf), cheapest wins:

  ``coo``     int32 flat index + value per kept entry
              → nnz * (4 + itemsize) bytes
  ``bitmap``  1 bit per entry (packed) + values of kept entries
              → ceil(size / 8) + nnz * itemsize bytes
  ``dense``   every entry, no index structure
              → size * itemsize bytes

``min(coo, bitmap, dense) <= dense`` holds by construction, so the
sparse exchange can never cost more than FedAvg's dense one.  Encoding
is lossless: kept values travel in their original dtype, masked-out
entries decode back to exact zeros.

Payloads hold host (numpy) buffers — they model bytes crossing the
network, not device arrays — and are produced/consumed at the federated
loop boundary, outside any jit trace.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INDEX_BYTES = 4                      # int32 flat index (coo)

CODECS = ("coo", "bitmap", "dense")


class PayloadError(ValueError):
    """A wire payload failed structural validation.

    Raised before any index reaches a device scatter: JAX's ``.at[]``
    silently *drops* out-of-range indices, so without this gate a
    truncated or corrupted payload would "succeed" while quietly losing
    updates.  Decoders and the server admission gate catch this and
    reject the payload rather than applying it.
    """


def coo_bytes(nnz: int, size: int, itemsize: int = 4) -> int:
    return nnz * (INDEX_BYTES + itemsize)


def bitmap_bytes(nnz: int, size: int, itemsize: int = 4) -> int:
    return math.ceil(size / 8) + nnz * itemsize


def dense_bytes(size: int, itemsize: int = 4) -> int:
    return size * itemsize


def codec_bytes(codec: str, nnz: int, size: int, itemsize: int = 4) -> int:
    if codec == "coo":
        return coo_bytes(nnz, size, itemsize)
    if codec == "bitmap":
        return bitmap_bytes(nnz, size, itemsize)
    if codec == "dense":
        return dense_bytes(size, itemsize)
    raise ValueError(f"unknown codec {codec!r}")


def cheapest_bytes(nnz: int, size: int, itemsize: int = 4
                   ) -> Tuple[str, int]:
    """(codec, bytes) of the cheapest encoding for nnz kept of size."""
    return min(((c, codec_bytes(c, nnz, size, itemsize)) for c in CODECS),
               key=lambda cb: cb[1])


@dataclass(frozen=True)
class LayerPayload:
    """One leaf of a delta pytree on the wire."""

    codec: str                       # coo | bitmap | dense
    shape: Tuple[int, ...]
    dtype: np.dtype
    nnz: int                         # kept (transmitted-value) entries
    nbytes: int                      # wire size under ``codec``
    idx: Optional[np.ndarray]        # (nnz,) int32 flat indices — coo only
    bitmap: Optional[np.ndarray]     # packed uint8 mask — bitmap only
    values: np.ndarray               # kept values (coo/bitmap) or full flat

    @property
    def size(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) if self.shape else 1

    def flat_indices(self) -> np.ndarray:
        """int32 flat indices of the transmitted entries (any codec)."""
        if self.codec == "coo":
            return self.idx
        if self.codec == "bitmap":
            mask = np.unpackbits(self.bitmap, count=self.size)
            return np.flatnonzero(mask).astype(np.int32)
        return np.arange(self.size, dtype=np.int32)


@dataclass(frozen=True)
class PayloadMeta:
    """Integrity envelope a sealed payload carries on the wire.

    ``checksum`` is a CRC-32 over every layer's header fields and
    buffers (``payload_checksum``), computed when the *sender* seals
    the payload — any post-seal corruption (bit flips in transit)
    fails verification server-side.  ``(client_id, round_index)`` is
    the dedup nonce: the server admits each (client, round) upload at
    most once, so replayed/duplicated payloads are rejected.
    """

    client_id: int
    round_index: int
    checksum: int

    @property
    def nonce(self) -> Tuple[int, int]:
        return (self.client_id, self.round_index)


@dataclass(frozen=True)
class Payload:
    """A full delta pytree on the wire (one client's upload).

    ``meta`` is the optional integrity envelope (``seal``): unsealed
    payloads still pass structural validation but skip checksum and
    dedup checks — sealing is the driver's job at the trust boundary.
    """

    treedef: jax.tree_util.PyTreeDef
    layers: Tuple[LayerPayload, ...]
    meta: Optional[PayloadMeta] = None

    @property
    def nbytes(self) -> int:
        return sum(lp.nbytes for lp in self.layers)

    @property
    def dense_nbytes(self) -> int:
        return sum(dense_bytes(lp.size, lp.dtype.itemsize)
                   for lp in self.layers)


def encode_leaf(leaf, codec: str = "auto") -> LayerPayload:
    """Encode one masked array; zeros are treated as masked-out.

    One boolean pass picks the kept entries and their count, and the
    codec is chosen before any buffer is built: ``bitmap`` packs that
    boolean and gathers through it (``np.compress``, about twice as fast
    as boolean indexing), ``dense`` copies the leaf, and only ``coo``
    builds an index array.
    """
    a = np.asarray(leaf)
    flat = a.reshape(-1)
    kept = flat != 0
    nnz, size, itemsize = (int(np.count_nonzero(kept)), int(flat.size),
                           flat.dtype.itemsize)
    if codec == "auto":
        codec, nbytes = cheapest_bytes(nnz, size, itemsize)
    else:
        nbytes = codec_bytes(codec, nnz, size, itemsize)
    if codec == "coo":
        idx = np.flatnonzero(kept).astype(np.int32)
        return LayerPayload(codec, a.shape, flat.dtype, nnz, nbytes,
                            idx=idx, bitmap=None, values=flat[idx])
    if codec == "bitmap":
        return LayerPayload(codec, a.shape, flat.dtype, nnz, nbytes,
                            idx=None, bitmap=np.packbits(kept),
                            values=np.compress(kept, flat))
    # copied: flat may be a view into the caller's array (a pulled stack)
    return LayerPayload(codec, a.shape, flat.dtype, size, nbytes,
                        idx=None, bitmap=None, values=flat.copy())


def encode(tree, codec: str = "auto") -> Payload:
    """Encode a masked delta pytree; per leaf the cheapest codec wins."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return Payload(treedef, tuple(encode_leaf(l, codec) for l in leaves))


def codec_breakdown(payloads) -> dict:
    """Total wire bytes by winning codec over a batch of ``Payload``s.

    Keys are every ``CODECS`` name (zero-filled), so downstream
    telemetry (repro.obs) gets a stable schema whatever the deltas
    looked like this round.
    """
    out = {c: 0 for c in CODECS}
    for p in payloads:
        for lp in p.layers:
            out[lp.codec] += lp.nbytes
    return out


def validate_layer(lp: LayerPayload, leaf_shape: Optional[Tuple[int, ...]]
                   = None) -> None:
    """Structural validation of one wire leaf; raises ``PayloadError``.

    Checks everything a decoder is about to trust: codec name, nnz vs
    buffer sizes, index dtype and bounds ``[0, size)``, bitmap length
    and popcount, and (when ``leaf_shape`` is given) the declared shape
    against the server's parameter leaf.  This must run before any
    scatter: JAX drops out-of-range indices silently and numpy wraps
    negative ones, so unvalidated corruption would otherwise be applied
    *partially* instead of rejected.
    """
    if lp.codec not in CODECS:
        raise PayloadError(f"unknown codec {lp.codec!r}")
    if leaf_shape is not None and tuple(lp.shape) != tuple(leaf_shape):
        raise PayloadError(f"payload shape {tuple(lp.shape)} != "
                           f"param shape {tuple(leaf_shape)}")
    size = lp.size
    if not 0 <= lp.nnz <= size:
        raise PayloadError(f"nnz {lp.nnz} outside [0, {size}]")
    values = np.asarray(lp.values)
    if values.ndim != 1:
        raise PayloadError(f"values must be 1-D, got shape {values.shape}")
    if np.dtype(values.dtype) != np.dtype(lp.dtype):
        raise PayloadError(f"values dtype {values.dtype} != declared "
                           f"{np.dtype(lp.dtype)}")
    if lp.codec == "dense":
        if values.size != size:
            raise PayloadError(f"dense values size {values.size} != "
                               f"leaf size {size}")
        return
    if values.size != lp.nnz:
        raise PayloadError(f"{lp.codec} values size {values.size} != "
                           f"nnz {lp.nnz}")
    if lp.codec == "coo":
        idx = lp.idx
        if idx is None or not np.issubdtype(np.asarray(idx).dtype,
                                            np.integer):
            raise PayloadError("coo indices missing or non-integral")
        idx = np.asarray(idx)
        if idx.size != lp.nnz:
            raise PayloadError(f"coo idx size {idx.size} != nnz {lp.nnz}")
        if idx.size and (int(idx.min()) < 0 or int(idx.max()) >= size):
            raise PayloadError(
                f"coo index out of bounds: [{int(idx.min())}, "
                f"{int(idx.max())}] not within [0, {size})")
        return
    bitmap = lp.bitmap                                    # codec == bitmap
    if bitmap is None:
        raise PayloadError("bitmap payload missing its bitmap")
    bitmap = np.asarray(bitmap)
    if bitmap.dtype != np.uint8 or bitmap.size != math.ceil(size / 8):
        raise PayloadError(f"bitmap buffer {bitmap.dtype}[{bitmap.size}] "
                           f"!= uint8[{math.ceil(size / 8)}]")
    pop = int(np.unpackbits(bitmap, count=size).sum())
    tail = int(np.unpackbits(bitmap)[size:].sum())
    if pop != lp.nnz or tail:
        raise PayloadError(f"bitmap popcount {pop} (+{tail} tail bits) "
                           f"!= nnz {lp.nnz}")


def validate_payload(payload: Payload, params=None) -> None:
    """Validate every leaf of a payload (``PayloadError`` on failure).

    ``params``: optional server parameter pytree to check leaf count
    and shapes against — the same checks ``apply_payloads`` enforces.
    """
    shapes = None
    if params is not None:
        leaves = jax.tree_util.tree_leaves(params)
        if len(payload.layers) != len(leaves):
            raise PayloadError(
                f"payload has {len(payload.layers)} leaves, params have "
                f"{len(leaves)}")
        shapes = [tuple(np.shape(l)) for l in leaves]
    for i, lp in enumerate(payload.layers):
        try:
            validate_layer(lp, shapes[i] if shapes else None)
        except PayloadError as e:
            raise PayloadError(f"leaf {i}: {e}") from None


def payload_checksum(payload: Payload) -> int:
    """CRC-32 over every layer's header fields and wire buffers."""
    crc = 0
    for lp in payload.layers:
        header = f"{lp.codec}|{tuple(lp.shape)}|{np.dtype(lp.dtype)}|" \
                 f"{lp.nnz}".encode()
        crc = zlib.crc32(header, crc)
        if lp.idx is not None:
            crc = zlib.crc32(np.ascontiguousarray(lp.idx), crc)
        if lp.bitmap is not None:
            crc = zlib.crc32(np.ascontiguousarray(lp.bitmap), crc)
        crc = zlib.crc32(np.ascontiguousarray(lp.values), crc)
    return crc


def seal(payload: Payload, client_id: int, round_index: int) -> Payload:
    """Attach the integrity envelope: checksum + (client, round) nonce.

    Called by the sender at the trust boundary, after any client-side
    fault but before the bytes 'cross the network' — so wire-level
    corruption is detectable and replays are dedupable server-side.
    """
    meta = PayloadMeta(client_id=int(client_id),
                       round_index=int(round_index),
                       checksum=payload_checksum(payload))
    return dataclasses.replace(payload, meta=meta)


def verify_checksum(payload: Payload) -> bool:
    """True iff the sealed checksum matches the buffers (unsealed: True —
    there is nothing to verify against)."""
    if payload.meta is None:
        return True
    return payload_checksum(payload) == payload.meta.checksum


def decode_leaf(lp: LayerPayload) -> jnp.ndarray:
    validate_layer(lp)
    if lp.codec == "dense":
        flat = lp.values
    else:
        flat = np.zeros(lp.size, lp.dtype)
        flat[lp.flat_indices()] = lp.values
    return jnp.asarray(flat.reshape(lp.shape))


def decode(payload: Payload):
    """Lossless inverse of encode: masked entries come back exact zeros."""
    return jax.tree_util.tree_unflatten(
        payload.treedef, [decode_leaf(lp) for lp in payload.layers])


def tree_dense_bytes(tree) -> int:
    """Bytes a dense (FedAvg-style) exchange of this pytree would cost."""
    return sum(dense_bytes(l.size, np.dtype(l.dtype).itemsize)
               for l in jax.tree_util.tree_leaves(tree))


def apply_payloads(params, payloads: Sequence[Payload]):
    """W <- W + Σ_k decode(payload_k), without materialising K dense deltas.

    Per leaf, the client deltas accumulate **delta-first in client
    order** into one zero-initialised f32 buffer, which is then added to
    the parameters once: runs of consecutive coo/bitmap clients
    concatenate their (index, value) buffers into a single scatter-add
    (``.at[idx].add``), and each dense-codec client folds in as one
    vector add at its position in the order — so the per-coordinate
    accumulation order is the client order regardless of which codec
    each client's encoder picked.  Codec choice is data-dependent and
    must never change the arithmetic: this exact order is what the
    fused path's on-device slot-ordered reduction
    (``repro.fed.strategy.scbf_sum_step``) mirrors, making the two
    bit-identical.  (That parity additionally assumes the backend's
    scatter applies duplicate indices in update order — true of the
    backends we run, and pinned by the parity tests rather than by the
    XLA spec.)  Peak extra memory is one dense leaf plus the compact
    buffers — never K dense pytrees.
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    n = len(leaves)
    # per leaf: ordered ops, each ("scatter", idx, val) | ("dense", val)
    ops: List[List[Tuple]] = [[] for _ in range(n)]
    for p in payloads:
        if len(p.layers) != n:
            raise PayloadError("payload structure does not match params")
        for i, lp in enumerate(p.layers):
            # full structural gate (bounds/dtype/nnz) before any scatter:
            # JAX would silently drop out-of-range indices (see
            # PayloadError) — a corrupt payload must fail, not half-apply
            validate_layer(lp, tuple(leaves[i].shape))
            if lp.codec == "dense":
                ops[i].append(("dense", lp.values.astype(np.float32)))
            else:
                ops[i].append(("scatter", lp.flat_indices(),
                               lp.values.astype(np.float32)))
    out = []
    for i, leaf in enumerate(leaves):
        flat = leaf.reshape(-1).astype(jnp.float32)
        if ops[i]:
            acc = jnp.zeros(flat.shape, jnp.float32)
            pend_idx: List[np.ndarray] = []
            pend_val: List[np.ndarray] = []

            def flush(acc):
                if pend_idx:
                    cat_idx = jnp.asarray(np.concatenate(pend_idx))
                    cat_val = jnp.asarray(np.concatenate(pend_val))
                    acc = acc.at[cat_idx].add(cat_val)
                    pend_idx.clear()
                    pend_val.clear()
                return acc

            for op in ops[i]:
                if op[0] == "scatter":
                    pend_idx.append(op[1])
                    pend_val.append(op[2])
                else:
                    acc = flush(acc) + jnp.asarray(op[1])
            acc = flush(acc)
            flat = flat + acc
        out.append(flat.reshape(leaf.shape).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
