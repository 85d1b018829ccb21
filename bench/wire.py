"""Plain reading of the program's wire format, for any model.

A payload (docs/WIRE_FORMAT.md) is a tree definition and one encoded
leaf per parameter leaf, in the order the tree flattens: the wire order.
Each leaf takes the cheapest of three codecs.  Written from the format's
documented contract; it imports nothing of the program.
"""
from __future__ import annotations

import math
from typing import List, Tuple

import numpy as np


def codec_bytes(nnz: int, size: int, itemsize: int = 4) -> dict:
    """Wire size of each codec for ``nnz`` kept entries of ``size``:
    int32 index + value per entry, a one-bit-per-entry bitmap + values,
    or every value."""
    return {"coo": nnz * (4 + itemsize),
            "bitmap": math.ceil(size / 8) + nnz * itemsize,
            "dense": size * itemsize}


def upload_bytes(leaves) -> int:
    """Bytes of one upload when every leaf takes its cheapest codec."""
    total = 0
    for leaf in leaves:
        nnz = int(np.count_nonzero(leaf))
        total += min(codec_bytes(nnz, int(leaf.size)).values())
    return total


def decode(payload) -> Tuple[List[np.ndarray], int]:
    """One program payload read back as float64 leaves in wire order, and
    the number of its leaves that break the wire format (a codec that is
    not the cheapest, a size that disagrees with the codec, an index out
    of range, or a count that does not match the values), counting one
    more where the tree definition holds another number of leaves."""
    bad = int(payload.treedef.num_leaves != len(payload.layers))
    leaves = []
    for lp in payload.layers:
        size = int(np.prod(lp.shape, dtype=np.int64)) if lp.shape else 1
        values = np.asarray(lp.values)
        flat = np.zeros(size, np.float64)
        costs = codec_bytes(int(lp.nnz), size, values.dtype.itemsize)
        if lp.codec == "dense":
            ok = values.size == size
            if ok:
                flat[:] = values
        elif lp.codec == "coo":
            idx = np.asarray(lp.idx, np.int64)
            ok = (idx.size == lp.nnz == values.size
                  and (idx.size == 0 or (idx.min() >= 0 and idx.max() < size))
                  and np.unique(idx).size == idx.size)
            if ok:
                flat[idx] = values
        elif lp.codec == "bitmap":
            bits = np.unpackbits(np.asarray(lp.bitmap, np.uint8))
            ok = (bits.size == 8 * math.ceil(size / 8)
                  and int(bits[size:].sum()) == 0
                  and int(bits[:size].sum()) == lp.nnz == values.size)
            if ok:
                flat[bits[:size].astype(bool)] = values
        else:
            ok = False
        ok = (ok and lp.codec in costs and lp.nbytes == costs[lp.codec]
              and lp.nbytes == min(costs.values()))
        bad += 0 if ok else 1
        leaves.append(flat.reshape(lp.shape))
    return leaves, bad
