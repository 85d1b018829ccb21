"""Wire-format subsystem: lossless round-trips, the bytes-never-exceed-
dense invariant, and sparse-apply == dense-apply equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import wire
from repro.core import selection
from repro.core.server import scbf_update
from repro.models.mlp_net import init_mlp

RATES = [0.05, 0.25, 0.5, 0.9]
SHAPES = [(4,), (1, 1), (8, 8), (100, 3), (33, 257), (3, 4, 5), (64,)]


def _masked_array(shape, density, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=shape).astype(dtype)
    keep = rng.random(shape) < density
    return jnp.asarray(np.where(keep, a, 0).astype(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5, 1.0])
def test_leaf_roundtrip_exact(shape, density):
    a = _masked_array(shape, density)
    lp = wire.encode_leaf(a)
    back = wire.decode_leaf(lp)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(back))
    assert lp.nbytes <= wire.dense_bytes(a.size, 4)


@pytest.mark.parametrize("codec", ["coo", "bitmap", "dense"])
def test_every_codec_roundtrips(codec):
    a = _masked_array((17, 23), 0.3, seed=len(codec))
    lp = wire.encode_leaf(a, codec=codec)
    assert lp.codec == codec
    np.testing.assert_array_equal(np.asarray(a),
                                  np.asarray(wire.decode_leaf(lp)))


def test_cheapest_bytes_is_min_and_never_above_dense():
    for size in [1, 7, 64, 10_000]:
        for nnz in {0, 1, size // 2, size - 1, size} - {-1}:
            nnz = max(0, nnz)
            codec, b = wire.cheapest_bytes(nnz, size, 4)
            assert b == min(wire.codec_bytes(c, nnz, size, 4)
                            for c in wire.CODECS)
            assert b <= wire.dense_bytes(size, 4)


@pytest.mark.parametrize("rate", RATES)
def test_mlp_payload_roundtrip_and_byte_invariant(rate):
    """Paper pipeline end to end: channel-select an MLP delta, encode,
    decode losslessly, and never pay more than the dense exchange."""
    key = jax.random.PRNGKey(0)
    params = init_mlp((40, 16, 8, 1), key)
    grads = [
        {"w": jax.random.normal(jax.random.fold_in(key, 2 * i), l["w"].shape),
         "b": jax.random.normal(jax.random.fold_in(key, 2 * i + 1),
                                l["b"].shape)}
        for i, l in enumerate(params)]
    masked, masks, _ = selection.select_gradients(grads, rate,
                                                  key=jax.random.PRNGKey(1))
    payload = wire.encode(tuple(masked))
    back = wire.decode(payload)
    for a, b in zip(jax.tree_util.tree_leaves(tuple(masked)),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert payload.nbytes <= payload.dense_nbytes
    # mask-based accounting agrees with the invariant too
    st = selection.UploadStats.from_masks(masks)
    assert st.sparse_bytes <= st.dense_bytes


@pytest.mark.parametrize("rate", RATES)
def test_sparse_apply_equals_dense_apply(rate):
    """scbf_update(payloads=...) == scbf_update(masked_deltas) on random
    MLP deltas — the scatter-add path reproduces the dense tree-sum."""
    key = jax.random.PRNGKey(3)
    params = init_mlp((30, 12, 4, 1), key)
    deltas = []
    for c in range(4):
        g = [{"w": jax.random.normal(jax.random.fold_in(key, 10 * c + i),
                                     l["w"].shape),
              "b": jax.random.normal(jax.random.fold_in(key, 10 * c + 5 + i),
                                     l["b"].shape)}
             for i, l in enumerate(params)]
        masked, _, _ = selection.select_gradients(
            g, rate, key=jax.random.fold_in(key, 100 + c))
        deltas.append(tuple(masked))
    dense_new = scbf_update(params, deltas)
    sparse_new = scbf_update(params, payloads=[wire.encode(d)
                                               for d in deltas])
    for a, b in zip(jax.tree_util.tree_leaves(dense_new),
                    jax.tree_util.tree_leaves(sparse_new)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_scbf_update_rejects_ambiguous_args():
    params = init_mlp((6, 3, 1), jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        scbf_update(params)
    with pytest.raises(ValueError):
        scbf_update(params, [params], payloads=[wire.encode(params)])


def test_apply_payloads_shape_mismatch_raises():
    params = {"w": jnp.zeros((4, 4))}
    bad = wire.encode({"w": jnp.ones((3, 3))})
    with pytest.raises(ValueError):
        wire.apply_payloads(params, [bad])


def test_kernel_compact_buffers_match_wire_coo():
    """The fused select-and-compact kernel emits exactly the (idx, value)
    buffers the COO codec ships for the same mask."""
    from repro.kernels import ops, ref
    g = jax.random.normal(jax.random.PRNGKey(5), (24, 17))
    row, col = ref.channel_norms_ref(g)
    thr = jnp.quantile(row[:, None] + col[None, :], 0.8)
    idx, vals, cnt = ops.select_compact(g, row, col, thr)
    n = int(cnt)
    masked = ref.select_mask_ref(g, row, col, thr)
    lp = wire.encode_leaf(masked, codec="coo")
    np.testing.assert_array_equal(np.asarray(idx[:n]), lp.idx)
    np.testing.assert_allclose(np.asarray(vals[:n]),
                               lp.values.astype(np.float32), rtol=1e-6)


def _encode_leaf_index_based(leaf, codec="auto"):
    """Frozen reference: the index-array encoder the one-pass
    ``wire.encode_leaf`` replaced (flat indices for every codec, a
    scattered uint8 mask for the bitmap)."""
    a = np.asarray(leaf)
    flat = a.reshape(-1)
    nz = np.flatnonzero(flat).astype(np.int32)
    nnz, size, itemsize = int(nz.size), int(flat.size), flat.dtype.itemsize
    if codec == "auto":
        codec, nbytes = wire.cheapest_bytes(nnz, size, itemsize)
    else:
        nbytes = wire.codec_bytes(codec, nnz, size, itemsize)
    if codec == "coo":
        return wire.LayerPayload(codec, a.shape, flat.dtype, nnz, nbytes,
                                 idx=nz, bitmap=None, values=flat[nz].copy())
    if codec == "bitmap":
        mask = np.zeros(size, np.uint8)
        mask[nz] = 1
        return wire.LayerPayload(codec, a.shape, flat.dtype, nnz, nbytes,
                                 idx=None, bitmap=np.packbits(mask),
                                 values=flat[nz].copy())
    return wire.LayerPayload(codec, a.shape, flat.dtype, size, nbytes,
                             idx=None, bitmap=None, values=flat.copy())


# auto crossovers for 4-byte values: coo/bitmap at 1/32 kept,
# bitmap/dense at 31/32
IDENTITY_DENSITIES = [0.0, 0.02, 0.045, 0.5, 0.95, 0.99, 1.0]
# 0-d, 1-D and 2-D sizes off a multiple of 8, and one 2-D on it
IDENTITY_SHAPES = [(), (1001,), (33, 257), (16, 64)]
# (dtype, with -0.0 among the dropped entries and a NaN among the kept)
IDENTITY_KINDS = [("float32", False), ("float32", True),
                  ("bfloat16", False), ("bfloat16", True), ("int32", False)]


def _identity_leaf(shape, density, dtype, specials, seed=0):
    """A leaf with exactly round(density * size) kept entries, returned
    as a view into a stack (as the emitters pass pulled chunk slots)."""
    rng = np.random.default_rng(seed)
    size = int(np.prod(shape, dtype=np.int64))
    dt = jnp.dtype(dtype)
    if dt.kind == "i":
        vals = rng.integers(1, 1000, size) * rng.choice([-1, 1], size)
    else:
        vals = rng.normal(size=size) + np.where(rng.random(size) < .5, 1, -1)
    flat = vals.astype(dt)
    assert np.count_nonzero(flat) == size
    order = rng.permutation(size)
    nkeep = int(round(density * size))
    flat[order[nkeep:]] = 0
    if specials:
        flat[order[nkeep:][::2]] = -0.0
        if nkeep:
            flat[order[0]] = np.nan
    stack = np.stack([np.zeros_like(flat), flat]).reshape((2,) + shape)
    return stack[1]


def _assert_same_payload(got, want):
    assert got.codec == want.codec
    assert got.nnz == want.nnz
    assert got.nbytes == want.nbytes
    assert tuple(got.shape) == tuple(want.shape)
    assert np.dtype(got.dtype) == np.dtype(want.dtype)
    for name in ("idx", "bitmap", "values"):
        g, w = getattr(got, name), getattr(want, name)
        if w is None:
            assert g is None, name
            continue
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert g.tobytes() == w.tobytes(), name
    treedef = jax.tree_util.tree_structure([0])
    assert (wire.payload_checksum(wire.Payload(treedef, (got,)))
            == wire.payload_checksum(wire.Payload(treedef, (want,))))


@pytest.mark.parametrize("kind", IDENTITY_KINDS,
                         ids=lambda k: k[0] + ("-negzero-nan" * k[1]))
@pytest.mark.parametrize("shape", IDENTITY_SHAPES, ids=str)
@pytest.mark.parametrize("density", IDENTITY_DENSITIES)
def test_encode_leaf_byte_identical_to_index_reference(kind, shape, density):
    """The one-pass encoder ships exactly what the index-based encoder
    shipped: codec, counts, buffers and checksum, for every codec forced
    and for the cheapest one; no payload buffer aliases the input."""
    dtype, specials = kind
    leaf = _identity_leaf(shape, density, dtype, specials)
    for codec in ("auto",) + wire.CODECS:
        got = wire.encode_leaf(leaf, codec)
        _assert_same_payload(got, _encode_leaf_index_based(leaf, codec))
        assert not np.shares_memory(got.values, leaf)


def test_identity_cases_reach_every_auto_codec():
    """The densities of the identity test straddle both crossovers
    (bitmap/dense lies at 15/16 kept for 2-byte values)."""
    want = {"float32": ["coo", "coo", "bitmap", "bitmap", "bitmap",
                        "dense", "dense"],
            "bfloat16": ["coo", "coo", "bitmap", "bitmap", "dense",
                         "dense", "dense"]}
    for dtype, codecs in want.items():
        picked = [wire.encode_leaf(
            _identity_leaf((33, 257), d, dtype, False)).codec
            for d in IDENTITY_DENSITIES]
        assert picked == codecs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_upload_stats_from_masks_counts_like_sum(seed):
    rng = np.random.default_rng(seed)
    masks = []
    for _ in range(4):
        layers = {}
        for name, shape in (("w0", (37, 16)), ("w1", (16, 9)), ("w2", (9,))):
            layers[name] = rng.random(shape) < rng.random()
        layers["b0"] = None
        layers["b1"] = rng.random((16,)) < 0.5
        masks.append(layers)
    st = selection.UploadStats.from_masks(masks)
    up = total = sparse = 0
    for m in masks:
        for v in m.values():
            if v is None:
                continue
            nnz = int(np.sum(v))
            up, total = up + nnz, total + v.size
            sparse += wire.cheapest_bytes(nnz, v.size, itemsize=4)[1]
    assert (st.uploaded_params, st.total_params, st.sparse_bytes) == (
        up, total, sparse)
