"""Encode/decode roundtrip + compression-ratio tests (paper §IV-D, Eq. 1/2)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; plain tests still run
    from _hypothesis_stub import given, settings, st

from repro.core import blocking, packing
from repro.core.apply import fake_quantize_array, pack_array, unpack_array
from repro.core.policy import StruMConfig, q_for_L
from repro.core.quantizers import int8_symmetric, n_low_for_p, quantize_blocks


@given(seed=st.integers(0, 500),
       method=st.sampled_from(["sparsity", "dliq", "mip2q"]),
       p=st.sampled_from([0.25, 0.5, 0.75]),
       k=st.integers(17, 80), n=st.integers(2, 40))
@settings(max_examples=40, deadline=None)
def test_roundtrip_exact(seed, method, p, k, n):
    """decode(pack(x)) == set-quantized values, bit-exactly, any shape."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    codes, scale = int8_symmetric(x, axis=0)
    w = 16
    n_low = n_low_for_p(p, w)
    q, L = (4, 7) if method != "mip2q" else (q_for_L(5), 5)
    blocks = blocking.to_blocks(codes, w)
    qb = quantize_blocks(blocks, method, n_low, q=q, L=L)
    pk = packing.pack(qb, method=method, scale=scale, k_dim=k,
                      n_low=n_low, q=q, L=L)
    dec = packing.decode_matrix(pk)
    ref = blocking.from_blocks(qb.values, k)
    assert bool(jnp.all(dec == ref))


def test_eq1_eq2_ratios():
    """Byte layout achieves the paper's Eq.1 / Eq.2 exactly for [1,16]."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    for method, p, q, L, want in [
        ("sparsity", 0.25, 4, 7, (9 - 8 * 0.25) / 8),
        ("sparsity", 0.5, 4, 7, 0.625),
        ("dliq", 0.5, 4, 7, 0.875),
        ("dliq", 0.25, 4, 7, (0.25 * (4 - 8) + 9) / 8),
        ("mip2q", 0.5, 4, 5, 0.875),
        ("mip2q", 0.75, 4, 5, (0.75 * (4 - 8) + 9) / 8),
    ]:
        cfg = StruMConfig(method=method, p=p, q=q, L=L)
        pk = pack_array(x, cfg)
        assert abs(pk.achieved_ratio() - want) < 1e-9, (method, p)
        assert abs(cfg.compression_ratio - want) < 1e-9


def test_unpack_matches_fake_quant():
    """pack->dequantize == fake_quantize (one transform, two paths)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(96, 24)).astype(np.float32))
    for method in ("sparsity", "dliq", "mip2q"):
        cfg = StruMConfig(method=method, p=0.5)
        via_pack = unpack_array(pack_array(x, cfg), x.shape)
        via_fake = fake_quantize_array(x, cfg)
        np.testing.assert_allclose(np.asarray(via_pack),
                                   np.asarray(via_fake), rtol=0, atol=0)


def test_pack_3d_expert_stack():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(4, 32, 16)).astype(np.float32))  # (E,K,N)
    cfg = StruMConfig(method="mip2q", p=0.5, L=7)
    pk = pack_array(x, cfg)
    back = unpack_array(pk, x.shape)
    assert back.shape == x.shape
    # error bounded by int8 + pow2-on-low error
    rel = float(jnp.linalg.norm((back - x).ravel()) / jnp.linalg.norm(x.ravel()))
    assert rel < 0.1


@given(nbits=st.sampled_from([2, 3, 4, 5, 8]), seed=st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_bitfield_pack_roundtrip(nbits, seed):
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(rng.integers(0, 1 << nbits, size=(3, 7, 5)), jnp.uint8)
    packed = packing._pack_fields(codes, nbits)
    back = packing._unpack_fields(packed, 7, nbits)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))


def _scatter_compact(values, mask, count):
    """The scatter that ``_gather_compact`` replaced: out[rank[i]] =
    values[i] where mask, unmasked positions parked in an overflow row."""
    nb, w, n = values.shape
    rank = jnp.cumsum(mask, axis=1) - mask.astype(jnp.int32)
    tgt = jnp.where(mask, rank, count)
    out = jnp.zeros((nb, count + 1, n), values.dtype)
    out = out.at[jnp.arange(nb)[:, None, None], tgt,
                 jnp.arange(n)[None, None, :]].set(values)
    return out[:, :count, :]


@pytest.mark.parametrize("dtype", [np.int8, np.uint8])
@pytest.mark.parametrize("masked", ["exact", "fewer", "more"])
def test_gather_compact_matches_the_scatter(dtype, masked):
    """The payload compaction writes the bytes the scatter wrote: with
    exactly ``count`` masked positions per block and column (what
    ``pack`` gives it), fewer (rows left 0) and more (the rest dropped)."""
    rng = np.random.default_rng(4)
    nb, w, n, count = 5, 16, 33, 8
    values = jnp.asarray(rng.integers(np.iinfo(dtype).min,
                                      int(np.iinfo(dtype).max) + 1,
                                      size=(nb, w, n)), dtype)
    keys = rng.random((nb, w, n)).argsort(axis=1).argsort(axis=1)
    k = {"exact": count, "fewer": count - 3, "more": count + 3}[masked]
    mask = jnp.asarray(keys < k)
    got = packing._gather_compact(values, mask, count)
    assert got.dtype == values.dtype
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_scatter_compact(values, mask,
                                                              count)))


def test_padding_blocks():
    x = jnp.arange(20, dtype=jnp.float32).reshape(10, 2)
    b = blocking.to_blocks(x, 16)
    assert b.shape == (1, 16, 2)
    back = blocking.from_blocks(b, 10)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
