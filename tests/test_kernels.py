"""Pallas kernel sweeps vs the pure-jnp oracle (interpret mode on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.apply import pack_array
from repro.core.policy import StruMConfig
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _case(m, k, n, method="mip2q", p=0.5, dtype=np.float32, **kw):
    cfg = StruMConfig(method=method, p=p, **kw)
    wt = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32))
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(dtype))
    packed = pack_array(wt, cfg)
    return x, packed


@pytest.mark.parametrize("m,k,n", [
    (1, 16, 128), (4, 96, 200), (17, 160, 384), (8, 48, 130),
    (33, 272, 96), (128, 128, 128),
])
@pytest.mark.parametrize("method", ["sparsity", "dliq", "mip2q"])
def test_matmul_shapes(m, k, n, method):
    x, packed = _case(m, k, n, method=method)
    y = ops.strum_matmul(x, packed, interpret=True)
    y_ref = ref.strum_matmul_ref(x, packed)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("p", [0.25, 0.5, 0.75])
@pytest.mark.parametrize("method,kw", [
    ("dliq", {"q": 4}), ("dliq", {"q": 2}),
    ("mip2q", {"L": 7}), ("mip2q", {"L": 5}), ("mip2q", {"L": 3}),
])
def test_matmul_params(p, method, kw):
    x, packed = _case(5, 112, 192, method=method, p=p, **kw)
    y = ops.strum_matmul(x, packed, interpret=True)
    y_ref = ref.strum_matmul_ref(x, packed)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_matmul_dtypes(dtype):
    x, packed = _case(4, 64, 160, dtype=np.float32)
    x = x.astype(dtype)
    y = ops.strum_matmul(x, packed, interpret=True, out_dtype=jnp.float32)
    y_ref = ref.strum_matmul_ref(x.astype(jnp.float32), packed)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=tol, atol=tol)


def test_gemv_decode_path():
    x, packed = _case(1, 256, 512)
    y = ops.strum_gemv(x, packed, interpret=True)
    y_ref = ref.strum_matmul_ref(x, packed)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


def test_matmul_leading_dims():
    cfg = StruMConfig(method="mip2q", p=0.5, L=5)
    wt = jnp.asarray(RNG.normal(size=(48, 96)).astype(np.float32))
    x = jnp.asarray(RNG.normal(size=(2, 3, 48)).astype(np.float32))
    packed = pack_array(wt, cfg)
    y = ops.strum_matmul(x, packed, interpret=True)
    assert y.shape == (2, 3, 96)
    y_ref = ref.strum_matmul_ref(x.reshape(-1, 48), packed).reshape(2, 3, 96)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


def test_kernel_streams_fewer_bytes():
    """The whole point: the packed operands are r× the int8 bytes."""
    _, packed = _case(1, 1024, 512)
    int8_bytes = 1024 * 512
    assert packed.payload_bytes() / int8_bytes == pytest.approx(0.875)


# ------------------------------------------------------------- block picker --

def test_pick_block_stays_aligned():
    """Regression: the tile must be a multiple of ``align`` and never exceed
    the padded axis, even when pref is unaligned or the dim is tiny."""
    from repro.kernels.ops import _pick_block
    assert _pick_block(256, 200, 128) == 128   # pref unaligned: round down
    assert _pick_block(5, 256, 128) == 128     # tiny dim: one aligned block
    assert _pick_block(3, 256, 16) == 16
    assert _pick_block(200, 256, 128) == 256   # padded-axis clamp
    assert _pick_block(300, 256, 128) == 256
    assert _pick_block(64, 32, 128) == 128     # pref below align: floor
    for dim in (1, 3, 8, 127, 128, 129, 512):
        for pref in (8, 100, 128, 256):
            for align in (8, 16, 128):
                b = _pick_block(dim, pref, align)
                padded = -(-dim // align) * align
                assert b % align == 0 and b <= max(padded, align), \
                    (dim, pref, align, b)


def test_matmul_tiny_weight():
    """Regression: a weight smaller than every alignment (3x5) still runs
    and matches the oracle through each applicable variant."""
    for method, p, variant in [("mip2q", 0.5, "onehot"),
                               ("dliq", 1.0, "maskfree"),
                               ("dliq", 0.0, "dense")]:
        x, packed = _case(2, 3, 5, method=method, p=p,
                          **({"L": 5} if method == "mip2q" else {"q": 4}))
        y = ops.strum_matmul(x, packed, interpret=True, variant=variant)
        y_ref = ref.strum_matmul_ref(x, packed)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4, err_msg=variant)


# ------------------------------------------- full-grid three-way parity --

GRID = []
for _w in (8, 16):
    for _p in (0.0, 0.25, 1.0):
        GRID.append(("sparsity", _w, _p, {}))
        for _q in (2, 4, 8):
            GRID.append(("dliq", _w, _p, {"q": _q}))
        for _L in (3, 5):
            GRID.append(("mip2q", _w, _p, {"L": _L}))


@pytest.mark.parametrize("method,w,p,kw", GRID)
def test_parity_pallas_ref_dequant_grid(method, w, p, kw):
    """Pallas (registry-selected variant) vs jnp oracle vs dequant+dot across
    the full method × w × q grid, incl. the p=1.0 / n_low=0 edge cases."""
    from repro import engine
    from repro.core import packing

    x, packed = _case(3, 48 if w == 8 else 64, 96, method=method, p=p, w=w,
                      **kw)
    cfg = StruMConfig(method=method, p=p, w=w, **kw)
    info = engine.LeafInfo(k_dim=x.shape[-1], n_out=96)
    variant = engine.select_variant(cfg, info, backend="interpret")
    y_pal = variant.fn(x, packed, interpret=True)
    y_ref = ref.strum_matmul_ref(x, packed)
    y_deq = jnp.dot(x, packing.dequantize(packed, jnp.float32),
                    preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4, err_msg=variant.name)
    np.testing.assert_allclose(np.asarray(y_deq), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


# ------------------------------------------------ blocks chosen from shapes --

# (K, N) of every packed linear of the two served cells: Qwen2-7B's q/o,
# k/v, gate/up and down, OLMo-1B's q/k/v/o, gate/up and down (w = 16,
# MIP2Q p = 0.5 L = 5: mask, hi and lo rows 2, 8, 4)
CELL_LINEARS = [(3584, 3584), (3584, 512), (3584, 18944), (18944, 3584),
                (2048, 2048), (2048, 8192), (8192, 2048)]
CELL_ROWS = (2, 8, 4)


@pytest.mark.parametrize("m", [16, 48, 128])
@pytest.mark.parametrize("k,n", CELL_LINEARS)
def test_block_choice_at_cell_widths(m, k, n):
    """Every block divides its padded axis in whole sub-tiles, the step's
    estimated VMEM stays within the limit Mosaic is given, and an FFN
    linear takes at most 1/16 of the grid steps of a 256 x 256 grid."""
    from repro.kernels.strum_matmul import (STEP_WEIGHTS, VMEM_BUDGET,
                                            VMEM_LIMIT, _vmem_bytes)
    bm, sk, sn, bk, bn, grid = ops.grid_plan(m, k, n, 16, CELL_ROWS)
    assert (bm, sk, sn) == (m, 256, 256)
    assert k % bk == 0 and n % bn == 0 and bk % sk == 0 and bn % sn == 0
    assert grid == (1, n // bn, k // bk)
    vmem = _vmem_bytes(bm, bk, bn, 16, CELL_ROWS, 2, sk, sn)
    assert vmem <= VMEM_BUDGET <= VMEM_LIMIT
    steps = grid[1] * grid[2]
    if max(k, n) in (8192, 18944):                       # the FFN linears
        assert 16 * steps <= (k // 256) * (n // 256), (k, n, grid)
    # the block holds at least the per-step target where the axes allow it
    assert bk * bn >= min(STEP_WEIGHTS, k * n // 2)


def test_block_choice_respects_budget():
    """Where every block of ``STEP_WEIGHTS`` or more overflows
    ``VMEM_BUDGET``, the chooser takes a smaller one that fits: a K of 101
    sub-tiles (a prime) at 128 rows allows only whole-K blocks, over the
    budget, or one sub-tile of K.  A small weight is one step."""
    from repro.kernels.strum_matmul import (VMEM_BUDGET, _vmem_bytes,
                                            block_shape)
    k = 101 * 256
    assert _vmem_bytes(128, k, 256, 16, CELL_ROWS, 2, 256, 256) \
        > VMEM_BUDGET
    bk, bn = block_shape(128, k, 3584, 16, CELL_ROWS, sub_k=256, sub_n=256)
    assert (bk, bn) == (256, 3584)
    assert _vmem_bytes(128, bk, bn, 16, CELL_ROWS, 2, 256, 256) \
        <= VMEM_BUDGET
    assert block_shape(8, 512, 256, 16, CELL_ROWS, sub_k=256,
                       sub_n=256) == (512, 256)


def _padded_operands(m, k, n, method, p, w, sk, sn, **kw):
    """x and packed fields padded as ``ops._prepare`` pads them to the
    ``(sk, sn)`` tile (K ragged to ``w`` and to ``sk``, N to ``sn``)."""
    x, packed = _case(m, k, n, method=method, p=p, w=w, **kw)
    k_pad = packed.mask.shape[0] * w
    x2 = ops._pad_axis(ops._pad_axis(x, 1, k_pad), 1, sk)
    f = {name: ops._pad_axis(ops._pad_axis(ops._min1(a, 1), 0, sk // w), 2,
                             sn)
         for name, a in (("mask", packed.mask), ("hi", packed.hi),
                         ("lo", packed.lo))}
    return x, packed, x2, f, ops._pad_axis(packed.scale, 1, sn)


@pytest.mark.parametrize("bk,bn", [(384, 256), (128, 256), (384, 128),
                                   (128, 128)],
                         ids=["one_step", "n_subtiles", "k_subtiles",
                              "one_subtile"])
@pytest.mark.parametrize("variant", ["onehot", "maskfree", "dense"])
def test_subtiled_kernel_bit_equal_to_tile_grid(variant, bk, bn):
    """A (bk, bn) block folded in 128 x 128 sub-tiles gives the bits of a
    grid of 128 x 128 tiles: the same decode, the same f32 dots, K summed
    in the same order.  K = 300 is ragged to w = 16 and to the tile, N =
    200 pads to two tiles, so padded rows and columns pass through."""
    from repro.kernels.strum_matmul import (strum_matmul_pallas,
                                            strum_matmul_pallas_dense,
                                            strum_matmul_pallas_maskfree)
    method, p, kw = {"onehot": ("mip2q", 0.5, {"L": 5}),
                     "maskfree": ("dliq", 1.0, {"q": 4}),
                     "dense": ("dliq", 0.0, {"q": 4})}[variant]
    m, sk, sn = 8, 128, 128
    x, packed, x2, f, scale = _padded_operands(m, 300, 200, method, p, 16,
                                               sk, sn, **kw)
    assert x2.shape == (m, 384) and scale.shape == (1, 256)

    def run(block_k, block_n):
        tiles = dict(block_m=m, block_k=block_k, block_n=block_n, sub_k=sk,
                     sub_n=sn, interpret=True)
        if variant == "onehot":
            return strum_matmul_pallas(
                x2, f["mask"], f["hi"], f["lo"], scale, w=16,
                n_low=packed.n_low, q=packed.q, method=method, **tiles)
        if variant == "maskfree":
            return strum_matmul_pallas_maskfree(
                x2, f["lo"], scale, w=16, q=packed.q, method=method, **tiles)
        return strum_matmul_pallas_dense(x2, f["hi"], scale, w=16, **tiles)

    y, y_tiles = np.asarray(run(bk, bn)), np.asarray(run(sk, sn))
    np.testing.assert_array_equal(y, y_tiles)
    np.testing.assert_allclose(y[:, :200],
                               np.asarray(ref.strum_matmul_ref(x, packed)),
                               rtol=1e-5, atol=1e-4)


def test_strum_matmul_one_step_of_many_subtiles():
    """Through the public wrapper: a 1024 x 512 weight is one grid step of
    eight 256 x 256 sub-tiles, bit-equal to the 256 x 256 tile grid."""
    from repro.kernels.strum_matmul import strum_matmul_pallas
    x, packed = _case(4, 1024, 512, method="mip2q", p=0.5, L=5)
    *_, bk, bn, grid = ops.grid_plan(4, 1024, 512, 16, CELL_ROWS)
    assert (bk, bn, grid) == (1024, 512, (1, 1, 1))
    y = ops.strum_matmul(x, packed, interpret=True)
    x8 = ops._pad_axis(x, 0, 8)
    y_tiles = strum_matmul_pallas(
        x8, packed.mask, packed.hi, packed.lo, packed.scale, w=16,
        n_low=packed.n_low, q=packed.q, method="mip2q", block_m=8,
        block_n=256, block_k=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_tiles)[:4])
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.strum_matmul_ref(x, packed)),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("mode", ["histream", "maskfree_p"])
def test_draft_one_step_of_many_subtiles(mode):
    """The draft lowerings take the same shape-chosen block: a 1024 x 512
    weight is one grid step of eight 256 x 256 sub-tiles, bit-equal to the
    draft kernel over the 256 x 256 tile grid."""
    from repro.kernels.strum_matmul import (strum_matmul_pallas_histream,
                                            strum_matmul_pallas_maskfree_p)
    x, packed = _case(4, 1024, 512, method="mip2q", p=0.5, L=5)
    assert ops.grid_steps(4, packed, mode) == 1
    y = ops.strum_matmul_draft(x, packed, mode=mode, interpret=True)
    x8 = ops._pad_axis(x, 0, 8)
    tiles = dict(w=16, n_low=packed.n_low, block_m=8, block_n=256,
                 block_k=256, interpret=True)
    if mode == "histream":
        y_tiles = strum_matmul_pallas_histream(
            x8, packed.mask, packed.hi, packed.scale, **tiles)
    else:
        y_tiles = strum_matmul_pallas_maskfree_p(
            x8, packed.hi, packed.scale, **tiles)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_tiles)[:4])
