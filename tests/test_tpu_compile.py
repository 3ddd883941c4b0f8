"""Compile the main-path Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler is installed with jaxlib and compiles
against a topology that is described, not attached.  This catches what
interpret mode cannot — Mosaic lowering gaps, unaligned tiles, scoped-VMEM
overruns — at the widths OLMo-1B serves with (d_model 2048, d_ff 8192,
head_dim 128, page_size 16), and at Qwen2-7B's grouped query rows in the
fused attention.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and the test workers each
import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import packing
from repro.core.policy import StruMConfig
from repro.kernels import ops
from repro.kernels.strum_attention import strum_paged_attention_pallas

MIXED = StruMConfig(method="mip2q", p=0.5)          # selects pallas:onehot
LOW_ONLY = StruMConfig(method="dliq", p=1.0, q=4)   # pallas:maskfree
HIGH_ONLY = StruMConfig(method="dliq", p=0.0)       # pallas:dense


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2 host, as a sharding to place shapes."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    return compiled


def _packed_shapes(cfg, k, n, chip):
    nb = -(-k // cfg.w)
    mb, nh, lb = packing.field_dims(cfg.w, cfg.n_low, cfg.q, cfg.method)
    return [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in (
        ((nb, mb, n), jnp.uint8), ((nb, max(nh, 1), n), jnp.int8),
        ((nb, max(lb, 1), n), jnp.uint8), ((1, n), jnp.float32))]


@pytest.mark.parametrize("variant,cfg", [
    ("onehot", MIXED), ("maskfree", LOW_ONLY), ("dense", HIGH_ONLY)])
@pytest.mark.parametrize("k,n", [(2048, 8192), (8192, 2048)])
def test_gemv_compiles_at_olmo_widths(chip, variant, cfg, k, n):
    """Decode GEMV tiles (M=8) of the MLP in and out projections."""
    def gemv(x, mask, hi, lo, scale):
        p = packing.PackedStruM(cfg.method, cfg.w, cfg.n_low, cfg.q, cfg.L,
                                k, scale, mask, hi, lo)
        return ops.strum_gemv(x, p, interpret=False, variant=variant)
    _compile(gemv, jax.ShapeDtypeStruct((8, k), jnp.bfloat16, sharding=chip),
             *_packed_shapes(cfg, k, n, chip))


def test_onehot_compiles_at_prefill_tile(chip):
    """A 128-row prefill tile of the general mixed-payload kernel."""
    k, n = 2048, 8192

    def matmul(x, mask, hi, lo, scale):
        p = packing.PackedStruM(MIXED.method, MIXED.w, MIXED.n_low, MIXED.q,
                                MIXED.L, k, scale, mask, hi, lo)
        return ops.strum_matmul(x, p, interpret=False, variant="onehot")
    _compile(matmul,
             jax.ShapeDtypeStruct((128, k), jnp.bfloat16, sharding=chip),
             *_packed_shapes(MIXED, k, n, chip))


def _compile_fused_attention(chip, b, pages, heads, rows):
    """cache:attn_fused over DLIQ q=4 pages (head_dim 128, page_size 16):
    ``b`` slots x ``pages`` pages, ``heads`` KV heads, ``rows`` query rows
    per KV head."""
    kv = StruMConfig(method="dliq", p=0.5, q=4)
    hd, ps = 128, 16
    nb = ps // kv.w
    mb, nh, lb = packing.field_dims(kv.w, kv.n_low, kv.q, kv.method)
    f = heads * hd

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    page = [s((b, pages, nb, mb, f), jnp.uint8),
            s((b, pages, nb, nh, f), jnp.int8),
            s((b, pages, nb, lb, f), jnp.uint8),
            s((b, pages, 1, f), jnp.float32)]

    def attn(q4, *rest):
        return strum_paged_attention_pallas(
            q4, *rest, w=kv.w, n_low=kv.n_low, q=kv.q, method=kv.method,
            interpret=False)
    _compile(attn, s((b, heads, rows, hd), jnp.float32), *page, *page,
             s((b, pages), jnp.int32), s((b,), jnp.int32))


def test_fused_attention_compiles(chip):
    """OLMo-1B's decode: 16 KV heads, one query row each, 4 decode slots x
    36 pages."""
    _compile_fused_attention(chip, b=4, pages=36, heads=16, rows=1)


@pytest.mark.parametrize("b,heads,rows", [(48, 4, 7), (1, 4, 896),
                                          (1, 16, 128)],
                         ids=["decode", "prefill_chunk", "olmo_prefill_chunk"])
def test_fused_attention_compiles_at_grouped_query_rows(chip, b, heads, rows):
    """Qwen2-7B's grouped queries: 28 query heads over 4 KV heads give 7
    rows per KV head in decode (odd, and below the 8-row sublane tile) and
    128 x 7 = 896 in a 128-token prefill chunk; 2048-token windows of 128
    pages, 48 decode slots.  Beside them OLMo-1B's 128-row prefill chunk
    over 16 KV heads, whose block folds fewer heads than its decode's."""
    _compile_fused_attention(chip, b=b, pages=128, heads=heads, rows=rows)
