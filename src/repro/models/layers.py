"""Shared model layers: norms, rotary, StruM-aware linears, MLPs, embeddings.

All layers are functional: ``apply(params_subtree, x, ...)``.  Parameter
*definitions* live next to the apply functions so shapes/axes stay in sync.

StruM integration (first-class feature): any linear's ``w`` leaf may be
replaced by its compressed form — a dict of arrays
``{"mask", "hi", "lo", "scale"}`` produced by
:func:`repro.engine.build_plan` (whose ``spec`` records the selected kernel
variant) or by the legacy ``strum_serve_params`` shim.  Static metadata
(method, w, p, q, L) rides the leaf (``spec``/``cfg``) or falls back to
``cfg.strum``.  Execution goes through :func:`repro.engine.dispatch` — the
registry-selected Pallas variant, the XLA dequant fallback, or (when mesh
context rides along as ``tp_mesh``/``tp_pattern``) the registry's
``sharded:*`` compressed-gather family; this module passes the mesh
through and never branches on it, and imports no kernels directly.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.policy import StruMConfig
from repro.models.params import ParamDef

__all__ = [
    "rms_norm", "nonparam_ln", "norm_def", "apply_norm",
    "linear_def", "linear", "mlp_def", "mlp",
    "rope_freqs", "apply_rope",
    "embed_def", "embed_lookup", "logits",
]


# ----------------------------------------------------------------- norms --

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def nonparam_ln(x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """OLMo's non-parametric LayerNorm — no scale, no bias."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(dt)


def norm_def(cfg, lead=()):
    if cfg.norm == "nonparam":
        return {}
    return {"scale": ParamDef(lead + (cfg.d_model,),
                              ("layers",) * len(lead) + ("embed_no_fsdp",),
                              init="ones")}


def apply_norm(p: dict, x: jnp.ndarray, cfg) -> jnp.ndarray:
    if cfg.norm == "nonparam" or "scale" not in p:
        return nonparam_ln(x)
    return rms_norm(x, p["scale"])


# --------------------------------------------------------------- linears --

def linear_def(d_in: int, d_out: int, in_axis: str, out_axis: str,
               bias: bool = False, lead=(), scale: float = 1.0) -> dict:
    lead_axes = ("layers",) * len(lead)
    d = {"w": ParamDef(lead + (d_in, d_out), lead_axes + (in_axis, out_axis),
                       scale=scale)}
    if bias:
        d["b"] = ParamDef(lead + (d_out,), lead_axes + (out_axis,), init="zeros")
    return d


def linear(p: dict, x: jnp.ndarray, *, strum: Optional[StruMConfig] = None,
           use_kernel: bool = False, backend: Optional[str] = None,
           accum_dtype=jnp.float32,
           tp_mesh=None, tp_pattern: Optional[str] = None) -> jnp.ndarray:
    """y = x @ W (+ b).  Dense or StruM-compressed weights.

    Compressed leaves dispatch through :mod:`repro.engine` — the variant a
    plan recorded, or one selected on the fly for legacy leaves.
    ``backend`` overrides per call (``"interpret"``, ``"xla"``, ...);
    ``use_kernel=True`` is the legacy spelling of ``backend="pallas"``.

    ``accum_dtype`` is the preferred element type of the contraction: when a
    contraction dim is TP-sharded, XLA all-reduces partial sums in this
    dtype — bf16 halves that collective payload (§Perf knob; per-shard MXU
    accumulation stays f32 internally either way).
    """
    acc = jnp.dtype(accum_dtype)
    wleaf = p.get("w", p)
    if isinstance(wleaf, dict) and "mask" in wleaf:  # compressed (module docstring)
        from repro.engine.dispatch import dispatch
        if backend is None and use_kernel:
            backend = "pallas"
        y = dispatch(wleaf, x, strum=strum, backend=backend,
                     accum_dtype=acc, tp_mesh=tp_mesh, tp_pattern=tp_pattern)
    else:
        w = p["w"]
        y = jnp.dot(x, w.astype(x.dtype),
                    preferred_element_type=acc).astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ------------------------------------------------------------------ MLPs --

def mlp_def(cfg, lead=()) -> dict:
    """SwiGLU (gated) or plain-GELU MLP."""
    d, f = cfg.d_model, cfg.d_ff
    out = {"wi": linear_def(d, f, "embed", "mlp", lead=lead)}
    if cfg.gated_mlp:
        out["wg"] = linear_def(d, f, "embed", "mlp", lead=lead)
    out["wo"] = linear_def(f, d, "mlp", "embed", lead=lead)
    return out


def mlp(p: dict, x: jnp.ndarray, cfg, **kw) -> jnp.ndarray:
    kw_c = dict(kw, tp_pattern="col")
    h = linear(p["wi"], x, **kw_c)
    if cfg.gated_mlp:
        h = jax.nn.silu(linear(p["wg"], x, **kw_c)) * h
    else:
        h = jax.nn.gelu(h)
    return linear(p["wo"], h, **dict(kw, tp_pattern="row"))


# ------------------------------------------------------------------ RoPE --

def rope_freqs(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    return jnp.concatenate([rx1, rx2], axis=-1).astype(x.dtype)


# ------------------------------------------------------------ embeddings --

def embed_def(cfg) -> dict:
    return {"table": ParamDef((cfg.padded_vocab, cfg.d_model),
                              ("vocab", "embed_no_fsdp"), scale=1.0)}


def embed_lookup(p: dict, ids: jnp.ndarray, dtype) -> jnp.ndarray:
    return p["table"].astype(dtype)[ids]


def logits(head_p: Optional[dict], embed_p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """LM head: tied (embed^T) or untied, under the ``head:dense`` scope so
    that a device trace attributes its ops (the scope is metadata only)."""
    with jax.named_scope("head:dense"):
        if head_p is not None:
            return jnp.dot(x, head_p["w"].astype(x.dtype),
                           preferred_element_type=jnp.float32)
        return jnp.dot(x, embed_p["table"].astype(x.dtype).T,
                       preferred_element_type=jnp.float32)
