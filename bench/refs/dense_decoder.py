"""Plain float32 reference of a dense decoder served with StruM codecs.

Covers OLMo (non-parametric LayerNorm, tied embedding) and Qwen2 (RMSNorm,
grouped-query attention, QKV bias), as their configuration files state
them, with the codecs those files name:

* every linear of a block is served as MIP2Q: per-output-channel int8 over
  the reduction axis, ``[1, w]`` blocks along it, and in each block the
  ``p * w`` values with the smallest power-of-two rounding error replaced by
  that power of two (shift clipped to ``[0, L]``);
* keys and values are stored in pages of ``page_size`` positions; a page
  that the server has sealed is DLIQ-coded (per-channel int8 over the page,
  the ``p * w`` smallest magnitudes of each block kept to ``q`` bits), the
  rest are exact.  Which pages are sealed when a query runs follows the
  server's documented schedule: a prompt is processed in chunks of
  ``prefill_chunk`` positions, and a chunk sees every page before its
  start sealed; a decoded token sees every full page before its own.

Everything is ``jax.numpy`` in float32 under ``default_matmul_precision
("highest")``, written from the published architecture and the codec
definitions, and imports nothing of the program.  Weights are drawn again
from the seed (``bench.lib.weights``), one layer at a time, so the
reference fits beside nothing else on the chip.

``control=True`` runs the same model with every activation the server keeps
in bfloat16 rounded to fp8 instead (4 significant bits, as e4m3 keeps; the
exponent range is not clipped): the precision step below the
configuration's, which the comparison must reject.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights as W

F32 = jnp.float32
QUERY_BLOCK = 256
ROW_BLOCK = 256
SEQ_BUCKET = 512


# ----------------------------------------------------------- architecture --

class Arch:
    """Sizes of the configuration file, read once."""

    def __init__(self, config: dict):
        c = config
        self.d = c["hidden_size"]
        self.layers = c["num_hidden_layers"]
        self.nh = c["num_attention_heads"]
        self.nkv = c.get("num_key_value_heads") or self.nh
        self.hd = c.get("head_dim") or self.d // self.nh
        self.f = c["intermediate_size"]
        self.vocab = c["vocab_size"]
        self.vocab_rows = -(-self.vocab // 256) * 256
        self.tied = bool(c.get("tie_word_embeddings", False))
        self.bias = bool(c.get("attention_bias", False)
                         or c.get("model_type") == "qwen2")
        self.theta = float(c.get("rope_theta", 10000.0))
        ref = c["reference"]
        self.norm = ref["norm"]
        self.eps = float(ref["norm_eps"])
        s = c["serve"]
        self.wcodec = s["weights"]
        self.kvcodec = s["kv"]
        self.page = int(s["page_size"])
        self.chunk = int(s["prefill_chunk"])
        if self.norm not in ("layernorm_nonparam", "rmsnorm"):
            raise ValueError(f"norm {self.norm!r}")
        if self.wcodec["method"] != "mip2q" or self.kvcodec["method"] != "dliq":
            raise ValueError("this reference implements MIP2Q weights and "
                             "DLIQ KV pages")

    def _key(self):
        return tuple(sorted((k, repr(v)) for k, v in vars(self).items()))

    def __eq__(self, other):
        return isinstance(other, Arch) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())


def leaves(config: dict) -> list:
    """Every parameter, named by its path in the program's tree.  The gains
    keep the residual stream and the attention scores of order one and the
    logits of order 2 (untied) or 4 (tied, where the embedding is also the
    head).  A weaker embedding or sharper attention makes this random model
    amplify bf16 rounding with depth until served tokens no longer follow
    the reference; far stronger logits leave no near-tie that even fp8
    rounding would flip, so the check could not see a precision drop."""
    a = Arch(config)
    d, L = a.d, a.layers
    qd, kd = a.nh * a.hd, a.nkv * a.hd
    emb = 4.0 / math.sqrt(d) if a.tied else 1.0
    out = [W.Leaf("embed/table", (a.vocab_rows, d), 0, emb)]
    blk = "blocks/pos0/"
    if a.norm == "rmsnorm":
        out.append(W.Leaf(blk + "norm1/scale", (d,), L, 0.1, 1.0))
    out += [W.Leaf(blk + "attn/wq/w", (d, qd), L, 1.0 / math.sqrt(d)),
            W.Leaf(blk + "attn/wk/w", (d, kd), L, 1.0 / math.sqrt(d)),
            W.Leaf(blk + "attn/wv/w", (d, kd), L, 1.0 / math.sqrt(d)),
            W.Leaf(blk + "attn/wo/w", (qd, d), L, 1.0 / math.sqrt(qd))]
    if a.bias:
        out += [W.Leaf(blk + "attn/wq/b", (qd,), L, 0.5),
                W.Leaf(blk + "attn/wk/b", (kd,), L, 0.5),
                W.Leaf(blk + "attn/wv/b", (kd,), L, 0.5)]
    if a.norm == "rmsnorm":
        out.append(W.Leaf(blk + "norm2/scale", (d,), L, 0.1, 1.0))
    out += [W.Leaf(blk + "mlp/wi/w", (d, a.f), L, 1.0 / math.sqrt(d)),
            W.Leaf(blk + "mlp/wg/w", (d, a.f), L, 1.0 / math.sqrt(d)),
            W.Leaf(blk + "mlp/wo/w", (a.f, d), L, 1.0 / math.sqrt(a.f))]
    if a.norm == "rmsnorm":
        out.append(W.Leaf("final_norm/scale", (d,), 0, 0.1, 1.0))
    if not a.tied:
        out.append(W.Leaf("lm_head/w", (d, a.vocab_rows), 0,
                          2.0 / math.sqrt(d)))
    return out


def packed_names(config: dict) -> set:
    """The leaves the configuration serves through the weight codec."""
    return {lf.name for lf in leaves(config)
            if lf.layers and lf.name.endswith("/w")}


# ----------------------------------------------------------------- codecs --

def _int8(x, scale_dtype):
    """Per-channel symmetric int8 over axis -2: (codes as f32, f32 scale).
    The scale is rounded to ``scale_dtype``, the type the values arrive in."""
    amax = jnp.max(jnp.abs(x.astype(scale_dtype)), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / jnp.asarray(127, scale_dtype),
                      jnp.asarray(1, scale_dtype)).astype(F32)
    codes = jnp.clip(jnp.round(x.astype(F32) / scale), -127, 127)
    return codes, scale


def _rank(key):
    """Position of each element of a block (axis -2) in ascending key order,
    ties broken by position."""
    w = key.shape[-2]
    rank = jnp.zeros(key.shape, jnp.int32)
    pos = jnp.arange(w).reshape((w, 1))
    for j in range(w):
        kj = key[..., j:j + 1, :]
        rank = rank + ((kj < key) | ((kj == key) & (j < pos))).astype(
            jnp.int32)
    return rank


def _pow2(codes, L):
    """Nearest signed power of two, shift in [0, L]; ties to the smaller;
    0 maps to +1."""
    a = jnp.abs(codes)
    best = jnp.full(a.shape, jnp.inf, F32)
    mag = jnp.ones(a.shape, F32)
    for k in range(L + 1):
        e = jnp.abs(a - 2.0 ** k)
        take = e < best
        best = jnp.where(take, e, best)
        mag = jnp.where(take, 2.0 ** k, mag)
    return jnp.where(codes < 0, -mag, mag)


@functools.partial(jax.jit, static_argnames=("bw", "n_low", "L"))
def mip2q_weight(w, *, bw, n_low, L):
    """(K, N) weight as served in bf16 -> its MIP2Q dequantized f32 value."""
    k, n = w.shape
    if k % bw:
        raise ValueError(f"K={k} is not a multiple of the block width {bw}")
    codes, scale = _int8(w, w.dtype)
    blocks = codes.reshape(k // bw, bw, n)
    p2 = _pow2(blocks, L)
    key = jnp.abs(blocks - p2) * 256 + jnp.abs(blocks)
    low = _rank(key) < n_low
    vals = jnp.where(low, p2, blocks).reshape(k, n)
    return vals * scale


def dliq_pages(x, *, page, bw, n_low, q):
    """(S, F) f32 keys or values -> every page DLIQ-coded and decoded."""
    s, f = x.shape
    pages = x.reshape(s // page, page, f)
    codes, scale = _int8(pages, F32)
    blocks = codes.reshape(s // page, page // bw, bw, f)
    low = _rank(jnp.abs(blocks)) < n_low
    step = 2.0 ** (8 - q)
    qmax = 2.0 ** (q - 1) - 1
    mant = jnp.clip(jnp.round(blocks / step), -qmax, qmax)
    vals = jnp.where(low, mant * step, blocks).reshape(s // page, page, f)
    return (vals * scale).reshape(s, f)


# ---------------------------------------------------------------- forward --

def _rnd_fp8(x):
    """The control's rounding: to 4 significant bits, half to even."""
    a = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.where(a > 0, a, 1.0)))
    q = jnp.exp2(e - 3)
    return jnp.where(a > 0, jnp.round(x / q) * q, 0.0)


def _norm(x, scale, a: Arch):
    if a.norm == "layernorm_nonparam":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + a.eps)
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(ms + a.eps) * scale


def _rope(x, pos, theta):
    """Rotate-half RoPE. x (S, H, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[:, None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, kq, vq, plen, a: Arch):
    """Causal GQA attention where key i is read from its coded page when the
    server had sealed that page before query j ran."""
    s = q.shape[0]
    rep = a.nh // a.nkv
    qg = (q / math.sqrt(a.hd)).reshape(s // QUERY_BLOCK, QUERY_BLOCK,
                                        a.nkv, rep, a.hd)
    j_all = jnp.arange(s).reshape(s // QUERY_BLOCK, QUERY_BLOCK)
    i = jnp.arange(s)

    def block(args):
        qb, jb = args
        sealed = jnp.where(jb < plen, (jb // a.chunk) * a.chunk,
                           (jb // a.page) * a.page)
        coded = i[None, :] < sealed[:, None]                      # (Q, S)
        causal = i[None, :] <= jb[:, None]
        s_raw = jnp.einsum("qgrd,sgd->grqs", qb, k)
        s_cod = jnp.einsum("qgrd,sgd->grqs", qb, kq)
        sc = jnp.where(coded, s_cod, s_raw)
        sc = jnp.where(causal, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        pc = jnp.where(coded, p, 0.0)
        o = (jnp.einsum("grqs,sgd->qgrd", pc, vq)
             + jnp.einsum("grqs,sgd->qgrd", p - pc, v))
        return o.reshape(QUERY_BLOCK, a.nh * a.hd)

    return jax.lax.map(block, (qg, j_all)).reshape(s, a.nh * a.hd)


def _layer(w, x, plen, a: Arch, control: bool):
    r = _rnd_fp8 if control else (lambda t: t)
    s = x.shape[0]
    pos = jnp.arange(s)
    kvc = a.kvcodec
    h = r(_norm(x, w.get("norm1/scale"), a))
    q = r(h @ w["attn/wq/w"] + w.get("attn/wq/b", 0.0))
    k = r(h @ w["attn/wk/w"] + w.get("attn/wk/b", 0.0))
    v = r(h @ w["attn/wv/w"] + w.get("attn/wv/b", 0.0))
    q = r(_rope(q.reshape(s, a.nh, a.hd), pos, a.theta))
    k = r(_rope(k.reshape(s, a.nkv, a.hd), pos, a.theta))
    v = v.reshape(s, a.nkv, a.hd)
    code = functools.partial(dliq_pages, page=a.page, bw=kvc["w"],
                             n_low=round(kvc["p"] * kvc["w"]), q=kvc["q"])
    kq = code(k.reshape(s, -1)).reshape(k.shape)
    vq = code(v.reshape(s, -1)).reshape(v.shape)
    o = r(_attention(q, k, v, kq, vq, plen, a))
    x = r(x + r(o @ w["attn/wo/w"]))
    h = r(_norm(x, w.get("norm2/scale"), a))
    g = r(h @ w["mlp/wg/w"])
    u = r(h @ w["mlp/wi/w"])
    m = r(jax.nn.silu(g) * u)
    return r(x + r(m @ w["mlp/wo/w"]))


@functools.partial(jax.jit, static_argnames=("a", "control"))
def _layer_jit(w, x, plen, *, a, control):
    with jax.default_matmul_precision("highest"):
        return _layer(w, x, plen, a, control)


@functools.partial(jax.jit, static_argnames=("a",))
def _gaps(x_ref, x_ctl, rows, served, valid, table, fnorm, *, a):
    """Per row: the served token's gap below the reference's best logit, and
    the gap of the control's first choice."""
    with jax.default_matmul_precision("highest"):
        def block(args):
            rb, sb, vb = args
            hr = _norm(x_ref[rb], fnorm, a)
            lr = (hr @ table)[:, :a.vocab]
            best = jnp.max(lr, -1)
            g_served = best - jnp.take_along_axis(lr, sb[:, None], -1)[:, 0]
            out = [jnp.where(vb, g_served, 0.0)]
            if x_ctl is not None:
                hc = _rnd_fp8(_norm(x_ctl[rb], fnorm, a))
                lc = (hc @ table)[:, :a.vocab]
                pick = jnp.argmax(lc, -1)
                g_ctl = best - jnp.take_along_axis(lr, pick[:, None], -1)[:, 0]
                out.append(jnp.where(vb, g_ctl, 0.0))
            return jnp.stack(out)

        nb = rows.shape[0] // ROW_BLOCK
        res = jax.lax.map(block, (rows.reshape(nb, ROW_BLOCK),
                                  served.reshape(nb, ROW_BLOCK),
                                  valid.reshape(nb, ROW_BLOCK)))
        return res.transpose(1, 0, 2).reshape(res.shape[1], -1)


def _bucket(n: int, b: int) -> int:
    return -(-n // b) * b


def _forward(config: dict, seed: int, seqs: list, control: bool):
    """Final hidden states of padded sequences ``[(ids, plen), ...]``, layer
    by layer; with ``control`` also those of the control."""
    a = Arch(config)
    lv = {lf.name: lf for lf in leaves(config)}
    wc = a.wcodec
    table = W.draw(seed, lv["embed/table"]).astype(F32)
    xs = [table[jnp.asarray(t)] for t, _ in seqs]
    xc = [_rnd_fp8(x) for x in xs] if control else None
    if a.tied:
        head = table.T
    else:
        del table
        head = W.draw(seed, lv["lm_head/w"]).astype(F32)
    blk = "blocks/pos0/"
    for layer in range(a.layers):
        w = {}
        for name, lf in lv.items():
            if not name.startswith(blk):
                continue
            raw = W.draw_layer(seed, lf, layer)
            short = name[len(blk):]
            if lf.name.endswith("/w"):
                w[short] = mip2q_weight(raw, bw=wc["w"],
                                        n_low=round(wc["p"] * wc["w"]),
                                        L=wc["L"])
            else:
                w[short] = raw.astype(F32)
        for n, (_, plen) in enumerate(seqs):
            xs[n] = _layer_jit(w, xs[n], jnp.int32(plen), a=a, control=False)
            if control:
                xc[n] = _layer_jit(w, xc[n], jnp.int32(plen), a=a,
                                   control=True)
        del w
    fnorm = (W.draw(seed, lv["final_norm/scale"]).astype(F32)
             if "final_norm/scale" in lv else None)
    return a, xs, xc, head, fnorm


def _pad(ids) -> np.ndarray:
    ids = np.asarray(ids, np.int32)
    out = np.zeros(_bucket(len(ids), SEQ_BUCKET), np.int32)
    out[:len(ids)] = ids
    return out


def compare(config: dict, seed: int, served: list, control: bool = False):
    """Reference (and optionally control) logits over each served request.

    ``served``: ``[(prompt ids, output ids), ...]``; the request was fed its
    own output, so the model runs over ``prompt + output[:-1]`` and row
    ``plen - 1 + t`` predicts ``output[t]``.  Returns the widest gap by which
    a served token's logit lies below the reference's best, per request and
    over all, and with ``control`` the same for the control's first choice.
    """
    seqs = [(_pad(np.concatenate([np.asarray(p, np.int32),
                                  np.asarray(o[:-1], np.int32)])), len(p))
            for p, o in served]
    with jax.default_matmul_precision("highest"):
        a, xs, xc, head, fnorm = _forward(config, seed, seqs, control)
        per_req, per_ctl, agree, rows_all = [], [], 0, 0
        for n, ((_, plen), (_, out)) in enumerate(zip(seqs, served)):
            nrows = len(out)
            rp = _bucket(nrows, ROW_BLOCK)
            rows = np.zeros(rp, np.int32)
            rows[:nrows] = plen - 1 + np.arange(nrows)
            tok = np.zeros(rp, np.int32)
            tok[:nrows] = out
            valid = np.arange(rp) < nrows
            g = np.asarray(_gaps(xs[n], xc[n] if control else None,
                                 jnp.asarray(rows), jnp.asarray(tok),
                                 jnp.asarray(valid), head, fnorm, a=a))
            per_req.append(float(g[0].max()))
            agree += int((g[0][:nrows] == 0).sum())
            rows_all += nrows
            if control:
                per_ctl.append(float(g[1].max()))
    res = {"max_gap": max(per_req), "per_request": per_req,
           "top1_agree": agree / max(rows_all, 1)}
    if control:
        res["control_max_gap"] = max(per_ctl)
        res["control_per_request"] = per_ctl
    return res


def last_logits(config: dict, seed: int, prompt) -> np.ndarray:
    """Reference logits over the true vocabulary after ``prompt``."""
    with jax.default_matmul_precision("highest"):
        a, xs, _, head, fnorm = _forward(config, seed,
                                         [(_pad(prompt), len(prompt))], False)
        h = _norm(xs[0][len(prompt) - 1], fnorm, a)
        return np.asarray((h @ head)[:a.vocab])
