"""Work counts against shapes computed by hand."""
import json
import os

import pytest

from bench.lib import work

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_eq1_bytes_per_block():
    # w=16, p=0.5: 2 B of header, 8 B of int8 highs, 8 x 4-bit lows = 4 B
    assert work.payload_bits({"method": "dliq", "w": 16, "p": 0.5,
                              "q": 4}) == 14 * 8
    # MIP2Q L=5 stores sign + 3-bit shift: q = 4, the same 14 B
    assert work.payload_bits({"method": "mip2q", "w": 16, "p": 0.5,
                              "L": 5}) == 14 * 8
    assert work.bytes_per_element({"method": "mip2q", "w": 16, "p": 0.5,
                                   "L": 5}) == 0.875


def test_matmul_work_olmo_decode():
    cfg = _config("olmo_1b")
    flops, nbytes = work.matmul(cfg, 16)
    d, f, L = 2048, 8192, 16
    elems = L * (4 * d * d + 3 * d * f)          # 1,073,741,824
    assert elems == 1073741824
    assert flops == 2 * 16 * elems
    scales = L * 4 * (4 * d + 2 * f + d)
    acts = L * 2 * 16 * (4 * (d + d) + 2 * (d + f) + (f + d))
    assert nbytes == elems * 0.875 + scales + acts


def test_attention_work_counts_sealed_pages_only():
    cfg = _config("qwen2_7b")
    # 47 positions: two sealed pages of 16, the rest in the hot tail
    flops, nbytes = work.attention(cfg, [47])
    L, nh, hd, f = 28, 28, 128, 4 * 128
    assert flops == L * 4 * nh * hd * 32
    page = 16 * f * 0.875 + 4 * f             # 7168 B + 2048 B of scales
    assert page == 9216
    assert nbytes == L * 2 * 2 * page
    assert work.attention(cfg, [15]) == (0.0, 0.0)


def test_token_flops():
    cfg = _config("olmo_1b")
    lin = 16 * (4 * 2048 * 2048 + 3 * 2048 * 8192)
    assert work.token_flops(cfg, 100) == pytest.approx(
        2 * (lin + 2048 * 50304) + 16 * 4 * 16 * 128 * 100)
