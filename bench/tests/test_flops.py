"""Peaks and the operation and byte counts of bench/flops.py."""

import json

import pytest

import flops
from run import BENCH


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")


def test_v5e_peaks():
    pk = flops.peak("TPU v5 lite")
    assert pk["bf16_flop_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("name,hand", [
    # 6·N + 6·L·s·d; N = L·(4d² + 2·d·d_ff) + V·d (tied output projection)
    ("gpt2-small", 6 * (12 * (4 * 768**2 + 2 * 768 * 3072) + 50257 * 768)
     + 6 * 12 * 1024 * 768),
    ("gpt2-medium", 6 * (24 * (4 * 1024**2 + 2 * 1024 * 4096)
                         + 50257 * 1024) + 6 * 24 * 1024 * 1024),
])
def test_model_flops_per_token(name, hand):
    got = flops.model_flops_per_token(config(name), 1024)
    assert got == hand
    assert round(got / 1e9, 2) == {"gpt2-small": 0.80,
                                   "gpt2-medium": 2.27}[name]


def test_outer_step_is_24_bytes_a_parameter():
    assert flops.outer_step_bytes(1) == 24
    assert flops.outer_step_bytes(123_456) == 24 * 123_456


def test_param_count_gpt2_small():
    # embeddings 50257·768 + positions 1024·768 + final LayerNorm, and per
    # layer q,k,v,o (4·768²), the MLP (2·768·3072) and two LayerNorms
    layer = 4 * 768**2 + 2 * 768 * 3072 + 4 * 768
    want = 50257 * 768 + 1024 * 768 + 2 * 768 + 12 * layer
    assert flops.param_count(config("gpt2-small")) == want


def test_causal_attention_flops_by_hand():
    # S = 4: query i attends to i + 1 keys, 1+2+3+4 = 10 pairs a head;
    # each pair costs 2·hd (QKᵀ) + 2·hd (PV)
    f, b = flops.flash_attention_fwd(batch=2, seq=4, heads=3, hd=8)
    assert f == 2 * 3 * 10 * 4 * 8
    assert b == 2 * (2 * 4 * 3 * 8) * 4   # q, k, v, o in bf16


def test_roofline_names_its_bound():
    pk = flops.peak("TPU v5 lite")
    t, bound = flops.roofline_time(197e12, 1.0, pk)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.roofline_time(1.0, 819e9, pk)
    assert (t, bound) == (1.0, "memory")
