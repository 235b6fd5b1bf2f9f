"""Operation and byte counts, against hand counts at phi3-mini's and
phi4-mini's widths."""

import json
import os

import pytest

from bench_testlib import BENCH
from harness import flops, peaks


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


PHI3, PHI4 = config("phi3-mini-3.8b"), config("phi4-mini-3.8b")

# Per layer: wq, wk, wv, wo, SwiGLU wi (2 x 8192 wide), wo2; then the head.
PHI3_PARAMS = 32 * (3072 * 3072 * 4 + 3072 * 16384 + 8192 * 3072) \
    + 3072 * 32064          # MHA: K and V as wide as Q
PHI4_PARAMS = 32 * (3072 * 3072 * 2 + 3072 * 1024 * 2 + 3072 * 16384
                    + 8192 * 3072) + 3072 * 200064


@pytest.mark.parametrize("cfg,params", [(PHI3, PHI3_PARAMS),
                                        (PHI4, PHI4_PARAMS)])
def test_dense_flops_are_two_per_weight(cfg, params):
    assert params in (3_722_379_264, 3_835_822_080)
    assert flops.dense_flops_per_token(cfg) == 2 * params


@pytest.mark.parametrize("cfg", [PHI3, PHI4])
def test_attention_flops(cfg):
    # 32 layers x (QK and PV: 2 x 2) x 3072 query width (32 x 96, 24 x 128)
    assert flops.attention_flops(cfg, 1000) == 32 * 4 * 3072 * 1000


def test_prefill_counts_each_position_once():
    # A chunk of 4 after 10 resident: positions attend to 11, 12, 13, 14.
    got = flops.prefill_flops(PHI3, 10, 4)
    want = 4 * flops.dense_flops_per_token(PHI3) \
        + flops.attention_flops(PHI3, 11 + 12 + 13 + 14)
    assert got == want
    assert flops.prefill_flops(PHI3, 10, 0) == 0


def test_decode_flops():
    got = flops.decode_flops(PHI4, [100, 300])
    assert got == 2 * flops.dense_flops_per_token(PHI4) \
        + flops.attention_flops(PHI4, 400)


@pytest.mark.parametrize("cfg,per_token", [(PHI3, 393_216 // 32),
                                           (PHI4, 131_072 // 32)])
def test_paged_decode_bytes_per_resident_token(cfg, per_token):
    """K and V of one token in one layer: 2 x kv_heads x head_dim x 2 B;
    32 layers of it are the 393,216 (phi3) and 131,072 (phi4) bytes a
    token holds in the pool."""
    c1 = flops.paged_decode_cost(cfg, [1000])
    c2 = flops.paged_decode_cost(cfg, [2000])
    assert c2["bytes"] - c1["bytes"] == per_token * 1000
    assert c1["flops"] == 4 * 3072 * 1000
    q_and_out = 2 * 3072 * 2
    assert c1["bytes"] == per_token * 1000 + q_and_out


def test_v5e_peaks_and_unknown_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
