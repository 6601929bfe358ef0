"""Shape-based operation and byte counts, against numbers worked by hand
from the published configs, and the peaks table."""
import json
import os

import pytest

from _tiny import ROOT

from benchmarks.chip import flops, peaks
from benchmarks.chip.families import dense_decoder as dense


def sizes(name):
    return dense.sizes(json.loads(open(
        os.path.join(ROOT, "benchmarks", "chip", "configs", f"{name}.json")).read()))


def test_yi_6b_counts():
    s = sizes("yi-6b")
    # attention 4096*(32+2*4)*128 + 32*128*4096, SwiGLU 3*4096*11008, 32 layers
    assert dense.layer_matmul_params(s) == 32 * (37_748_736 + 135_266_304)
    # + untied embedding and head 2*64000*4096 + 65 RMSNorm weights of 4096
    assert dense.param_count(s) == 6_061_035_520
    assert dense.step_bytes(s) == 2 * (5_536_481_280 + 262_144_000 + 266_240)
    assert dense.kv_bytes_per_token(s) == 2 * 2 * 32 * 4 * 128 == 65_536
    # one prompt token: 2 * matmul params + attention to itself + the head
    assert dense.prefill_flops(s, 1) == 11_072_962_560 + 4 * 32 * 32 * 128 + 524_288_000


def test_stablelm_3b_counts():
    s = sizes("stablelm-3b")
    assert dense.layer_matmul_params(s) == 32 * (26_214_400 + 53_084_160)
    # LayerNorm carries a bias: 65 * 2560 * 2 norm weights
    assert dense.param_count(s) == 2_537_553_920 + 257_556_480 + 332_800 == 2_795_443_200
    assert dense.kv_bytes_per_token(s) == 2 * 2 * 32 * 32 * 80 == 327_680


@pytest.mark.parametrize("name", ["yi-6b", "stablelm-3b"])
def test_sequence_is_sum_of_tokens(name):
    s = sizes(name)
    mm, head = 2 * dense.layer_matmul_params(s), 2 * s.d * s.vocab
    per_ctx = 4 * s.layers * s.heads * s.head_dim
    n_p, n_new = 5, 4
    want = n_p * mm + per_ctx * sum(range(1, n_p + 1)) + head  # prefill, last logits
    want += sum(mm + per_ctx * (n_p + i + 1) + head for i in range(n_new - 1))
    assert dense.sequence_flops(s, n_p, n_new) == want
    assert dense.attn_flops(s, 7, 3) == per_ctx * (8 + 9 + 10)


@pytest.mark.parametrize("name,kv", [("yi-6b", 65_536), ("stablelm-3b", 327_680)])
def test_state_and_prefill_bytes(name, kv):
    s = sizes(name)
    # a decode step at position t reads the t + 1 live K/V rows of its sequence
    assert dense.token_bytes(s, 0) == kv
    assert dense.token_bytes(s, 255) == 256 * kv
    assert dense.prefill_bytes(s) == dense.step_bytes(s)


def test_least_time_is_the_larger_bound():
    p = peaks.peak("TPU v5 lite")
    assert flops.least_time(197e12, 0, p) == pytest.approx(1.0)
    assert flops.least_time(1.0, 819e9 * 2, p) == pytest.approx(2.0)


def test_peaks_keyed_by_device_kind():
    p = peaks.peak("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert p["source"]
    with pytest.raises(KeyError):
        peaks.peak("cpu")
