"""Operation and byte counts from shapes, against hand counts."""
import json

import pytest
from bench_testkit import ROOT

from bench import flops
from bench.peaks import peaks_for

QWEN = json.loads((ROOT / "bench/configs/qwen3-1.7b.json").read_text())
# one layer: q 2048x2048, k and v 2048x1024, o 2048x2048, MLP 3 x 2048x6144
LAYER = 4_194_304 + 2 * 2_097_152 + 4_194_304 + 3 * 12_582_912
NONEMB = 28 * (LAYER + 2 * 2048 + 2 * 128) + 2048
EMB = 151936 * 2048


def test_qwen3_param_counts_by_hand():
    assert flops.layer_matmul_params(QWEN) == LAYER == 50_331_648
    assert flops.nonembedding_params(QWEN) == NONEMB == 1_409_410_048
    assert flops.param_count(QWEN) == NONEMB + EMB == 1_720_574_976


def test_param_count_matches_the_program_tree():
    from repro.config import get_arch
    from repro.models.flops import param_count
    assert flops.param_count(QWEN) == param_count(get_arch("qwen3-1.7b"))


def test_decode_counts_by_hand():
    kv = [100, 200]
    attn = 28 * 4 * 16 * 128 * (100 + 200)
    unembed = 2 * 2048 * 151936
    assert flops.decode_flops(QWEN, kv) == 2 * (2 * NONEMB + unembed) + attn
    per_pos = 28 * 2 * 8 * 128 * 2               # layers x K,V x heads x head_dim x bf16
    assert flops.kv_bytes_per_position(QWEN) == per_pos == 114_688
    want = (NONEMB + EMB) * 2 + per_pos * 300 + 2 * 151936 * 2
    assert flops.decode_bytes(QWEN, kv) == want


def test_prefill_counts_causal_attention_and_last_logits():
    got = flops.prefill_flops(QWEN, 2, 4)
    attn = 28 * 4 * 16 * 128 * (1 + 2 + 3 + 4)
    assert got == 2 * (2 * NONEMB * 4 + attn + 2 * 2048 * 151936)


def test_roofline_picks_the_binding_bound():
    v5e = peaks_for("TPU v5 lite")
    t, bound = flops.roofline_seconds(197e12, 819e9 * 2, v5e)
    assert (t, bound) == (pytest.approx(2.0), "memory")
    t, bound = flops.roofline_seconds(197e12 * 3, 819e9, v5e)
    assert (t, bound) == (pytest.approx(3.0), "compute")
    # a batch-16 decode step at short contexts is bound by memory
    lens = [100] * 16
    _, bound = flops.roofline_seconds(flops.decode_flops(QWEN, lens),
                                      flops.decode_bytes(QWEN, lens), v5e)
    assert bound == "memory"
