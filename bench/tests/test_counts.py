"""The operation and byte counts against values worked out by hand."""

import pytest

import counts
import harness
from conftest import SPEC

C = harness.load_config(SPEC, "qwen3-1.7b")


def test_dense_layer_params_qwen3():
    # q 2048x2048, k and v 2048x1024 each, o 2048x2048, mlp 3x2048x6144
    assert counts.dense_layer_params(C) == (
        4_194_304 + 2 * 2_097_152 + 4_194_304 + 37_748_736)


def test_decode_flops_by_hand():
    # 28 layers x (2 x 50,331,648 + 4 x 16 x 128 x 101 keys) + 2 x 2048
    # x 151,936 for the head, at cache position 100
    layer = 2 * 50_331_648 + 4 * 16 * 128 * 101
    assert counts.decode_flops(C, 100) == 28 * layer + 2 * 2048 * 151_936


def test_prefill_flops_causal_half():
    # 4 tokens attend 1 + 2 + 3 + 4 = 10 keys; one row of logits
    layer = 2 * 50_331_648 * 4 + 4 * 16 * 128 * 10
    assert counts.prefill_flops(C, 4) == 28 * layer + 2 * 2048 * 151_936


def test_page_bytes():
    # keys and values, 8 positions x 8 KV heads x 128 x 2 bytes
    assert counts.page_bytes(C, 8) == 32_768
    assert counts.paged_decode_bytes(C, 8, 10) == 28 * 32_768 * 10


def test_qr_tasks_match_the_paper():
    k = counts.qr_task_counts(32)
    assert sum(k.values()) == 11_440            # paper section 4.1
    assert k == {"geqrf": 32, "larft": 496, "tsqrf": 496, "ssrft": 10416}


def test_qr_flops_by_hand():
    # 2 x 2 tiles of b = 2: geqrf 2 x 4/3 x 8, larft 1 x 16, tsqrf
    # 1 x 16, ssrft 1 x 32
    assert counts.qr_plan_flops(4, 2) == pytest.approx(2 * 32 / 3 + 64)
    # tends to the dense Householder count 4/3 n^3
    assert counts.qr_plan_flops(2048, 64) == pytest.approx(
        4 / 3 * 2048 ** 3, rel=0.03)


def test_peaks_table_is_published_and_keyed_by_kind():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")
