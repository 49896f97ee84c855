"""The benchmark's operation and byte counts (`harness/counts.py` and
each family's own hooks over it) against values worked by hand from the
published sizes."""

import json
import pathlib

import pytest

from benchmarks.families import gpt2, megatron_bert
from benchmarks.harness import counts, peaks

ROOT = pathlib.Path(__file__).resolve().parents[2]
GPT = json.loads((ROOT / "benchmarks/configs/cerebras-gpt-1.3b.json").read_text())
BERT = json.loads((ROOT / "benchmarks/configs/megatron-bert-345m.json").read_text())


def test_gpt_parameters():
    # per layer 12 h^2 + 13 h at h 2048; table 50257 x 2048; positions
    # 2048 x 2048; final LayerNorm 2 h
    layer = 12 * 2048 * 2048 + 13 * 2048
    assert counts.layer_params(2048, 8192) == layer == 50358272
    total = 24 * layer + 50257 * 2048 + 2048 * 2048 + 2 * 2048
    assert gpt2.total_params(GPT) == total == 1315723264 == GPT["parameters"]


def test_bert_parameters():
    layer = 12 * 1024 * 1024 + 13 * 1024
    head = (1024 * 1024 + 1024) + 2 * 1024 + (1024 * 1024 + 1024) + 2 * 1024 + 2
    total = (24 * layer + 2 * 1024 + 29056 * 1024 + 512 * 1024 + 2 * 1024 + head)
    assert megatron_bert.total_params(BERT) == total == BERT["parameters"]


def test_train_flops_per_token():
    # GPT at s 2048: 6 x 1.208 G matmul weights + 12 s h L attention +
    # 6 V h head
    gpt = 6 * 24 * 12 * 2048 ** 2 + 12 * 2048 * 2048 * 24 + 6 * 50257 * 2048
    assert gpt2.train_flops_per_token(GPT, 2048) == gpt
    assert gpt == pytest.approx(9.07e9, rel=2e-3)
    bert = (6 * 24 * 12 * 1024 ** 2 + 12 * 512 * 1024 * 24
            + 6 * 29056 * 1024 + 6 * 1024 ** 2)
    assert megatron_bert.train_flops_per_token(BERT, 512) == bert
    assert bert == pytest.approx(2.148e9, rel=2e-3)


def test_kv_bytes_per_token():
    s = gpt2.sizes(GPT)
    # 2 x 24 x 2048 x 2 B
    assert counts.kv_bytes_per_token(s["layers"], s["hidden"]) == 196608


def test_each_family_says_whether_its_attention_is_causal():
    assert gpt2.CAUSAL is True and megatron_bert.CAUSAL is False
    assert megatron_bert.sizes(BERT)["heads"] == 16
    assert gpt2.sizes(GPT)["hidden"] // gpt2.sizes(GPT)["heads"] == 128


def test_attention_counts():
    # BERT, one layer, batch 16: two matmuls forward and four backward
    # of 2 b nh s^2 hd each
    pair = 2 * 16 * 16 * 512 * 512 * 64
    flops, nbytes = counts.attention_train_counts(16, 16, 512, 64, causal=False)
    assert flops == 6 * pair
    assert nbytes == 12 * 16 * 16 * 512 * 64 * 2
    causal, _ = counts.attention_train_counts(16, 16, 512, 64, causal=True)
    assert causal == flops / 2


def test_decode_counts_are_bound_by_memory():
    flops, nbytes = counts.decode_paged_counts(10000, 24, 2048)
    assert nbytes == 10000 * 196608
    assert flops == nbytes  # 4 operations per 4 bytes
    p = peaks.chip_peaks("TPU v5 lite")
    assert nbytes / p["hbm_bytes_per_s"] > flops / p["bf16_flops"]


def test_an_unknown_device_has_no_peak():
    assert peaks.chip_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.chip_peaks("cpu")
