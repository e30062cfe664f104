"""Operation and byte counts against numbers worked by hand."""

import json
import os

import pytest

from benchmark.lib import counts, device
from benchmark.lib.spec import BENCH_DIR


def cfg(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_medium_by_hand():
    c = cfg("gpt2-medium")
    # a layer: 4 * 1024^2 + 2 * 1024 * 4096 = 12,582,912; x 24 = 301,989,888
    # readout 50257 * 1024 = 51,463,168
    assert counts.matmul_params(c) == 301_989_888 + 51_463_168 == 353_453_056
    # embeddings 51,463,168 + 1,048,576 + 1024 + 2048; a layer's biases and
    # LayerNorms 4*1024 + 4096 + 1024 + 4*1024 = 13,312
    assert counts.total_params(c) == (51_463_168 + 1_048_576 + 3072
                                      + 24 * (12_582_912 + 13_312))
    assert round(counts.total_params(c) / 1e6, 1) == 354.8
    # forward at context 512.5: 2 * 353,453,056 + 4 * 24 * 1024 * 512.5
    fwd = 706_906_112 + 50_380_800
    assert counts.forward_flops_token(c, 512.5) == fwd
    assert counts.train_flops_token(c, 1024) == 3 * fwd == 2_271_860_736


def test_gpt2_large_by_hand():
    c = cfg("gpt2-large")
    # a layer: 4 * 1280^2 + 2 * 1280 * 5120 = 19,660,800; x 36 = 707,788,800
    # readout 50257 * 1280 = 64,328,960
    assert counts.matmul_params(c) == 707_788_800 + 64_328_960
    assert round(counts.total_params(c) / 1e6) == 774
    assert counts.kv_bytes_row(c) == 184_320          # 2 * 36 * 1280 * 2 B
    assert counts.weight_bytes(c) == 2 * 772_117_760
    # 16 tokens decoded on 8 slots, 100 live rows each: two passes over the
    # weights, 1600 rows of cache
    need = counts.decode_needed(c, contexts_sum=1600.0, n_tokens=16,
                                n_slots=8)
    assert need["bytes"] == 2 * 1_544_235_520 + 1600 * 184_320
    assert need["flops"] == 2 * 772_117_760 * 16 + 4 * 36 * 1280 * 1600
    # a prompt of 3 tokens attends 1 + 2 + 3 positions
    assert counts.sequence_forward_flops(c, 0, 3) == (
        2 * 772_117_760 * 3 + 4 * 36 * 1280 * 6)
    assert counts.sequence_forward_flops(c, 10, 2) == (
        counts.forward_flops_token(c, 11) + counts.forward_flops_token(c, 12))


def test_peaks_table():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in benchmark/peaks.json"):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks("_source")
