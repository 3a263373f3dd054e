"""The operation and byte functions against hand counts."""
from __future__ import annotations

import pytest

from perfbench import cells

GPT2 = cells.load_cell("gpt2m.packed-1chip")
LOGREG = cells.load_cell("logreg32.engine-1chip")


def test_gpt2_medium_is_the_published_model():
    c = GPT2.config
    assert (c["n_embd"], c["n_layer"], c["n_head"], c["n_positions"],
            c["vocab_size"]) == (1024, 24, 16, 1024, 50257)
    matmul_params = 12 * 1024 * 1024 * 24          # qkv, proj, up, down
    embed = 50257 * 1024 + 1024 * 1024             # tied head + positions
    assert matmul_params + embed == 354_501_632    # "355M"


def test_gpt2_round_flops_by_hand():
    # per token: 6 x (301,989,888 layer weights + 51,463,168 head weights)
    #            + causal attention 6 x 1024 x 1024 x 24
    per_token = 6 * (301_989_888 + 51_463_168) + 150_994_944
    assert per_token == 2_271_713_280
    tokens = 4 * GPT2.traffic["batch"] * 1024
    mod = GPT2.reference_module()
    assert mod.flops_per_round(GPT2.config, GPT2.traffic) == per_token * tokens
    assert mod.min_bytes_per_round(GPT2.config, GPT2.traffic) is None


def test_logreg_round_counts_by_hand():
    mod = LOGREG.reference_module()
    rows = 32 * 8 * 32768                 # one epoch-equivalent: 8,388,608
    assert rows == 32 * 262144
    assert mod.flops_per_round(LOGREG.config, LOGREG.traffic) == 400 * rows
    # a row is 100 float32 features and a float32 label
    assert mod.min_bytes_per_round(LOGREG.config, LOGREG.traffic) == 404 * rows
    table = 32 * 262144 * 100 * 4
    assert table == 3_355_443_200         # 21% of 16 GB


def test_peaks_are_the_published_ones_and_an_unknown_device_is_an_error():
    peaks = cells.peaks_of("TPU v5 lite")
    assert peaks["bf16_flops"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        cells.peaks_of("TPU v9")
