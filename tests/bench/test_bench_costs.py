"""Cost functions and peaks of the benchmark at known shapes."""
from __future__ import annotations

import sys

import pytest

from _bench_cells import REPO

sys.path.insert(0, str(REPO))

from bench import peaks  # noqa: E402
from bench.costs import fedagg, smollm_sgd  # noqa: E402

SMOLLM = {"hidden_size": 576, "intermediate_size": 1536,
          "num_hidden_layers": 30, "num_attention_heads": 9,
          "num_key_value_heads": 3, "vocab_size": 49152}


def test_smollm_matmul_params_are_the_published_count_less_norms():
    # 134,515,008 parameters = 134,479,872 in matmuls + 30*2*576 + 576 in
    # RMSNorm scales (the tied embedding counted once, as the unembedding)
    assert smollm_sgd.matmul_params(SMOLLM) == 134_479_872
    assert smollm_sgd.matmul_params(SMOLLM) + 30 * 2 * 576 + 576 \
        == 134_515_008


def test_smollm_step_flops():
    per_token = 6 * 134_479_872 + 12 * 30 * 9 * 64 * 32
    assert smollm_sgd.step_flops(SMOLLM, 32, 32) == per_token * 32 * 32


def test_fedagg_counts_the_logical_problem():
    assert fedagg.flops(10, 1000) == 20_000
    assert fedagg.bytes_(10, 1000) == 10 * 1000 * 4 + 10 * 4 + 1000 * 4
    # a ragged N is not rounded up to a tile
    assert fedagg.bytes_(3, 129) == 3 * 129 * 4 + 3 * 4 + 129 * 4


def test_roofline_share_names_its_bound():
    share, bound = peaks.roofline_share(0.0, 819e9, 2.0, "TPU v5 lite")
    assert bound == "memory" and share == pytest.approx(50.0)
    share, bound = peaks.roofline_share(197e12, 1.0, 1.0, "TPU v5 lite")
    assert bound == "compute" and share == pytest.approx(100.0)


def test_unknown_chip_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v99")
