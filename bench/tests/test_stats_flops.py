import json
import os

import numpy as np
import pytest

import flops
import stats

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_is_numpys(q):
    rng = np.random.default_rng(3)
    values = rng.exponential(size=237).tolist()
    assert stats.percentile(values, q) == pytest.approx(np.percentile(values, q))


def test_percentile_small_and_empty():
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([1.0, 3.0], 50) == 2.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_iqr_share_is_the_statistics_modules():
    values = [10.0, 10.2, 9.9, 10.4, 10.1, 9.8]
    # statistics.quantiles (exclusive): q1 = 9.875, q3 = 10.25, median 10.05
    assert stats.iqr_share(values) == pytest.approx((10.25 - 9.875) / 10.05)


def test_bge_large_forward_by_hand():
    cfg = config("bge-large-en")
    rows, seq, h, inter = 64, 128, 1024, 4096
    tokens = rows * seq
    per_layer = (
        8 * tokens * h * h  # four h x h projections, 2 ops per multiply-add
        + 4 * tokens * h * inter  # two MLP products
        + 4 * rows * seq * seq * h  # scores and context
    )
    assert flops.forward_flops("bert", cfg, rows, seq) == 24 * per_layer
    # 64 x 128 slots of bge-large: about 5.05 TFLOP
    assert flops.forward_flops("bert", cfg, rows, seq) == pytest.approx(5.05e12, rel=0.01)


def test_deberta_base_forward_by_hand():
    cfg = config("deberta-v3-base")
    rows, seq, h, inter, span = 16, 512, 768, 3072, 256
    tokens = rows * seq
    per_layer = (
        8 * tokens * h * h
        + 4 * tokens * h * inter
        + 4 * rows * seq * seq * h
        + 4 * rows * seq * (2 * span) * h  # content-to-position, position-to-content
        + 4 * (2 * span) * h * h  # the relative table through Wk and Wq
    )
    want = 12 * per_layer + 2 * rows * h * h
    assert flops.forward_flops("deberta-v2", cfg, rows, seq) == want
