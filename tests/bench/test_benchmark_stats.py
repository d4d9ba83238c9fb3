"""Percentile, rate, operation-count, interval and shape arithmetic against
hand-worked cases."""

import pytest

from benchmark import flops, stats
from benchmark.reference import cnn_mnist, resnet9
from benchmark.trace import reduce


@pytest.mark.parametrize("values,q,want", [
    ([10.0], 90, 10.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 12)), 90, 10.0),          # rank 0.9 * 10 = 9 -> 10
    ([5, 1, 3, 2, 4], 90, 4.6),              # rank 3.6 -> 4 + 0.6
    ([1, 2], 100, 2.0),
    ([1, 2], 0, 1.0),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_intervals_and_rate():
    # window opens at 100.0; three units of 10 rounds end at 104.5, 109, 113.5
    stamps = [104.5, 109.0, 113.5]
    assert stats.intervals([100.0] + stamps) == [4.5, 4.5, 4.5]
    assert stats.rounds_per_s(100.0, stamps, [10, 10, 10]) == pytest.approx(
        30 / 13.5)
    # a unit of one round every half second
    assert stats.rounds_per_s(0.0, [0.5, 1.0, 1.5, 2.0], [1] * 4) == 2.0


@pytest.mark.parametrize("stamps", [[], [5.0]])
def test_rate_needs_a_window(stamps):
    with pytest.raises(ValueError):
        stats.rounds_per_s(5.0, stamps, [1] * len(stamps))


@pytest.mark.parametrize("rounds,ok,want", [
    ([10, 10, 10], [True, True, True], (30, 0)),
    ([10, 10, 10], [True, False, True], (30, 10)),
    ([1, 1, 1, 1], [True, True], (4, 2)),     # two never completed
    ([], [], (0, 0)),
])
def test_operation_counts(rounds, ok, want):
    assert stats.operation_counts(rounds, ok) == want


def test_union_subtract_length():
    u = reduce.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert u == [(0, 3), (5, 8), (10, 11)]
    assert reduce.length(u) == 7
    assert reduce.subtract([(0, 12)], u) == [(3, 5), (8, 10), (11, 12)]
    assert reduce.subtract([(1, 2), (4, 9)], [(0, 3), (5, 6), (8, 20)]) == [
        (4, 5), (6, 8)]
    assert reduce.subtract([(0, 4)], []) == [(0, 4)]


def test_self_times_of_nested_events():
    # a while of 100 holding two bodies of 30 and 50, the second holding 20
    events = [(0, 100, 1), (10, 30, 2), (40, 50, 3), (45, 20, 4),
              (100, 5, 5)]
    assert reduce.self_times(events) == [20, 30, 30, 20, 5]


@pytest.mark.parametrize("tf_op,want", [
    ("jit(step)/local_train/vmap()/while/body/conv:", ("jit(step)",
                                                         "local_train")),
    ("jit(chained)/while/body/aggregate_rlr/reduce_sum", ("jit(chained)",
                                                          "aggregate_rlr")),
    ("jit(eval_fn)/while/body/dot_general", ("jit(eval_fn)", "")),
    ("", ("", "")),
])
def test_scope_of(tf_op, want):
    assert reduce.scope_of(tf_op) == want


def test_gap_attribution():
    spans = [(0, 10, "dispatch"), (10, 12, "eval_boundary"), (20, 30, "wait")]
    assert reduce.attribute_gap((1, 3), spans) == "dispatch"
    assert reduce.attribute_gap((9, 12), spans) == "eval_boundary"
    assert reduce.attribute_gap((13, 19), spans) == reduce.BETWEEN
    assert reduce.attribute_gap((18, 30), spans) == "wait"
    assert reduce.attribute_gap((11, 19), spans) == reduce.BETWEEN


def test_forward_flops_by_hand():
    # 28x28x1: conv 1->32 on 26x26, conv 32->64 on 24x24, fc 9216->128->10
    want = (2 * 9 * 1 * 32 * 26 * 26 + 2 * 9 * 32 * 64 * 24 * 24
            + 2 * 9216 * 128 + 2 * 128 * 10)
    assert cnn_mnist.forward_flops((28, 28, 1)) == want == 23984896
    # ResNet-9 on 32x32x3: eight 3x3 SAME convolutions and the head
    convs = [(3, 64, 32), (64, 128, 32), (128, 128, 16), (128, 128, 16),
             (128, 256, 16), (256, 512, 8), (512, 512, 4), (512, 512, 4)]
    want = sum(2 * 9 * i * o * s * s for i, o, s in convs) + 2 * 512 * 10
    assert resnet9.forward_flops((32, 32, 3)) == want


def test_round_flops():
    # three forward passes' worth per example
    assert flops.round_train_flops(10.0, 7) == 210.0


def test_peaks_table():
    assert flops.peaks("TPU v5 lite")["bf16_tflops"] == 197.0
    assert flops.peaks("TPU v5 lite")["hbm_gbytes_per_s"] == 819.0
    with pytest.raises(ValueError):
        flops.peaks("cpu")
