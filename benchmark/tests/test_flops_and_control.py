"""``flops.py`` against a hand count, the comparison's own arithmetic, and the
control: the reference one precision below what the configurations state
(int8 and float8 for bfloat16), put in the program's place at a size a test run can
hold, has to come out as not correct."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, flops, peaks  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_one_layer_by_hand():
    model = _config("dsllm7b-sim")
    # q, k, v, o: 4 x 4096 x 4096; gate, up, down: 3 x 4096 x 11008
    assert flops.layer_matmul_params(model) == 4 * 4096 * 4096 + 3 * 4096 * 11008 == 202_375_168
    assert flops.matmul_params(model) == 2 * 202_375_168 + 4096 * 12800
    assert flops.total_params(model) == model["parameters"]["total"]
    # one sequence of 2,048 tokens, one head of 128: QK^T and PV are 2 x 2048^2 x 128
    # multiply-adds each over the full square, half of it under a causal mask
    fwd = flops.attention_flops(1, 1, 2048, 128, True, False)
    assert fwd == 2 * 2 * (2048 * 2048 / 2) * 128
    assert flops.attention_flops(1, 1, 2048, 128, True, True) == 2 * fwd
    assert flops.attention_bytes(2, 32, 2048, 128, 2, False) == 4 * 2 * 32 * 2048 * 128 * 2
    step = flops.train_flops(model, 2, 2048)
    by_hand = 6 * (2 * 202_375_168 + 4096 * 12800) * 4096 + 2 * 3 * fwd * 2 * 32
    assert step == by_hand and 1.1e13 < step < 1.3e13


def test_roofline_names_the_bound_and_peaks_refuse_unknown_kinds():
    v5e = peaks.peaks_for("TPU v5 lite")
    least, bound = flops.roofline_seconds(197e12, 1e9, v5e)
    assert bound == "compute" and least == pytest.approx(1.0)
    assert flops.roofline_seconds(1e9, 819e9, v5e) == (pytest.approx(1.0), "memory")
    with pytest.raises(RuntimeError, match="no published peak"):
        peaks.peaks_for("cpu")


def test_change_gap_is_of_norms_by_the_worst_leaf():
    ref = {"a": 1.0, "b": 2.0, "b2": 2.0, "c": 4.0, "dead": 1e-5}  # median 2
    assert compare.change_gap(dict(ref), ref) == (0.0, "")
    # a leaf left where it was reads 1; a dead leaf is left out whatever it reads
    assert compare.change_gap(dict(ref, c=0.0, dead=5.0), ref) == (1.0, "c")
    # a small leaf is measured against the median leaf, not against itself
    assert compare.change_gap(dict(ref, a=1.5), ref)[0] == pytest.approx(0.25)
    ok, table = compare.judge({"change_gap.u0": {"value": float("nan"), "at": "a"}},
                              {"change_gap": 1.0})
    assert ok is False and table["change_gap.u0"]["limit"] == 1.0


@pytest.mark.parametrize("traffic_name", ["tiny.fedavg", "tiny.fedavg.sampled"])
def test_control_one_precision_below_is_not_correct(traffic_name):
    from benchmark import run
    from benchmark.drivers import sim

    traffic = run.load_traffic(traffic_name)
    driver = sim.Driver(_config("tiny"), traffic, seed=11, chips=1, device_type="cpu")
    truth = driver.reference_readings("highest")
    for precision in ("int8", "float8"):
        control = driver.reference_readings(precision)
        ok, table = compare.judge(compare.numbers(control, truth), traffic["limits"])
        assert ok is False, (precision, table)
    same, _ = compare.judge(compare.numbers(truth, truth), traffic["limits"])
    assert same is True


def test_a_traffic_file_is_laid_over_its_base():
    from benchmark import run

    one, four = run.load_traffic("fedavg8"), run.load_traffic("fedavg8.mesh4")
    assert {k for k in one if one[k] != four[k]} == {"why", "limits"}
    assert four["limits"] == dict(one["limits"], loss_gap=0.0001) and "base" not in four
    sampled = run.load_traffic("tiny.fedavg.sampled.mesh4")  # two levels
    assert sampled["clients_per_round"] == 4 and sampled["limits"] == run.load_traffic("tiny.fedavg")["limits"]


def test_every_seed_gives_the_sampled_cohort_the_same_sizes():
    from benchmark import reference, run, traffic

    t = run.load_traffic("tiny.fedavg.sampled")
    cohort = reference.sampled_clients(0, 8, 4)
    assert sorted(cohort) != [0, 1, 2, 3] and len(set(cohort)) == 4
    orders = set()
    for seed in (1, 2, 2**31 + 7):
        sizes = [len(x) for x, _ in traffic.make_shards(t, 96, seed)]
        assert sorted(sizes[c] for c in cohort) == sorted(t["shard_sequences"][:4])
        assert sorted(sizes) == sorted(t["shard_sequences"])
        orders.add(tuple(sizes))
    assert len(orders) > 1
