"""FLOPs per token, the summarize work, and the fault-cycle generator,
against hand arithmetic."""
import json

import pytest

from bench import faultcycle, flops, spec


def _config(name):
    with open(spec.ROOT / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_starcoder2_flops_per_token_by_hand():
    c = _config("starcoder2-3b")
    d, h, kv, hd, ff, V = 3072, 24, 2, 128, 12288, 49152
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d      # q, k, v, o
    mlp = 2 * d * ff                                        # gelu: in, out
    assert flops.layer_matmul_params(c) == attn + mlp == 95_944_704
    S = 4096            # sliding window 4096 covers the whole sequence
    ctx = (S + 1) / 2
    fwd = 6 * (2 * (attn + mlp) + 4 * h * hd * ctx) + 2 * d * V
    assert flops.train_flops_per_token(c, S) == pytest.approx(3 * fwd)
    assert flops.train_flops_per_token(c, S) == pytest.approx(4.8128e9,
                                                              rel=1e-4)


def test_one_layer_flops_per_token_by_hand():
    c = _config("starcoder2-3b-1layer")
    S = 2048
    fwd = 2 * 95_944_704 + 4 * 24 * 128 * (S + 1) / 2 + 2 * 3072 * 49152
    assert flops.train_flops_per_token(c, S) == pytest.approx(3 * fwd)


def test_gated_mlp_and_sliding_window():
    c = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
             head_dim=4, intermediate_size=16, mlp="swiglu",
             num_hidden_layers=1, vocab_size=10, sliding_window=4)
    assert flops.layer_matmul_params(c) == 8 * 8 + 2 * 8 * 4 + 8 * 8 \
        + 3 * 8 * 16
    # positions 0..7 attend to 1, 2, 3, 4, 4, 4, 4, 4 keys
    assert flops.mean_context(8, 4) == pytest.approx(26 / 8)
    assert flops.mean_context(8, 0) == pytest.approx(4.5)


def test_summarize_work_is_one_pass_over_the_rows():
    w = flops.summarize_work(40, 300)
    assert w["bytes"] == 4 * 40 * 300 + 4 * 40 + 12 * 40
    t, bound = flops.least_time_s(w, 197e12, 819e9)
    assert bound == "memory"
    assert t == pytest.approx(w["bytes"] / 819e9)


def test_fault_cycle_is_fixed_by_the_seed_and_rotates_every_worker():
    a = faultcycle.schedule(2 ** 31 + 7, 8, 4, lead=2, cycle=5, fault=3)
    assert a == faultcycle.schedule(2 ** 31 + 7, 8, 4, lead=2, cycle=5,
                                    fault=3)
    assert [i.start_window for i in a] == [2, 7, 12, 17, 22, 27, 32, 37]
    assert all(i.end_window == i.start_window + 3 for i in a)
    for block in (a[:4], a[4:]):
        assert sorted(i.worker for i in block) == [0, 1, 2, 3]
    orders = {tuple(i.worker for i in faultcycle.schedule(s, 8, 4, 2, 5, 3))
              for s in range(20)}
    assert len(orders) > 10


def test_cycle_count_fits_the_window_in_whole_rounds():
    assert faultcycle.cycle_count(30.0, 1.0, 4, 8) == 28
    assert faultcycle.cycle_count(3.0, 1.0, 4, 8) == 8
    assert faultcycle.cycle_count(13.0, 1.0, 4, 8) == 12
    assert faultcycle.cycle_count(35.0, 4.2, 4, 8) == 8


def test_utilization_is_flops_over_the_chips_peak():
    c = _config("starcoder2-3b")
    per_token = flops.train_flops_per_token(c, 4096)
    assert flops.utilization(1000.0, c, 4096, 1, 197e12) == pytest.approx(
        100.0 * 1000.0 * per_token / 197e12)
    assert flops.utilization(1000.0, c, 4096, 4, 197e12) == pytest.approx(
        25.0 * 1000.0 * per_token / 197e12)
