"""``harness/ouro_work.py``: a layer application's FLOPs against a
brute-force count over the products' shapes, causal pairs against the mask,
the parameters against a count of a toy tree, and the counts ISSUE 32 states
for the cell: a loop's work is per APPLICATION, its parameters per layer."""

import numpy as np
import pytest

from benchmark.harness import ouro_work, spec

CELL = "ouro-2.6b.loop4-steady-s4096"


def cell_model():
    cell = spec.load_cell(CELL)
    return cell["config"]["model"], cell["mix"]


def toy(**over):
    return dict({"hidden_size": 8, "intermediate_size": 12, "head_dim": 4,
                 "num_attention_heads": 3, "num_key_value_heads": 3,
                 "num_hidden_layers": 2, "total_ut_steps": 3,
                 "vocab_size": 20, "activation_dtype": "bfloat16"}, **over)


def matmul_flops(*shapes):
    """2 FLOPs a multiply-add of each ``[m, k] x [k, n]`` product."""
    return sum(2.0 * m * k * n for m, k, n in shapes)


@pytest.mark.parametrize("seq", [1, 8, 128])
def test_causal_pairs_match_the_mask(seq):
    assert ouro_work.causal_pairs(seq) == int(np.tril(
        np.ones((seq, seq))).sum())


@pytest.mark.parametrize("kv_heads", [3, 1])
def test_one_application_is_its_products_brute_force(kv_heads):
    model, seq = toy(num_key_value_heads=kv_heads), 8
    H, F, d, heads = 8, 12, 4, 3
    part = ouro_work.application_forward_flops_per_token(model, seq)
    # One sequence of ``seq`` tokens through one layer, product by product.
    assert part["projections"] * seq == matmul_flops(
        (seq, H, heads * d), (seq, H, kv_heads * d), (seq, H, kv_heads * d),
        (seq, heads * d, H))
    assert part["mlp"] * seq == matmul_flops(
        (seq, H, F), (seq, H, F), (seq, F, H))
    # Scores and values: per head and query, d multiply-adds a visible key,
    # twice.
    pairs = int(np.tril(np.ones((seq, seq))).sum())
    assert part["attention"] * seq == 2 * 2.0 * d * heads * pairs


def test_a_loop_counts_work_by_application_and_parameters_by_layer():
    model, seq = toy(), 8
    once = toy(total_ut_steps=1)
    per_token = ouro_work.train_flops_per_token(model, seq)
    single = ouro_work.train_flops_per_token(once, seq)
    # Three passes are three times the work of one, heads included ...
    assert per_token == {k: 3 * v for k, v in single.items()}
    assert ouro_work.applications(model) == 6
    # ... over the same parameters.
    assert ouro_work.parameters(model) == ouro_work.parameters(once)
    layer = 8 * 4 * (2 * 3 + 2 * 3) + 3 * 8 * 12 + 4 * 8
    assert ouro_work.parameters(model) == {
        "layers": 2 * layer, "embedding_and_head": 2 * 20 * 8,
        "final_norm_and_gate": 8 + 8 + 1,
        "all": 2 * layer + 2 * 20 * 8 + 17}
    # Forward + backward is three times the forward; a pass is its layers
    # and its exit.
    by_pass = ouro_work.forward_flops_per_token_by_pass(model, seq)
    assert len(by_pass) == 3
    assert 3 * sum(sum(p.values()) for p in by_pass) \
        == pytest.approx(sum(per_token.values()))
    assert by_pass[0]["head"] == 2.0 * 8 * 20


def test_the_heads_work_counts_three_products_an_exit():
    model = toy()
    heads = ouro_work.exit_heads(model, 2, 8)
    tokens = 16
    assert heads["exits"] == 3
    assert heads["flops"] == 3 * 3 * matmul_flops((tokens, 8, 20))
    states = 3 * tokens * 8 * 2
    assert heads["bytes"] == 3 * states + 2 * 8 * 20 * 2 + 8 * 20 * 4


def test_the_cells_counts_are_the_issues():
    model, mix = cell_model()
    assert (mix["batch"], mix["seq"]) == (2, 4096)
    part = ouro_work.application_forward_flops_per_token(model, mix["seq"])
    assert part["projections"] == pytest.approx(33.55e6, rel=1e-3)
    assert part["mlp"] == pytest.approx(69.21e6, rel=1e-3)
    assert part["attention"] == pytest.approx(16.78e6, rel=1e-3)
    assert sum(part.values()) == pytest.approx(119.54e6, rel=1e-4)
    assert ouro_work.applications(model) == 24
    per_token = ouro_work.train_flops_per_token(model, mix["seq"])
    assert per_token["head"] == pytest.approx(3 * 4 * 201.33e6, rel=1e-4)
    assert sum(per_token.values()) / 3 == pytest.approx(3674e6, rel=1e-3)
    step = sum(per_token.values()) * mix["batch"] * mix["seq"]
    assert step == pytest.approx(90.3e12, rel=1e-3)  # 458 ms at 197 TFLOP/s
    # The four heads are 21.9 % of the required FLOPs here and 3.4 % in the
    # whole model's 192 applications.
    assert per_token["head"] / sum(per_token.values()) == pytest.approx(
        0.219, abs=0.001)
    whole = ouro_work.train_flops_per_token(
        dict(model, num_hidden_layers=48), mix["seq"])
    assert whole["head"] / sum(whole.values()) == pytest.approx(
        0.034, abs=0.001)
    heads = ouro_work.exit_heads(model, mix["batch"], mix["seq"])
    assert heads["flops"] == pytest.approx(19.8e12, rel=2e-3)
    ms, bound = ouro_work.least_ms(heads, 197e12, "TPU v5 lite")
    assert bound == "flops" and ms == pytest.approx(100.5, rel=1e-3)
    assert heads["bytes"] / 819e9 * 1e3 < 2.0  # far under it
    assert ouro_work.parameters(model)["all"] == 509_661_185
    # The family hands the harness the same count.
    family = spec.load_module("families", "ouro")
    assert family.flops_per_token(model, mix["seq"]) == per_token
    assert family.positions(model, mix["seq"]) == 4096
