"""``harness/sdar_work.py``: the visible pairs of the block-diffusion mask
and the step's FLOPs by part against brute force, and the counts ISSUE 26
states for the cell."""

import numpy as np
import pytest

from benchmark.harness import sdar_work, spec

reference = spec.load_module("reference", "sdar_moe")


def cell_model():
    cell = spec.load_cell("sdar-30b-a3b.bd-steady-s4096")
    return cell["config"]["model"], cell["mix"]


@pytest.mark.parametrize("length,block", [(8, 4), (32, 4), (64, 32), (96, 8),
                                          (128, 128)])
def test_visible_pairs_match_the_mask_built_from_its_rules(length, block):
    index = np.arange(2 * length)
    dense = np.asarray(reference.visible(index, index, length, block))
    assert sdar_work.visible_pairs(length, block) == int(dense.sum())


def test_attention_flops_are_four_d_a_visible_pair_and_query_head():
    model = {"num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 16, "block_length": 4, "num_hidden_layers": 3,
             "activation_dtype": "bfloat16"}
    length, batch = 32, 2
    index = np.arange(2 * length)
    pairs = int(np.asarray(reference.visible(index, index, length, 4)).sum())
    # Scores and values: two products of 2 D FLOPs a pair, a query head.
    brute = sum(2 * 2 * 16 for _b in range(batch) for _h in range(4)
                for _p in range(pairs))
    work = sdar_work.attention(model, batch, length)
    assert work["forward"]["flops"] == brute
    assert work["backward"]["flops"] == 2 * brute
    q_side, kv_side = 2 * 64 * 4 * 16 * 2, 2 * 64 * 2 * 16 * 2
    assert work["forward"]["bytes"] == 2 * q_side + 2 * kv_side
    assert work["backward"]["bytes"] == 4 * q_side + 4 * kv_side


def test_the_cells_counts_are_the_issues():
    model, mix = cell_model()
    assert (mix["batch"], mix["seq"], model["block_length"]) == (2, 4096, 4)
    assert sdar_work.visible_pairs(4096, 4) == 4096 ** 2 + 4096 * 4
    part = sdar_work.forward_flops_per_position(model, mix["seq"])
    assert part["projections"] == pytest.approx(37.7e6, rel=2e-3)
    assert part["attention"] == pytest.approx(33.6e6, rel=2e-3)
    assert part["experts"] == pytest.approx(9.4e6, rel=5e-3)
    assert part["router"] == pytest.approx(0.5e6, rel=5e-2)
    per_token = sdar_work.train_flops_per_token(model, mix["seq"])
    step = sum(per_token.values()) * mix["batch"] * mix["seq"]
    assert step == pytest.approx(25.9e12, rel=2e-3)  # 131 ms at 197 TFLOP/s
    assert sdar_work.expected_rows(model, 2, 4096) == 16384
    # The family hands the harness the same count.
    family = spec.load_module("families", "sdar_moe")
    assert family.flops_per_token(model, mix["seq"]) == per_token
    assert family.positions(model, mix["seq"]) == 4096


def test_grouped_products_brute_force():
    model = {"hidden_size": 8, "moe_intermediate_size": 6,
             "num_experts_per_tok": 2, "num_experts": 2,
             "num_experts_routed": 4, "activation_dtype": "bfloat16",
             "num_hidden_layers": 1}
    work = sdar_work.grouped_products(model, batch=1, seq=4)
    rows = 1 * 2 * 4 * 2 * 2 / 4  # positions x top-k x held / routed
    assert work["rows"] == rows
    forward = sum(2 * 8 * 6 for _product in range(3) for _r in range(int(rows)))
    assert work["flops"] == 3 * forward
    assert work["bytes"] == 3 * rows * 2 * (3 * 8 + 3 * 6) \
        + 2 * 3 * 8 * 6 * (2 * 2 + 4)
