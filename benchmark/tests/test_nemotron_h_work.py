"""``harness/nemotron_h_work.py``: the scan's FLOPs against a brute-force
count over the chunked form's products, causal pairs against the mask, the
grouped products of two-matrix experts, and the counts ISSUE 30 states for
the cell."""

import numpy as np
import pytest

from benchmark.harness import nemotron_h_work, spec

CELL = "nemotron-3-nano-30b-a3b.ntp-steady-s8192"


def cell_model():
    cell = spec.load_cell(CELL)
    return cell["config"]["model"], cell["mix"]


@pytest.mark.parametrize("H,P,G,N,L,chunks", [
    (4, 8, 2, 16, 8, 3), (2, 4, 1, 8, 4, 2), (8, 4, 4, 4, 16, 1)])
def test_scan_flops_are_the_chunked_forms_products(H, P, G, N, L, chunks):
    """Each product of the chunked form, a multiply-add at a time."""
    model = {"mamba_num_heads": H, "mamba_head_dim": P, "n_groups": G,
             "ssm_state_size": N, "chunk_size": L}
    S = L * chunks
    brute = 0
    for _chunk in range(chunks):
        brute += 2 * G * L * L * N        # C B^T, once a group
        brute += 2 * H * L * L * P        # decayed scores times x
        brute += 2 * H * L * P * N        # the closing state
        brute += 2 * H * L * P * N        # the entering state times C
    assert nemotron_h_work.scan_forward_flops_per_token(model) * S == brute


def test_scan_work_counts_forward_and_backward_once():
    model = {"mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
             "ssm_state_size": 16, "chunk_size": 8,
             "hybrid_override_pattern": "MEM*", "activation_dtype": "bfloat16"}
    work = nemotron_h_work.scan(model, batch=2, seq=24)
    tokens = 48
    assert work["layers"] == 2
    assert work["flops"] == 3 * tokens \
        * nemotron_h_work.scan_forward_flops_per_token(model)
    ins = tokens * (2 * (4 * 8 + 2 * 2 * 16) + 4 * 4)   # x, B, C; dt float32
    out = tokens * 2 * 4 * 8
    assert work["bytes"] == 2 * (ins + out) + ins


@pytest.mark.parametrize("seq", [1, 8, 128])
def test_causal_pairs_match_the_mask(seq):
    assert nemotron_h_work.causal_pairs(seq) == int(
        np.tril(np.ones((seq, seq))).sum())


def test_grouped_products_of_two_matrices_brute_force():
    model = {"hidden_size": 8, "moe_intermediate_size": 6,
             "num_experts_per_tok": 2, "n_routed_experts": 2,
             "num_experts_routed": 4, "activation_dtype": "bfloat16",
             "hybrid_override_pattern": "EM"}
    work = nemotron_h_work.grouped_products(model, batch=1, seq=8)
    rows = 1 * 8 * 2 * 2 / 4  # tokens x top-k x held / routed
    assert work["rows"] == rows and work["layers"] == 1
    forward = sum(2 * 8 * 6 for _product in range(2)
                  for _r in range(int(rows)))
    assert work["flops"] == 3 * forward
    assert work["bytes"] == 3 * rows * 2 * (2 * 8 + 2 * 6) \
        + 2 * 2 * 8 * 6 * (2 * 2 + 4)


def test_the_cells_counts_are_the_issues():
    model, mix = cell_model()
    assert (mix["batch"], mix["seq"]) == (2, 8192)
    assert nemotron_h_work.kinds(model) == {"M": 4, "E": 4, "*": 1}
    part = nemotron_h_work.forward_flops_per_token(model, mix["seq"])
    assert part["M"]["ssm_projections"] == pytest.approx(77.4e6, rel=1e-3)
    assert part["M"]["ssm_scan"] == pytest.approx(3.4e6, rel=5e-3)
    assert part["E"]["experts_shared"] == pytest.approx(39.9e6, rel=1e-3)
    assert part["E"]["experts_routed"] == pytest.approx(7.5e6, rel=5e-3)
    assert part["E"]["router"] == pytest.approx(0.7e6, rel=2e-2)
    assert part["*"]["attention_projections"] == pytest.approx(
        46.8e6, rel=1e-3)
    assert part["*"]["attention"] == pytest.approx(67.1e6, rel=1e-3)
    per_token = nemotron_h_work.train_flops_per_token(model, mix["seq"])
    assert per_token["head"] == pytest.approx(3 * 88.1e6, rel=1e-3)
    assert sum(per_token.values()) / 3 == pytest.approx(717.6e6, rel=1e-4)
    step = sum(per_token.values()) * mix["batch"] * mix["seq"]
    assert step == pytest.approx(35.3e12, rel=2e-3)  # 179 ms at 197 TFLOP/s
    # The state-space blocks are 45 % of the required FLOPs.
    ssm = per_token["ssm_projections"] + per_token["ssm_scan"]
    assert ssm / sum(per_token.values()) == pytest.approx(0.45, abs=0.005)
    assert nemotron_h_work.expected_rows(model, 2, 8192) == 6144
    # The scan is bound by its bytes, as the issue reckons.
    scan = nemotron_h_work.scan(model, 2, 8192)
    ms, bound = nemotron_h_work.least_ms(scan, 197e12, "TPU v5 lite")
    assert bound == "hbm" and ms == pytest.approx(1.08, rel=1e-2)
    assert scan["flops"] / 197e12 * 1e3 == pytest.approx(0.85, rel=1e-2)
    # The family hands the harness the same count.
    family = spec.load_module("families", "nemotron_h")
    assert family.flops_per_token(model, mix["seq"]) == per_token
    assert family.positions(model, mix["seq"]) == 8192
