"""The FLOP functions against a count by hand."""

import json
import os

import pytest

from benchmark.harness import spec


def model(name):
    with open(os.path.join(spec.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("seq", [128, 512])
def test_bert_base_by_hand(seq):
    # One layer, forward, per token: q, k, v, o: 4 * 768 * 768 multiply-adds;
    # MLP: 2 * 768 * 3072; attention: scores and values, 2 * seq * 768.
    macs = 4 * 768 * 768 + 2 * 768 * 3072
    matmul = 12 * 2 * macs + 2 * 768 * (768 + 2) / seq  # + pooler, classifier
    attention = 12 * 2 * 2 * seq * 768
    got = spec.load_module("families", "bert").flops_per_token(
        model("bert-base"), seq)
    assert got["matmul"] == pytest.approx(3 * matmul)
    assert got["attention"] == pytest.approx(3 * attention)


def test_bert_base_steps_in_teraflops():
    f = spec.load_module("families", "bert").flops_per_token
    per_step = lambda b, s: sum(f(model("bert-base"), s).values()) * b * s
    assert per_step(32, 128) / 1e12 == pytest.approx(2.146, abs=0.002)
    assert per_step(32, 512) / 1e12 == pytest.approx(9.278, abs=0.005)
    assert per_step(64, 512) / 1e12 == pytest.approx(18.556, abs=0.01)
    # No embedding look-up is counted: 6 * 23.8 M rows * tokens would add
    # 0.585 TFLOP to the B=32, S=128 step.


def test_vit_base_16_by_hand():
    s = 197
    macs = 4 * 768 * 768 + 2 * 768 * 3072
    encoder = 12 * 2 * macs * s
    patches = 2 * 196 * (16 * 16 * 3) * 768
    head = 2 * 768 * 1000
    attention = 12 * 2 * 2 * s * 768 * s
    got = spec.load_module("families", "vit").flops_per_token(
        model("vit-base-16"))
    assert got["matmul"] * s == pytest.approx(3 * (encoder + patches + head))
    assert got["attention"] * s == pytest.approx(3 * attention)
    per_step = sum(got.values()) * s * 128
    assert per_step / 1e12 == pytest.approx(13.49, abs=0.02)
