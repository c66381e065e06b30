"""The work functions against counts made by hand."""

import json
import os

import harness

BENCH = harness.HERE


def _load(name):
    cfg = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    return cfg, harness.load_module(os.path.join(BENCH, "work", name + ".py"))


def test_resnet50_forward_is_4_1_gmac():
    cfg, work = _load("resnet50")
    # He et al. 2015 give 3.8e9 multiply-adds for the 50-layer net with the
    # stride on the 1x1; with it on the 3x3 (v1.5) the count is 4.09e9
    assert work.forward_macs(cfg) == 4_089_184_256
    assert work.train_flops_per_sample(cfg, {}) == 6 * 4_089_184_256


def test_gpt2_medium_matmul_parameters_and_flops():
    cfg, work = _load("gpt2-medium")
    d, v, n = 1024, 50257, 24
    assert work.matmul_params(cfg) == n * 12 * d * d + d * v == 353_453_056
    t = 1024
    per_token_fwd = 2 * 353_453_056 + n * 2 * t * d       # causal: half the square
    assert work.train_flops_per_sample(cfg, {"seq_len": t}) == 3 * per_token_fwd * t


def test_gpt2_medium_kernel_work_by_hand():
    cfg, work = _load("gpt2-medium")
    traffic = {"batch": 8, "seq_len": 1024}
    (f_fwd, b_fwd), (f_bwd, b_bwd) = work.flash_attention_step(cfg, traffic)
    # one layer, one sequence: QK^T and PV are 2*T*T*d FLOP each, halved
    assert f_fwd == 24 * 8 * 2 * 1024 * 1024 * 1024 and f_bwd == 2 * f_fwd
    assert b_fwd == 24 * 4 * (8 * 1024 * 1024 * 2) and b_bwd == 2 * b_fwd
