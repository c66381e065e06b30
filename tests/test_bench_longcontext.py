"""bench --model transformerlm-long (round-4 verdict #3): the long-context
TRAINING leg emits one JSON line carrying tokens/sec, the sequence length,
and the attention implementation under test. Tiny T on CPU keeps it a
contract test; T=4096/8192 on the chip is not measured (ROADMAP S5)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_longcontext_leg_json_contract(attn):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", BIGDL_BENCH_SEQ="128",
               BIGDL_BENCH_ATTN=attn)
    r = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.benchmark", "--run",
         "--model", "transformerlm-long", "--batch", "1", "--iters", "3",
         "--warmup", "1", "--dtype", "fp32", "--no-streamed"],
        cwd=ROOT, capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["metric"] == "transformerlm-long_train_tokens_per_sec_per_chip"
    assert line["unit"] == "tokens/sec"
    assert line["value"] > 0
    assert line["seq_len"] == 128
    assert line["attention_impl"] == attn
    assert line["batch"] == 1


def test_analytic_flops_scale_with_t():
    from bigdl_tpu.benchmark import _long_lm_flops

    f4k, f8k = _long_lm_flops(4096), _long_lm_flops(8192)
    assert f8k > f4k                       # attention term grows with T
    # the non-attention part is T-independent: doubling T less than
    # doubles per-token flops at this width
    assert f8k < 2 * f4k


def test_malformed_seq_env_fails_only_the_long_leg():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", BIGDL_BENCH_SEQ="8k")
    # unrelated legs still import and run (exit-0 contract preserved)
    r = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.benchmark", "--run",
         "--model", "lenet", "--batch", "32", "--iters", "2", "--warmup", "1",
         "--dtype", "fp32", "--no-streamed"],
        cwd=ROOT, capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stderr[-800:]
    assert json.loads(r.stdout.strip().splitlines()[-1])["value"] > 0
    # the long leg itself reports the reason
    r = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.benchmark", "--run",
         "--model", "transformerlm-long", "--batch", "1", "--iters", "2",
         "--warmup", "1", "--dtype", "fp32", "--no-streamed"],
        cwd=ROOT, capture_output=True, text=True, timeout=900, env=env)
    assert "BIGDL_BENCH_SEQ" in (r.stderr + r.stdout)


def test_auto_attention_rejected_for_ab_leg():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", BIGDL_BENCH_SEQ="64",
               BIGDL_BENCH_ATTN="auto")
    r = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.benchmark", "--run",
         "--model", "transformerlm-long", "--batch", "1", "--iters", "2",
         "--warmup", "1", "--dtype", "fp32", "--no-streamed"],
        cwd=ROOT, capture_output=True, text=True, timeout=900, env=env)
    assert "flash|full" in (r.stderr + r.stdout)
