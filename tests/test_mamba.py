"""Mamba-2's mixer (``nn.Mamba2Mixer``, ``nn.mamba.ssd_scan``) against the
benchmark's plain reference for ``nemotron-3-super-120b-a12b``: the chunked
scan against the sequential recurrence, the whole mixer against the
reference's layer, and eight shares of a layer's heads, one group each, adding
up to the whole layer."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
NAME = "nemotron-3-super-120b-a12b"


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    import harness
    yield harness.load_module(os.path.join(BENCH, "reference", NAME + ".py"))
    sys.path.remove(BENCH)


def _cfg(**over):
    cfg = json.load(open(os.path.join(BENCH, "configs", NAME + ".json")))
    cfg.update(json.load(open(os.path.join(BENCH, "tests", "tiny", NAME + ".json")))["config"])
    cfg.update(over)
    return cfg


def _scan_inputs(t, heads=4, p=8, groups=2, s=8, strong=True, seed=0):
    """Random inputs of the scan; with ``strong``, a quarter of the steps
    decay by exp(-20) and more in one position."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (2, t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (2, t, heads)) - 1.0)
    if strong:
        dt = jnp.where(jax.random.uniform(keys[2], dt.shape) < 0.25, 3.0, dt)
    a = -jnp.exp(jax.random.uniform(keys[3], (heads,), minval=0.0, maxval=jnp.log(16.0)))
    b = jax.random.normal(keys[4], (2, t, groups, s))
    c = jax.random.normal(keys[5], (2, t, groups, s))
    return x, dt, a, b, c


def _sequential(ref, x, dt, a, b, c):
    return jax.vmap(ref.recurrence, in_axes=(0, 0, None, 0, 0))(x, dt, a, b, c)


@pytest.mark.parametrize("t,chunk", [(40, 16), (64, 16), (7, 16), (48, 8)])
def test_the_chunked_scan_is_the_sequential_recurrence(ref, t, chunk):
    """Forward and every input's gradient, at lengths that are and are not a
    multiple of the chunk, with strong decays (exp(-48) in one step: a chunk's
    mask then reads 0 where it must). Both in fp32 at the highest precision;
    the two orders of summation agree to 2e-5 of the output's scale, and the
    scan in bfloat16 misses by 1e-3 and more (checked below)."""
    from bigdl_tpu.nn.mamba import ssd_scan
    args = _scan_inputs(t)
    probe = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*args, chunk)
        want = _sequential(ref, *args)
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, atol=2e-5 * scale)
        g_got = jax.grad(lambda *v: jnp.sum(ssd_scan(*v, chunk) * probe),
                         argnums=(0, 1, 2, 3, 4))(*args)
        g_want = jax.grad(lambda *v: jnp.sum(_sequential(ref, *v) * probe),
                          argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("x dt a b c".split(), g_got, g_want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.max(jnp.abs(b))), err_msg=name)
    # the control: the same scan with bfloat16 operands is told apart
    low = ssd_scan(*[v.astype(jnp.bfloat16) if i in (0, 3, 4) else v
                     for i, v in enumerate(args)], chunk)
    assert float(jnp.max(jnp.abs(low - want))) > 1e-3 * scale


def _mixer(cfg, seed=1):
    """A mixer at the tiny cut's widths and weights drawn wider than the
    family's, so that every part of it tells in the output."""
    from bigdl_tpu.nn import Mamba2Mixer
    mixer = Mamba2Mixer(cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                        cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"],
                        cfg["chunk_size"], cfg["layer_norm_epsilon"])
    key = jax.random.PRNGKey(seed)
    p = {k: v + (0.2 if k in ("d_skip", "norm") else 0.3 if k == "in" else 0.0)
         * jax.random.normal(jax.random.fold_in(key, i), v.shape)
         for i, (k, v) in enumerate(sorted(mixer.get_params().items()))}
    return mixer, p


#: the reference's names of a Mamba layer's weights, by the mixer's
AS_REF = {"a_log": "a_log", "conv_b": "conv.b", "conv_w": "conv.w", "d_skip": "d",
          "dt_bias": "dt_bias", "in": "in", "norm": "gate_norm.g", "out": "out"}


def _ref_mamba(ref, cfg, p, h, q=lambda a: a):
    """The reference's layer less its residual, on a normed input with a
    gain of 1: the mixer alone."""
    p = {AS_REF[k]: v for k, v in p.items()}
    p["norm.g"] = jnp.ones((cfg["hidden_size"],))
    return jax.vmap(lambda hs: ref._mamba(hs, p, cfg, q) - hs)(h)


def test_the_mixer_is_the_references_layer(ref):
    """The mixer at the tiny cut's widths (2 groups, chunks of 16 over 40
    positions) against the reference's sequential layer, output and every
    weight's gradient, to 1e-4 of each one's scale: fp32 both, the two orders
    of summation and the two convolutions. Each of a bfloat16 reference, a
    dropped ``D`` term and a norm over all channels in place of each group's
    misses by more than ten times that."""
    cfg = _cfg()
    mixer, p = _mixer(cfg)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 40, cfg["hidden_size"]))
    x = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True) + cfg["layer_norm_epsilon"])
    probe = jax.random.normal(jax.random.PRNGKey(5), h.shape)
    with jax.default_matmul_precision("highest"):
        out = mixer.apply(p, {}, x)[0]
        want = _ref_mamba(ref, cfg, p, h)
        grads = jax.grad(lambda p: jnp.sum(mixer.apply(p, {}, x)[0] * probe))(p)
        want_grads = jax.grad(lambda p: jnp.sum(_ref_mamba(ref, cfg, p, h) * probe))(p)
    tol = 1e-4 * float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(out - want))) <= tol
    for k in p:
        g_scale = float(jnp.max(jnp.abs(want_grads[k])))
        assert float(jnp.max(jnp.abs(grads[k] - want_grads[k]))) <= 1e-4 * g_scale, k
    # the controls, each told apart by the same tolerance
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        misses = {"bfloat16": _ref_mamba(ref, cfg, p, h, bf16),
                  "no D": _ref_mamba(ref, cfg, dict(p, d_skip=0 * p["d_skip"]), h),
                  "one norm": jax.vmap(lambda hs: _ungrouped(ref, cfg, p, hs))(h)}
    for name, miss in misses.items():
        assert float(jnp.max(jnp.abs(miss - want))) > 10 * tol, name


def _ungrouped(ref, cfg, p, hs):
    """The mixer as the reference computes it, with the gated norm over
    every channel at once in place of each group's."""
    w = {AS_REF[k]: v for k, v in p.items()}
    w["norm.g"] = jnp.ones((cfg["hidden_size"],))
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, s, inner, t = cfg["n_groups"], cfg["ssm_state_size"], heads * hd, hs.shape[0]
    zxbcdt = ref._rms(hs, w["norm.g"], cfg["layer_norm_epsilon"]) @ w["in"]
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:-heads], zxbcdt[:, -heads:]
    xbc = jax.nn.silu(ref._conv(xbc, w["conv.w"], w["conv.b"]))
    xs = xbc[:, :inner].reshape(t, heads, hd)
    b = xbc[:, inner:inner + g * s].reshape(t, g, s)
    c = xbc[:, inner + g * s:].reshape(t, g, s)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = ref.recurrence(xs, dt, -jnp.exp(w["a_log"]), b, c) + w["d"][:, None] * xs
    y = y.reshape(t, inner) * jax.nn.silu(z)
    y = ref._rms(y, w["gate_norm.g"], cfg["layer_norm_epsilon"])
    return y @ w["out"]


def test_eight_shares_of_the_heads_add_up_to_the_whole_layer(ref):
    """The deployment's tensor-parallel cut of a Mamba layer: 16 heads in 8
    groups, each of eight chips holding one group and its 2 heads (their
    columns of ``W_in`` for z, x and dt, the group's B and C columns, the
    convolution's channels of those, their A, D, dt bias, the norm's gain over
    their channels and their rows of ``W_out``). The shares' outputs add up
    to the reference's whole layer."""
    from bigdl_tpu.nn import Mamba2Mixer
    cfg = _cfg(hidden_size=32, mamba_num_heads=16, mamba_head_dim=4, n_groups=8,
               ssm_state_size=4, chunk_size=8)
    heads, hd, g, s = 16, 4, 8, 4
    inner, per = heads * hd, heads // g
    _, p = _mixer(cfg, seed=3)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 20, cfg["hidden_size"]))
    x = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), -1, keepdims=True) + cfg["layer_norm_epsilon"])
    with jax.default_matmul_precision("highest"):
        want = _ref_mamba(ref, cfg, p, h)
        total = 0.0
        for r in range(g):
            mine = np.arange(r * per * hd, (r + 1) * per * hd)          # its channels
            bc = np.arange(r * s, (r + 1) * s)                           # its group
            heads_of = np.arange(r * per, (r + 1) * per)
            columns = np.concatenate([mine, inner + mine, 2 * inner + bc,
                                      2 * inner + g * s + bc, 2 * inner + 2 * g * s + heads_of])
            conv = np.concatenate([mine, inner + bc, inner + g * s + bc])
            share = Mamba2Mixer(cfg["hidden_size"], per, hd, 1, s, 4, 8,
                                cfg["layer_norm_epsilon"])
            total = total + share.apply(
                {"in": p["in"][:, columns], "conv_w": p["conv_w"][:, conv],
                 "conv_b": p["conv_b"][conv], "a_log": p["a_log"][heads_of],
                 "d_skip": p["d_skip"][heads_of], "dt_bias": p["dt_bias"][heads_of],
                 "norm": p["norm"][mine], "out": p["out"][mine]}, {}, x)[0]
    np.testing.assert_allclose(total, want, atol=1e-4 * float(jnp.max(jnp.abs(want))))
