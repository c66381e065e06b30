"""The dropless routed expert layer (``MoE(router="topk")``): a layer that is
told which experts it holds computes their part of the sum, the parts of all
the shares add up to the uncut layer, and no pair for a held expert is
dropped under any imbalance."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.kernels.grouped_matmul import grouped_matmul
from bigdl_tpu.parallel import MoE, expert_parallel_rules

D, HID, E, K, T = 64, 32, 16, 4, 96


def _uncut(x, p, k, first=0, count=None, norm=True, routed_on=None, gate=jax.nn.silu):
    """The layer written out densely: softmax over all experts (the router
    reading ``routed_on`` where given), the k largest, renormalised, every
    expert of [first, first + count) over every token with its routing
    weight."""
    hid = p["w_out"].shape[1]
    probs = jax.nn.softmax((x if routed_on is None else routed_on) @ p["w_gate"], -1)
    top_p, top_e = jax.lax.top_k(probs, k)
    if norm:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(first, first + (p["w_in"].shape[0] if count is None else count)):
        w = jnp.sum(jnp.where(top_e == e, top_p, 0.0), -1)
        h = x @ p["w_in"][e]
        y = y + w[:, None] * ((gate(h[:, :hid]) * h[:, hid:]) @ p["w_out"][e])
    return y


@pytest.fixture
def layer():
    key = jax.random.PRNGKey(1)
    full = MoE(D, HID, E, router="topk", top_k=K)
    params = {k: 0.1 * jax.random.normal(jax.random.fold_in(key, i), v.shape)
              for i, (k, v) in enumerate(sorted(full.get_params().items()))}
    return full, params, jax.random.normal(key, (T, D))


def _share(params, first, count):
    return {"w_gate": params["w_gate"], "w_in": params["w_in"][first:first + count],
            "w_out": params["w_out"][first:first + count]}


def test_all_experts_held_is_the_uncut_layer(layer):
    full, params, x = layer
    with jax.default_matmul_precision("highest"):
        y, state = full.apply(params, full.get_state(), x)
        np.testing.assert_allclose(y, _uncut(x, params, K), atol=2e-6)
    assert float(state["pairs_held"]) == T * K
    assert float(state["dropped_fraction"]) == 0.0
    np.testing.assert_allclose(float(jnp.sum(state["expert_load"])), 1.0, rtol=1e-6)


@pytest.mark.parametrize("norm", [True, False])
def test_eight_shares_add_up_to_the_uncut_layer(layer, norm):
    """The guide's tie of share to model: each of eight chips holds 2 of the
    16 experts, routes over all 16 and computes its own part."""
    _, params, x = layer
    total, pairs = 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for rank in range(8):
            m = MoE(D, HID, E, router="topk", top_k=K, held=(2 * rank, 2),
                    norm_topk_prob=norm)
            y, state = m.apply(_share(params, 2 * rank, 2), m.get_state(), x)
            np.testing.assert_allclose(
                y, _uncut(x, params, K, 2 * rank, 2, norm), atol=2e-6)
            total, pairs = total + y, pairs + float(state["pairs_held"])
        np.testing.assert_allclose(total, _uncut(x, params, K, norm=norm), atol=2e-6)
    assert pairs == T * K


def _towards_held(params, x, first, count, tokens=None, sign=1.0):
    """A router biased so that every pair of the first ``tokens`` tokens (all
    of them if None) is for a held expert and none of the others' is (the
    other way round with ``sign=-1``): a column of ones carries the bias
    through the router's matrix."""
    held = (jnp.arange(E) >= first) & (jnp.arange(E) < first + count)
    ones = jnp.ones((T,)) if tokens is None else jnp.where(jnp.arange(T) < tokens, 1.0, -1.0)
    return (dict(params, w_gate=params["w_gate"].at[0].set(
        sign * jnp.where(held, 50.0, -50.0))), x.at[:, 0].set(ones))


@pytest.mark.parametrize("towards,pairs", [("held", T * K), ("elsewhere", 0)])
def test_no_pair_is_dropped_under_imbalance(layer, towards, pairs):
    """A router biased so that every token's k experts are held here (every
    pair has a row: the static bound is tokens x k), and so that none is."""
    _, params, x = layer
    first, count = 4, 4
    params, x = _towards_held(params, x, first, count,
                              sign=1.0 if towards == "held" else -1.0)
    m = MoE(D, HID, E, router="topk", top_k=K, held=(first, count))
    with jax.default_matmul_precision("highest"):
        y, state = m.apply(_share(params, first, count), m.get_state(), x)
        np.testing.assert_allclose(y, _uncut(x, params, K, first, count), atol=2e-6)
    assert float(state["pairs_held"]) == pairs
    assert float(state["dropped_fraction"]) == 0.0
    if not pairs:
        assert float(jnp.max(jnp.abs(y))) == 0.0


# held=(4, 4) of 16 at top-4 of 96 tokens: 384 pairs, a pass holds 192 rows
@pytest.mark.parametrize("bias,pairs,passes", [
    (None, None, 1),            # seeded: about 96 held pairs
    ("held", T * K, 2),         # every pair held: twice a pass's rows
    (61, 61 * K, 2),            # 244 pairs: not a multiple of a pass's rows
])
def test_gradients_match_the_uncut_layer(layer, bias, pairs, passes):
    """Parameters' and input's gradients against the dense form, with the held
    pairs inside one pass and under overflow (the pass repeated)."""
    _, params, x = layer
    first, count = 4, 4
    if bias is not None:
        params, x = _towards_held(params, x, first, count, None if bias == "held" else bias)
    m = MoE(D, HID, E, router="topk", top_k=K, held=(first, count))
    probe = jnp.cos(jnp.arange(D))

    def routed(p, x):
        y, state = m.apply(_share(p, first, count), m.get_state(), x)
        return jnp.sum(y * probe), state

    def dense(p, x):
        return jnp.sum(_uncut(x, p, K, first, count) * probe)

    with jax.default_matmul_precision("highest"):
        got, state = jax.grad(routed, (0, 1), has_aux=True)(params, x)
        want = jax.grad(dense, (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=5e-6)
    assert float(state["row_passes"]) == passes
    assert float(state["dropped_fraction"]) == 0.0
    if pairs is not None:
        assert float(state["pairs_held"]) == pairs


@pytest.mark.parametrize("gate", ["relu", "silu"])
@pytest.mark.parametrize("apart", [True, False])
def test_the_routers_input_apart_from_the_experts_and_the_relu_gate(layer, gate, apart):
    """``(x, r)``: the router reads ``r`` and the experts ``x`` (a router that
    stands before attention reads the layer's input); ``gate`` is the
    activation of an expert's gate. Output and all gradients against the
    dense form, ``r``'s gradient through the routing weights among them; a
    3-D input is routed position by position alike."""
    _, params, x = layer
    first, count = 4, 4
    r = jax.random.normal(jax.random.PRNGKey(9), x.shape) if apart else None
    m = MoE(D, HID, E, router="topk", top_k=K, held=(first, count), gate=gate)
    assert ("gate=relu" in repr(m)) == (gate == "relu")
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[gate]
    probe = jnp.cos(jnp.arange(D))

    def routed(p, x, r):
        y, _ = m.apply(_share(p, first, count), m.get_state(), (x, r) if apart else x)
        return jnp.sum(y * probe), y

    def dense(p, x, r):
        y = _uncut(x, p, K, first, count, routed_on=r if apart else None, gate=act)
        return jnp.sum(y * probe), y

    r_arg = r if apart else jnp.zeros(())
    with jax.default_matmul_precision("highest"):
        got, y = jax.grad(routed, (0, 1, 2), has_aux=True)(params, x, r_arg)
        want, y_want = jax.grad(dense, (0, 1, 2), has_aux=True)(params, x, r_arg)
        np.testing.assert_allclose(y, y_want, atol=2e-6)
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, b, atol=5e-6)
        if apart:
            assert float(jnp.max(jnp.abs(got[2]))) > 1e-4       # the router's input matters
            other = _uncut(x, params, K, first, count, gate=act)    # routed on x instead
            assert float(jnp.max(jnp.abs(y - other))) > 1e-3
            y3, _ = m.apply(_share(params, first, count), m.get_state(),
                            (x.reshape(2, T // 2, D), r.reshape(2, T // 2, D)))
            np.testing.assert_allclose(y3.reshape(T, D), y, atol=2e-6)


@pytest.mark.parametrize("norm", [True, False])
def test_shared_experts_counted_once_with_the_eight_shares_are_the_uncut_layer(layer, norm):
    """Shared experts (a gated MLP of width ``shared`` that every token passes
    through, under ``bigdl_moe_shared``): each of eight chips computes its two
    held experts' part plus the shared MLP; the routed parts added up, with
    the shared MLP counted once, are the uncut layer with every expert held,
    and that is the dense form plus the MLP written out."""
    from bigdl_tpu.parallel.moe import gated_mlp
    _, params, x = layer
    key = jax.random.PRNGKey(5)
    shared = {"shared_in": 0.1 * jax.random.normal(key, (D, 2 * 48)),
              "shared_out": 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (48, D))}
    plain_mlp = lambda x: (jax.nn.silu(x @ shared["shared_in"][:, :48])
                           * (x @ shared["shared_in"][:, 48:])) @ shared["shared_out"]
    with jax.default_matmul_precision("highest"):
        mlp = plain_mlp(x)
        np.testing.assert_allclose(gated_mlp(x, shared["shared_in"], shared["shared_out"]),
                                   mlp, atol=1e-6)
        routed = 0.0
        for rank in range(8):
            m = MoE(D, HID, E, router="topk", top_k=K, held=(2 * rank, 2),
                    norm_topk_prob=norm, shared=48)
            assert set(m.get_params()) == {"w_gate", "w_in", "w_out", "shared_in", "shared_out"}
            y, _ = m.apply({**_share(params, 2 * rank, 2), **shared}, m.get_state(), x)
            routed = routed + (y - mlp)
        whole = MoE(D, HID, E, router="topk", top_k=K, norm_topk_prob=norm, shared=48)
        y, _ = whole.apply({**params, **shared}, whole.get_state(), x)
        np.testing.assert_allclose(routed + mlp, y, atol=5e-6)
        np.testing.assert_allclose(y, _uncut(x, params, K, norm=norm) + mlp, atol=5e-6)
    assert "shared_in" not in MoE(D, HID, E, router="topk", top_k=K).get_params()
    with pytest.raises(ValueError, match="shared"):
        MoE(D, HID, E, router="top1", shared=48)


def test_one_pass_when_all_experts_are_held(layer):
    """A pass's rows are every pair then, whatever the routing."""
    full, params, x = layer
    params, x = _towards_held(params, x, 4, 4)
    _, state = full.apply(params, full.get_state(), x)
    assert float(state["row_passes"]) == 1.0
    assert float(state["dropped_fraction"]) == 0.0


def _avals(jaxpr):
    """Every value of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


@pytest.mark.parametrize("grad", [False, True])
def test_no_tensor_of_every_pair_by_a_feature(layer, grad):
    """With fewer experts held than there are, nothing between the sort and
    the sum by token has tokens x top_k rows of a feature dimension (D, H or
    2H): what is that long belongs to the routing (the pairs' order, their
    groups, the experts' loads)."""
    _, params, x = layer
    first, count = 4, 4
    m = MoE(D, HID, E, router="topk", top_k=K, held=(first, count))
    fn = lambda p, x: m.apply(p, m.get_state(), x)[0]
    if grad:
        fn = jax.grad(lambda p, x, fn=fn: jnp.sum(fn(p, x)), (0, 1))
    jaxpr = jax.make_jaxpr(fn)(_share(params, first, count), x).jaxpr
    shapes = {tuple(a.shape) for a in _avals(jaxpr) if hasattr(a, "shape")}
    assert (T * K // 2, D) in shapes                # a pass's rows are there
    wide = {s for s in shapes if T * K in s or s[:2] == (T, K)}
    assert wide and not any({D, HID, 2 * HID} & set(s) for s in wide), wide


@pytest.mark.parametrize("sizes", [[40, 0, 100], [0, 0, 0], [128, 64, 64]])
def test_grouped_matmul_kernel_matches_the_plain_form(sizes):
    """The Pallas kernel through the interpreter against ``ragged_dot``,
    forward and both gradients; rows past the groups come out zero."""
    key = jax.random.PRNGKey(0)
    lhs = jax.random.normal(key, (256, 64))
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (3, 64, 48))
    sizes = jnp.asarray(sizes, jnp.int32)
    f = lambda a, b, force: jnp.sum(jnp.sin(grouped_matmul(a, b, sizes, force)))
    with jax.default_matmul_precision("highest"):
        got, want = grouped_matmul(lhs, rhs, sizes, True), grouped_matmul(lhs, rhs, sizes, False)
        np.testing.assert_allclose(got, want, atol=1e-4)
        assert float(jnp.max(jnp.abs(got[int(sizes.sum()):]), initial=0.0)) == 0.0
        for a, b in zip(jax.grad(f, (0, 1))(lhs, rhs, True),
                        jax.grad(f, (0, 1))(lhs, rhs, False)):
            np.testing.assert_allclose(a, b, atol=1e-4)


def test_topk_arguments_are_checked():
    with pytest.raises(ValueError):
        MoE(D, HID, E, router="topk")                       # no top_k
    with pytest.raises(ValueError):
        MoE(D, HID, E, router="topk", top_k=4, held=(12, 8))  # past the last
    with pytest.raises(ValueError):
        MoE(D, HID, E, router="top1", held=(0, 4))          # not this router's
    with pytest.raises(ValueError):
        MoE(D, HID, E, router="topk", top_k=4, gate="gelu")   # no such gate
    with pytest.raises(ValueError):
        MoE(D, HID, E, router="top2", gate="relu")          # not this router's
    x = jnp.zeros((T, D))
    with pytest.raises(ValueError):                         # nor a router's own input
        MoE(D, HID, E).apply(MoE(D, HID, E).get_params(), {}, (x, x))
    m = MoE(D, HID, E, router="topk", top_k=4)
    with pytest.raises(ValueError):                         # the two of one shape
        m.apply(m.get_params(), m.get_state(), (x, x[:8]))


def test_expert_parallel_rules_shard_the_held_matrices():
    from jax.sharding import PartitionSpec as P
    m = MoE(D, HID, E, router="topk", top_k=K)
    rules = expert_parallel_rules(axis="model")
    specs = {k: rules.spec_for(f"moe/{k}", v.shape) for k, v in m.get_params().items()}
    assert specs["w_in"] == P("model", None, None) == specs["w_out"]
    assert specs["w_gate"] == P()                   # the router stays whole
