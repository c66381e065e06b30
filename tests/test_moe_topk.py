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


# held=(4, 4): a pass is 192 rows
@pytest.mark.parametrize("bias,passes", [(None, 1), ("held", 2), (61, 2)])
def test_pass_fill_is_the_held_pairs_over_the_rows_of_the_passes(layer, bias, passes):
    """``pass_fill``: the rows the grouped products ran over, over the rows of
    the passes run; 1 only where every pass is full."""
    _, params, x = layer
    first, count = 4, 4
    if bias is not None:
        params, x = _towards_held(params, x, first, count, None if bias == "held" else bias)
    m = MoE(D, HID, E, router="topk", top_k=K, held=(first, count))
    _, state = m.apply(_share(params, first, count), m.get_state(), x)
    assert float(state["row_passes"]) == passes
    assert float(state["pass_fill"]) == pytest.approx(
        float(state["pairs_held"]) / (passes * 192), rel=1e-6)
    assert (float(state["pass_fill"]) < 1.0) == (bias != "held")


def _spy_on_the_sizes(monkeypatch):
    """What the routed layer hands ``grouped_matmul`` as group sizes, each
    call's sum, as the calls run (inside the passes' loop too)."""
    from bigdl_tpu.kernels import grouped_matmul as gm
    seen, real = [], gm.grouped_matmul

    def spy(lhs, rhs, group_sizes, force_pallas=None):
        jax.debug.callback(lambda s: seen.append(int(s)), jnp.sum(group_sizes))
        return real(lhs, rhs, group_sizes, force_pallas)

    monkeypatch.setattr(gm, "grouped_matmul", spy)
    return seen


@pytest.mark.parametrize("bias,per_pass", [(None, None), (61, [192, 61 * K - 192])])
def test_the_products_are_handed_the_held_rows_of_a_pass_only(layer, monkeypatch, bias, per_pass):
    """Forward and VJP, the sizes of every grouped product sum to the held
    pairs inside its pass, not to the pass's 192 rows: the rows past the held
    pairs are in no group, and the last of two passes holds the remainder."""
    _, params, x = layer
    first, count = 4, 4
    if bias is not None:
        params, x = _towards_held(params, x, first, count, bias)
    m = MoE(D, HID, E, router="topk", top_k=K, held=(first, count))
    seen = _spy_on_the_sizes(monkeypatch)
    y, state = m.apply(_share(params, first, count), m.get_state(), x)
    jax.effects_barrier()
    held = int(state["pairs_held"])
    if per_pass is None:
        assert 0 < held < 192
        per_pass = [held]
    assert sum(per_pass) == held
    # gate and up, then down: two products a pass
    assert sorted(seen) == sorted(per_pass * 2)
    seen.clear()
    jax.grad(lambda p: jnp.sum(m.apply(p, m.get_state(), x)[0]))(_share(params, first, count))
    jax.effects_barrier()
    # the forward's two a pass, and the backward's recomputed two a pass
    assert sorted(seen) == sorted(per_pass * 4)


@pytest.mark.parametrize("bias", [None, 61])
def test_the_layer_through_the_kernel_skipping_dead_rows_is_the_uncut_layer(
        layer, monkeypatch, bias):
    """megablox's kernel through the interpreter, with the rows past the held
    pairs left to the group it skips: the layer's output and every gradient
    against the dense form, in one pass and in two."""
    from bigdl_tpu.kernels import grouped_matmul as gm
    _, params, x = layer
    first, count = 4, 4
    if bias is not None:
        params, x = _towards_held(params, x, first, count, bias)
    real = gm.grouped_matmul
    monkeypatch.setattr(gm, "grouped_matmul",
                        lambda lhs, rhs, sizes: real(lhs, rhs, sizes, True))
    m = MoE(D, HID, E, router="topk", top_k=K, held=(first, count))
    probe = jnp.cos(jnp.arange(D))

    def routed(p, x):
        y, state = m.apply(_share(p, first, count), m.get_state(), x)
        return jnp.sum(y * probe), (y, state)

    def dense(p, x):
        y = _uncut(x, p, K, first, count)
        return jnp.sum(y * probe), y

    with jax.default_matmul_precision("highest"):
        got, (y, state) = jax.grad(routed, (0, 1), has_aux=True)(params, x)
        want, y_want = jax.grad(dense, (0, 1), has_aux=True)(params, x)
    np.testing.assert_allclose(y, y_want, atol=2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=5e-6)
    assert float(state["row_passes"]) == (1.0 if bias is None else 2.0)
    assert float(state["pass_fill"]) < 1.0


def test_a_pass_at_the_sdar_shape_runs_the_row_tiles_of_its_held_rows(monkeypatch):
    """The SDAR cell's pass (16,384 positions x top-8, 16 of 128 experts
    held: 32,768 rows) with half its rows held, at random cuts between the
    groups: the sizes ``_window`` gives, as ``grouped_matmul`` hands them to
    megablox, leave megablox's own count of row tiles at most 0.65 of the
    count with the dead rows put in the last held group."""
    from jax.experimental.pallas.ops.tpu import megablox
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata
    from bigdl_tpu.kernels import grouped_matmul as gm
    from bigdl_tpu.parallel.moe import _held_rows, _window
    tokens, k, groups = 16384, 8, 16
    rows = _held_rows(tokens * k, groups, 128)
    assert rows == 32768
    sizes = jnp.asarray(np.random.default_rng(0).multinomial(
        rows // 2, np.full(groups, 1 / groups)), jnp.int32)
    *_, inside = _window(jnp.zeros((tokens, 8)), jnp.ones((tokens, k)),
                         jnp.arange(tokens * k, dtype=jnp.int32), sizes,
                         jnp.int32(0), rows)
    handed = []
    monkeypatch.setattr(megablox, "gmm", lambda lhs, rhs, s, *rest: handed.append(s)
                        or jnp.zeros((rows, rhs.shape[2])))
    gm.grouped_matmul(jnp.zeros((rows, 8)), jnp.zeros((groups, 8, 8)), inside, True)

    def row_tiles(s):
        return int(make_group_metadata(group_sizes=s, m=rows, tm=gm._tile_m(rows),
                                       start_group=jnp.int32(0), num_nonzero_groups=groups,
                                       visit_empty_groups=False)[1])

    padded = jnp.append(inside.at[-1].add(rows - jnp.sum(inside)), 0)
    assert int(jnp.sum(handed[0][:groups])) == rows // 2
    assert row_tiles(handed[0]) <= 0.65 * row_tiles(padded)


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


@pytest.mark.parametrize("sizes", [[40, 0, 100], [0, 0, 0], [128, 64, 64],
                                   [30, 20, 10]])
def test_grouped_matmul_kernel_matches_the_plain_form(sizes):
    """The Pallas kernel through the interpreter against ``ragged_dot``,
    forward and both gradients; rows past the groups (196 of 256 in the last
    case, as a pass past its held pairs) come out zero."""
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


def _sum_by_token(rows, token_of, tokens, weight=None):
    """The plain XLA form the kernel replaced: (tokens, D) in fp32, row ``r``
    of ``rows``, times ``weight[r]`` if given, added to token ``token_of[r]``
    by an fp32 scatter-add."""
    part = rows.astype(jnp.float32)
    if weight is not None:
        part = part * weight[:, None]
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[token_of].add(part)


# tokens, rows held by each token in turn (a pattern repeated), dead rows, D
@pytest.mark.parametrize("tokens,held,dead,d", [
    (96, (0, 1, K), 40, 64),        # tokens with none, one and top_k rows
    (300, (K, 1, 0, 2), 21, 128),   # 300 tokens: not a multiple of the 128-token block
    (5, (1,), 3, 64),               # fewer tokens and rows than a block
    (40, (0, K, 1), 8, 2048),       # the SDAR and DeepSeek cells' width
    (40, (1, 0, K), 8, 2560),       # the SmallThinker cell's
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("weighted", [True, False])
def test_the_sum_kernel_is_the_scatter_add_by_token(tokens, held, dead, d, dtype, weighted):
    """``bigdl_moe_sum`` through the interpreter against the fp32 scatter-add,
    within fp32 round-off: rows in the order a pass holds them (by expert, not
    by token), dead rows among them that name tokens and hold values the sum
    must not see, weighted (the layer's output) and not (its input's
    gradient), rows in bfloat16 and in fp32."""
    from bigdl_tpu.kernels.token_sum import sum_by_token
    rng = np.random.default_rng(tokens + d)
    counts = np.resize(held, tokens)
    token_of = np.concatenate([np.repeat(np.arange(tokens), counts),
                               rng.integers(0, tokens, dead)])
    live = np.arange(len(token_of)) < counts.sum()
    order = rng.permutation(len(token_of))
    token_of, live = token_of[order], live[order]
    rows = jnp.asarray(rng.normal(size=(len(token_of), d)), dtype)
    weight = jnp.asarray(rng.random(len(token_of)), jnp.float32) if weighted else None
    got = sum_by_token(rows, jnp.asarray(token_of, jnp.int32), jnp.asarray(live), tokens, weight)
    want = _sum_by_token(rows[live], jnp.asarray(token_of[live]), tokens,
                         None if weight is None else weight[live])
    assert got.dtype == jnp.float32 and got.shape == (tokens, d)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * float(jnp.max(jnp.abs(want))))
    np.testing.assert_array_equal(got[counts == 0], 0.0)


def test_the_sum_kernel_cuts_rows_too_wide_for_its_budget(monkeypatch):
    """Where a whole row's plan passes the VMEM budget the kernel walks the
    features in blocks of whole lane tiles, the pairs once for each."""
    from bigdl_tpu.kernels import token_sum as ts
    monkeypatch.setattr(ts, "_BUDGET_BYTES", ts._vmem_bytes(128, 128, 128, 4))
    assert ts._tiles(300, 260, 384, 4) == ts._Tiles(128, 128, 128)
    rng = np.random.default_rng(3)
    token_of = jnp.asarray(rng.permutation(300 * K)[:260] // K, jnp.int32)
    rows = jnp.asarray(rng.normal(size=(260, 384)), jnp.float32)
    weight = jnp.asarray(rng.random(260), jnp.float32)
    got = ts.sum_by_token(rows, token_of, jnp.ones(260, bool), 300, weight)
    np.testing.assert_allclose(got, _sum_by_token(rows, token_of, 300, weight), rtol=0, atol=1e-5)


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
