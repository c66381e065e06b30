"""A decoder built from a published configuration's keys.

``TransformerLM`` writes its layers out one by one from the Torch table
algebra, one kind of layer. ``ConfigDecoder`` is built from the keys a model's
``config.json`` has (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``moe_intermediate_size``, ``num_experts``, ``num_experts_per_tok``,
``vocab_size``, ...) and runs its layers as ONE ``lax.scan`` body over
stacked weights: the step program traces and compiles one period of the
layer pattern whatever the depth. A layer is

    h = h + Attention(RMSNorm(h), positions)      nn.MultiHeadAttention
    h = h + Experts(RMSNorm(h))                   parallel.MoE, router="topk"

with grouped-query heads of their own ``head_dim``, per-head RMSNorm on
queries and keys (``qk_norm``), RoPE by position ids, and the routed gated
expert layer (``expert_gate``: ``"silu"`` or ``"relu"``), which is told which
experts it holds (``held``). With ``router_input="layer"`` the router reads
the layer's input ``h`` as it enters, before the first RMSNorm (a router that
stands before attention), where by default it reads what the experts read.

Layers may be of several kinds (``LayerKind``): ``sliding_window_layout`` and
``rope_layout``, a config's lists of one number a layer, say which layers see
a causal window of ``sliding_window_size`` keys (1) or every key (0), and
which turn queries and keys by RoPE (1) or carry no positions at all (0).
The lists may be longer than the depth (a config cut in depth keeps its
published lists); the first ``num_hidden_layers`` entries count. The shortest
period of the pattern is written out inside the scan body, one attention
template a kind with its own static mask and its RoPE or none, and the scan
runs over the periods: no layer computes two kinds and selects. The stacked
weights keep the depth in front (all kinds hold the same shapes) and are cut
into periods where the scan takes them. A pattern of one kind is a period of
one: the scan body is then the one layer and takes the stacked weights as
they are.

The modules are the layer's templates: the scan body calls their ``apply`` on
a layer's slice of the stacked parameters, attention under the scope
``bigdl_attn_window`` or ``bigdl_attn_full`` by kind
(``obs/trace.py``). ``remat`` rematerialises the layers, each on its own: the
scan keeps, a layer, its input and what is dear to make again (``KEPT``: the
flash kernels' ``q``, ``k``, ``v``, ``out`` and logsumexp, the routing, the
hidden state after attention), ``N * T * ((2 * heads + 2 * kv_heads) *
head_dim + 2 * hidden) * 2`` bytes and the routing's ``12 * N * T * top_k``,
0.44 GB a layer at 2 x 8,192 positions, 32 + 4 + 4 heads of 128 and hidden
2,048 in bf16; the norms, the projections into the heads (the per-head norms'
backward reads their results), the router's logits and its softmax run again
in the backward pass, the flash forward kernel, the output projection, top-k
and the sort do not. ``remat=False`` keeps every value of every layer.

``block_diffusion=(L, b)`` trains by diffusion over blocks (BD3-LMs,
arXiv:2503.09573): the input is ``[x_t ; x_0]``, a noised copy of a sequence of
``L`` tokens and the clean sequence, ``2L`` positions with position ids
``[0..L-1 ; 0..L-1]`` under ``kernels.flash_attention.BlockDiffusion(L, b)``;
only the noised half reaches the head. Without it the decoder is causal
(every layer, or within its window). Block diffusion takes no windowed layer.

In training the output is ``Table(hidden, head weight)`` for a criterion that
streams the vocabulary (``nn/fused_loss.py``): ``WeightedTokenCriterion``
here, which weights each position and ignores those with a negative target.
In evaluation it is the logits.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bigdl_tpu import nn
from bigdl_tpu.kernels.flash_attention import RESIDUAL_NAMES, BlockDiffusion
from bigdl_tpu.nn.abstractnn import TensorModule
from bigdl_tpu.nn.criterion import AbstractCriterion
from bigdl_tpu.nn.fused_loss import chunked_softmax_xent
from bigdl_tpu.obs import trace
from bigdl_tpu.parallel.moe import ROUTING_NAMES, MoE
from bigdl_tpu.utils.random_generator import RandomGenerator
from bigdl_tpu.utils.table import Table

#: What a rematerialised layer keeps across the backward pass beside its
#: input, by the names the values are tagged with where they are made: the
#: flash kernels' operands and residuals, the routing, and the hidden state
#: after attention (tagged in ``apply``).
KEPT = RESIDUAL_NAMES + ROUTING_NAMES + ("decoder_after_attention",)

# the layers' health leaves as the decoder's own state: the worst layer for
# what warns, the sum for what counts
_HEALTH = {"aux_loss": jnp.sum, "router_z_loss": jnp.mean,
           "dropped_fraction": jnp.max, "expert_load_max": jnp.max,
           "pairs_held": jnp.sum, "row_passes": jnp.max}


class LayerKind(NamedTuple):
    """What tells one kind of layer from another: the keys its attention
    sees (``window`` newest, or every one the decoder's mask allows for
    ``None``) and whether queries and keys are turned by RoPE."""
    window: Optional[int]
    rope: bool


def _period(kinds: list) -> list:
    """The shortest leading run of ``kinds`` that, repeated, gives them all."""
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)):
            return kinds[:p]


class ConfigDecoder(TensorModule):
    """Token ids ``(N, T)`` int32 → ``Table(hidden, head)`` in training, logits
    in evaluation; see the module docstring. ``num_experts`` is the router's
    width (all experts), ``held=(first, count)`` what this chip holds."""

    CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
                   "num_attention_heads", "num_key_value_heads", "head_dim",
                   "moe_intermediate_size", "num_experts",
                   "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
                   "rope_theta", "initializer_range", "sliding_window_layout",
                   "sliding_window_size", "rope_layout")
    #: other spellings of those keys that published configs use
    CONFIG_ALIASES = {"moe_ffn_hidden_size": "moe_intermediate_size",
                      "moe_num_primary_experts": "num_experts",
                      "moe_num_active_primary_experts": "num_experts_per_tok"}

    def __init__(self, vocab_size: int, hidden_size: int,
                 num_hidden_layers: int, num_attention_heads: int,
                 num_key_value_heads: int, head_dim: int,
                 moe_intermediate_size: int, num_experts: int,
                 num_experts_per_tok: int, norm_topk_prob: bool = True,
                 rms_norm_eps: float = 1e-6, rope_theta: float = 10000.0,
                 initializer_range: float = 0.02,
                 held: Optional[tuple] = None, qk_norm: bool = True,
                 block_diffusion: Optional[tuple] = None, remat: bool = True,
                 sliding_window_layout: Optional[Sequence[int]] = None,
                 sliding_window_size: Optional[int] = None,
                 rope_layout: Optional[Sequence[int]] = None,
                 router_input: str = "experts", expert_gate: str = "silu"):
        super().__init__()
        self.vocab_size, self.hidden_size = int(vocab_size), int(hidden_size)
        self.num_hidden_layers = n = int(num_hidden_layers)
        self.initializer_range = float(initializer_range)
        self.remat = bool(remat)
        self.block_diffusion = block_diffusion and tuple(block_diffusion)
        if router_input not in ("experts", "layer"):
            raise ValueError(f"router_input must be 'experts' or 'layer', got "
                             f"{router_input!r}")
        self.router_input = router_input
        windowed = [0] * n if sliding_window_layout is None else \
            list(sliding_window_layout)[:n]
        turned = [1] * n if rope_layout is None else list(rope_layout)[:n]
        if len(windowed) != n or len(turned) != n:
            raise ValueError(f"a layout shorter than the {n} layers")
        if any(windowed) and sliding_window_size is None:
            raise ValueError("sliding_window_layout names windowed layers and "
                             "sliding_window_size is not given")
        #: the layer pattern's shortest period, a ``LayerKind`` a layer
        self.period = _period([
            LayerKind(int(sliding_window_size) if w else None, bool(r))
            for w, r in zip(windowed, turned)])
        mask = BlockDiffusion(*self.block_diffusion) if self.block_diffusion else None
        self.norm = nn.RMSNorm(hidden_size, eps=rms_norm_eps)
        #: one attention template a layer of the period
        self.attentions = [nn.MultiHeadAttention(
            hidden_size, num_attention_heads, causal=mask is None,
            with_bias=False, num_kv_heads=num_key_value_heads, rope=kind.rope,
            rope_base=rope_theta, window=kind.window, head_dim=head_dim,
            qk_norm=qk_norm, qk_norm_eps=rms_norm_eps, mask=mask)
            for kind in self.period]
        self.experts = MoE(hidden_size, moe_intermediate_size, num_experts,
                           router="topk", top_k=num_experts_per_tok,
                           norm_topk_prob=norm_topk_prob, held=held,
                           gate=expert_gate)
        # the templates lend their shapes; their own copies are never read
        self._layer_shapes = jax.tree_util.tree_map(lambda a: a.shape, {
            "attn": self.attentions[0].get_params(),
            "attn_norm": self.norm.get_params()["weight"],
            "moe": self.experts.get_params(),
            "moe_norm": self.norm.get_params()["weight"]})
        for template in (*self.attentions, self.experts, self.norm):
            template._params, template._grads = {}, {}
        self.reset()

    @classmethod
    def from_config(cls, config: dict, **more) -> "ConfigDecoder":
        """From a ``config.json``'s keys (those of ``CONFIG_KEYS`` it has, or
        of ``CONFIG_ALIASES`` in their place); ``more`` overrides them and
        gives what a config has no key for."""
        keys = {**{v: k for k, v in cls.CONFIG_ALIASES.items() if k in config},
                **{k: k for k in cls.CONFIG_KEYS if k in config}}
        return cls(**{**{k: config[named] for k, named in keys.items()}, **more})

    def reset(self) -> None:
        """Matrices N(0, ``initializer_range``), gains 1, drawn on the device
        from the global generator's next salt."""
        n, std = self.num_hidden_layers, self.initializer_range
        is_shape = lambda v: isinstance(v, tuple)
        # (shape, is a gain): a gain holds one number a channel
        spec = {"embed": ((self.vocab_size, self.hidden_size), False),
                "final_norm": ((self.hidden_size,), True),
                "head": ((self.vocab_size, self.hidden_size), False),
                "layers": jax.tree_util.tree_map(
                    lambda shape: ((n,) + shape, len(shape) == 1),
                    self._layer_shapes, is_leaf=is_shape)}
        leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=is_shape)

        def draw(key):
            return [jnp.ones(shape, jnp.float32) if gain else
                    std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                            jnp.float32)
                    for i, (shape, gain) in enumerate(leaves)]

        key = jax.random.PRNGKey(RandomGenerator.next_salt() & 0x7FFFFFFF)
        self._params = jax.tree_util.tree_unflatten(treedef, jax.jit(draw)(key))
        self._state = {k: jnp.zeros((), jnp.float32) for k in _HEALTH}
        self.zero_grad_parameters()

    def zero_grad_parameters(self) -> None:
        # no buffer of zeros the size of the model: the optimizers never read
        # a module's own gradient, and `get_grads` makes one when asked
        self._grads = {}

    def get_grads(self) -> dict:
        return self._grads or jax.tree_util.tree_map(jnp.zeros_like,
                                                     self._params)

    def grad_scales(self) -> dict:
        scale = 0.0 if self.is_frozen() else self.scale_w
        return jax.tree_util.tree_map(lambda _: scale, self._params)

    def _positions(self, t: int):
        if not self.block_diffusion:
            return jnp.arange(t)
        length = self.block_diffusion[0]
        if t != 2 * length:
            raise ValueError(f"block diffusion over {length} tokens takes "
                             f"{2 * length} positions, got {t}")
        return jnp.tile(jnp.arange(length), 2)

    def apply(self, params, state, input, *, training=False, rng=None):
        h = params["embed"][input]                              # (N, T, D)
        positions = self._positions(input.shape[1])
        norm, experts_state = self.norm, self.experts.get_state()

        def layer_of(attention):
            scope = trace.SCOPE_ATTN_FULL if attention.window is None \
                else trace.SCOPE_ATTN_WINDOW

            def layer(h, p):
                a, _ = norm.apply({"weight": p["attn_norm"]}, {}, h)
                with jax.named_scope(scope):
                    a, _ = attention.apply(p["attn"], {}, (a, positions),
                                           training=training)
                after = checkpoint_name(h + a, "decoder_after_attention")
                m, _ = norm.apply({"weight": p["moe_norm"]}, {}, after)
                m, health = self.experts.apply(
                    p["moe"], experts_state,
                    (m, h) if self.router_input == "layer" else m,
                    training=training)
                return after + m, {k: health[k] for k in _HEALTH}

            if self.remat:
                return jax.checkpoint(layer, policy=jax.checkpoint_policies
                                      .save_only_these_names(*KEPT))
            return layer

        layers = [layer_of(attention) for attention in self.attentions]
        if len(layers) == 1:
            body, stacked = layers[0], params["layers"]
        else:
            # the period written out: each of its layers on its own slice of
            # the period's weights, the health leaves a row a layer
            stacked = jax.tree_util.tree_map(
                lambda a: a.reshape((-1, len(layers)) + a.shape[1:]),
                params["layers"])

            def body(h, period):
                health = []
                for i, layer in enumerate(layers):
                    h, leaves = layer(h, jax.tree_util.tree_map(
                        lambda a: a[i], period))
                    health.append(leaves)
                return h, {k: jnp.stack([leaves[k] for leaves in health])
                           for k in _HEALTH}

        h, health = jax.lax.scan(body, h, stacked)
        if self.block_diffusion:
            h = h[:, :self.block_diffusion[0]]      # the noised half predicts
        h, _ = norm.apply({"weight": params["final_norm"]}, {}, h)
        new_state = {k: jax.lax.stop_gradient(fold(health[k]))
                     for k, fold in _HEALTH.items()}
        if training:
            return Table(h, params["head"]), new_state
        return h @ params["head"].T, new_state

    def __repr__(self):
        kinds = "" if len(self.period) == 1 else f" in periods of {list(self.period)}"
        return (f"ConfigDecoder({self.num_hidden_layers} layers{kinds}, "
                f"hidden={self.hidden_size}, {self.attentions[0]!r}, "
                f"{self.experts!r})")


class WeightedTokenCriterion(AbstractCriterion):
    """``sum_i w_i * -log softmax(head(h_i))[y_i] / n`` over the ``n`` positions
    of ``Table(hidden, head weight)``. ``target`` packs both as one float
    array ``(N, 2, T)``: ``target[:, 0]`` the token ids (exact in float32 up
    to 2**24; a negative id is ignored, loss and gradient 0),
    ``target[:, 1]`` the weights. Block diffusion's objective has the masked
    positions' clean tokens as targets with weight ``1/t`` and every other
    position ignored. The vocabulary streams in chunks of ``chunk_size``."""

    size_average = True

    def __init__(self, chunk_size: int = 8192):
        super().__init__()
        self.chunk_size = int(chunk_size)

    def apply(self, input, target):
        hidden, weight = (input.values() if isinstance(input, Table)
                          else list(input))[:2]
        h2 = hidden.reshape(-1, hidden.shape[-1])
        labels = target[:, 0].reshape(-1).astype(jnp.int32)
        weights = target[:, 1].reshape(-1).astype(jnp.float32)
        losses = chunked_softmax_xent(h2, weight, None, labels, self.chunk_size)
        return jnp.sum(losses * weights) / h2.shape[0]

    def __repr__(self):
        return f"WeightedTokenCriterion(chunk={self.chunk_size})"


from bigdl_tpu.utils.serializer import register as _register_serializable  # noqa: E402

_register_serializable(ConfigDecoder)
