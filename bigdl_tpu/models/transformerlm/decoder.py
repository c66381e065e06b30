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

Latent attention (DeepSeek-V2's MLA, ``nn.LatentAttention``) takes the
attention template's place where a config names ``kv_lora_rank`` (with
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim`` and, for YaRN,
``rope_scaling``; ``q_lora_rank`` must be null): one kind of layer, causal.
``first_k_dense_replace`` leading layers are dense, a gated MLP of width
``intermediate_size`` after attention, on their own weights (``dense`` in the
parameter tree, stacked the same way); they run each once before the scan,
which runs over the routed layers that follow. ``n_shared_experts`` gives the
routed layer its shared experts (``MoE(shared=)``). A config without these
keys builds the decoder it did.

The modules are the layer's templates: the scan body calls their ``apply`` on
a layer's slice of the stacked parameters, attention under the scope
``bigdl_attn_window``, ``bigdl_attn_full`` or ``bigdl_attn_latent`` by kind
and a dense layer's MLP under ``bigdl_mlp_dense`` (``obs/trace.py``).
``remat`` rematerialises the layers, dense and routed, each on its own: the
scan keeps, a layer, its input and what is dear to make again (``KEPT``: the
flash kernels' ``q``, ``k``, ``v``, ``out`` and logsumexp, the routing, the
hidden state after attention), ``N * T * ((heads + kv_heads) * (d_qk + d_v)
+ 2 * hidden) * 2`` bytes, with ``d_qk`` the query/key head and ``d_v`` the
value head (both ``head_dim`` but under latent attention, where ``kv_heads``
is ``heads``), and the routing's ``12 * N * T * top_k``: 0.44 GB a layer at 2
x 8,192 positions, 32 + 4 + 4 heads of 128 and hidden 2,048 in bf16, 0.47 GB
at 16,384 positions, 16 + 16 heads of 192 / 128 and hidden 2,048; the norms,
the projections into the heads (the per-head norms' backward reads their
results), the router's logits and its softmax run again in the backward pass,
the flash forward kernel, the output projection, top-k and the sort do not.
``remat=False`` keeps every value of every layer.

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
from bigdl_tpu.parallel.moe import ROUTING_NAMES, MoE, gated_mlp
from bigdl_tpu.utils.random_generator import RandomGenerator
from bigdl_tpu.utils.table import Table

#: What a rematerialised layer keeps across the backward pass beside its
#: input, by the names the values are tagged with where they are made: the
#: flash kernels' operands and residuals, the routing, and the hidden state
#: after attention (tagged in ``apply``).
KEPT = RESIDUAL_NAMES + ROUTING_NAMES + ("decoder_after_attention",)

# the layers' health leaves as the decoder's own state: the worst layer for
# what warns, the sum for what counts, the mean for a share
_HEALTH = {"aux_loss": jnp.sum, "router_z_loss": jnp.mean,
           "dropped_fraction": jnp.max, "expert_load_max": jnp.max,
           "pairs_held": jnp.sum, "row_passes": jnp.max, "pass_fill": jnp.mean}


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
                   "sliding_window_size", "rope_layout", "kv_lora_rank",
                   "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                   "v_head_dim", "rope_scaling", "first_k_dense_replace",
                   "intermediate_size", "n_shared_experts")
    #: other spellings of those keys that published configs use
    CONFIG_ALIASES = {"moe_ffn_hidden_size": "moe_intermediate_size",
                      "moe_num_primary_experts": "num_experts",
                      "moe_num_active_primary_experts": "num_experts_per_tok",
                      "n_routed_experts": "num_experts"}

    def __init__(self, vocab_size: int, hidden_size: int,
                 num_hidden_layers: int, num_attention_heads: int,
                 num_key_value_heads: int, head_dim: Optional[int] = None,
                 moe_intermediate_size: int = None, num_experts: int = None,
                 num_experts_per_tok: int = None, norm_topk_prob: bool = True,
                 rms_norm_eps: float = 1e-6, rope_theta: float = 10000.0,
                 initializer_range: float = 0.02,
                 held: Optional[tuple] = None, qk_norm: bool = True,
                 block_diffusion: Optional[tuple] = None, remat: bool = True,
                 sliding_window_layout: Optional[Sequence[int]] = None,
                 sliding_window_size: Optional[int] = None,
                 rope_layout: Optional[Sequence[int]] = None,
                 router_input: str = "experts", expert_gate: str = "silu",
                 kv_lora_rank: Optional[int] = None,
                 q_lora_rank: Optional[int] = None,
                 qk_nope_head_dim: Optional[int] = None,
                 qk_rope_head_dim: Optional[int] = None,
                 v_head_dim: Optional[int] = None,
                 rope_scaling: Optional[dict] = None,
                 first_k_dense_replace: int = 0,
                 intermediate_size: Optional[int] = None,
                 n_shared_experts: int = 0):
        super().__init__()
        self.vocab_size, self.hidden_size = int(vocab_size), int(hidden_size)
        #: the leading dense layers, before the scan over the routed ones
        self.dense_layers = k = int(first_k_dense_replace or 0)
        self.num_hidden_layers = int(num_hidden_layers)
        n = self.num_hidden_layers - k
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
        if kv_lora_rank is not None:
            if q_lora_rank is not None:
                raise ValueError("latent attention with a query low-rank path "
                                 "(q_lora_rank) is not built")
            if mask is not None or len(self.period) > 1 or self.period[0].window:
                raise ValueError("latent attention is causal over every key: "
                                 "no mask, window or layout beside it")
            #: the one attention template (latent attention: one kind of layer)
            self.attentions = [nn.LatentAttention(
                hidden_size, num_attention_heads, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, rope_base=rope_theta,
                rope_scaling=rope_scaling, norm_eps=rms_norm_eps)]
        else:
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
                           gate=expert_gate,
                           shared=int(n_shared_experts or 0) * moe_intermediate_size)
        self.expert_gate = expert_gate
        # the templates lend their shapes; their own copies are never read
        norm_shape = self.norm.get_params()["weight"].shape
        attn_shapes = jax.tree_util.tree_map(lambda a: a.shape,
                                             self.attentions[0].get_params())
        self._layer_shapes = {"attn": attn_shapes, "attn_norm": norm_shape,
                              "moe": jax.tree_util.tree_map(
                                  lambda a: a.shape, self.experts.get_params()),
                              "moe_norm": norm_shape}
        if k:
            self._dense_shapes = {
                "attn": attn_shapes, "attn_norm": norm_shape,
                "mlp": {"w_in": (hidden_size, 2 * intermediate_size),
                        "w_out": (intermediate_size, hidden_size)},
                "mlp_norm": norm_shape}
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
        k, std = self.dense_layers, self.initializer_range
        is_shape = lambda v: isinstance(v, tuple)
        # (shape, is a gain): a gain holds one number a channel
        stack = lambda n, shapes: jax.tree_util.tree_map(
            lambda shape: ((n,) + shape, len(shape) == 1), shapes, is_leaf=is_shape)
        spec = {"embed": ((self.vocab_size, self.hidden_size), False),
                "final_norm": ((self.hidden_size,), True),
                "head": ((self.vocab_size, self.hidden_size), False),
                "layers": stack(self.num_hidden_layers - k, self._layer_shapes)}
        if k:
            spec["dense"] = stack(k, self._dense_shapes)
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

        def layer_of(attention, dense=False):
            if isinstance(attention, nn.LatentAttention):
                scope = trace.SCOPE_ATTN_LATENT
            else:
                scope = trace.SCOPE_ATTN_FULL if attention.window is None \
                    else trace.SCOPE_ATTN_WINDOW

            def layer(h, p):
                a, _ = norm.apply({"weight": p["attn_norm"]}, {}, h)
                with jax.named_scope(scope):
                    a, _ = attention.apply(p["attn"], {}, (a, positions),
                                           training=training)
                after = checkpoint_name(h + a, "decoder_after_attention")
                if dense:
                    m, _ = norm.apply({"weight": p["mlp_norm"]}, {}, after)
                    with jax.named_scope(trace.SCOPE_MLP_DENSE):
                        return after + gated_mlp(m, p["mlp"]["w_in"], p["mlp"]["w_out"],
                                                 self.expert_gate)
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

        if self.dense_layers:
            # the leading dense layers, each once, on their own weights
            dense = layer_of(self.attentions[0], dense=True)
            for i in range(self.dense_layers):
                h = dense(h, jax.tree_util.tree_map(lambda a: a[i], params["dense"]))
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
        if self.dense_layers:
            kinds += f", the first {self.dense_layers} dense"
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
