"""Attention layers — long-context first-class via ring attention.

No reference counterpart (SURVEY.md §5.7: the reference predates attention layers);
required capability of the TPU build. ``MultiHeadAttention`` projects with fused QKV,
runs :func:`~bigdl_tpu.parallel.ring_attention` when the Engine mesh has a ``seq``
axis (sequence sharded, K/V rotating over ICI) and the single-chip Pallas flash
kernel (kernels/flash_attention.py; plain fused attention off-TPU) otherwise —
the same module scales from one chip to a sequence-parallel mesh unchanged.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.abstractnn import TensorModule
from bigdl_tpu.nn.initialization import InitializationMethod, Xavier


def rope_rotate(x: jnp.ndarray, positions: jnp.ndarray,
                base: float = 10000.0) -> jnp.ndarray:
    """Rotary position embedding (split-half convention): ``x (..., t, d)``
    rotated by per-position angles ``positions (t,)``. Each (x[i], x[i+d/2])
    pair turns by ``pos / base^(2i/d)`` — attention scores then depend only
    on RELATIVE distance, which is what lets RoPE models extrapolate and
    makes the rotation cache-free (the decode path rotates the single new
    position by its absolute index; nothing else changes).

    ``positions`` may also be (b, t) — per-BATCH-ROW absolute positions, the
    continuous-batching decode case where every cache slot sits at its own
    depth; ``x`` is then (b, h, t, d) and the angles broadcast over heads."""
    d = x.shape[-1]
    half = d // 2
    inv_freq = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., t, half)
    if positions.ndim == 2:
        ang = ang[:, None]                 # (b, 1, t, half): broadcast heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


class MultiHeadAttention(TensorModule):
    """Self-attention over (batch, seq, embed) inputs.

    ``attention_impl``: "auto" (ring iff the mesh has a ``seq`` axis, else the
    single-chip flash kernel with off-TPU fallback), "ring", "flash", or
    "full" (plain fused attention, the numerical oracle).

    ``head_dim`` (default ``embed_dim // num_heads``) may be set apart from
    the width: the projections are then ``embed -> heads * head_dim`` and back
    (32 heads of 128 over a width of 2048). ``qk_norm`` puts an RMSNorm with
    a gain of its own over each head's query and key before RoPE. ``mask`` is
    a static description the flash kernels take in ``causal``'s place
    (``kernels.flash_attention.BlockDiffusion``). ``window`` (with
    ``causal``) is sliding-window attention, a position sees the ``window``
    newest keys with its own: where the flash kernels are taken it reaches
    them as the description ``CausalWindow(window)`` and they walk the band's
    tiles alone; the ``"full"`` path writes the same mask out. The input may be
    ``(x, positions)``: RoPE then turns by those position ids, ``(t,)`` or
    ``(b, t)``, which may repeat (a sequence and its noised copy), in place
    of ``0 .. t-1``. On the flash path keys and values reach the kernel at
    their own head count.
    """

    @property
    def kv_heads(self) -> int:
        # pre-GQA pickles lack _kv_heads; they are plain MHA by construction
        kv = self.__dict__.get("_kv_heads")
        return kv if kv is not None else self.num_heads

    def __init__(self, embed_dim: int, num_heads: int, causal: bool = False,
                 with_bias: bool = True, attention_impl: str = "auto",
                 w_init: Optional[InitializationMethod] = None,
                 num_kv_heads: Optional[int] = None,
                 rope: bool = False, rope_base: float = 10000.0,
                 window: Optional[int] = None,
                 lora_rank: Optional[int] = None,
                 lora_alpha: Optional[float] = None,
                 head_dim: Optional[int] = None, qk_norm: bool = False,
                 qk_norm_eps: float = 1e-6, mask=None):
        super().__init__()
        if head_dim is None and embed_dim % num_heads != 0:
            raise ValueError(f"embed_dim {embed_dim} % num_heads {num_heads} != 0")
        if rope and (head_dim or embed_dim // num_heads) % 2 != 0:
            raise ValueError("rope needs an even head_dim")
        if mask is not None and (causal or window is not None):
            raise ValueError("mask stands in causal's place: give one of them")
        if window is not None:
            if not causal:
                raise ValueError("window (sliding-window attention) requires "
                                 "causal=True")
            if int(window) < 1:
                raise ValueError(f"window must be >= 1, got {window!r}")
            if attention_impl == "ring":
                raise ValueError(
                    "window is served by the masked single-device path; "
                    "it cannot honor attention_impl='ring' (sequence-"
                    "parallel banded attention is not implemented)")
        if attention_impl not in ("auto", "ring", "full", "flash"):
            raise ValueError(f"attention_impl must be auto|ring|full|flash, "
                             f"got {attention_impl!r}")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = int(head_dim or embed_dim // num_heads)
        self.qk_norm, self.qk_norm_eps = bool(qk_norm), float(qk_norm_eps)
        self.mask = mask
        # grouped-query attention (beyond reference): kv_heads < num_heads
        # shares each K/V head across a GROUP of query heads — the decode
        # KV cache (and its HBM bandwidth) shrinks by num_heads/kv_heads;
        # kv_heads=1 is multi-query attention
        if num_kv_heads is None:
            self._kv_heads = num_heads
        else:
            self._kv_heads = int(num_kv_heads)
            if self._kv_heads < 1 or num_heads % self._kv_heads != 0:
                raise ValueError(
                    f"num_kv_heads must be a positive divisor of num_heads "
                    f"{num_heads}, got {num_kv_heads!r}")
        self.causal = causal
        self.with_bias = with_bias
        self.attention_impl = attention_impl
        self.rope = bool(rope)
        self.rope_base = float(rope_base)
        # sliding-window attention (Mistral-style): each position attends to
        # the last `window` positions only — O(T·W) scores and a W-bounded
        # decode cache REACH (the cache itself stays max_len; the mask bounds
        # what the softmax sees). The flash kernels take it as a static mask
        # description and skip the tiles outside the band (`_attend`).
        self.window = None if window is None else int(window)
        if lora_rank is not None and int(lora_rank) < 1:
            raise ValueError(f"lora_rank must be >= 1, got {lora_rank!r}")
        self.lora_rank = None if lora_rank is None else int(lora_rank)
        self.lora_alpha = (float(lora_alpha) if lora_alpha is not None
                           else (float(lora_rank) if lora_rank else None))
        self.w_init = w_init or Xavier()
        self.reset()

    @property
    def inner_dim(self) -> int:
        """Width of the heads side by side: ``embed_dim`` unless ``head_dim``
        was set apart from it (older pickles have no such attribute)."""
        return self.num_heads * self.head_dim

    def reset(self) -> None:
        e, inner = self.embed_dim, self.inner_dim
        if self.kv_heads == self.num_heads and inner == e:
            # plain MHA keeps the fused-QKV parameter layout (existing
            # checkpoints/archives stay loadable)
            self._params = {
                "qkv_weight": jnp.asarray(
                    self.w_init.init((3 * e, e), fan_in=e, fan_out=3 * e)),
                "out_weight": jnp.asarray(
                    self.w_init.init((e, e), fan_in=e, fan_out=e)),
            }
            if self.with_bias:
                self._params["qkv_bias"] = jnp.zeros((3 * e,), jnp.float32)
                self._params["out_bias"] = jnp.zeros((e,), jnp.float32)
        else:
            kv = 2 * self.kv_heads * self.head_dim
            self._params = {
                "q_weight": jnp.asarray(
                    self.w_init.init((inner, e), fan_in=e, fan_out=inner)),
                "kv_weight": jnp.asarray(
                    self.w_init.init((kv, e), fan_in=e, fan_out=kv)),
                "out_weight": jnp.asarray(
                    self.w_init.init((e, inner), fan_in=inner, fan_out=e)),
            }
            if self.with_bias:
                self._params["q_bias"] = jnp.zeros((inner,), jnp.float32)
                self._params["kv_bias"] = jnp.zeros((kv,), jnp.float32)
                self._params["out_bias"] = jnp.zeros((e,), jnp.float32)
        if getattr(self, "qk_norm", False):
            self._params["q_norm"] = jnp.ones((self.head_dim,), jnp.float32)
            self._params["k_norm"] = jnp.ones((self.head_dim,), jnp.float32)
        if getattr(self, "lora_rank", None):
            self._extend_lora_params()   # adapters survive re-randomise
        self.zero_grad_parameters()

    def _expand_kv(self, x):
        """(b, kv_heads, t, d) → (b, num_heads, t, d): broadcast each KV head
        over its query group (XLA fuses the broadcast into the consumer)."""
        if self.kv_heads == self.num_heads:
            return x
        return jnp.repeat(x, self.num_heads // self.kv_heads, axis=1)

    # ----------------------------------------------------------------- LoRA
    def _extend_lora_params(self) -> None:
        from bigdl_tpu.nn.initialization import RandomNormal
        r = self.lora_rank
        for name in [k for k in self._params if k.endswith("_weight")]:
            out_d, in_d = self._params[name].shape
            self._params[f"lora_{name}_a"] = jnp.asarray(
                RandomNormal(0.0, 0.02).init((r, in_d), fan_in=in_d,
                                             fan_out=r))
            self._params[f"lora_{name}_b"] = jnp.zeros((out_d, r), jnp.float32)
        self.zero_grad_parameters()

    def _rebuild_init_args(self, set_keys=None, pop_keys=()):
        """Fluent-mutator bookkeeping: bind recorded positionals to names,
        apply overrides — the serializer rebuilds from these."""
        import inspect
        args, kwargs = self._init_args
        names = list(inspect.signature(type(self).__init__).parameters)[1:]
        merged = {**dict(zip(names, args)), **kwargs, **(set_keys or {})}
        for k in pop_keys:
            merged.pop(k, None)
        self._init_args = ((), merged)

    def add_lora(self, rank: int, alpha: Optional[float] = None
                 ) -> "MultiHeadAttention":
        """Attach rank-``rank`` LoRA adapters to every projection (qkv/out);
        base weights freeze (grad-scale 0), only the adapters train. Fluent
        mutator: also updates the recorded constructor args so the portable
        serializer rebuilds the adapted structure."""
        if self.lora_rank:
            raise ValueError("attention already has LoRA adapters")
        if int(rank) < 1:
            raise ValueError(f"rank must be >= 1, got {rank!r}")
        self.lora_rank = int(rank)
        self.lora_alpha = float(alpha) if alpha is not None else float(rank)
        self._extend_lora_params()
        self._rebuild_init_args({"lora_rank": self.lora_rank,
                                 "lora_alpha": self.lora_alpha})
        self._apply_cache = {}
        return self

    def merge_lora(self) -> "MultiHeadAttention":
        """Bake the adapters into the base projections and drop them."""
        if not self.lora_rank:
            raise ValueError("attention has no LoRA adapters to merge")
        p = self.get_params()
        scale = self.lora_alpha / self.lora_rank
        for name in [k for k in p if k.endswith("_weight")
                     and not k.startswith("lora_")]:
            a, b = p.pop(f"lora_{name}_a"), p.pop(f"lora_{name}_b")
            p[name] = p[name] + b @ a * scale
        self.set_params(p)
        self.zero_grad_parameters()   # drop the stale lora grad entries
        self.lora_rank = self.lora_alpha = None
        self._rebuild_init_args(pop_keys=("lora_rank", "lora_alpha"))
        self._apply_cache = {}
        return self

    def grad_scales(self) -> dict:
        if self.is_frozen():
            return {k: 0.0 for k in self._params}
        if getattr(self, "lora_rank", None):
            return {k: (self.scale_w if k.startswith("lora_") else 0.0)
                    for k in self._params}
        return super().grad_scales()

    def _w(self, params, name):
        """Effective projection weight: base, or base + BA·α/r under LoRA."""
        w = params[name]
        if getattr(self, "lora_rank", None):
            w = w + (params[f"lora_{name}_b"] @ params[f"lora_{name}_a"]
                     * (self.lora_alpha / self.lora_rank))
        return w

    def _attend(self, q, k, v):
        """``k`` and ``v`` at their own head count: the flash kernels read a
        group's shared head through their index maps; the other paths get
        them widened."""
        from bigdl_tpu.parallel.ring_attention import full_attention, ring_attention
        mask, window = getattr(self, "mask", None), getattr(self, "window", None)
        if window is not None:
            # the constructor refuses a window beside a mask, and under 'ring'
            from bigdl_tpu.kernels.flash_attention import CausalWindow
            mask = CausalWindow(window)

        def flash():
            from bigdl_tpu.kernels.flash_attention import flash_attention
            return flash_attention(q, k, v, self.causal, None, mask)

        def full():
            if mask is None:
                return full_attention(q, self._expand_kv(k), self._expand_kv(v),
                                      causal=self.causal)
            from bigdl_tpu.kernels.flash_attention import dense_mask
            return full_attention(q, self._expand_kv(k), self._expand_kv(v),
                                  kv_mask=dense_mask(mask, q.shape[2])[None, None])

        if self.attention_impl == "flash":
            return flash()
        if self.attention_impl == "full":
            return full()
        from bigdl_tpu.utils.engine import Engine
        mesh = Engine.mesh() if Engine.is_initialized() else None
        if mesh is None or Engine.SEQ_AXIS not in mesh.axis_names:
            if self.attention_impl == "ring":
                raise RuntimeError(
                    "attention_impl='ring' needs an Engine mesh with a "
                    f"'{Engine.SEQ_AXIS}' axis")
            # single chip: the flash kernel engages on TPU and degrades to the
            # plain fused attention elsewhere (kernels/flash_attention.py)
            return flash()
        if window is not None:
            return full()       # no banded ring attention: the mask written out
        if mask is not None:
            raise ValueError("ring attention takes no mask but causal")
        return ring_attention(q, self._expand_kv(k), self._expand_kv(v),
                              mesh=mesh, seq_axis=Engine.SEQ_AXIS,
                              causal=self.causal)

    def _head_norm(self, x, gain):
        """RMSNorm over each head's ``head_dim``, statistics in fp32."""
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.qk_norm_eps).astype(x.dtype) \
            * gain.astype(x.dtype)

    def _project_qkv(self, params, input, b, t):
        q, k, v = self._project(params, input, b, t)
        if getattr(self, "qk_norm", False):
            q = self._head_norm(q, params["q_norm"])
            k = self._head_norm(k, params["k_norm"])
        return q, k, v

    def _project(self, params, input, b, t):
        if "qkv_weight" in params:
            qkv = input @ self._w(params, "qkv_weight").T
            if self.with_bias:
                qkv = qkv + params["qkv_bias"]
            qkv = qkv.reshape(b, t, 3, self.num_heads, self.head_dim)
            q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
            return q, k, v                                     # all (b,h,t,d)
        q = input @ self._w(params, "q_weight").T
        kv = input @ self._w(params, "kv_weight").T
        if self.with_bias:
            q = q + params["q_bias"]
            kv = kv + params["kv_bias"]
        q = q.reshape(b, t, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
        kv = kv.reshape(b, t, 2, self.kv_heads, self.head_dim)
        k, v = (kv[:, :, i].transpose(0, 2, 1, 3) for i in range(2))
        return q, k, v                       # q (b,h,t,d); k,v (b,kv_h,t,d)

    def apply(self, params, state, input, *, training=False, rng=None):
        positions = None
        if isinstance(input, (tuple, list)):
            input, positions = input
        b, t, _ = input.shape
        e = self.inner_dim
        q, k, v = self._project_qkv(params, input, b, t)
        if isinstance(state, dict) and "page_k" in state:
            return self._paged_decode_step(params, state, q, k, v, b, t, e)
        if isinstance(state, dict) and "cache_k" in state:
            return self._decode_step(params, state, q, k, v, b, t, e)
        if getattr(self, "rope", False):
            pos = jnp.arange(t) if positions is None else positions
            q = rope_rotate(q, pos, self.rope_base)
            k = rope_rotate(k, pos, self.rope_base)
        o = self._attend(q, k, v)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, e)
        out = o @ self._w(params, "out_weight").T
        if self.with_bias:
            out = out + params["out_bias"]
        return out, state

    def _decode_step(self, params, state, q, k, v, b, t, e):
        """KV-cached incremental decode (``nn.incremental.install_decode_cache``
        puts the cache in this module's state; containers thread it through
        unchanged APIs). Input is the next ``t`` positions (t == 1 for the
        classic token-by-token decode; t > 1 is the CHUNKED prefill the
        serving engine uses to absorb a whole prompt in one program): append
        k/v at ``pos``, attend each query against the cached prefix up to its
        own position — O(L) per token instead of the O(L^2) full-prefix
        re-run. ``pos`` is a scalar for lock-step batches, or a PER-ROW (b,)
        vector for continuous batching where every cache slot sits at its own
        depth (the serving engine's slot-recycled decode batch). The
        reference SequenceBeamSearch's numHiddenLayers/hiddenSize constructor
        args exist for exactly this cache; here it is module state, not a
        search-owned buffer."""
        from jax import lax

        from bigdl_tpu.parallel.ring_attention import full_attention

        pos = state["pos"]
        per_slot = pos.ndim == 1
        if getattr(self, "rope", False):
            # rotate the new positions by their ABSOLUTE indices; cached
            # keys were already rotated when they were written. Per-slot,
            # every row rotates by its own depth.
            if per_slot:
                ppos = pos[:, None] + jnp.arange(t)[None, :]        # (b, t)
            else:
                ppos = pos + jnp.arange(t)                          # (t,)
            q = rope_rotate(q, ppos, self.rope_base)
            k = rope_rotate(k, ppos, self.rope_base)
        # cache persists at kv_heads width — the GQA memory win; heads are
        # broadcast per step only inside the fused attend
        if per_slot:
            # every row writes its chunk at its OWN position: one vmapped
            # dynamic_update_slice instead of a batch-wide slice
            row_write = jax.vmap(
                lambda c, u, p: lax.dynamic_update_slice(c, u, (0, p, 0)))
            ck = row_write(state["cache_k"], k, pos)
            cv = row_write(state["cache_v"], v, pos)
        else:
            ck = lax.dynamic_update_slice(state["cache_k"], k, (0, 0, pos, 0))
            cv = lax.dynamic_update_slice(state["cache_v"], v, (0, 0, pos, 0))
        lmax = ck.shape[2]
        # query j (absolute position pos+j) sees keys <= pos+j: causal within
        # the chunk, full visibility of the cached prefix
        kpos = jnp.arange(lmax)
        if per_slot:
            qpos = pos[:, None] + jnp.arange(t)[None, :]            # (b, t)
            kv_mask = kpos[None, None, :] <= qpos[:, :, None]       # (b, t, L)
            if getattr(self, "window", None) is not None:
                kv_mask &= kpos[None, None, :] > qpos[:, :, None] - self.window
            kv_mask = kv_mask[:, None]                              # (b,1,t,L)
        else:
            qpos = pos + jnp.arange(t)                              # (t,)
            kv_mask = kpos[None, :] <= qpos[:, None]                # (t, L)
            if getattr(self, "window", None) is not None:
                kv_mask &= kpos[None, :] > qpos[:, None] - self.window
            kv_mask = kv_mask[None, None]                           # (1,1,t,L)
        o = full_attention(q, self._expand_kv(ck), self._expand_kv(cv),
                           causal=False, kv_mask=kv_mask)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, e)
        out = o @ self._w(params, "out_weight").T
        if self.with_bias:
            out = out + params["out_bias"]
        return out, {"cache_k": ck, "cache_v": cv, "pos": pos + t}

    def _paged_decode_step(self, params, state, q, k, v, b, t, e):
        """Paged KV-cached decode (``serving/paged_cache.py`` puts the page
        pool in this module's state): write the new K/V THROUGH the page
        table (physical page ``table[row, pos // page_tokens]``, offset
        ``pos % page_tokens``), then gather the pool back into the SAME
        ``(b, kv_heads, max_len, head_dim)`` logical view the slot grid
        holds — a static-shape gather by page index, so the attention math
        (RoPE by absolute position, position mask, fused attend) is the
        per-slot ``_decode_step``'s verbatim and the emitted tokens stay
        bitwise-identical to the unpaged engine.

        ``t == 1`` is the classic token-by-token decode; ``t > 1`` is the
        speculative VERIFY chunk (k drafted tokens + 1), written through
        the table one position at a time with a vectorized (b, t) scatter
        — its start clamps to ``max_len - t`` exactly like the slot grid's
        ``dynamic_update_slice``, so a rewound row re-writes the same
        physical offsets and the spec acceptance stays bitwise the
        target's. Prompts still prefill on the CONTIGUOUS batch-1 cache
        and are scattered in page-granularly by ``assign_cache_pages`` — a
        ragged multi-page prefill through the table would cost a second
        program shape.

        Free rows riding the static decode batch have table rows pointing
        at the reserved trash page (physical 0): their writes land where
        nobody attends, and a long-idle row's drifting ``pos`` clamps onto
        its LAST table entry — trash again. Unallocated logical pages
        gather finite junk that the ``kpos <= pos`` mask weights to exactly
        0.0."""
        from bigdl_tpu.parallel.ring_attention import full_attention

        pos = state["pos"]
        if pos.ndim != 1:
            raise ValueError(
                "paged decode cache requires per-slot positions "
                "(install_paged_cache installs them)")
        table = state["page_table"]                     # (b, W) int32
        pk, pv = state["page_k"], state["page_v"]
        ptok = pk.shape[2]
        w = table.shape[1]
        lmax = w * ptok
        if getattr(self, "rope", False):
            ppos = pos[:, None] + jnp.arange(t)[None, :]        # (b, t)
            q = rope_rotate(q, ppos, self.rope_base)
            k = rope_rotate(k, ppos, self.rope_base)
        if t == 1:
            lp = jnp.clip(pos // ptok, 0, w - 1)        # logical page (b,)
            off = pos % ptok                            # in-page offset (b,)
            phys = jnp.take_along_axis(table, lp[:, None], axis=1)[:, 0]
            pk = pk.at[phys, :, off, :].set(k[:, :, 0, :])
            pv = pv.at[phys, :, off, :].set(v[:, :, 0, :])
        else:
            # verify chunk: t per-position writes, start clamped so the
            # window stays in-bounds (the slot grid's update-slice clamp)
            wpos = (jnp.clip(pos, 0, lmax - t)[:, None]
                    + jnp.arange(t)[None, :])           # (b, t) absolute
            lp = wpos // ptok                           # (b, t) logical page
            off = wpos % ptok                           # (b, t) offset
            phys = jnp.take_along_axis(table, lp, axis=1)   # (b, t) physical
            pk = pk.at[phys, :, off, :].set(k.transpose(0, 2, 1, 3))
            pv = pv.at[phys, :, off, :].set(v.transpose(0, 2, 1, 3))
        # static-shape gather: (b, W, kv_h, ptok, hd) → the slot-grid view
        ck = pk[table].transpose(0, 2, 1, 3, 4).reshape(
            b, pk.shape[1], lmax, pk.shape[3])
        cv = pv[table].transpose(0, 2, 1, 3, 4).reshape(
            b, pv.shape[1], lmax, pv.shape[3])
        kpos = jnp.arange(lmax)
        qpos = pos[:, None] + jnp.arange(t)[None, :]            # (b, t)
        kv_mask = kpos[None, None, :] <= qpos[:, :, None]       # (b, t, L)
        if getattr(self, "window", None) is not None:
            kv_mask &= kpos[None, None, :] > qpos[:, :, None] - self.window
        kv_mask = kv_mask[:, None]                              # (b,1,t,L)
        o = full_attention(q, self._expand_kv(ck), self._expand_kv(cv),
                           causal=False, kv_mask=kv_mask)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, e)
        out = o @ self._w(params, "out_weight").T
        if self.with_bias:
            out = out + params["out_bias"]
        return out, {"page_k": pk, "page_v": pv, "page_table": table,
                     "pos": pos + t}

    def __repr__(self):
        gqa = (f", kv_heads={self.kv_heads}"
               if self.kv_heads != self.num_heads else "")
        return (f"MultiHeadAttention(embed={self.embed_dim}, heads={self.num_heads}"
                f"{gqa}, causal={self.causal}, impl={self.attention_impl})")


class CrossAttention(TensorModule):
    """Encoder-decoder attention: queries from the first Table element,
    keys/values from the second (the memory).

    Input ``T(x, memory)`` with x (N, Tq, E), memory (N, Tk, E) → (N, Tq, E).
    The reference's ``Attention`` layer covers this case in its transformer
    (SURVEY.md §2.1 tail; expected upstream ``<dl>/nn/Attention.scala`` —
    unverified, mount empty). Routed through the plain fused attention path:
    cross-attention is never causal and Tq ≠ Tk, which is where the fused
    jnp form is already the right TPU program (one (Tq,Tk) einsum chain,
    fused by XLA — the flash kernel's streaming-softmax trick buys nothing
    at parity-scale memory lengths)."""

    def __init__(self, embed_dim: int, num_heads: int, with_bias: bool = True,
                 w_init: Optional[InitializationMethod] = None):
        super().__init__()
        if embed_dim % num_heads != 0:
            raise ValueError(f"embed_dim {embed_dim} % num_heads {num_heads} != 0")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.with_bias = with_bias
        self.w_init = w_init or Xavier()
        self.reset()

    def reset(self) -> None:
        e = self.embed_dim
        self._params = {
            "q_weight": jnp.asarray(self.w_init.init((e, e), fan_in=e, fan_out=e)),
            "kv_weight": jnp.asarray(
                self.w_init.init((2 * e, e), fan_in=e, fan_out=2 * e)),
            "out_weight": jnp.asarray(
                self.w_init.init((e, e), fan_in=e, fan_out=e)),
        }
        if self.with_bias:
            self._params["q_bias"] = jnp.zeros((e,), jnp.float32)
            self._params["kv_bias"] = jnp.zeros((2 * e,), jnp.float32)
            self._params["out_bias"] = jnp.zeros((e,), jnp.float32)
        self.zero_grad_parameters()

    def apply(self, params, state, input, *, training=False, rng=None):
        from bigdl_tpu.parallel.ring_attention import full_attention

        x, memory = input[1], input[2]
        b, tq, e = x.shape
        tk = memory.shape[1]
        h, d = self.num_heads, self.head_dim
        q = x @ params["q_weight"].T
        kv = memory @ params["kv_weight"].T
        if self.with_bias:
            q = q + params["q_bias"]
            kv = kv + params["kv_bias"]
        q = q.reshape(b, tq, h, d).transpose(0, 2, 1, 3)
        kv = kv.reshape(b, tk, 2, h, d)
        k, v = (kv[:, :, i].transpose(0, 2, 1, 3) for i in range(2))
        o = full_attention(q, k, v, causal=False)
        o = o.transpose(0, 2, 1, 3).reshape(b, tq, e)
        out = o @ params["out_weight"].T
        if self.with_bias:
            out = out + params["out_bias"]
        return out, state

    def __repr__(self):
        return f"CrossAttention(embed={self.embed_dim}, heads={self.num_heads})"
