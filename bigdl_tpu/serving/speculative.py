"""Greedy speculative decoding: draft proposes, target verifies in ONE chunk.

Plain greedy decode pays one full target-model forward per token. A small
draft model can guess the next ``k`` tokens cheaply; the target then checks
all ``k`` guesses in a SINGLE chunked forward — the same t>1
last-position-logits shape the engine's bucketed prefill already compiles —
and keeps the longest correct prefix. Output is token-identical to plain
greedy at ANY acceptance rate, because every emitted token is either a
proposal the target's own argmax agreed with, or the target's argmax itself:

- **Propose**: feed the draft ``cur, d1, …, dk`` (k+1 single-token steps;
  the last output is discarded) so its cache ends holding every token a
  full accept would need — the rewind below is then valid at any ``j``.
- **Verify**: the target runs the chunk ``[cur, d1 … dk]`` as one t=k+1
  cached forward. Position ``i``'s argmax ``g_i`` is the greedy token after
  ``… cur d1 … d_i`` — the chunked-prefill == full-forward invariant
  (PR 7) IS the verify step; no second program shape exists.
- **Accept**: ``j`` = leading positions where ``g_i == d_{i+1}``. Emit
  ``d1 … d_j`` plus the CORRECTION ``g_j`` — always 1..k+1 tokens per
  round, never zero (the correction is exactly what plain greedy would
  have emitted, so a 0%-acceptance draft degrades to plain decode plus
  overhead, never to wrong tokens).
- **Rewind**: both caches advanced k+1 rows; the accepted depth is
  ``1 + j``, so every position leaf steps back by ``k - j`` — computed
  in-program per row (``_CACHE_POS_KEYS`` are per-slot vectors), so rows of
  a continuous batch accept independently inside one compiled program.

:func:`build_spec_step` / :func:`build_spec_prefill` are the program
builders; :class:`ServingEngine` fuses them into its bucket grid (the
``compiled_programs`` ledger stays ``len(buckets) + 2`` with speculation
on), and :class:`SpeculativeDecoder` is the standalone offline form pinned
bitwise against ``nn.greedy_generate`` by ``tests/test_fleet.py``.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np


def _env_spec_tokens(default: int = 4) -> int:
    return int(os.environ.get("BIGDL_SPEC_TOKENS", default))


def build_spec_prefill(model, draft):
    """Fused context prefill: one target forward (greedy next-token at every
    position + finiteness) and one draft forward to fill ITS cache from the
    same tokens. Returns ``run(params, params_d, state, state_d, tokens) →
    (next_all (N, L) int32, ok scalar, state, state_d)``."""
    import jax.numpy as jnp

    def run(params, params_d, state, state_d, tokens):
        logits, st = model.apply(params, state, tokens,
                                 training=False, rng=None)
        _, st_d = draft.apply(params_d, state_d, tokens,
                              training=False, rng=None)
        ok = jnp.isfinite(logits).all()
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                ok, st, st_d)

    return run


def build_spec_step(model, draft, k: int):
    """One draft-propose / chunk-verify / accept / rewind round over a
    per-slot batch. Returns ``run(params, params_d, state, state_d,
    tok (S,)) → (props (S, k), greedy (S, k+1), n_acc (S,), ok (S,),
    state, state_d)`` where row ``r`` emits ``props[r, :n_acc[r]]`` followed
    by the correction ``greedy[r, n_acc[r]]``, and both returned states are
    already rewound to the accepted depth."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.nn.incremental import _CACHE_POS_KEYS, _leaf_key

    if k < 1:
        raise ValueError(f"spec_tokens must be >= 1, got {k}")

    def run(params, params_d, state, state_d, tok):
        # draft: k+1 single-token steps (cur, d1, …, dk) so the draft cache
        # holds every token a full accept keeps; last proposal is discarded
        def dstep(carry, _):
            st_d, t = carry
            logits, st_d = draft.apply(params_d, st_d, t[:, None],
                                       training=False, rng=None)
            nt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
            return (st_d, nt), nt

        (st_d, _), props_all = lax.scan(
            dstep, (state_d, tok), None, length=k + 1)
        props = jnp.transpose(props_all)[:, :k]            # (S, k)

        # target: verify the whole chunk in ONE t=k+1 cached forward
        chunk = jnp.concatenate([tok[:, None], props], axis=1)  # (S, k+1)
        logits, st = model.apply(params, state, chunk,
                                 training=False, rng=None)
        ok = jnp.isfinite(logits).all(axis=(1, 2))          # (S,)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (S, k+1)

        # accept the longest prefix the target agrees with, then rewind
        # both caches from depth +k+1 to the accepted depth +1+j
        match = (greedy[:, :k] == props).astype(jnp.int32)
        n_acc = jnp.cumprod(match, axis=1).sum(axis=1)      # (S,) in [0, k]
        back = (k - n_acc).astype(jnp.int32)

        def rewind(s):
            def g(path, leaf):
                if _leaf_key(path) in _CACHE_POS_KEYS:
                    return leaf - back
                return leaf
            return jax.tree_util.tree_map_with_path(g, s)

        return props, greedy, n_acc, ok, rewind(st), rewind(st_d)

    return run


class SpeculativeDecoder:
    """Standalone (offline) speculative greedy decode over a batch of
    same-length prompts — the engine-free form (tests use it).

    ``model`` is the served target, ``draft`` the proposer (any
    cached-decode-capable causal LM over the same vocabulary; a smaller/
    shallower one is the point). ``spec_tokens`` is k, the proposals per
    round (BIGDL_SPEC_TOKENS, default 4). Programs are cached on the TARGET
    model's ``_apply_cache`` keyed by shape + draft identity, like every
    other decode program."""

    def __init__(self, model, draft, spec_tokens: Optional[int] = None,
                 dtype=None):
        import jax.numpy as jnp

        if draft is model:
            pass   # allowed: pins acceptance at ~100% (tests)
        if spec_tokens is None:
            spec_tokens = _env_spec_tokens()
        if spec_tokens < 1:
            raise ValueError(
                f"spec_tokens must be >= 1, got {spec_tokens}")
        self._model = model
        self._draft = draft
        self.spec_tokens = int(spec_tokens)
        self._dtype = jnp.float32 if dtype is None else dtype
        self.proposed = 0
        self.accepted = 0
        self.rounds = 0

    def stats(self) -> dict:
        rate = (self.accepted / self.proposed) if self.proposed else 0.0
        return {"spec_tokens": self.spec_tokens, "rounds": self.rounds,
                "proposed": self.proposed, "accepted": self.accepted,
                "acceptance_rate": round(rate, 4)}

    def generate(self, prompt, decode_length: int,
                 eos_id: Optional[int] = None) -> np.ndarray:
        """``prompt`` (N, T0) int32 → (N, T0 + decode_length) int32,
        token-identical to ``nn.greedy_generate``. With ``eos_id``, a row
        stops after emitting it and pads the remainder with 0."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu import nn

        model, draft, k = self._model, self._draft, self.spec_tokens
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None, :]
        n, t0 = prompt.shape
        if decode_length < 1:
            raise ValueError(
                f"decode_length must be >= 1, got {decode_length}")
        # a round may start at depth t0 + decode_length - 1 and write k+1
        # rows; dynamic_update_slice clamps on overflow, so headroom is a
        # correctness requirement, not an optimization
        total = t0 + decode_length + k
        dname = jnp.dtype(self._dtype).name

        params = model.get_params()
        params_d = draft.get_params()
        st = nn.install_decode_cache(model, n, total, dtype=self._dtype,
                                     per_slot=True)
        nn.clear_decode_cache(model)
        st_d = nn.install_decode_cache(draft, n, total, dtype=self._dtype,
                                       per_slot=True)
        nn.clear_decode_cache(draft)

        pkey = ("spec_prefill", id(draft), n, t0, total, dname)
        fn_pre = model._apply_cache.get(pkey)
        if fn_pre is None:
            fn_pre = jax.jit(build_spec_prefill(model, draft))
            model._apply_cache[pkey] = fn_pre
        skey = ("spec_step", id(draft), n, total, k, dname)
        fn_step = model._apply_cache.get(skey)
        if fn_step is None:
            fn_step = jax.jit(build_spec_step(model, draft, k))
            model._apply_cache[skey] = fn_step

        next_all, ok, st, st_d = fn_pre(params, params_d, st, st_d,
                                        jnp.asarray(prompt))
        if not bool(np.asarray(ok)):
            raise FloatingPointError(
                "non-finite logits in speculative prefill")
        cur = np.asarray(next_all)[:, t0 - 1].copy()       # (N,)

        out = [[int(cur[r])] for r in range(n)]
        done = [eos_id is not None and int(cur[r]) == eos_id
                or decode_length == 1 for r in range(n)]
        while not all(done):
            props, greedy, n_acc, ok, st, st_d = fn_step(
                params, params_d, st, st_d, jnp.asarray(cur))
            props = np.asarray(props)
            greedy = np.asarray(greedy)
            n_acc = np.asarray(n_acc)
            ok = np.asarray(ok)
            self.rounds += 1
            for r in range(n):
                if done[r]:
                    continue
                if not bool(ok[r]):
                    raise FloatingPointError(
                        f"non-finite logits in speculative round, row {r}")
                j = int(n_acc[r])
                self.proposed += k
                self.accepted += j
                emitted = [int(props[r, i]) for i in range(j)]
                emitted.append(int(greedy[r, j]))
                for t in emitted:
                    out[r].append(t)
                    if (eos_id is not None and t == eos_id) \
                            or len(out[r]) >= decode_length:
                        done[r] = True
                        break
                if not done[r]:
                    cur[r] = out[r][-1]
        seqs = np.zeros((n, t0 + decode_length), np.int32)
        seqs[:, :t0] = prompt
        for r in range(n):
            gen = out[r][:decode_length]
            seqs[r, t0:t0 + len(gen)] = gen
        return seqs
