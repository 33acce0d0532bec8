"""Beam-search decoding for the TransformerLM family.

EXTENSION BEYOND THE REFERENCE (whose inference surface is
``model.predict`` — SURVEY.md §2.5; no decoding algorithms of any kind).
Completes the framework's decoding inventory next to greedy/top-k/top-p
``generate``, speculative decoding, and sharded generation.

TPU-first shape: the ``beam_size`` axis is folded into the batch
(``B·K`` rows through the SAME cached :meth:`decode_step` every other
decode path uses — one compiled program, MXU-batched across beams), and
the whole search runs inside one ``lax.scan``:

- scores live as summed log-probs ``[B·K]`` (f32);
- each step ranks the ``K·V`` candidates per sequence with one
  ``lax.top_k`` and reindexes beams with a batched gather — the KV cache
  rows travel WITH their beams (``jnp.take`` on the cache's batch axis;
  HBM-bandwidth-bound, the standard beam-search cost);
- finished beams (``eos_id``) are frozen by giving them a single
  zero-cost continuation (the eos token itself), the standard trick that
  keeps the scan body static-shaped.

First-step subtlety: the K initial beams per sequence must be the top-K
DISTINCT tokens of the prefill logits — seeding K identical beams would
make every later top-K pick K copies of one continuation.

Length normalization: ``length_penalty`` α rescales final scores by
``len^{-α}`` (len = generated tokens through each beam's eos). α=0 (the
default) ranks by raw joint log-prob.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .transformer import TransformerLM


def generate_beam(model: TransformerLM, params, prompt, n_new: int,
                  beam_size: int = 4, eos_id: Optional[int] = None,
                  length_penalty: float = 0.0):
    """Beam-search continuation: ``prompt [B, T0]`` int →
    ``(sequences [B, T0+n_new] int32, scores [B] f32)``.

    ``scores`` are the selected beams' summed next-token log-probs
    (length-normalized iff ``length_penalty > 0``). ``beam_size=1``
    reproduces greedy :meth:`TransformerLM.generate` exactly. With
    ``eos_id``, a beam that emits it is frozen — its later positions
    repeat ``eos_id`` and its score stops accumulating.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    B, T0 = prompt.shape
    K = int(beam_size)
    if K < 1:
        raise ValueError(f"beam_size must be >= 1, got {K}")
    if K > model.vocab:
        raise ValueError(
            f"beam_size {K} exceeds vocab {model.vocab} (fewer than K "
            "distinct first tokens exist)"
        )
    total = T0 + int(n_new)
    if total > model.max_len:
        raise ValueError(
            f"prompt {T0} + n_new {n_new} exceeds max_len {model.max_len}"
        )
    if n_new < 1:
        return prompt, jnp.zeros((B,), jnp.float32)
    # One compiled program for the whole search (prefill + scan): an
    # eager lax.scan dispatches per construct instead of once.
    return _beam_rollout(model, params, prompt, int(n_new), K,
                         None if eos_id is None else int(eos_id),
                         float(length_penalty))


@partial(jax.jit, static_argnames=("model", "n_new", "K", "eos_id",
                                   "length_penalty"))
def _beam_rollout(model, params, prompt, n_new: int, K: int, eos_id,
                  length_penalty: float):
    B, T0 = prompt.shape
    total = T0 + n_new

    # Prefill once on the B prompt rows, then tile each row's cache to its
    # K beams (cheaper than prefilling B·K identical rows).
    logits, cache0 = model.prefill(params, prompt, model.init_cache(B, total))
    cache = {k: jnp.repeat(v, K, axis=1) for k, v in cache0.items()}

    logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))  # [B, V]
    top_lp, top_tok = jax.lax.top_k(logp0, K)                      # [B, K]
    scores = top_lp.reshape(B * K)
    first = top_tok.reshape(B * K).astype(jnp.int32)
    buf = jnp.zeros((B * K, total), jnp.int32)
    buf = jax.lax.dynamic_update_slice(
        buf, jnp.repeat(prompt, K, axis=0), (0, 0))
    buf = buf.at[:, T0].set(first)
    finished = (first == eos_id) if eos_id is not None else \
        jnp.zeros((B * K,), bool)
    lengths = jnp.ones((B * K,), jnp.int32)  # generated tokens incl. eos
    V = model.vocab
    rows = jnp.arange(B)[:, None] * K                              # [B, 1]

    def step(carry, t):
        buf, cache, scores, finished, lengths, token = carry
        logits, cache = model.decode_step(params, token, t, cache)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))  # [B·K, V]
        if eos_id is not None:
            # frozen beams: exactly one candidate (eos again) at zero cost
            frozen = jnp.full((V,), -jnp.inf).at[int(eos_id)].set(0.0)
            lp = jnp.where(finished[:, None], frozen[None, :], lp)
        cand = (scores[:, None] + lp).reshape(B, K * V)
        new_scores, flat = jax.lax.top_k(cand, K)            # [B, K]
        parent = rows + flat // V                            # global row ix
        tok = (flat % V).astype(jnp.int32)
        gparent = parent.reshape(B * K)
        # beams move: their cache rows, output buffers, and flags go along
        cache = {k: jnp.take(v, gparent, axis=1) for k, v in cache.items()}
        buf = jnp.take(buf, gparent, axis=0)
        token = tok.reshape(B * K)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, token[:, None], t + 1, axis=1)
        finished = jnp.take(finished, gparent, axis=0)
        lengths = jnp.take(lengths, gparent, axis=0) + \
            (~finished).astype(jnp.int32)
        if eos_id is not None:
            finished |= token == eos_id
        return (buf, cache, new_scores.reshape(B * K), finished, lengths,
                token), None

    (buf, _, scores, _, lengths, _), _ = jax.lax.scan(
        step, (buf, cache, scores, finished, lengths, first),
        jnp.arange(T0, total - 1),
    )
    ranked = scores
    if length_penalty:
        ranked = scores / (lengths.astype(jnp.float32) **
                           float(length_penalty))
    best = jnp.argmax(ranked.reshape(B, K), axis=1)
    pick = jnp.arange(B) * K + best
    return jnp.take(buf, pick, axis=0), jnp.take(ranked, pick, axis=0)
