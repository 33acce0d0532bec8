"""A functional causal-transformer LM with sequence-parallel training.

EXTENSION BEYOND THE REFERENCE. The reference's largest sequence model is a
whole-sequence-per-worker IMDB LSTM (SURVEY.md §5.7: long-context support
"entirely absent"); this module is the model family that makes the
framework's long-context machinery (``ops/ring_attention.py``,
``ops/ulysses.py``) usable end-to-end: a GPT-style decoder-only LM whose
training step shards the BATCH over the ``"data"`` mesh axis and the
SEQUENCE over a ``"seq"`` axis in ONE ``shard_map`` program — maximum
context length scales linearly with the seq-axis size, attention stays
exact, and the whole dp×sp step is a single XLA executable.

Design notes (TPU-first):

- The model is a pure function over a flat dict of named arrays (layer
  stacks carry a leading ``[L, ...]`` axis) — no framework objects cross the
  jit boundary, and the same ``apply`` serves the sharded step and the
  single-device oracle (``seq_axis=None``).
- Attention is pluggable per call: dense reference (oracle), ring
  (``ppermute`` KV rotation — few-head friendly, P nearest-neighbor hops),
  or Ulysses (two ``all_to_all``s — needs ``H % P == 0``). Positions are
  absolute (derived from the shard's seq-axis rank), so causal masking is
  exact across shard boundaries.
- Targets are supplied pre-shifted by the host (``make_lm_batches``), so no
  cross-shard halo exchange is needed for the next-token objective.
- Params/optimizer state replicate over both axes; gradients ride one
  two-axis ``psum``. (Compose with ``parallel/fsdp.py`` to shard state —
  the apply function is already the form ``build_fsdp_train_step`` takes.)
"""

from __future__ import annotations

import collections
import contextlib
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.flash_attention import flash_attention
from ..ops.flash_decode import (_block_t, aligned_cache_length,
                                cache_write_row, decode_attention,
                                latent_block_t, latent_decode_attention,
                                latent_write_row)
from ..ops.gated_delta import (conv_chunk, conv_step, gdn_chunk,
                                gdn_decode_update, l2_normalize, pack_state,
                                state_group, unpack_state)
from ..ops.paged_attention import paged_chunk_attention, paged_decode_attention
from ..ops.pallas_ops import _LANE, _pad_up, is_tpu_backend
from ..ops.ring_attention import attention_reference, ring_attention_local
from ..ops.ulysses import ulysses_attention_local
from ..parallel.expert import EXPERT_STACKS
from ..parallel.mesh import DATA_AXIS, build_mesh_2axis
from ..parallel.param_utils import glorot, make_opt_init, shard_by_specs

SEQ_AXIS = "seq"


def build_mesh_sp(data: Optional[int] = None, seq: int = 1, devices=None) -> Mesh:
    """A 2-D ``("data", "seq")`` mesh; ``seq`` = sequence-parallel degree."""
    return build_mesh_2axis(SEQ_AXIS, data=data, second=seq, devices=devices)


def select_tokens(logits, key, temperature: float = 0.0,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None, row_offset=0):
    """The generation sampling rule shared by the plain and sharded decode
    paths (speculative decoding samples host-side against its acceptance
    test — see ``generate_speculative``): greedy at ``temperature<=0``;
    otherwise sample
    ``softmax(logits/temperature)`` restricted by top-k then nucleus
    ``top_p`` (the most-probable token always survives). ``logits`` is
    ``[B, V]``; returns ``[B]`` int32.

    Each row draws from its own key, ``fold_in(key, row_offset + i)`` —
    NOT from one batched draw — so a batch sharded over a mesh axis
    samples the identical tokens the gathered batch would
    (``row_offset`` = the shard's first global row; see
    models/sharded_generate.py)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, int(top_k))[0][:, -1:]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    if top_p is not None and float(top_p) < 1.0:
        logits = jnp.where(
            nucleus_mask(logits, float(top_p)), logits, -jnp.inf
        )
    rows = row_offset + jnp.arange(logits.shape[0])
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(rows)
    return jax.vmap(
        lambda k, l: jax.random.categorical(k, l)
    )(keys, logits).astype(jnp.int32)


def nucleus_mask(logits, top_p: float):
    """Boolean keep-mask of the top-p nucleus, per row of ``[B, V]`` logits.

    The nucleus is the smallest prefix of the probability-sorted vocabulary
    whose mass reaches ``top_p``; a token is kept iff the cumulative
    probability BEFORE it is still < ``top_p`` (so the argmax always
    survives). The mask is scattered back through the sort permutation —
    NOT applied as a value threshold — so a boundary logit's duplicates
    outside the prefix are cut by RANK; a value threshold would admit every
    tie and silently widen the nucleus.
    """
    sort_ix = jnp.argsort(logits, axis=-1)[:, ::-1]
    sorted_logits = jnp.take_along_axis(logits, sort_ix, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    keep = cum_before < float(top_p)
    rows = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros(logits.shape, bool).at[rows, sort_ix].set(keep)


@jax.named_scope("sample")
def select_slot_tokens(logits, out_pos, temps, keys):
    """Per-SLOT token selection for the serving engine: row ``i`` of
    ``logits`` ``[S, V]`` is greedy iff ``temps[i] <= 0`` (matching
    :func:`select_tokens`' convention), else sampled from
    ``softmax(logits_i / temps_i)`` with key ``fold_in(keys[i],
    out_pos[i])`` — ``out_pos`` is the absolute position the emitted token
    will occupy. Position-keyed folding makes a request's draw stream a
    function of ``(seed, position)`` alone: the same request produces the
    same tokens whatever slot it lands in and whatever else is co-batched,
    and the prefill's first token and every decode step share one rule.
    ``temps`` is TRACED (``[S]`` f32), not static — one compiled program
    serves any mix of greedy and sampled requests.

    The sampled branch sits behind a ``lax.cond`` on ``any(temps > 0)``:
    per-row threefry (``fold_in`` + ``categorical`` over V) is the single
    most expensive scalar-bound op in a small decode program, and an
    all-greedy batch — the common serving configuration, and every verify
    chunk position of one — must not pay for draws it discards. The cond
    predicate is unbatched, so the speculative verify's vmap over chunk
    positions keeps it a real branch, not a select of both sides. Outputs
    are bitwise unchanged: the taken branch IS the previous expression,
    and with every temp <= 0 the old ``where`` reduced to ``greedy``."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _mixed(_):
        scaled = (logits.astype(jnp.float32)
                  / jnp.maximum(temps, 1e-6)[:, None])
        sk = jax.vmap(jax.random.fold_in)(keys, out_pos)
        sampled = jax.vmap(jax.random.categorical)(sk, scaled)
        return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(temps > 0), _mixed, lambda _: greedy, None)


@jax.named_scope("loss")
def _summed_xent(logits, targets):
    """Summed next-token cross-entropy: ``-Σ (logit_at_target - logsumexp)``.

    The max/lse formulation instead of ``log_softmax`` + gather: the full
    ``[B, T, V]`` log-prob tensor is never materialized (two reductions and
    one gather over raw logits), which on TPU measured ~4× faster in the
    loss head at d_model 1024 / V 8k — CE is HBM-bound, not FLOPs-bound.
    """
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
    at = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - at)


def _xent_blocks(w, block: int):
    """Zero-pad ``w`` ``[D, V]`` to a multiple of ``block`` and reshape to
    per-block stacks ``[nc, D, block]`` for the chunked-loss scans. The
    scans mask the pad COLUMNS of each logits block (a pad weight column
    would give ``±huge`` logits, not ``-inf``)."""
    D, V = w.shape
    nc = -(-V // block)
    pad = nc * block - V
    if pad:
        w = jnp.concatenate([w, jnp.zeros((D, pad), w.dtype)], axis=1)
    return w.reshape(D, nc, block).transpose(1, 0, 2), nc, pad


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_summed_xent(h, w, targets, block: int = 8192):
    """:func:`_summed_xent` over ``logits = h @ w`` WITHOUT materializing
    ``[B, T, V]`` — the logits head streams in ``block``-column chunks.

    Forward: one ``lax.scan`` over vocab blocks accumulates the running
    max / scaled exp-sum (online logsumexp) and the logit at the target,
    so peak memory is ``[B, T, block]`` instead of ``[B, T, V]`` (~2 GB
    fwd+bwd at B4·T2048·V128k bf16 — the imported-checkpoint vocab sizes
    ``hf_import`` already handles). Backward recomputes each block's
    logits and emits ``(softmax − onehot) @ wᵀ`` contributions blockwise —
    the logits' cotangent never materializes either. Exact to float
    tolerance against :func:`_summed_xent` (online vs global lse differ
    only in summation order; pinned in tests).

    ``h`` ``[..., D]``, ``w`` ``[D, V]`` (pass ``params["tok"].T`` for tied
    embeddings — AD transposes the gradient back), integer ``targets``
    shaped like ``h``'s leading dims. Returns the SUMMED cross-entropy.
    """
    loss, _ = _chunked_xent_fwd(h, w, targets, block)
    return loss


@jax.named_scope("loss")
def _chunked_xent_fwd(h, w, targets, block: int):
    wb, nc, _ = _xent_blocks(w, block)
    V = w.shape[1]
    shape = targets.shape
    f32 = jnp.float32
    cols = jnp.arange(block)

    def body(carry, xs):
        m, s, at = carry
        wblk, off = xs
        # f32 accumulation regardless of backend matmul defaults — the
        # exactness-vs-dense-head contract must not drift with the
        # platform's bf16 pass count (same discipline as decode_chunk)
        logits = jnp.matmul(h, wblk,
                            preferred_element_type=f32)  # [..., block]
        logits = jnp.where(off + cols < V, logits, -jnp.inf)  # pad columns
        bm = jnp.max(logits, axis=-1)
        nm = jnp.maximum(m, bm)
        s = s * jnp.exp(m - nm) + jnp.sum(
            jnp.exp(logits - nm[..., None]), axis=-1)
        t_off = targets - off
        inb = (t_off >= 0) & (t_off < block)
        att = jnp.take_along_axis(
            logits, jnp.clip(t_off, 0, block - 1)[..., None], axis=-1
        )[..., 0]
        at = at + jnp.where(inb, att, 0.0)
        return (nm, s, at), None

    offsets = jnp.arange(nc, dtype=targets.dtype) * block
    init = (jnp.full(shape, -jnp.inf, f32), jnp.zeros(shape, f32),
            jnp.zeros(shape, f32))
    (m, s, at), _ = jax.lax.scan(body, init, (wb, offsets))
    lse = m + jnp.log(s)
    return jnp.sum(lse - at), (h, w, targets, lse)


@jax.named_scope("loss")
def _chunked_xent_bwd(block: int, res, g):
    h, w, targets, lse = res
    wb, nc, pad = _xent_blocks(w, block)
    f32 = jnp.float32

    cols = jnp.arange(block)

    def body(dh, xs):
        wblk, off = xs
        logits = jnp.matmul(h, wblk, preferred_element_type=f32)
        logits = jnp.where(off + cols < w.shape[1], logits, -jnp.inf)
        p = jnp.exp(logits - lse[..., None])
        t_off = targets - off
        onehot = (jnp.arange(block, dtype=targets.dtype)
                  == t_off[..., None]).astype(f32)
        q = p - onehot  # [..., block]; softmax − target indicator
        dh = dh + jnp.matmul(q, wblk.T.astype(f32),
                             preferred_element_type=f32)
        dwblk = jnp.einsum("...d,...v->dv", h.astype(f32), q,
                           preferred_element_type=f32)
        return dh, dwblk

    offsets = jnp.arange(nc, dtype=targets.dtype) * block
    dh, dwb = jax.lax.scan(body, jnp.zeros(h.shape, f32), (wb, offsets))
    dw = dwb.transpose(1, 0, 2).reshape(w.shape[0], -1)
    if pad:
        dw = dw[:, :w.shape[1]]
    return (g * dh).astype(h.dtype), (g * dw).astype(w.dtype), None


chunked_summed_xent.defvjp(
    lambda h, w, t, block: _chunked_xent_fwd(h, w, t, block),
    _chunked_xent_bwd,
)


@partial(jax.jit,
         static_argnames=("model", "n_new", "temperature", "top_k", "top_p"))
def _generate_rollout(model, params, prompt, key, n_new: int,
                      temperature: float, top_k, top_p):
    """``TransformerLM.generate``'s compiled body (static-cached on the
    model instance + decode geometry): batched prefill, then a
    ``lax.scan`` of KV-cached decode steps writing into the output
    buffer."""
    B, T0 = prompt.shape
    total = T0 + n_new

    def select(logits, key):
        return select_tokens(logits, key, temperature, top_k, top_p)

    key, k0 = jax.random.split(key)
    logits, cache = model.prefill(
        params, prompt, model.init_cache(B, total)
    )
    first = select(logits[:, -1], k0)
    buf = jnp.zeros((B, total), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
    buf = buf.at[:, T0].set(first)

    def step(carry, t):
        buf, cache, token, key = carry
        logits, cache = model.decode_step(params, token, t, cache)
        key, kt = jax.random.split(key)
        nxt = select(logits, kt)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, nxt[:, None], t + 1, axis=1
        )
        return (buf, cache, nxt, key), None

    (buf, _, _, _), _ = jax.lax.scan(
        step, (buf, cache, first, key), jnp.arange(T0, total - 1)
    )
    return buf


@partial(jax.jit, static_argnames=("model", "length", "chunk"))
def _prefill_jit(model, params, prompt, length: int, chunk: int):
    """Compiled prompt ingestion (cache allocation + prefill as one
    program; static-cached on the model instance + geometry)."""
    B = prompt.shape[0]
    return model.prefill(params, prompt,
                         model.init_cache(B, length, chunk=chunk))


def spec_round_accept(pt, pd_draft, d_toks, u):
    """One speculative round's acceptance math (the distribution-preserving
    rejection rule), as a pure traced function → ``(n, resid)``.

    ``pt`` ``[B, k+1, V]`` target probabilities over the verify chunk,
    ``pd_draft`` ``[B, k, V]`` the draft's proposal distributions,
    ``d_toks`` ``[B, k]`` the proposals, ``u`` ``[B, k]`` the acceptance
    uniforms. Proposal ``i`` is accepted while ``u_i < min(1,
    p_t(d_i)/p_d(d_i))``; ``n`` is the accepted-prefix length and ``resid``
    the distribution the correction token must be drawn from: the clamped
    normalized residual ``(p_t − p_d)+`` at the first rejection, or —
    expressed uniformly by padding ``pd`` with a zero row at index ``k`` so
    the residual at the bonus slot IS ``p_t`` — the target's own
    distribution after a fully-accepted round.

    Split out of :func:`_spec_rollout_device` so the exact closed-form
    emission-distribution test (``tests/models/test_speculative.py``) can
    marginalize the uniforms and the residual resample analytically against
    THE code the compiled rollout runs — a mutation of the residual clamp
    or the bonus-slot padding fails that test, not just a loose TV smoke.
    """
    B, spec_k = d_toks.shape
    pd = jnp.concatenate(
        [pd_draft, jnp.zeros((B, 1, pt.shape[-1]), jnp.float32)], axis=1)
    pt_d = jnp.take_along_axis(
        pt[:, :spec_k], d_toks[..., None], axis=-1)[..., 0]
    pd_d = jnp.take_along_axis(
        pd[:, :spec_k], d_toks[..., None], axis=-1)[..., 0]
    ratio = pt_d / jnp.maximum(pd_d, 1e-20)          # [B, spec_k]
    accept = (u < jnp.minimum(ratio, 1.0)).astype(jnp.int32)
    n = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)  # [B]
    # residual at the stop slot (p_t itself at the bonus slot — pd's zero
    # padding row makes the formula uniform)
    ptn = jnp.take_along_axis(pt, n[:, None, None], axis=1)[:, 0]  # [B, V]
    pdn = jnp.take_along_axis(pd, n[:, None, None], axis=1)[:, 0]
    resid = jnp.maximum(ptn - pdn, 0.0)
    z = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(z > 0, resid / jnp.maximum(z, 1e-30), ptn)
    return n, resid


def spec_verify_select(logits, drafts, pos, temps, keys):
    """Serving-side speculative accept/select over one verify chunk:
    ``logits`` ``[S, C, V]`` (``C = K+1``: carry + K drafts scored in one
    ``decode_chunk``), ``drafts`` ``[S, K]`` the deterministic proposals,
    ``pos`` ``[S]`` each row's carry position, ``temps``/``keys`` the
    per-slot selection state → ``(sel [S, C] int32, n [S] int32)``.

    For every chunk offset ``j``, ``sel[:, j]`` is the token the
    NON-speculative engine would emit at position ``pos+1+j`` — the exact
    :func:`select_slot_tokens` rule with the exact ``fold_in(key,
    position)`` keying — and a draft is accepted while it matches:
    ``n = Σ cumprod(sel[:, :K] == drafts)``. The correction at the stop
    slot is ``sel[:, n]`` itself, so the emitted prefix ``sel[:, :n+1]``
    is BITWISE the sequential stream (greedy and sampled alike): each
    accepted match feeds the verify chunk the same token the sequential
    path would have fed its next step, so the next logits row is the same
    logits the sequential path would have computed, by induction.

    This IS :func:`spec_round_accept`'s distribution-preserving rejection
    rule specialized to a DETERMINISTIC (delta) proposal and coupled to
    the engine's ``(seed, position)``-keyed draw stream: with
    ``p_d = δ_d`` the rule accepts with probability ``min(1, p_t(d)/1)
    = p_t(d)`` — realized here by drawing ``x ~ p_t`` with the position's
    own key and accepting iff ``x == d`` — and on rejection the draw
    ``x | x ≠ d`` is distributed exactly as the clamped residual
    ``(p_t − δ_d)+ / (1 − p_t(d))``, while a fully-accepted round's bonus
    draw is ``p_t`` itself. Marginally identical to the PR 1 rule
    (pinned in tests against :func:`spec_round_accept`), with the bonus
    property that the coupling makes speculation bitwise invisible."""
    C = logits.shape[1]
    K = drafts.shape[1]
    out_pos = pos[:, None] + 1 + jnp.arange(C)[None, :]        # [S, C]
    sel = jax.vmap(
        lambda lg, op: select_slot_tokens(lg, op, temps, keys),
        in_axes=(1, 1), out_axes=1)(logits, out_pos)           # [S, C]
    match = (sel[:, :K] == drafts).astype(jnp.int32)
    n = jnp.sum(jnp.cumprod(match, axis=1), axis=1)            # [S]
    return sel, n


@partial(jax.jit, static_argnames=("target", "draft", "spec_k", "total",
                                   "sampled"))
def _spec_rollout_device(target, draft, params, draft_params, t_cache,
                         d_cache, carry0, buf0, pos0, spec_k: int,
                         total: int, sampled: bool = False,
                         temperature=1.0, key0=None):
    """The compiled speculative round loop (see
    ``TransformerLM._generate_speculative_device``). ``target``/``draft``
    are static (hashable by identity — the jit cache keys on the model
    instances, so repeated rollouts at one geometry reuse the executable).

    ``sampled=False``: greedy — accept while the target argmax agrees
    (a cumprod over the match mask), correction = the target argmax at
    the first disagreement; output pinned equal to the host driver and
    the target's own greedy rollout. ``sampled=True`` (round 5; only the
    BOOL is static — ``temperature`` is a traced scalar, so serving many
    temperatures reuses one executable): the
    distribution-preserving rejection rule ON DEVICE in f32 — the draft
    SAMPLES its proposals (``jax.random.categorical`` per step), each is
    accepted w.p. ``min(1, p_t(d)/p_d(d))``, the first rejection
    resamples from the residual ``(p_t − p_d)+`` (normalized), and a
    fully-accepted round draws its bonus token from ``p_t`` — expressed
    uniformly by padding ``p_d`` with a zero row at index ``spec_k`` so
    the residual at the bonus slot IS ``p_t``. The host driver
    (``_spec_accept_row``, f64) stays the distributional oracle; the two
    match in DISTRIBUTION, not bitwise (independent RNG streams).

    Returns ``(buf, (rounds, proposed, accepted))``; ``buf[:, :total]``
    is the output. Per-row invariants mirror the batched host loop: rows
    freeze at ``pos = total - 1``; the last draft proposal is ingested
    into the draft cache for every row each round (spurious writes are
    repaired before any query attends them — the chunk-margin invariant).
    """
    B = carry0.shape[0]
    rows = jnp.arange(B)
    zero = jnp.zeros((), jnp.int32)
    inv_t = 1.0 / jnp.asarray(temperature, jnp.float32)
    if key0 is None:
        key0 = jax.random.PRNGKey(0)

    def cond(state):
        pos = state[0]
        return jnp.any(pos + 1 < total)

    def body(state):
        pos, carry, buf, t_cache, d_cache, key, stats = state
        rounds, proposed, acc = stats
        active = (pos + 1) < total
        key, kd, ka, kc = jax.random.split(key, 4)

        def dstep(c, kdi):
            tok, p, dc = c
            dl, dc = draft.decode_step(draft_params, tok, p, dc)
            if sampled:
                scaled = dl.astype(jnp.float32) * inv_t
                nt = jax.random.categorical(kdi, scaled,
                                            axis=-1).astype(jnp.int32)
                pd = jax.nn.softmax(scaled, axis=-1)  # [B, V] f32
            else:
                nt = jnp.argmax(dl, axis=-1).astype(jnp.int32)
                pd = jnp.zeros((B, 0), jnp.float32)  # unused
            return (nt, p + 1, dc), (nt, pd)

        (_, pend, d_cache), (d_toks, d_pd) = jax.lax.scan(
            dstep, (carry, pos, d_cache), jax.random.split(kd, spec_k))
        d_toks = d_toks.T  # [B, spec_k]
        chunk = jnp.concatenate([carry[:, None], d_toks], axis=1)
        vl, t_cache = target.decode_chunk(params, chunk, pos, t_cache)
        if sampled:
            pt = jax.nn.softmax(vl.astype(jnp.float32) * inv_t,
                                axis=-1)                 # [B, k+1, V]
            u = jax.random.uniform(ka, (B, spec_k), jnp.float32)
            n, resid = spec_round_accept(
                pt, jnp.transpose(d_pd, (1, 0, 2)), d_toks, u)
            corr = jax.random.categorical(
                kc, jnp.log(jnp.maximum(resid, 1e-30)),
                axis=-1).astype(jnp.int32)
        else:
            t_arg = jnp.argmax(vl, axis=-1).astype(jnp.int32)
            # greedy acceptance: longest agreeing prefix, then the
            # target's correction/bonus — `_spec_accept_row`'s t<=0 branch
            match = (t_arg[:, :spec_k] == d_toks).astype(jnp.int32)
            n = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # [B]
            corr = jnp.take_along_axis(t_arg, n[:, None], axis=1)[:, 0]
        for i in range(spec_k + 1):  # masked variable-length emission
            val = d_toks[:, i] if i < spec_k else corr
            val = jnp.where(jnp.int32(i) < n, val, corr)
            idx = jnp.minimum(pos + 1 + i, total - 1)
            do = active & (jnp.int32(i) <= n) & (pos + 1 + i < total)
            buf = buf.at[rows, idx].set(jnp.where(do, val, buf[rows, idx]))
        # ingest the last proposal into the draft cache for ALL rows
        _, d_cache = draft.decode_step(draft_params, d_toks[:, -1], pend,
                                       d_cache)
        pos = jnp.where(active, jnp.minimum(pos + n + 1, total - 1), pos)
        carry = jnp.where(active, corr, carry)
        nact = jnp.sum(active.astype(jnp.int32))
        stats = (rounds + 1, proposed + spec_k * nact,
                 acc + jnp.sum(jnp.where(active, n, zero)))
        return pos, carry, buf, t_cache, d_cache, key, stats

    state = (pos0, carry0, buf0, t_cache, d_cache, key0,
             (zero, zero, zero))
    pos, carry, buf, _, _, _, stats = jax.lax.while_loop(cond, body, state)
    return buf, stats


def _layer_norm(x, scale, bias, eps: float = 1e-5):
    # One-VMEM-pass Pallas kernel on TPU (fwd + bwd), jnp fallback elsewhere.
    from ..ops.layer_norm import layer_norm

    return layer_norm(x, scale, bias, eps)


def _spec_probs(logits_row, temperature: float):
    """Host-side softmax in f64 (speculative decoding's acceptance math)."""
    x = np.asarray(logits_row, np.float64) / temperature
    x -= x.max()
    e = np.exp(x)
    return e / e.sum()


def _spec_accept_row(vl_row, d_toks_row, d_probs_row, spec_k: int,
                     vocab: int, temperature: float, rng):
    """One row's speculative acceptance → ``(emitted tokens, n accepted)``.

    ``vl_row [spec_k+1, V]`` target logits over the chunk; greedy accepts
    while the target argmax agrees, sampled mode applies the
    distribution-preserving rejection rule (accept draft ``d`` w.p.
    ``min(1, p_t(d)/p_d(d))``, resample rejections from ``(p_t − p_d)+``,
    bonus from ``p_t``). Shared by the batch-1 and batched loops so the
    rule can never drift between them.
    """
    if temperature <= 0.0:
        t_arg = vl_row.argmax(axis=-1)
        n = 0
        while n < spec_k and int(t_arg[n]) == int(d_toks_row[n]):
            n += 1
        return [int(x) for x in d_toks_row[:n]] + [int(t_arg[n])], n
    n = 0
    for i in range(spec_k):
        pt = _spec_probs(vl_row[i], temperature)
        pd = d_probs_row[i]
        d = int(d_toks_row[i])
        if rng.random() < min(1.0, pt[d] / max(pd[d], 1e-20)):
            n += 1
            continue
        resid = np.maximum(pt - pd, 0.0)
        z = resid.sum()
        resid = resid / z if z > 0 else pt
        return ([int(x) for x in d_toks_row[:n]]
                + [int(rng.choice(vocab, p=resid))], n)
    return ([int(x) for x in d_toks_row]
            + [int(rng.choice(vocab,
                              p=_spec_probs(vl_row[spec_k], temperature)))],
            n)


@jax.named_scope("kv_write")
def write_prompt_cache(kc, vc, ks, vs, windowed: bool):
    """Prompt K/V ``ks``/``vs`` ``[L, B, H, T0, Dh]`` into the cache
    ``kc``/``vc`` ``[L, B, H, Tc, Dh]`` at positions ``0..T0-1`` — THE
    single home of the ring-write convention (rolling caches keep only
    the prompt's last ``Tc`` positions, scattered to their ``p mod Tc``
    slots; shorter prompts take the contiguous fast path, where
    ``p mod Tc == p``). Shared by :meth:`TransformerLM.prefill` and the
    tensor-parallel generator (``models/tensor_lm.py``)."""
    T0, Tc = ks.shape[3], kc.shape[3]
    if windowed and T0 > Tc:
        slots = (np.arange(T0 - Tc, T0) % Tc).astype(np.int32)
        return (kc.at[:, :, :, slots].set(ks[:, :, :, T0 - Tc:]),
                vc.at[:, :, :, slots].set(vs[:, :, :, T0 - Tc:]))
    return (jax.lax.dynamic_update_slice_in_dim(kc, ks, 0, axis=3),
            jax.lax.dynamic_update_slice_in_dim(vc, vs, 0, axis=3))


@jax.named_scope("kv_write")
def cache_gather_slot(cache, slot):
    """Slice one batch row ``slot`` (traced int) out of a KV cache
    ``{"k"/"v": [L, B, Hkv, T, Dh]}`` → the same dict with ``B == 1``.
    The batch axis of a serving cache is the SLOT axis (one row per
    multiplexed request — ``serving/cache.py``); gather + scatter keep
    per-slot prefill a pure function over the shared buffers."""
    # (an entry that is no [L, B, ...] stack, the expert layer's counters,
    # has no slot axis and passes through; a linear layer's state ``"s"``
    # has five axes like K and V, its convolution tail ``"conv"`` three)
    return {
        n: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1) if c.ndim >= 3
        else c for n, c in cache.items()
    }


@jax.named_scope("kv_write")
def cache_scatter_slot(cache, slot, slot_cache):
    """Inverse of :func:`cache_gather_slot`: write the ``B == 1`` slice
    ``slot_cache`` back into batch row ``slot`` of ``cache``."""
    return {
        n: jax.lax.dynamic_update_slice_in_dim(c, slot_cache[n], slot,
                                               axis=1) if c.ndim >= 3
        else slot_cache[n] for n, c in cache.items()
    }


def _adapter_ctx(model, rows):
    """Enter ``model``'s per-slot adapter context when it has one
    (:class:`~elephas_tpu.models.lora.MultiTenantLM` — ``rows`` selects
    each batch row's adapter inside every ``_attn_proj`` traced under the
    context); plain models get a no-op, so one kernel source serves both."""
    ctx = getattr(model, "adapter_context", None)
    if ctx is None:
        return contextlib.nullcontext()
    return ctx(rows)


@jax.named_scope("kv_write")
def _cache_update_rows(cache, new, pos, per_row: bool):
    """Write ``new`` ``[B, Hkv, S, Dh]`` into ``cache`` ``[B, Hkv, T, Dh]``
    at time offset ``pos`` — one shared scalar offset (plain
    dynamic_update_slice, the fast path) or one offset PER ROW (vmapped;
    batched speculative decoding's rows advance independently)."""
    if not per_row:
        return jax.lax.dynamic_update_slice_in_dim(cache, new, pos, axis=2)
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=1)
    )(cache, new, pos)


MOE_COUNTS = ("pairs_held", "rows_computed", "rows_max_expert",
              "experts_touched", "layer_calls")


def _count_moe(cache, stats, row: int):
    """Fold what one expert layer counted (``stats``: the list
    ``MoEFeedForward.apply_dropless`` filled, or ``None``) into row
    ``row`` of the cache's ``moe_counts`` ``[2, 5]`` (row 0 the decode
    steps', row 1 the chunk forwards'; columns :data:`MOE_COUNTS`): sums,
    but a running maximum for the most rows at one expert. The counters
    ride the donated cache, so no step fetches them."""
    if not stats:
        return cache
    c = cache["moe_counts"]
    for st in stats:
        new = jnp.stack([c[row, 0] + st[0], c[row, 1] + st[1],
                         jnp.maximum(c[row, 2], st[2]), c[row, 3] + st[3],
                         c[row, 4] + 1])
        c = c.at[row].set(new)
    return {**cache, "moe_counts": c}


def _rope_angles(positions, dh: int, theta: float = 10000.0,
                 inv_freq=None):
    """RoPE angles for absolute ``positions`` ``[...]`` → ``(cos, sin)``
    each ``[..., dh/2]`` (Su et al. 2021; ``theta`` = frequency base —
    10000 classically, 500000 for Llama-3-family checkpoints).
    ``inv_freq`` ``[dh/2]`` replaces the frequencies ``theta`` gives (a
    scaled rotary: :func:`yarn_rope`)."""
    half = dh // 2
    if inv_freq is None:
        inv_freq = float(theta) ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def yarn_rope(dim: int, theta: float, scaling: Dict[str, Any]):
    """YaRN rotary scaling (Peng et al. 2023, as the DeepSeek-V2/V3 family
    publishes it under ``rope_scaling``) → ``(inv_freq [dim/2] float32,
    table factor, softmax factor)``.

    Each of the ``dim/2`` frequencies ``theta ** (-2i/dim)`` is a blend of
    itself and itself over ``factor``: dimension ``i`` keeps its own
    frequency below ``low``, takes the divided one above ``high``, and a
    linear ramp between, where ``low``/``high`` are the dimensions that
    turn ``beta_fast``/``beta_slow`` times over
    ``original_max_position_embeddings`` positions (floor / ceiling,
    clipped to the dimensions there are). With ``m(s) = 0.1 s ln(factor) +
    1``: the cos/sin tables are multiplied by ``m(mscale) /
    m(mscale_all_dim)`` and the softmax scale by ``m(mscale_all_dim) ** 2``."""
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling type {kind!r}: only 'yarn' is read")
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    half = dim // 2

    def turns_at(n_rot):     # the dimension that turns n_rot times
        return (dim * np.log(orig / (n_rot * 2 * np.pi))
                / (2 * np.log(float(theta))))

    low = max(int(np.floor(turns_at(float(scaling.get("beta_fast", 32))))), 0)
    high = min(int(np.ceil(turns_at(float(scaling.get("beta_slow", 1))))),
               dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    own = float(theta) ** (-np.arange(half) / half)
    inv_freq = own / factor * ramp + own * (1.0 - ramp)

    def m(s):
        return 0.1 * float(s) * np.log(factor) + 1.0 if factor > 1 else 1.0

    all_dim = m(scaling.get("mscale_all_dim", 0))
    return (inv_freq.astype(np.float32),
            float(m(scaling.get("mscale", 1)) / all_dim), float(all_dim ** 2))


def _rope_rotate(x, cos, sin):
    """Rotate head vectors ``x`` ``[..., H, Dh]`` by per-position angles
    ``cos``/``sin`` ``[..., 1, Dh/2]`` (broadcast over heads). Pairing is
    HALF-SPLIT (NeoX-style): dim ``i`` rotates with dim ``i + Dh/2`` — NOT
    the interleaved even/odd layout some RoPE checkpoints use; permute
    accordingly when importing foreign weights."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


_UNIFORM_WINDOW = object()  # _attend sentinel: "the model-wide window"


def _period_group(tree, p: int):
    """``[L, ...]`` leading-dim stacks → ``[L/p, p, ...]`` for the
    mixed-window period scans (dict of arrays/lazy tensors, or one
    array). THE single home of the regroup convention — apply_hidden,
    prefill, decode_step, and decode_chunk must all slice group ``g`` as
    ``windows[g]``'s layer, which this layout guarantees (row-major:
    scan step ``i`` covers layers ``i·p .. i·p+p-1`` in order)."""
    def one(v):
        return v.reshape((v.shape[0] // p, p) + tuple(v.shape[1:]))

    if isinstance(tree, dict):
        return {k: one(v) for k, v in tree.items()}
    return one(tree)


def _period_ungroup(arr, n_layers: int):
    """Inverse of :func:`_period_group` for scan-stacked outputs
    (``[L/p, p, ...]`` → ``[L, ...]``)."""
    return arr.reshape((n_layers,) + tuple(arr.shape[2:]))


# Below this many elements a gradient leaf rides a plain ``psum``: the ring's
# 2(P-1) nearest-neighbor hops only win once the payload amortizes their
# launch latency (per-layer FFN/attention stacks qualify; norm scales don't).
_RING_MIN_ELEMS = 65536


def ring_psum(x, axis_name: str):
    """All-reduce ``x`` over the named mesh axis as a ``ppermute`` ring —
    reduce-scatter then all-gather, each ``P - 1`` nearest-neighbor hops of
    ``size/P`` chunks — instead of one monolithic ``psum``.

    Same sum as ``jax.lax.psum`` up to float reassociation (the chunks
    accumulate around the ring rather than in XLA's reduction tree), so
    use it where allclose-parity suffices, not bit-parity. Written against
    the named axis only — no pmap, no mesh object — so it composes with
    any ``shard_map``/GSPMD program that carries the axis. The chunked
    form is what lets XLA overlap the hops with unrelated compute: each
    hop is a small independent collective, not one axis-wide barrier.
    """
    n = axis_size(axis_name)
    if n == 1:
        return x
    me = jax.lax.axis_index(axis_name)
    flat = x.reshape(-1)
    csz = -(-flat.size // n)
    pad = csz * n - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(n, csz)
    perm = [(i, (i + 1) % n) for i in range(n)]
    take = lambda c, i: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False)
    put = lambda c, v, i: jax.lax.dynamic_update_index_in_dim(c, v, i, 0)
    # reduce-scatter: after step s, chunk (me-s-1) mod n holds the partials
    # of ranks {me-s-1, ..., me}; after n-1 steps rank me owns the COMPLETE
    # chunk (me+1) mod n.
    for s in range(n - 1):
        buf = jax.lax.ppermute(take(chunks, (me - s) % n), axis_name, perm)
        recv = (me - s - 1) % n
        chunks = put(chunks, take(chunks, recv) + buf, recv)
    # all-gather the completed chunks around the same ring.
    for s in range(n - 1):
        buf = jax.lax.ppermute(take(chunks, (me + 1 - s) % n), axis_name,
                               perm)
        chunks = put(chunks, buf, (me - s) % n)
    out = chunks.reshape(-1)
    if pad:
        out = out[:x.size]
    return out.reshape(x.shape)


def _reduce_on_backward(reduce_ct):
    """DrJAX-style broadcast/reduce pair as a custom-vjp identity tag:
    forward passes the (param) tree through untouched; the backward applies
    ``reduce_ct`` to the cotangent tree AT THE PROGRAM POINT where it is
    produced. Wrapping each layer's param slice inside the block scan makes
    that point "as soon as this layer's backward segment finishes" — the
    per-bucket gradient collectives issue interleaved with the remaining
    backward compute instead of as one serialized block after it, and the
    latency-hiding scheduler can overlap them."""

    @jax.custom_vjp
    def tag(tree):
        return tree

    tag.defvjp(lambda tree: (tree, None), lambda _, ct: (reduce_ct(ct),))
    return tag


def _remat_wrap(fn, remat: str):
    """Apply the block-scan remat policy: ``"none"`` stores all residuals
    (the default — fastest when activations fit), ``"dots"`` saves matmul
    outputs and recomputes the cheap elementwise/norm ops, ``"full"``
    recomputes the whole block from its input (max memory relief; the
    long-context companion to ``accum_steps``)."""
    if remat == "none":
        return fn
    if remat == "dots":
        return jax.checkpoint(
            fn, prevent_cse=False,
            policy=jax.checkpoint_policies.checkpoint_dots)
    if remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    raise ValueError(f"Unknown remat policy: {remat!r} (none|dots|full)")


class TransformerLM:
    """Decoder-only LM: embed → L pre-norm blocks (attn + FFN) → norm → head.

    ``apply(params, tokens, positions, attn)`` is pure; ``attn`` is one of
    ``"dense"`` (full attention, the oracle path), ``"flash"`` (blockwise
    exact attention — the single-shard memory-efficient path), ``"ring"``,
    or ``"ulysses"`` — the latter two call the INSIDE-shard_map bodies over
    ``seq_axis`` and are only valid under ``shard_map``.
    """

    _supports_speculative = True
    # leading layers that stand OUTSIDE the layer scan with leaves of their
    # own (``dense_<leaf> [n_lead, ...]``): the MoE variant's leading dense
    # layers, whose FFN leaves have another shape than the scanned sparse
    # layers'. The scanned stacks then hold ``n_layers - n_lead`` layers.
    n_lead = 0
    LEAD = "dense_"

    def __init__(self, vocab: int, d_model: int, n_heads: int, n_layers: int,
                 d_ff: int, max_len: int, compute_dtype: str = "float32",
                 pos_encoding: str = "learned", tie_embeddings: bool = False,
                 n_kv_heads: Optional[int] = None, activation: str = "relu",
                 norm: str = "layernorm", norm_eps: float = 1e-5,
                 attn_bias: bool = False, ffn_bias: bool = True,
                 rope_theta: float = 10000.0,
                 attn_window: Optional[int] = None,
                 head_dim: Optional[int] = None, qk_norm: bool = False,
                 rope_layers: str = "all", window_cache: str = "horizon",
                 q_lora_rank: Optional[int] = None,
                 kv_lora_rank: Optional[int] = None,
                 qk_nope_head_dim: Optional[int] = None,
                 qk_rope_head_dim: Optional[int] = None,
                 v_head_dim: Optional[int] = None,
                 rope_scaling: Optional[Dict[str, Any]] = None,
                 layer_types=None, linear_heads: Optional[int] = None,
                 linear_key_head_dim: Optional[int] = None,
                 linear_value_head_dim: Optional[int] = None,
                 linear_conv_kernel_dim: int = 4,
                 linear_allow_neg_eigval: bool = False,
                 linear_gate: str = "silu", state_dtype: str = "float32",
                 norm_order: str = "pre", act_dtype: Optional[str] = None,
                 passes: int = 1, pass_norm: bool = True):
        # LATENT attention (``kv_lora_rank``; DeepSeek-V2's MLA): keys and
        # values are projections ``wkv_b`` of one joint latent of
        # ``kv_lora_rank`` numbers a position (``wkv_a``, RMS-normed by
        # ``kv_a_norm``), keys carry besides ``qk_rope_head_dim`` rotary
        # dimensions that ALL heads share, queries come through a latent
        # of ``q_lora_rank`` (``wq_a``, ``q_a_norm``, ``wq_b``), a head's key
        # is ``qk_nope_head_dim +
        # qk_rope_head_dim`` wide and its value ``v_head_dim``. The cache
        # holds the latent and the shared rotary key, not keys and values
        # (``init_cache``). ``rope_scaling``: the published YaRN dictionary
        # (:func:`yarn_rope`), read by the latent form only.
        self.latent = kv_lora_rank is not None
        if self.latent:
            if None in (q_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                        v_head_dim):
                raise ValueError(
                    "latent attention (kv_lora_rank) needs q_lora_rank, "
                    "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
            if (pos_encoding != "rotary" or attn_window is not None
                    or qk_norm or attn_bias or head_dim is not None
                    or n_kv_heads not in (None, n_heads)):
                raise ValueError(
                    "latent attention is rotary (its shared key is the "
                    "rotary part), attends every earlier position (a latent "
                    "row has no window mask or ring), and has its own norms "
                    "and head sizes: no attn_window, qk_norm, attn_bias, "
                    "head_dim or n_kv_heads beside kv_lora_rank")
            self.q_rank = int(q_lora_rank)
            self.kv_rank = int(kv_lora_rank)
            self.nope_dim = int(qk_nope_head_dim)
            self.rope_dim = int(qk_rope_head_dim)
            self.v_dim = int(v_head_dim)
            head_dim = self.nope_dim + self.rope_dim
            # one cached row: latent | rotary key | zeros to whole lanes
            self.latent_row = _pad_up(self.kv_rank + self.rope_dim, _LANE)
        elif any(v is not None for v in (
                q_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                rope_scaling)):
            raise ValueError(
                "q_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim "
                "and rope_scaling belong to latent attention: give "
                "kv_lora_rank")
        if head_dim is None and d_model % n_heads:
            raise ValueError(f"d_model {d_model} not divisible by {n_heads} heads")
        # ``head_dim``: the size of one head where the family publishes it
        # apart from the quotient (q is then ``n_heads * head_dim`` wide,
        # not ``d_model``). ``qk_norm``: RMSNorm over each head of q and of
        # k, one learned scale of ``head_dim`` each, before the rotation.
        # ``rope_layers="windowed"``: rotary positions on the window layers
        # only, none on a full-attention layer. ``window_cache="ring"``: a
        # model of window AND full layers keeps two cache stacks side by
        # side, a ring of the window's length for its window layers and
        # the horizon for its full layers (``init_cache``); "horizon", the
        # default, keeps one horizon-long stack for every layer.
        self.head_dim = (d_model // n_heads if head_dim is None
                         else int(head_dim))
        self.d_attn = n_heads * (self.v_dim if self.latent
                                 else self.head_dim)
        # softmax scale and rotary frequencies where they are not the
        # defaults (``head_dim ** -0.5``, ``rope_theta``'s): YaRN's
        self.attn_scale = self._inv_freq = None
        if self.latent:
            self.attn_scale = self.head_dim ** -0.5
            if rope_scaling is not None:
                inv, table, soft = yarn_rope(self.rope_dim, rope_theta,
                                             rope_scaling)
                if table != 1.0:
                    raise ValueError(
                        "rope_scaling: mscale != mscale_all_dim scales the "
                        "rotary tables themselves, which is not in the "
                        "program")
                self._inv_freq, self.attn_scale = inv, self.attn_scale * soft
        else:
            self.rope_dim = self.head_dim
        # ``qk_norm="whole"``: ONE RMSNorm over the whole q projection and
        # one over the whole k projection (scales ``n_heads * head_dim`` and
        # ``n_kv_heads * head_dim`` wide) before the heads are split, the
        # Olmo 2 / Olmo 3 family's form; ``True`` is the per-head one.
        if qk_norm not in (False, True, "whole"):
            raise ValueError(f"Unknown qk_norm: {qk_norm!r}")
        self.qk_norm = qk_norm
        # ``rope_layers="none"``: a rotary model none of whose layers
        # rotates (position reaches it some other way: its linear layers)
        if rope_layers not in ("all", "windowed", "none"):
            raise ValueError(f"Unknown rope_layers: {rope_layers}")
        # ``norm_order="post"``: the REORDERED block, ``h + N1(Mixer(h))``
        # then ``h + N2(FFN(h))``: the norms stand on what a sublayer
        # returns, not on what it reads ("pre", the default, is ``h +
        # Mixer(N1(h))``). Same leaves, ``ln1_s`` / ``ln2_s``.
        # ``"sandwich"``: a norm on both sides, ``h + N1o(Mixer(N1(h)))``
        # then ``h + N2o(FFN(N2(h)))``, the outer norms' scales two more
        # leaves, ``ln1_out_s`` / ``ln2_out_s``.
        if norm_order not in ("pre", "post", "sandwich"):
            raise ValueError(f"Unknown norm_order: {norm_order!r}")
        if norm_order == "sandwich" and norm != "rmsnorm":
            raise ValueError("norm_order='sandwich' has scale-only outer "
                             "norms: norm='rmsnorm'")
        self.norm_order = norm_order
        # ``passes``: a LOOPED stack (Ouro's ``total_ut_steps``): the whole
        # layer stack runs ``passes`` times a token, the final norm
        # ``lnf`` after every pass (what the next pass reads is the normed
        # state), the logits after the last. The weights are one stack;
        # the cache has one layer per pass and layer, pass ``u``'s layer
        # ``l`` at cache layer ``u * L + l`` (:meth:`init_cache`).
        # ``pass_norm=False``: no norm between passes (the next pass reads
        # the last one's residual stream as it is).
        if int(passes) != passes or passes < 1:
            raise ValueError(f"passes must be a whole number >= 1, got "
                             f"{passes!r}")
        self.passes = int(passes)
        self.pass_norm = bool(pass_norm)
        # ``act_dtype`` (default: the compute dtype): what a sublayer's
        # matmuls GIVE and its elementwise steps run in. The matmuls still
        # take compute-dtype inputs (one MXU pass), but under "float32"
        # their float32 accumulators are not rounded on the way out: the
        # FFN's gate and up products meet in float32 and are rounded once
        # for the down product, and a sublayer's result reaches the
        # residual (or, reordered, its norm) unrounded. A handful of
        # roundings a sublayer become the one of each matmul's input. For a
        # model whose depth amplifies rounding noise (PERF.md §6, PR 34).
        self._wide = (act_dtype is not None
                      and jnp.dtype(act_dtype) != jnp.dtype(compute_dtype))
        self.act_dtype = jnp.dtype(compute_dtype if act_dtype is None
                                   else act_dtype)
        # LINEAR-ATTENTION layers (``layer_types``: one of "full_attention"
        # / "linear_attention" a layer; Gated DeltaNet, ops/gated_delta.py):
        # a layer whose memory of the past is a recurrent STATE ``[dk, dv]``
        # a head and the last inputs of a short convolution, not rows of a
        # cache. Its mixer leaves are stacked over the linear layers alone
        # (``lin_*``, ``A_log``, ``dt_bias``: ``[n_linear, ...]``), the full
        # layers' ``wq``..``wo`` over the full layers alone, norms and FFN
        # over all ``L``; the layer scans slice each stack by the layer's
        # number among the layers of its kind (:meth:`_layer_slice`).
        # ``linear_gate``: the output gate's activation; ``state_dtype``:
        # what the cached state is kept in (the arithmetic is float32).
        kinds = ("full_attention",) * n_layers if layer_types is None \
            else tuple(layer_types)
        unknown = sorted(set(kinds) - {"full_attention", "linear_attention"})
        if unknown or len(kinds) != n_layers:
            raise ValueError(
                f"layer_types: {n_layers} entries of 'full_attention' / "
                f"'linear_attention' (sliding layers go by attn_window), "
                f"got {len(kinds)} with {unknown}")
        self.layer_kinds = tuple(
            "linear" if k == "linear_attention" else "full" for k in kinds)
        self.n_linear = self.layer_kinds.count("linear")
        self.hybrid = self.n_linear > 0
        if self.hybrid:
            if None in (linear_heads, linear_key_head_dim,
                        linear_value_head_dim):
                raise ValueError(
                    "linear_attention layers need linear_heads, "
                    "linear_key_head_dim and linear_value_head_dim")
            if (self.latent or attn_window is not None
                    or self.n_linear == n_layers):
                raise ValueError(
                    "linear_attention layers stand beside full-attention "
                    "layers with a K/V cache: no kv_lora_rank, no "
                    "attn_window, and at least one full_attention layer")
            if linear_gate not in ("silu", "sigmoid"):
                raise ValueError(f"Unknown linear_gate: {linear_gate!r}")
            self.lin_heads = int(linear_heads)
            self.lin_dk = int(linear_key_head_dim)
            self.lin_dv = int(linear_value_head_dim)
            self.lin_conv = int(linear_conv_kernel_dim)
            self.lin_neg_eigval = bool(linear_allow_neg_eigval)
            self.lin_gate = linear_gate
            self.state_dtype = jnp.dtype(state_dtype)
            # channels the short convolution runs over: q | k | v
            self.lin_channels = self.lin_heads * (2 * self.lin_dk
                                                  + self.lin_dv)
        if window_cache not in ("horizon", "ring"):
            raise ValueError(f"Unknown window_cache: {window_cache}")
        self.rope_layers = rope_layers
        n_kv_heads = n_heads if n_kv_heads is None else int(n_kv_heads)
        if n_kv_heads < 1 or n_heads % n_kv_heads:
            raise ValueError(
                f"n_heads {n_heads} not divisible by n_kv_heads {n_kv_heads}"
            )
        self.n_kv_heads = n_kv_heads
        if pos_encoding not in ("learned", "rotary"):
            raise ValueError(f"Unknown pos_encoding: {pos_encoding}")
        if pos_encoding == "rotary" and self.head_dim % 2:
            raise ValueError(
                f"rotary needs an even head dim, got {self.head_dim}"
            )
        self.pos_encoding = pos_encoding
        # Architecture knobs covering the common decoder families (the
        # defaults reproduce this project's round-1 model exactly):
        # GPT-2  = gelu + layernorm + attn_bias + ffn_bias + learned pos
        #          + tied embeddings;
        # Llama  = swiglu + rmsnorm + no biases + rotary (+ GQA, rope_theta).
        # models/hf_import.py builds these configs from HF checkpoints.
        if activation not in ("relu", "gelu", "swiglu"):
            raise ValueError(f"Unknown activation: {activation}")
        if norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"Unknown norm: {norm}")
        self.activation = activation
        self.norm = norm
        self.norm_eps = float(norm_eps)
        self.attn_bias = bool(attn_bias)
        self.ffn_bias = bool(ffn_bias)
        self.rope_theta = float(rope_theta)
        # Sliding-window attention (Mistral convention): query t sees keys
        # (t-window, t]. Exact O(T·window) compute on the flash/decode
        # kernel paths — out-of-window tiles are neither DMA'd nor
        # computed (ops/pallas_flash.py, ops/flash_decode.py).
        # PER-LAYER windows (Gemma-2-style alternating SWA, Qwen2
        # layer_types): pass a length-n_layers sequence of int/None. The
        # layer scans decompose over the pattern's minimal period (see
        # _window_period), so periodic patterns stay compiled scans;
        # decode uses a rolling buffer only when EVERY layer is windowed
        # (one full-attention layer forces a horizon cache anyway).
        if attn_window is None or isinstance(attn_window, (int, np.integer)):
            if attn_window is not None and int(attn_window) < 1:
                raise ValueError(
                    f"attn_window must be >= 1, got {attn_window}")
            uniform = None if attn_window is None else int(attn_window)
            self.attn_windows = (uniform,) * n_layers
        else:
            ws = tuple(None if w is None else int(w) for w in attn_window)
            if len(ws) != n_layers:
                raise ValueError(
                    f"per-layer attn_window needs {n_layers} entries, "
                    f"got {len(ws)}")
            if any(w is not None and w < 1 for w in ws):
                raise ValueError(f"attn_window entries must be >= 1: {ws}")
            self.attn_windows = ws
        distinct = set(self.attn_windows)
        self.mixed_window = len(distinct) > 1
        # the uniform scalar view (None for mixed models — every consumer
        # that cannot handle per-layer windows guards on mixed_window)
        self.attn_window = (self.attn_windows[0]
                            if not self.mixed_window else None)
        # decode cache policy: rolling iff every layer is windowed
        self._ring_cache = all(w is not None for w in self.attn_windows)
        self._max_window = max((w for w in self.attn_windows
                                if w is not None), default=None)
        # ...and TWO stacks (ring for the window layers, horizon for the
        # full ones) for a model of both that asks for it
        self._two_kind = (window_cache == "ring" and not self._ring_cache
                          and self._max_window is not None)
        self.tie_embeddings = bool(tie_embeddings)
        self.vocab = vocab
        self.d_model = d_model
        self.n_heads = n_heads
        self.n_layers = n_layers
        self.d_ff = d_ff
        self.max_len = max_len
        self.aux_weight = 0.0  # MoE variant sets a nonzero weight
        # Mixed precision the TPU way: params/optimizer/logits/loss stay
        # float32, block activations and matmuls run in compute_dtype
        # ("bfloat16" doubles MXU rate); layernorm statistics and attention
        # accumulators stay float32 regardless (the ring/ulysses bodies
        # already accumulate in f32 for sub-f32 inputs).
        self.compute_dtype = jnp.dtype(compute_dtype)

    def param_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        V, D, L, F, T = (self.vocab, self.d_model,
                         self.n_layers - self.n_lead, self.d_ff,
                         self.max_len)
        f32 = jnp.float32
        sds = jax.ShapeDtypeStruct
        Dq = self.d_attn
        Dkv = self.head_dim * self.n_kv_heads
        shapes = {
            "tok": sds((V, D), f32),
            "ln1_s": sds((L, D), f32), "ln1_b": sds((L, D), f32),
            "wq": sds((L, D, Dq), f32),
            "wk": sds((L, D, Dkv), f32),
            "wv": sds((L, D, Dkv), f32),
            "wo": sds((L, Dq, D), f32),
            "ln2_s": sds((L, D), f32), "ln2_b": sds((L, D), f32),
            "w1": sds((L, D, F), f32), "b1": sds((L, F), f32),
            "w2": sds((L, F, D), f32), "b2": sds((L, D), f32),
            "lnf_s": sds((D,), f32), "lnf_b": sds((D,), f32),
        }
        if self.norm == "rmsnorm":  # rmsnorm is scale-only
            for k in ("ln1_b", "ln2_b", "lnf_b"):
                del shapes[k]
        if self.norm_order == "sandwich":
            for k in self._OUT_NORMS:
                shapes[k + "_s"] = sds((L, D), f32)
        if self.activation == "swiglu":
            shapes["w3"] = sds((L, D, F), f32)
        if not self.ffn_bias:
            for k in ("b1", "b2"):
                del shapes[k]
        if self.qk_norm:
            whole = self.qk_norm == "whole"
            shapes["qn_s"] = sds((L, Dq if whole else self.head_dim), f32)
            shapes["kn_s"] = sds((L, Dkv if whole else self.head_dim), f32)
        if self.hybrid:
            # the attention leaves cover the full layers alone, and the
            # linear layers' mixer has leaves of its own (``lin_qkv``: the
            # q | k | v projections side by side, the channels the short
            # convolution ``lin_conv`` runs over; ``lin_ab``: beta | a)
            Ln, H = self.n_linear, self.lin_heads
            C, Dv = self.lin_channels, self.lin_heads * self.lin_dv
            for k in self._full_keys():
                shapes[k] = sds((L - Ln,) + shapes[k].shape[1:], f32)
            shapes.update(
                lin_qkv=sds((Ln, D, C), f32),
                lin_conv=sds((Ln, self.lin_conv, C), f32),
                lin_ab=sds((Ln, D, 2 * H), f32),
                A_log=sds((Ln, H), f32), dt_bias=sds((Ln, H), f32),
                lin_z=sds((Ln, D, Dv), f32),
                lin_norm_s=sds((Ln, self.lin_dv), f32),
                lin_o=sds((Ln, Dv, D), f32))
        if self.latent:
            # wq | wk | wv give way to the latent projections; ``wkv_b``
            # holds, a head, its keys' un-rotated part then its values
            H, r = self.n_heads, self.kv_rank
            for k in ("wq", "wk", "wv"):
                del shapes[k]
            shapes["wq_a"] = sds((L, D, self.q_rank), f32)
            shapes["q_a_norm"] = sds((L, self.q_rank), f32)
            shapes["wq_b"] = sds((L, self.q_rank, H * self.head_dim), f32)
            shapes["wkv_a"] = sds((L, D, r + self.rope_dim), f32)
            shapes["kv_a_norm"] = sds((L, r), f32)
            shapes["wkv_b"] = sds((L, r, H * (self.nope_dim + self.v_dim)),
                                  f32)
        if self.attn_bias:
            shapes["bq"] = sds((L, Dq), f32)
            shapes["bk"] = sds((L, Dkv), f32)
            shapes["bv"] = sds((L, Dkv), f32)
            shapes["bo"] = sds((L, D), f32)
        if not self.tie_embeddings:
            shapes["head"] = sds((D, V), f32)
        if self.pos_encoding == "learned":
            shapes["pos"] = sds((T, D), f32)
        return shapes

    def init(self, seed: int = 0) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(seed)
        out: Dict[str, np.ndarray] = {}
        for name, sds in self.param_shapes().items():
            if name.endswith(("_s", "_norm")):   # norm scales
                out[name] = np.ones(sds.shape, sds.dtype)
            elif name.startswith(("ln", "b")) or name in ("A_log",
                                                          "dt_bias"):
                # (a linear layer's decay then has median exp(-ln 2))
                out[name] = np.zeros(sds.shape, sds.dtype)
            elif name in ("tok", "pos") or name.endswith("wg_b"):
                out[name] = (rng.normal(size=sds.shape) * 0.02).astype(
                    sds.dtype)
            else:
                out[name] = glorot(rng, *sds.shape, dtype=sds.dtype)
        return out

    def specs(self) -> Dict[str, P]:
        """Replicated over both mesh axes (shard state via fsdp if needed)."""
        return {k: P() for k in self.param_shapes()}

    def shard_params(self, mesh: Mesh, params: Dict[str, Any]) -> Dict[str, Any]:
        return shard_by_specs(mesh, self.specs(), params)

    # ------------------------------------------------------------------
    def _window_period(self) -> int:
        """Minimal period ``p`` (dividing L) such that the per-layer window
        pattern tiles — 1 for uniform models, 2 for Gemma-2-style
        alternation, L (full unroll) for aperiodic patterns."""
        ws = tuple(zip(self._scan_windows, self.layer_kinds[self.n_lead:]))
        L = len(ws)
        for p in range(1, L + 1):
            if L % p == 0 and ws == ws[:p] * (L // p):
                return p
        return L

    @property
    def _scan_windows(self):
        """The windows of the layers the layer scan covers (all of them
        but the ``n_lead`` leading ones)."""
        return self.attn_windows[self.n_lead:]

    def _rope_on(self, window) -> bool:
        """Does a layer of this window rotate q and k? Every layer of a
        rotary model, or (``rope_layers="windowed"``) its window layers
        only: a full-attention layer then carries no position at all."""
        return self.pos_encoding == "rotary" and (
            self.rope_layers == "all"
            or (self.rope_layers == "windowed" and window is not None))

    def _lead_params(self, params, j: int):
        """Leading layer ``j``'s leaves under the names the layer body
        reads (``dense_wq[j]`` as ``wq``)."""
        return {k: params[self.LEAD + k][j] for k in self._lead_keys()}

    def _lead_keys(self):
        return ()

    def _stacked_keys(self):
        """Scanned leaves the cached layer walk does NOT slice per layer:
        it hands ``(whole stack, layer index)`` to the layer body instead
        (an expert stack a Pallas kernel reads in place)."""
        return ()

    _FULL_KEYS = ("wq", "wk", "wv", "wo", "qn_s", "kn_s", "bq", "bk", "bv",
                  "bo")
    _LINEAR_KEYS = ("lin_qkv", "lin_conv", "lin_ab", "A_log", "dt_bias",
                    "lin_z", "lin_norm_s", "lin_o")

    def _full_keys(self):
        """The scanned leaves only a full-attention layer has (in a model
        with linear layers they are stacked over the full layers alone)."""
        return tuple(k for k in TransformerLM._block_keys(self)
                     if k in self._FULL_KEYS)

    def _group_layers(self, stacks, p: int):
        """:func:`_period_group` of the scanned stacks (a dict) for a scan
        of ``p`` sub-layers a step, by each leaf's own length: ``[L, ...]``
        -> ``[steps, p, ...]``, and a stack that covers one kind of layer
        only (``[n_linear, ...]``, ``[n_full, ...]``) -> ``[steps, n_kind /
        steps, ...]``, so every leaf has the scan's leading axis."""
        steps = (self.n_layers - self.n_lead) // p
        return {k: v.reshape((steps, v.shape[0] // steps)
                             + tuple(v.shape[1:]))
                for k, v in stacks.items()}

    def _layer_slice(self, lps, g: int, step: int = 0):
        """Sub-layer ``g``'s leaves out of one scan step's ``lps`` (each
        ``[p, ...]``; or, in a model with linear layers, ``[layers of the
        leaf's kind in a period, ...]``: the leaf is then taken at the
        layer's number among its kind, and the other kind's leaves are
        left out). With ``step`` the ``lps`` are the WHOLE stacks and the
        layer is sub-layer ``g`` of period ``step``: one static index a
        leaf, which the compiler reads in place."""
        p = self._window_period()
        if not self.hybrid:
            return {k: v[step * p + g] for k, v in lps.items()}
        period = self.layer_kinds[:p]
        kind, at = period[g], period[:g].count(period[g])
        other = self._LINEAR_KEYS if kind == "full" else self._FULL_KEYS
        mine = self._FULL_KEYS if kind == "full" else self._LINEAR_KEYS
        return {k: v[step * period.count(kind) + at if k in mine
                     else step * p + g]
                for k, v in lps.items() if k not in other}

    def _cache_slots(self):
        """Where each layer's K/V live: ``(lead, scan)``. ``lead[j]`` is
        ``(names, index)`` for leading layer ``j``; ``scan[g]`` is
        ``(names, base, step)`` for sub-layer ``g`` of a scan step, whose
        index in its stack at step ``i`` is ``base + i * step``. One
        stack ``("k", "v")`` indexed by the layer's own number (a latent
        model's ``("k",)``: its rows are key and value at once), or with
        two kinds of cache ``("kw", "vw")`` for a window layer and
        ``("k", "v")`` for a full one, each indexed by how many layers of
        its kind come before."""
        ws, L0, p = self.attn_windows, self.n_lead, self._window_period()
        if self.hybrid:
            # a full layer's K/V in ``("k", "v")``, a linear layer's state
            # and convolution tail in ``("s", "conv")``, each stack indexed
            # by the layer's number among the layers of its kind
            stacks = {"full": ("k", "v"), "linear": ("s", "conv")}
            period = self.layer_kinds[:p]
            return [], [(stacks[kind], period[:g].count(kind),
                         period.count(kind))
                        for g, kind in enumerate(period)]
        if not self._two_kind:
            # (a latent model's one stack of rows is keys alone)
            kv = ("k",) if self.latent else ("k", "v")
            return ([(kv, j) for j in range(L0)],
                    [(kv, L0 + g, p) for g in range(p)])

        def names(w):
            return ("k", "v") if w is None else ("kw", "vw")

        def before(l):   # layers of layer l's kind among layers 0..l-1
            return sum((w is None) == (ws[l] is None) for w in ws[:l])

        period = ws[L0:L0 + p]
        return ([(names(ws[j]), before(j)) for j in range(L0)],
                [(names(w), before(L0 + g),
                  sum((v is None) == (w is None) for v in period))
                 for g, w in enumerate(period)])

    def _is_ring(self, kn: str) -> bool:
        """Is the cache stack named ``kn`` a ring (row ``pos mod R``,
        masked by age)? A window layer's own stack is, and so is the one
        stack of a model whose every layer is windowed."""
        return self._ring_cache or kn == "kw"

    def decode_walks(self, cache):
        """The distinct ways :meth:`decode_step` calls the decode kernel on
        ``cache`` and how many layers take each: ``[(cache_len, window,
        ring, block, n_layers)]``, the arguments of
        :func:`~elephas_tpu.ops.flash_decode.kv_block_walk` (the serving
        engine counts the kernel's visits from them). ``block`` is the
        positions a visit of the layer's kernel covers: the latent
        kernel's is wider (``latent_block_t``)."""
        block_t = latent_block_t if self.latent else _block_t
        kinds = collections.Counter()
        for w, kind in zip(self.attn_windows, self.layer_kinds):
            if kind == "linear":       # no keys to walk: a state
                continue
            kn = "kw" if self._two_kind and w is not None else "k"
            T = cache[kn].shape[3]
            # (a looped stack walks the layer once a pass)
            kinds[T, w, self._is_ring(kn), block_t(T)] += self.passes
        return [(*kind, n) for kind, n in kinds.items()]

    @jax.named_scope("attn_core")
    def _attend(self, q, k, v, attn: str, seq_axis: str, rope=None,
                rope_tables=None, window=_UNIFORM_WINDOW):
        """``rope=(cos, sin)`` is only ever non-None on the ``"flash"``
        path (see ``_block_fwd``): on TPU the rotation fuses into the
        Pallas kernels via ``rope_tables`` (the duplicated C2/S2 tables,
        built ONCE per forward in ``apply_with_aux`` — building them here
        would re-materialize them every scanned layer); elsewhere it is
        applied here before the scan.

        ``window`` is THIS layer's sliding window (the per-layer scans
        pass it explicitly); the default resolves to the model-wide
        uniform window and refuses mixed-window models — a caller that
        has not been taught per-layer windows must fail loudly, not
        silently attend unwindowed."""
        if window is _UNIFORM_WINDOW:
            if self.mixed_window:
                raise NotImplementedError(
                    "this attention path has no per-layer window support; "
                    "mixed attn_window models run the core single-device "
                    "family (apply/prefill/decode/generate) only"
                )
            window = self.attn_window
        w = window
        self._latent_dense_only(attn)
        if attn == "dense":
            return attention_reference(q, k, v, causal=True, window=w,
                                       scale=self.attn_scale)
        if attn == "flash":
            # Blockwise exact attention (custom-VJP flash fwd+bwd): no
            # [T, T] materialization in either direction. Single-shard
            # sequence only — the sp>1 equivalents are ring/ulysses.
            if rope_tables is not None:
                from ..ops.pallas_flash import flash_attention_rope

                return flash_attention_rope(q, k, v, *rope_tables, True,
                                            window=w)
            if rope is not None:
                q = _rope_rotate(q, *rope)
                k = _rope_rotate(k, *rope)
            return flash_attention(q, k, v, causal=True, window=w)
        if attn in ("ring", "ulysses"):
            # Sliding windows (uniform or per-layer) ride the sp paths:
            # the ring masks on absolute positions and skips wholly-
            # expired visits (O(T·window)); Ulysses' post-all-to-all
            # sequence is global so the flash window applies unchanged.
            if attn == "ring":
                return ring_attention_local(q, k, v, causal=True,
                                            axis_name=seq_axis, window=w)
            return ulysses_attention_local(q, k, v, causal=True,
                                           axis_name=seq_axis, window=w)
        raise ValueError(f"Unknown attn: {attn}")

    def _latent_dense_only(self, attn: str) -> None:
        """The blockwise and sequence-parallel attention paths take keys
        and values of ONE head size under the scale ``head_dim ** -0.5``;
        a latent model's keys and values differ in size and its scale
        carries the rotary scaling's factor."""
        if self.latent and attn != "dense":
            raise NotImplementedError(
                f"attn={attn!r}: the flash, ring and ulysses kernels take "
                "keys and values of one head size and a fixed softmax "
                "scale, and a latent-attention model has keys of "
                f"{self.head_dim}, values of {self.v_dim} and a scale of "
                "its own: run attn='dense'")

    def apply(self, params: Dict[str, Any], tokens, positions,
              attn: str = "dense", seq_axis: str = SEQ_AXIS):
        """``tokens``/``positions``: int ``[B, T_local]`` → logits
        ``[B, T_local, V]``. ``positions`` are ABSOLUTE sequence positions
        (the host computes them per shard), so causal masking and positional
        embeddings are correct under sequence sharding."""
        return self.apply_with_aux(params, tokens, positions, attn, seq_axis)[0]

    def apply_with_aux(self, params: Dict[str, Any], tokens, positions,
                       attn: str = "dense", seq_axis: str = SEQ_AXIS,
                       grad_reduce=None, remat: str = "none"):
        """Like :meth:`apply` but also returns the summed auxiliary loss
        (0.0 for the dense-FFN base model; the MoE variant's load-balancing
        term). ``grad_reduce``/``remat`` as in :meth:`apply_hidden`."""
        h, aux = self.apply_hidden(params, tokens, positions, attn,
                                   seq_axis, grad_reduce=grad_reduce,
                                   remat=remat)
        return self._logits(params, h), aux

    def apply_hidden(self, params: Dict[str, Any], tokens, positions,
                     attn: str = "dense", seq_axis: str = SEQ_AXIS,
                     grad_reduce=None, remat: str = "none",
                     final_norm: bool = True):
        """The forward up to (and including) the final norm — everything
        except the logits projection. Lets large-vocab losses stream the
        head (:func:`chunked_summed_xent`) instead of materializing
        ``[B, T, V]``. Returns ``(h [B, T, D], aux)``;
        ``final_norm=False`` stops before the norm (what a multi-token
        prediction module reads).

        ``grad_reduce`` (training only) wraps each scan step's layer-param
        slice with a :func:`_reduce_on_backward` tag, so the per-layer
        gradient collectives fire inside the scan's backward as each
        segment completes; ``remat`` is the block-scan rematerialization
        policy (:func:`_remat_wrap`)."""
        self._latent_dense_only(attn)
        if self.hybrid and attn in ("ring", "ulysses"):
            raise NotImplementedError(
                f"attn={attn!r}: a linear-attention layer's recurrence runs "
                "along the whole sequence from a zero state, and no scan "
                "split over a sequence axis is in the program: run "
                "attn='dense' or 'flash' on one shard")
        h = self._embed(params, tokens, positions)
        rope = self._rope_for(positions)
        # Fused-rope tables are built ONCE here — inside the scanned layer
        # body XLA could not hoist them, re-materializing [B, T, Dh] f32
        # pairs every layer.
        tables = None
        if rope is not None and attn == "flash" and is_tpu_backend():
            from ..ops.pallas_flash import make_rope_tables

            cos, sin = rope
            with jax.named_scope("embed"):
                tables = make_rope_tables(cos[..., 0, :], sin[..., 0, :])

        def attend_for(w):
            return lambda q, k, v, rp=None: self._attend(
                q, k, v, attn, seq_axis, rope=rp,
                rope_tables=tables if self._rope_on(w) else None, window=w)

        def rope_for(w):
            return rope if self._rope_on(w) else None

        p = self._window_period()
        windows = self._scan_windows

        def block(h, lps):
            # p sub-layers per scan step — each with ITS static window
            # (p == 1 for uniform models: the plain layer scan)
            if grad_reduce is not None:
                lps = grad_reduce(lps)
            aux_sum = jnp.asarray(0.0, jnp.float32)
            for g in range(p):
                lp = self._layer_slice(lps, g) if p > 1 else lps
                h, aux, _, _ = self._block_fwd(
                    h, lp, attend_for(windows[g]),
                    attn, seq_axis, rope=rope_for(windows[g]),
                )
                aux_sum = aux_sum + aux
            return h, aux_sum

        def one_pass(h):
            """The whole stack once: ``(h, per-step aux, leading aux)``."""
            aux_lead = None
            for j in range(self.n_lead):   # leading layers, outside the scan
                w = self.attn_windows[j]
                h, aux, _, _ = self._block_fwd(
                    h, self._lead_params(params, j), attend_for(w), attn,
                    seq_axis, rope=rope_for(w), dense=True)
                aux_lead = aux if aux_lead is None else aux_lead + aux
            stacks = {k: params[k] for k in self._block_keys()}
            if p > 1:
                stacks = self._group_layers(stacks, p)
            with jax.named_scope("layers"):
                h, auxes = jax.lax.scan(_remat_wrap(block, remat), h, stacks)
            return h, auxes, aux_lead

        if self.passes == 1:
            h, auxes, aux_lead = one_pass(h)
        else:
            def looped(h, u):
                h, auxes, aux_lead = one_pass(self._between_passes(
                    params, h, u))
                aux = jnp.sum(auxes)
                return h, aux if aux_lead is None else aux + aux_lead

            h, auxes = jax.lax.scan(looped, h, jnp.arange(self.passes))
            aux_lead = None
        if final_norm:
            h = self._norm_h(params, "lnf", h)
        aux = jnp.sum(auxes)
        return h, aux if aux_lead is None else aux + aux_lead

    def _between_passes(self, params, h, u):
        """What pass ``u`` (traced) of a looped stack reads: the final
        norm of the last pass's output, or for pass 0 the embedding as it
        is. The norm is computed either way; a select keeps one.
        Without ``pass_norm`` the output as it is."""
        if not self.pass_norm:
            return h
        return jnp.where(u > 0, self._norm_h(params, "lnf", h).astype(
            h.dtype), h)

    def head_weight(self, params):
        """The ``[D, V]`` logits matrix (transposed token embedding under
        ``tie_embeddings`` — AD routes the gradient back through the
        transpose)."""
        return params["tok"].T if self.tie_embeddings else params["head"]

    @jax.named_scope("head")
    def _logits(self, params, h):
        """Output projection: the ``head`` matrix, or the transposed token
        embedding when ``tie_embeddings`` (Press & Wolf 2017 — halves the
        embedding-side parameter count and often improves small LMs)."""
        return h @ self.head_weight(params)

    @jax.named_scope("embed")
    def _embed(self, params, tokens, positions):
        """Token (+ learned-position) embedding in the compute dtype."""
        h = params["tok"][tokens]
        if self.pos_encoding == "learned":
            h = h + params["pos"][positions]
        return h.astype(self.compute_dtype)

    @jax.named_scope("embed")
    def _rope_for(self, positions):
        """Layer-invariant RoPE angles for ``positions`` ``[B, T]`` →
        ``(cos, sin)`` shaped ``[B, T, 1, Dh/2]``, or ``None`` for learned
        positions — computed ONCE per forward, outside the layer scan."""
        if self.pos_encoding != "rotary":
            return None
        cos, sin = _rope_angles(positions, self.rope_dim, self.rope_theta,
                                self._inv_freq)
        return cos[:, :, None, :], sin[:, :, None, :]

    def _block_fwd(self, h, lp, attend, attn: str, seq_axis: str,
                   ep_groups: Optional[int] = None, rope=None,
                   dense: bool = False):
        """One transformer block on ``h`` ``[B, T, D]`` — THE single source
        of the block math (scanned over the stacked ``[L, ...]`` params by
        the teacher-forced forward and by ``prefill``, which also needs the
        per-layer K/V). Weight matrices cast to the compute dtype at use;
        layernorm runs in f32; under ``pos_encoding="rotary"`` the q/k head
        vectors rotate by ``rope`` (from :meth:`_rope_for` — angles of the
        ABSOLUTE positions, so sequence sharding needs nothing extra, and
        the cached K are stored pre-rotated). Under grouped-query attention
        (``n_kv_heads < n_heads``) the returned (cacheable) k/v carry only
        the KV heads; they are repeated up to full heads for the attention
        compute (rotation commutes with the repeat). ``rope=None`` is a
        layer without rotation (learned positions, or a full-attention
        layer under ``rope_layers="windowed"``); ``dense=True`` is a
        leading layer, whose FFN is the dense one whatever the class.
        Returns ``(h_new, aux, k, v)``; a latent model's ``k`` is the row
        its cache keeps ``[B, T, 1, row]`` and its ``v`` ``None``."""
        if self.latent:
            return self._block_fwd_latent(h, lp, attend, attn, seq_axis,
                                          ep_groups, rope, dense)
        if "lin_qkv" in lp:
            return self._block_fwd_linear(h, lp, attn, seq_axis, ep_groups,
                                          dense)
        B, T = h.shape[0], h.shape[1]
        H = self.n_heads
        Hkv = self.n_kv_heads
        Dh = self.head_dim
        cd = self.compute_dtype
        fused_rope = rope is not None and attn == "flash"
        with jax.named_scope("attn"):
            x = self._sub_in(lp, "ln1", h)
            q = self._attn_proj(lp, "q", x).reshape(B, T, H, Dh)
            k = self._attn_proj(lp, "k", x).reshape(B, T, Hkv, Dh)
            v = self._attn_proj(lp, "v", x).reshape(B, T, Hkv, Dh)
            q, k = self._qk_normed(lp, q, k)
            if rope is not None and not fused_rope:
                q = _rope_rotate(q, *rope)
                k = _rope_rotate(k, *rope)
        if fused_rope:
            # rotation happens inside the flash attend (fused into the
            # Pallas kernels on TPU — rotated q/k never hit HBM). The
            # RETURNED k still carries the rotation for cache consumers;
            # XLA removes it when the training scan discards k.
            a = attend(q, k, v, rope).astype(cd)
            with jax.named_scope("attn"):
                k = _rope_rotate(k, *rope)
        else:
            a = attend(q, k, v).astype(cd)  # ops broadcast KV heads as needed
        h = self._attn_out(lp, h, a.reshape(B, T, self.d_attn))
        h, aux = self._ffn_residual(lp, h, attn, seq_axis, ep_groups,
                                    dense=dense)
        return h, aux, k, v

    def _block_fwd_latent(self, h, lp, attend, attn: str, seq_axis: str,
                          ep_groups, rope, dense: bool):
        """:meth:`_block_fwd` of a latent-attention layer in the PUBLISHED
        form: keys and values multiplied out of the latent rows
        (:meth:`_latent_kv`), then plain causal attention."""
        B, T = h.shape[0], h.shape[1]
        cd = self.compute_dtype
        q_nope, q_pe, row = self._latent_qc(lp, h, rope)
        with jax.named_scope("attn"):
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            k, v = self._latent_kv(lp, row)
        a = attend(q, k, v).astype(cd)
        h = self._attn_out(lp, h, a.reshape(B, T, self.d_attn))
        h, aux = self._ffn_residual(lp, h, attn, seq_axis, ep_groups,
                                    dense=dense)
        return h, aux, row[:, :, None, :], None

    def _block_fwd_linear(self, h, lp, attn: str, seq_axis: str, ep_groups,
                          dense: bool):
        """:meth:`_block_fwd` of a linear-attention layer over whole
        sequences ``h`` ``[B, T, D]`` that start at position 0: the short
        convolution from a zero tail, the chunkwise form of the recurrence
        from a zero state. Nothing of either is returned (a caller that
        caches goes through :meth:`decode_chunk`)."""
        B = h.shape[0]
        xc, z, g, beta = self._linear_in(lp, h)
        with jax.named_scope("attn"), jax.named_scope("conv"):
            y, _ = conv_chunk(
                xc, jnp.zeros((B, (self.lin_conv - 1) * xc.shape[-1]),
                              xc.dtype), lp["lin_conv"])
        q, k, v = self._linear_qkv(y)
        with jax.named_scope("attn_core"), jax.named_scope("attn_linear"):
            o, _ = gdn_chunk(q, k, v, g, beta, jnp.zeros(
                (B, self.lin_heads, self.lin_dk, self.lin_dv), jnp.float32))
        h = self._linear_out(lp, h, o, z)
        h, aux = self._ffn_residual(lp, h, attn, seq_axis, ep_groups,
                                    dense=dense)
        return h, aux, None, None

    @jax.named_scope("attn")
    def _linear_in(self, lp, h):
        """A linear layer's projections of ``h`` ``[..., D]``: ``(x [..., C]``
        the q | k | v channels BEFORE the short convolution, in
        ``act_dtype``; ``z [..., H, dv]`` what the output gate reads; ``g``,
        ``beta`` ``[..., H]`` float32: the log decay ``-exp(A_log) softplus(a +
        dt_bias)`` and the delta rule's ``sigmoid(b)``, doubled where the
        transition may have a negative eigenvalue``)``."""
        H, f32 = self.lin_heads, jnp.float32
        x = self._sub_in(lp, "ln1", h)
        cd = x.dtype
        xc = self._mm(x, lp["lin_qkv"])
        z = self._split_heads(self._mm(x, lp["lin_z"]), H)
        ab = jnp.matmul(x, lp["lin_ab"].astype(cd),
                        preferred_element_type=f32)
        beta = jax.nn.sigmoid(ab[..., :H])
        if self.lin_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(
            ab[..., H:] + lp["dt_bias"].astype(f32))
        return xc, z, g, beta

    @jax.named_scope("attn")
    def _linear_qkv(self, y):
        """The convolution's output ``y`` ``[..., C]`` float32 split into
        heads: ``(q, k [..., H, dk]`` each of unit length, q times ``dk **
        -0.5``; ``v [..., H, dv])``."""
        H, dk, dv = self.lin_heads, self.lin_dk, self.lin_dv
        lead = y.shape[:-1]
        q = l2_normalize(y[..., :H * dk].reshape(*lead, H, dk)) * dk ** -0.5
        k = l2_normalize(y[..., H * dk:2 * H * dk].reshape(*lead, H, dk))
        return q, k, y[..., 2 * H * dk:].reshape(*lead, H, dv)

    @jax.named_scope("attn")
    def _linear_out(self, lp, h, o, z):
        """The recurrence's output ``o`` ``[..., H, dv]`` through the gated
        norm (RMSNorm over a head's ``dv``, one learned scale, times the
        gate's activation of ``z``), the output projection and the
        residual."""
        cd = self.compute_dtype
        act = jax.nn.silu if self.lin_gate == "silu" else jax.nn.sigmoid
        y = self._rms(o.astype(jnp.float32), lp["lin_norm_s"]) * act(
            z.astype(jnp.float32))
        y = y.astype(cd).reshape(*o.shape[:-2], -1)
        return (h + self._sub_out(
            lp, "ln1", self._mm(y, lp["lin_o"]))).astype(h.dtype)

    def _block_keys(self):
        keys = ["ln1_s", "wq", "wk", "wv", "wo", "ln2_s", "w1", "w2"]
        if self.norm == "layernorm":
            keys += ["ln1_b", "ln2_b"]
        if self.ffn_bias:
            keys += ["b1", "b2"]
        if self.activation == "swiglu":
            keys += ["w3"]
        if self.attn_bias:
            keys += ["bq", "bk", "bv", "bo"]
        if self.qk_norm:
            keys += ["qn_s", "kn_s"]
        if self.norm_order == "sandwich":
            keys += [k + "_s" for k in self._OUT_NORMS]
        if self.latent:
            keys = [k for k in keys if k not in ("wq", "wk", "wv")]
            keys += ["wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
                     "wkv_b"]
        if self.hybrid:
            keys += list(self._LINEAR_KEYS)
        return tuple(keys)

    def _norm_h(self, lp, prefix: str, x):
        """Pre/post-block normalization in f32: layernorm (Pallas-fused on
        TPU) or scale-only rmsnorm per ``self.norm``. ``lp`` is a params
        dict (stacked layer slice or the top-level dict for ``"lnf"``)."""
        # in a profile the final norm belongs to the head, whichever
        # layout's forward calls it
        with (jax.named_scope("head") if prefix == "lnf"
              else contextlib.nullcontext()):
            x32 = x.astype(jnp.float32)
            s = lp[prefix + "_s"]
            if self.norm == "rmsnorm":
                ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                return x32 * jax.lax.rsqrt(ms + self.norm_eps) * s
            return _layer_norm(x32, s, lp[prefix + "_b"], self.norm_eps)

    # the outer norms of a ``norm_order="sandwich"`` block, by sublayer
    _OUT_NORMS = ("ln1_out", "ln2_out")

    def _sub_in(self, lp, prefix: str, h):
        """What a sublayer (mixer ``ln1``, FFN ``ln2``) reads of the
        residual stream ``h``, in the compute dtype: its norm, or under
        ``norm_order="post"`` the stream itself."""
        if self.norm_order == "post":
            return h.astype(self.compute_dtype)
        return self._norm_h(lp, prefix, h).astype(self.compute_dtype)

    def _sub_out(self, lp, prefix: str, y):
        """What a sublayer adds to the residual stream: its result ``y``,
        or under ``norm_order="post"`` the norm of it (``"sandwich"``: the
        norm ``<prefix>_out`` of it)."""
        if self.norm_order == "post":
            return self._norm_h(lp, prefix, y).astype(y.dtype)
        if self.norm_order == "sandwich":
            return self._norm_h(lp, prefix + "_out", y).astype(y.dtype)
        return y

    def _attn_proj(self, lp, name: str, x, wide: bool = False):
        """Attention projection ``x @ w<name>`` (+ ``b<name>`` under
        ``attn_bias``), in ``x``'s dtype; ``wide``: in ``act_dtype`` (the
        accumulator as it is)."""
        cd = x.dtype
        y = self._mm(x, lp["w" + name], wide)
        if self.attn_bias:
            y = y + lp["b" + name].astype(y.dtype)
        return y

    def _mm(self, x, w, wide: bool = True):
        """``x @ w`` with ``w`` in ``x``'s dtype; ``wide`` under an
        ``act_dtype`` wider than the compute dtype: the result in it."""
        if wide and self._wide:
            return jnp.matmul(x, w.astype(x.dtype),
                              preferred_element_type=self.act_dtype)
        return x @ w.astype(x.dtype)

    def _split_heads(self, y, n: int):
        """A projection's result ``y`` ``[..., n * d]`` as heads ``[..., n,
        d]``, every cached forward's way from a matmul to heads. The
        barrier keeps the compiler from folding the split into the matmul:
        folded, the dot wants its weight with the contracted axis minor,
        and since a parameter's layout is fixed the program transposes the
        layer's whole matrix, every layer, every step, to save the
        re-layout of this small result (``wq`` of K-EXAONE: 100 MB a
        layer, 2.4 ms of a 15 ms decode step; PERF.md §6, PR 35). With
        it the dot reads its layer of the stack in place. An identity:
        no bit of ``y`` changes."""
        y = jax.lax.optimization_barrier(y)
        return y.reshape(*y.shape[:-1], n, y.shape[-1] // n)

    @jax.named_scope("qk_norm")
    def _qk_normed(self, lp, q, k):
        """RMSNorm over the ``head_dim`` of every head of q and of k, one
        learned scale each (``qn_s``, ``kn_s``), before the rotation; the
        identity without ``qk_norm``."""
        if not self.qk_norm:
            return q, k
        if self.qk_norm == "whole":
            # one norm over all heads' columns, before the split
            def whole(x, scale):
                flat = x.reshape(*x.shape[:-2], -1)
                return self._rms(flat, scale).reshape(x.shape)

            return whole(q, lp["qn_s"]), whole(k, lp["kn_s"])
        return self._rms(q, lp["qn_s"]), self._rms(k, lp["kn_s"])

    @jax.named_scope("attn")
    def _qkv_heads(self, lp, h, rope):
        """``ln1`` → q/k/v projections → rotation of ``h`` ``[..., D]``, one
        position a row ``[B, D]`` or a block ``[B, S, D]`` (the cached
        decode steps and chunk forwards, dense and paged): ``(q [..., H,
        Dh], k, v [..., Hkv, Dh])``, k pre-rotated as the caches and pages
        store it (prefill does the same). ``rope`` is ``(cos, sin)``, each
        ``[B, 1, Dh/2]`` for a step, or ``None`` for a layer that does not
        rotate."""
        x = self._sub_in(lp, "ln1", h)
        q = self._split_heads(self._attn_proj(lp, "q", x), self.n_heads)
        k = self._split_heads(self._attn_proj(lp, "k", x), self.n_kv_heads)
        v = self._split_heads(self._attn_proj(lp, "v", x), self.n_kv_heads)
        q, k = self._qk_normed(lp, q, k)
        if rope is not None:
            q = _rope_rotate(q, *rope)
            k = _rope_rotate(k, *rope)
        return q, k, v

    def _rms(self, x, scale):
        """Scale-only RMSNorm over the last axis in float32, back in
        ``x``'s dtype (the q/k norms; the latent form's ``q_a_norm`` /
        ``kv_a_norm``)."""
        x32 = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(ms + self.norm_eps) * scale).astype(
            x.dtype)

    @jax.named_scope("attn")
    def _latent_qc(self, lp, h, rope):
        """``ln1`` → the latent form's projections for ``h`` ``[..., D]``
        (a chunk ``[B, S, D]`` or one position a row ``[B, D]``):
        ``(q_nope [..., H, nope], q_pe [..., H, rope] rotated, row [...,
        latent_row])``. ``row`` is what the cache keeps of a position: the
        normed latent, the ONE rotated key all heads share, zeros up to
        whole lanes."""
        cd = self.compute_dtype
        r, dn = self.kv_rank, self.nope_dim
        x = self._sub_in(lp, "ln1", h)
        q = self._split_heads(
            self._rms(x @ lp["wq_a"].astype(cd),
                      lp["q_a_norm"]) @ lp["wq_b"].astype(cd), self.n_heads)
        ckv = x @ lp["wkv_a"].astype(cd)
        c = self._rms(ckv[..., :r], lp["kv_a_norm"])
        q_pe = _rope_rotate(q[..., dn:], *rope)
        k_pe = _rope_rotate(ckv[..., None, r:], *rope)[..., 0, :]
        return q[..., :dn], q_pe, self._to_row(c, k_pe)

    def _to_row(self, latent, rotary):
        """``[latent | rotary | zeros]`` ``[..., latent_row]``: the layout
        of a cached row, and of the absorbed query that meets it."""
        x = jnp.concatenate([latent, rotary], axis=-1)
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                       + [(0, self.latent_row - x.shape[-1])])

    def _latent_kv(self, lp, rows):
        """The published form's keys and values of latent ``rows`` ``[...,
        n, latent_row]``: ``(k [..., n, H, nope + rope], v [..., n, H,
        v_dim])``, every head's key ending in the one shared rotary key."""
        cd = self.compute_dtype
        r, dn, H = self.kv_rank, self.nope_dim, self.n_heads
        lead = rows.shape[:-1]
        kv = (rows[..., :r].astype(cd) @ lp["wkv_b"].astype(cd)).reshape(
            *lead, H, dn + self.v_dim)
        k_pe = jnp.broadcast_to(
            rows[..., None, r:r + self.rope_dim].astype(cd),
            (*lead, H, self.rope_dim))
        return jnp.concatenate([kv[..., :dn], k_pe], axis=-1), kv[..., dn:]

    def _w_absorbed(self, lp):
        """``(W_UK, W_UV)``: the keys' and the values' part of ``wkv_b`` as
        ``[rank, H, nope]`` and ``[rank, H, v_dim]``, slices of the one
        stored matrix (the absorbed decode step multiplies the query by the
        first and the attended latent by the second)."""
        w = lp["wkv_b"].astype(self.compute_dtype).reshape(
            self.kv_rank, self.n_heads, self.nope_dim + self.v_dim)
        return w[..., :self.nope_dim], w[..., self.nope_dim:]

    @jax.named_scope("embed")
    def _rope_step(self, pos_b):
        """The decode steps' rotation angles for per-row positions
        ``pos_b`` ``[B]``: ``(cos, sin)`` each ``[B, 1, Dh/2]``, or
        ``None`` without rotary positions."""
        if self.pos_encoding != "rotary":
            return None
        cos, sin = _rope_angles(pos_b, self.rope_dim, self.rope_theta,
                                self._inv_freq)
        return cos[:, None, :], sin[:, None, :]

    @jax.named_scope("attn")
    def _attn_out(self, lp, h, a):
        """Output projection of the attended heads ``a`` plus the residual."""
        return (h + self._sub_out(
            lp, "ln1", self._attn_proj(lp, "o", a, wide=True))).astype(h.dtype)

    @jax.named_scope("ffn")
    def _ffn_residual(self, lp, h, attn: str, seq_axis: str,
                      ep_groups: Optional[int] = None, dense: bool = False,
                      stats: Optional[list] = None):
        """The block's second half, shared by every cached and uncached
        layer body: ``ln2`` → :meth:`_ffn` → residual, on ``h`` ``[B, T, D]``
        or (one decode position) ``[B, D]``. Returns ``(h_new, aux)``.
        ``dense=True`` (a leading layer) takes the dense FFN whatever the
        class; ``stats``, a list, is handed on to an expert FFN that
        counts its work (``MoEFeedForward.apply_dropless``)."""
        cd = self.compute_dtype
        x = self._sub_in(lp, "ln2", h)
        if dense:
            def ffn(xs):
                return TransformerLM._ffn(self, lp, xs, attn, seq_axis)
        else:
            kw = {} if stats is None else {"stats": stats}

            def ffn(xs):
                return self._ffn(lp, xs, attn, seq_axis,
                                 ep_groups=ep_groups, **kw)
        def add(out):
            out = self._sub_out(lp, "ln2", out)
            if self._wide:       # added as it is, rounded once
                return (h + out).astype(cd)
            return h + out.astype(cd)

        if h.ndim == 2:
            out, aux = ffn(x[:, None, :])
            return add(out[:, 0]), aux
        out, aux = ffn(x)
        return add(out), aux

    def _ffn(self, lp, x, attn: str, seq_axis: str,
             ep_groups: Optional[int] = None, reduce=None):
        """Per-block FFN hook → ``(residual_delta, aux_loss)``. The MoE
        variant overrides this with routed experts (which keep f32 routing
        regardless of ``compute_dtype`` — argmax ties must match the
        oracle); ``ep_groups`` overrides its dense-path dispatch grouping
        (decode passes 1 — a single position has no groups). ``reduce``
        sums partial ``w2`` outputs BEFORE the (replicated) ``b2`` — the
        tensor-parallel caller's psum hook, keeping the activation/bias
        dispatch in this one place (``models/tensor_lm.py``)."""
        del attn, seq_axis, ep_groups
        cd = x.dtype
        u = self._mm(x, lp["w1"])
        if self.ffn_bias:
            u = u + lp["b1"].astype(u.dtype)
        if self.activation == "swiglu":
            u = jax.nn.silu(u) * self._mm(x, lp["w3"])
        elif self.activation == "gelu":
            # tanh approximation == HF's gelu_new (what GPT-2 trained with)
            u = jax.nn.gelu(u, approximate=True)
        else:
            u = jax.nn.relu(u)
        out = self._mm(u.astype(cd), lp["w2"])
        if reduce is not None:
            out = reduce(out)
        if self.ffn_bias:
            out = out + lp["b2"].astype(out.dtype)
        return out, jnp.asarray(0.0, jnp.float32)

    def loss(self, params, tokens, positions, targets, attn="dense",
             seq_axis: str = SEQ_AXIS):
        """Summed next-token cross-entropy over the local shard."""
        logits = self.apply(params, tokens, positions, attn, seq_axis)
        return _summed_xent(logits, targets)

    # -- autoregressive inference (KV cache) ----------------------------
    def init_cache(self, batch: int, length: Optional[int] = None,
                   chunk: int = 1) -> Dict[str, Any]:
        """Zeroed KV cache ``{"k"/"v": [L, B, Hkv, T, Dh]}`` where ``T`` is
        ``length`` (default ``max_len``) rounded up to the flash-decode
        T-block, so the kernel never pads (a pad would recopy the cache in
        HBM every decode step); the extra positions are masked by ``pos``.
        Size ``length`` to the actual decode horizon — every step attends
        over the whole cache. T rides the sublane axis so the kernel streams
        contiguous ``[BT, Dh]`` tiles per (batch, kv-head). Under
        grouped-query attention the cache holds only the KV heads: memory
        scales down by ``n_heads / n_kv_heads``. The layers are STACKED in
        one buffer per half on purpose: :meth:`decode_step` carries the
        stack through its layer scan, writes one row a layer in place and
        hands the kernel the whole stack with a layer index, so a donated
        cache is never sliced or copied.

        Sliding-window models get a ROLLING buffer instead: ``T`` is the
        window (not the horizon — memory stays O(window) however long the
        rollout), position ``p`` writes slot ``p mod T``, and the decode
        paths mask by slot AGE. ``chunk`` adds slots of margin
        (``spec_k + 1`` for speculative decoding): a rejected draft's row
        has overwritten the position one ring length before it, and the
        margin keeps that position outside every window by then; the
        sharded walks, which write a chunk into the ring in place, need
        it for the chunk itself. :meth:`decode_chunk` needs none.

        A model of window and full layers under ``window_cache="ring"``
        gets BOTH, side by side: ``{"k"/"v": [L_full, B, Hkv, T, Dh]}`` at
        the horizon for its full layers and ``{"kw"/"vw": [L_win, B, Hkv,
        R, Dh]}`` for its window layers, ``R`` the largest window plus
        ``chunk`` rounded up to whole 128-row tiles (a window layer writes
        row ``pos mod R`` and masks by age, a full layer writes row
        ``pos``); each stack is indexed by the layer's number among the
        layers of its kind (:meth:`_cache_slots`).

        A model with LINEAR-attention layers keeps, beside the ``"k"/"v"``
        of its full layers (``[L_full, B, Hkv, T, Dh]``), what its linear
        layers remember, which does not grow with the context: ``"s":
        [L_lin, B, H/g, dk, g dv]`` in ``state_dtype`` (float32), a head's
        recurrent state with ``g`` heads side by side so a tile is whole
        lanes (``ops/gated_delta.py``), and ``"conv": [L_lin, B, (W - 1)
        C]`` in ``act_dtype``, the last ``W - 1`` inputs of the short
        convolution over the ``C`` q | k | v channels. Unlike rows of a
        cache, neither is ever repaired by a later write: what must not
        touch them does not (:meth:`decode_step`'s ``live``,
        :meth:`decode_chunk`'s ``n_valid`` and its zero start at position
        0).

        A LATENT-attention model keeps ONE stack and no values: ``{"k":
        [L, B, 1, T, latent_row]}``, a position's row the normed latent
        (``kv_lora_rank``), then the one rotary key its heads share, then
        zeros up to whole 128-column lanes (512 + 64 -> 640: 1,280 bytes a
        position a layer in bfloat16, what the chip's tiled layout would
        make of 576 columns anyway). To the absorbed decode step the row
        is the key of ONE KV head and its first ``kv_lora_rank`` columns
        the value, so the stack has the K stack's five axes and every
        consumer of ``cache["k"]``'s slot and time axes reads it
        unchanged.

        A LOOPED model (``passes`` > 1) keeps every stack ``passes`` times
        as deep: each pass attends and writes its own layers, pass ``u``'s
        layer of number ``n`` among its stack's at ``u * n_stack + n``
        (``n_stack`` the stack's layers a pass)."""
        L, U = self.n_layers, self.passes
        T_req = self.max_len if length is None else length
        if self.hybrid:
            Ln, H = self.n_linear, self.lin_heads
            g = state_group(H, self.lin_dv)
            kv = (U * (L - Ln), batch, self.n_kv_heads,
                  aligned_cache_length(T_req), self.head_dim)
            return {
                "k": jnp.zeros(kv, self.compute_dtype),
                "v": jnp.zeros(kv, self.compute_dtype),
                "s": jnp.zeros((U * Ln, batch, H // g, self.lin_dk,
                                g * self.lin_dv), self.state_dtype),
                "conv": jnp.zeros(
                    (U * Ln, batch, (self.lin_conv - 1) * self.lin_channels),
                    self.act_dtype)}
        if self.latent:
            return {"k": jnp.zeros(
                (U * L, batch, 1, aligned_cache_length(T_req),
                 self.latent_row), self.compute_dtype)}
        if self._two_kind:
            n_win = sum(w is not None for w in self.attn_windows)
            R = aligned_cache_length(
                -(-(self._max_window + int(chunk)) // 128) * 128)
            T = aligned_cache_length(T_req)
            Dh, Hkv, cd = self.head_dim, self.n_kv_heads, self.compute_dtype
            return {"k": jnp.zeros((U * (L - n_win), batch, Hkv, T, Dh), cd),
                    "v": jnp.zeros((U * (L - n_win), batch, Hkv, T, Dh), cd),
                    "kw": jnp.zeros((U * n_win, batch, Hkv, R, Dh), cd),
                    "vw": jnp.zeros((U * n_win, batch, Hkv, R, Dh), cd)}
        if self._ring_cache:
            # window-clamped buffers carry `chunk` extra slots (not
            # chunk-1): the buffer is then strictly LARGER than the
            # window, which tells a clamped ring (T > window: it wraps)
            # from a horizon-bounded one (T <= window: the whole rollout
            # fits, nothing ever wraps). Mixed all-windowed
            # models share one ring sized to the LARGEST window (smaller-
            # window layers mask more slots by age; a model with any
            # full-attention layer takes the horizon branch instead).
            T_req = min(T_req, self._max_window) + int(chunk)
        T = aligned_cache_length(T_req)
        shape = (U * L, batch, self.n_kv_heads, T, self.head_dim)
        # two DISTINCT buffers: the serving kernels donate the cache, and
        # XLA refuses to donate one buffer twice (`{"k": z, "v": z}` would
        # alias them)
        return {"k": jnp.zeros(shape, self.compute_dtype),
                "v": jnp.zeros(shape, self.compute_dtype)}

    def prefill(self, params, tokens, cache, ffn_tag: str = "dense"):
        """Batched prompt ingestion: run the full (matrix-matrix) forward
        over ``tokens`` ``[B, T0]``, writing every position's K/V into
        ``cache`` at offset 0. Returns ``(logits [B, T0, V], cache)``.

        ``ffn_tag`` routes the per-block FFN: ``"dense"`` (default) is the
        single-device path (MoE uses its full-expert-stack oracle); a
        non-dense tag makes the MoE FFN dispatch over the LIVE ``"seq"``
        mesh axis against local expert shards — what
        ``models/sharded_generate.py`` passes. The attention math is
        identical either way (the tag only reaches ``_ffn``)."""
        B, T0 = tokens.shape
        if self.hybrid or self.passes > 1:
            # a linear layer's state and tail, and a looped stack's later
            # passes, are written by the cached chunk forward alone
            # (position 0: from zero)
            return self.decode_chunk(params, tokens, 0, cache)
        positions = jnp.broadcast_to(jnp.arange(T0), (B, T0))
        h = self._embed(params, tokens, positions)

        rope = self._rope_for(positions)

        def prefill_attend_for(w):
            # Long prompts: fused flash attention on TPU keeps prefill
            # memory O(tile) instead of the dense T² score tensor; the
            # Pallas kernels pad and mask arbitrary prompt lengths
            # internally, so no pre-padding is needed here.
            @jax.named_scope("attn_core")
            def attend(q, k, v):
                # (a latent model's keys and values differ in size: the
                # dense path, whatever the backend)
                if self.latent or not is_tpu_backend():
                    return attention_reference(q, k, v, causal=True,
                                               window=w,
                                               scale=self.attn_scale)
                return flash_attention(q, k, v, causal=True, window=w)

            return attend

        def rope_for(w):
            return rope if self._rope_on(w) else None

        lead_ks, lead_vs = [], []
        for j in range(self.n_lead):   # leading layers, outside the scan
            w = self.attn_windows[j]
            h, _, k, v = self._block_fwd(
                h, self._lead_params(params, j), prefill_attend_for(w),
                ffn_tag, SEQ_AXIS, ep_groups=1, rope=rope_for(w), dense=True)
            lead_ks.append(k)
            lead_vs.append(v)

        p = self._window_period()
        windows = self._scan_windows
        n_scan = len(windows)
        lps = {k: params[k] for k in self._block_keys()}

        def block(h, lps_g):
            ks_g, vs_g = [], []
            for g in range(p):
                lp = self._layer_slice(lps_g, g) if p > 1 else lps_g
                h, _, k, v = self._block_fwd(
                    h, lp, prefill_attend_for(windows[g]),
                    ffn_tag, SEQ_AXIS, ep_groups=1, rope=rope_for(windows[g]),
                )
                ks_g.append(k)
                vs_g.append(v)
            if p == 1:
                return h, (ks_g[0], vs_g[0])
            return h, (jnp.stack(ks_g), jnp.stack(vs_g))

        if p > 1:
            lps = self._group_layers(lps, p)
        with jax.named_scope("layers"):
            h, (ks, vs) = jax.lax.scan(block, h, lps)
        if self.latent:
            # the rows [L, B, T0, 1, row] into the one stack at 0..T0-1
            with jax.named_scope("kv_write"):
                if lead_ks:
                    ks = jnp.concatenate([jnp.stack(lead_ks), ks])
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], ks.transpose(0, 1, 3, 2, 4).astype(
                        cache["k"].dtype), 0, axis=3)
            h = self._norm_h(params, "lnf", h)
            return self._logits(params, h), {**cache, "k": ck}
        if p > 1:  # [L/p, p, B, T0, Hkv, Dh] → [L, B, T0, Hkv, Dh]
            ks = _period_ungroup(ks, n_scan)
            vs = _period_ungroup(vs, n_scan)
        with jax.named_scope("kv_write"):
            if lead_ks:
                ks = jnp.concatenate([jnp.stack(lead_ks), ks])
                vs = jnp.concatenate([jnp.stack(lead_vs), vs])
            # → cache layout [L, B, Hkv, T0, Dh]
            ks = ks.transpose(0, 1, 3, 2, 4)
            vs = vs.transpose(0, 1, 3, 2, 4)
        if self._two_kind:
            # each kind into its own stack, in layer order: the full
            # layers' prompt at rows 0..T0-1, the window layers' last R
            # positions at their ``p mod R`` slots
            full = np.array([l for l, w in enumerate(self.attn_windows)
                             if w is None])
            win = np.array([l for l, w in enumerate(self.attn_windows)
                            if w is not None])
            ck, cv = write_prompt_cache(cache["k"], cache["v"], ks[full],
                                        vs[full], False)
            ckw, cvw = write_prompt_cache(cache["kw"], cache["vw"], ks[win],
                                          vs[win], True)
            cache = {**cache, "k": ck, "v": cv, "kw": ckw, "vw": cvw}
        else:
            ck, cv = write_prompt_cache(cache["k"], cache["v"], ks, vs,
                                        self._ring_cache)
            cache = {**cache, "k": ck, "v": cv}
        h = self._norm_h(params, "lnf", h)
        return self._logits(params, h), cache

    def prefill_slot(self, params, tokens, slot, cache, pos0=0,
                     n_valid=None):
        """Prompt ingestion into ONE batch row of a multi-slot cache: run
        :meth:`decode_chunk` over ``tokens`` ``[1, T0]`` at positions
        ``pos0..pos0+T0-1`` against slot ``slot``'s (traced int) rows of
        ``cache`` ``{"k"/"v": [L, S, Hkv, T, Dh]}`` →
        ``(logits [1, T0, V], cache)``.

        ``pos0`` (traced int, default 0) is the CHUNKED-prefill hook: a
        long prompt lands as fixed-size chunks, each continuing where the
        last stopped, with decode steps for other slots interleaved
        between chunks (``serving/engine.py``). A chunk at ``pos0 > 0``
        attends the slot's existing cache rows ``0..pos0-1`` plus its own
        earlier positions — exactly what ``decode_chunk`` already
        computes, so chunk boundaries cannot change the math.

        The serving engine's prefill-insert primitive
        (``serving/cache.py``): a new request lands in a free slot without
        touching the other slots' state, and the chunked cached forward is
        exactly a prefill when it starts at position 0 (pinned against the
        teacher-forced forward in ``tests/models/test_speculative.py``).
        ``tokens`` may be right-padded past the real prompt (bucketed
        compile reuse): pad positions write K/V the decode loop overwrites
        before any query attends them — the same staleness-repair invariant
        speculative decoding relies on — and their logits are garbage the
        caller must not sample from (take row ``T0_real − 1``).

        Rolling (all-windowed) caches are refused: :meth:`decode_chunk`
        takes them, but the margin that lets a ring roll back rejected
        drafts is kept per rollout, not per slot (``serving/cache.py``
        documents the restriction). A model
        with TWO kinds of cache is served: its window layers' rings are
        filled from the real tokens only, so a padded ``tokens`` needs
        ``n_valid`` (how many of them are real; traced), see
        :meth:`decode_chunk`."""
        if self._ring_cache:
            raise NotImplementedError(
                "prefill_slot needs a linear (horizon) cache; all-windowed "
                "models allocate rolling buffers — serve those with at "
                "least one full-attention layer, or without slot batching"
            )
        slot_cache = cache_gather_slot(cache, slot)
        logits, slot_cache = self.decode_chunk(params, tokens, pos0,
                                               slot_cache, n_valid=n_valid)
        return logits, cache_scatter_slot(cache, slot, slot_cache)

    def decode_step(self, params, token, pos, cache, live=None):
        """One cached decode step: ``token`` ``[B]`` int at absolute
        position ``pos`` (scalar, or per-row ``[B]`` — batched speculative
        decoding advances rows independently) → ``(logits [B, V] f32,
        new_cache)``. Attends over cache positions ``0..pos``; for the
        dense model this is bit-close to the teacher-forced forward one
        position at a time. The MoE variant routes each decoded position
        as its OWN dispatch group (the causally correct choice — no future
        competition), which intentionally differs from teacher-forced
        whole-block routing.

        The cache is updated IN PLACE: the stacked ``[L, B, Hkv, T, Dh]``
        buffers are part of the layer scan's carry, layer ``l`` writes its
        one new K and V row per batch row (``kv_write``) and attends
        through the kernel's stacked-cache form with layer index ``l``
        (mixed-window models: scan step ``i``, group ``g`` → layer
        ``i·p + g``; with two kinds of cache, ``window_cache="ring"``,
        each layer in the stack of its kind: :meth:`_cache_slots`). Jitted
        with the cache donated (every serving kernel; a rollout's
        ``lax.scan`` carry) the program holds no second cache and moves no
        more than the new rows.

        ``live`` ``[B]`` bool (``None``: every row) says which rows are
        real. A row that is not (the serving engine's free slots and its
        parked partial prefills ride every batch) still writes a K/V row
        at its position, which a later write repairs; a linear layer's
        state and convolution tail are folded into themselves and never
        repaired, so such a row leaves them as they were."""
        B = token.shape[0]
        H = self.n_heads
        Hkv = self.n_kv_heads
        Dh = self.head_dim
        cd = self.compute_dtype
        pos = jnp.asarray(pos)
        pos_b = jnp.broadcast_to(pos, (B,))
        h = self._embed(params, token, pos_b)  # [B, D]
        rope = self._rope_step(pos_b)

        def one_layer(h, lp, cache, window, names, layer, dense=False):
            kn, vn = names
            ring = self._is_ring(kn)
            q, k_new, v_new = self._qkv_heads(
                lp, h, rope if self._rope_on(window) else None)
            with jax.named_scope("kv_write"):
                T = cache[kn].shape[3]
                ck, cv = cache_write_row(
                    cache[kn], cache[vn], k_new, v_new, layer,
                    jnp.mod(pos, T) if ring else pos)
            # grouped attention straight against layer `layer` of the
            # stacked Hkv-head cache (query head h = kv_head·G + g,
            # matching the repeat layout the training paths broadcast to):
            # flash-decode Pallas kernel on TPU (one VMEM pass over the
            # layer, read in place), einsum reference elsewhere
            with jax.named_scope("attn_core"), jax.named_scope(
                    "attn_full" if window is None else "attn_window"):
                a = decode_attention(
                    q.reshape(B, Hkv, H // Hkv, Dh), ck, cv, pos,
                    window=window, ring=ring, layer=layer).astype(cd)
            return self._cached_layer_tail(
                lp, h, a.reshape(B, self.d_attn), {**cache, kn: ck, vn: cv},
                dense, 0)

        def latent_layer(h, lp, cache, window, names, layer, dense=False):
            # the ABSORBED form: W_UK goes into the query and W_UV into the
            # output, so every head attends the cached rows themselves
            # (score against a whole row, value its first kv_rank columns)
            # and no key or value is multiplied out
            (kn,) = names
            q_nope, q_pe, row = self._latent_qc(lp, h, rope)
            w_uk, w_uv = self._w_absorbed(lp)
            with jax.named_scope("attn"), jax.named_scope("mla_absorb"):
                q = self._to_row(
                    jnp.einsum("bhd,rhd->bhr", q_nope, w_uk), q_pe)
            with jax.named_scope("kv_write"):
                ck = latent_write_row(cache[kn], row, layer, pos)
            with jax.named_scope("attn_core"), jax.named_scope(
                    "attn_latent"):
                o = latent_decode_attention(
                    q, ck, pos, layer=layer, rank=self.kv_rank,
                    scale=self.attn_scale).astype(cd)
            with jax.named_scope("attn"), jax.named_scope("mla_absorb"):
                a = jnp.einsum("bhr,rhd->bhd", o, w_uv)
            return self._cached_layer_tail(
                lp, h, a.reshape(B, self.d_attn), {**cache, kn: ck}, dense,
                0)

        def linear_layer(h, lp, cache, window, names, layer, dense=False):
            # one read and one write of a live row's state (the kernel's),
            # and of its tail; a row that is not live keeps both
            sn, cn = names
            xc, z, g, beta = self._linear_in(lp, h)
            tail = jax.lax.dynamic_index_in_dim(cache[cn], layer, 0,
                                                keepdims=False)
            with jax.named_scope("attn"), jax.named_scope("conv"):
                y, new_tail = conv_step(xc, tail, lp["lin_conv"])
            with jax.named_scope("kv_write"):
                if live is not None:
                    new_tail = jnp.where(live[:, None], new_tail, tail)
                cc = jax.lax.dynamic_update_index_in_dim(
                    cache[cn], new_tail, layer, 0)
            q, k, v = self._linear_qkv(y)
            with jax.named_scope("attn_core"), jax.named_scope(
                    "attn_linear"):
                o, cs = gdn_decode_update(q, k, v, g, beta, cache[sn],
                                          layer, live)
            return self._cached_ffn(lp, self._linear_out(lp, h, o, z),
                                    {**cache, sn: cs, cn: cc}, dense, 0)

        h, cache = self._walk_cached(
            params, h, cache,
            self._layer_body(one_layer, latent_layer, linear_layer))
        h = self._norm_h(params, "lnf", h)
        return self._logits(params, h), cache

    def _layer_body(self, one_layer, latent_layer, linear_layer):
        """The ``one_layer`` :meth:`_walk_cached` calls, chosen layer by
        layer from a cached forward's bodies by where the layer's memory
        lives (a linear layer's is the state stack ``"s"``)."""
        if self.latent:
            return latent_layer

        def body(h, lp, cache, window, names, layer, dense=False):
            pick = linear_layer if names[0] == "s" else one_layer
            return pick(h, lp, cache, window, names, layer, dense)

        return body if self.hybrid else one_layer

    def _cached_layer_tail(self, lp, h, a, cache, dense: bool, row: int):
        """What every cached layer body ends with: the output projection
        of the attended heads ``a`` and the residual, then the FFN half
        (counting an expert layer's work into row ``row`` of the cache's
        ``moe_counts``) → ``(h, cache)``."""
        return self._cached_ffn(lp, self._attn_out(lp, h, a), cache, dense,
                                row)

    def _cached_ffn(self, lp, h, cache, dense: bool, row: int):
        """The FFN half of a cached layer body (a linear layer's too)."""
        stats = [] if "moe_counts" in cache and not dense else None
        h, _ = self._ffn_residual(lp, h, "dense", SEQ_AXIS, 1, dense=dense,
                                  stats=stats)
        return h, _count_moe(cache, stats, row)

    def _walk_cached(self, params, h, cache, one_layer):
        """Run ``one_layer(h, lp, cache, window, names, layer, dense)`` →
        ``(h, cache)`` over every layer with the WHOLE cache in the carry:
        the leading layers one by one, then the layer scan over the
        scanned stacks and a step counter only (``p`` sub-layers a step for
        a periodic window pattern). ``names``/``layer`` say where the
        layer's K/V live (:meth:`_cache_slots`). Each layer writes its new
        rows into the carried stacks and reads its layer in place, so
        under donation the program never slices, restacks or copies a
        layer of the cache (as xs/ys of the scan it did all three).

        A looped stack (``passes`` > 1) runs that walk in a scan over the
        passes, the cache still in the carry: pass ``u`` reads
        :meth:`_between_passes` of the last pass's output and attends and
        writes its own cache layers (:meth:`init_cache`) with the same
        weights."""
        if self.passes == 1:
            return self._walk_pass(params, h, cache, one_layer)

        def one_pass(carry, u):
            h, cache = carry
            return self._walk_pass(params, self._between_passes(
                params, h, u), cache, one_layer, u), None

        (h, cache), _ = jax.lax.scan(one_pass, (h, cache),
                                     jnp.arange(self.passes))
        return h, cache

    def _walk_pass(self, params, h, cache, one_layer, u=None):
        """:meth:`_walk_cached`'s walk over the stack once; ``u`` (traced)
        the pass of a looped stack, whose layers of a cache stack are
        ``u`` passes' worth further in."""
        lead, scan = self._cache_slots()
        if u is None:
            def at(names, layer):
                return layer
        else:
            per = {k: v.shape[0] // self.passes for k, v in cache.items()}

            def at(names, layer):
                return u * per[names[0]] + layer
        for j, (names, layer) in enumerate(lead):
            h, cache = one_layer(h, self._lead_params(params, j), cache,
                                 self.attn_windows[j], names,
                                 at(names, layer), dense=True)
        p = self._window_period()
        windows = self._scan_windows

        whole = self._stacked_keys()

        def block(carry, inputs, unrolled: bool = False):
            h, cache = carry
            lp, i = inputs  # layer params (×p if mixed); scan step
            for g, (names, base, step) in enumerate(scan):
                if unrolled:     # ``lp``: the whole stacks, ``i`` static
                    lp_g = self._layer_slice(lp, g, step=i)
                else:
                    lp_g = self._layer_slice(lp, g) if p > 1 else lp
                if whole:
                    lp_g = {**lp_g,
                            **{k: (params[k], i * p + g) for k in whole}}
                h, cache = one_layer(h, lp_g, cache, windows[g], names,
                                     at(names, i * step + base))
            return (h, cache), None

        lps = {k: params[k] for k in self._block_keys() if k not in whole}
        steps = len(windows) // p
        with jax.named_scope("layers"):
            if p > 1 and (steps == 1 or (
                    self.hybrid and steps <= self._UNROLL_STEPS)):
                # a pattern with no shorter period than the stack itself
                # (a cut of one period; a depth its period does not
                # divide) or a cut of two periods: no loop, and each
                # layer's weights are ONE static index into their stack,
                # which the compiler reads in place, where a scan over
                # grouped stacks (and a reshape-then-index by hand) copies
                # a step's slice of every stack: 12.8 ms of a 32.8 ms
                # decode step on the chip for two periods of four 7B-wide
                # layers (PERF.md §6, PR 34). "In place" held for the
                # stacks whose product is used as it comes (``wo``, the
                # FFN's, ``lin_qkv``); ``wq`` / ``wk`` / ``wv`` and
                # ``lin_z`` were sliced AND transposed, scan or no scan,
                # because their product was reshaped into heads straight
                # after the dot. Since :meth:`_split_heads` it holds for
                # every stack a cached layer multiplies by but one: a
                # latent layer's ``wkv_b``, whose heads are the BATCH of
                # the absorbed step's two products (PERF.md §7, PR 35)
                for i in range(steps):
                    (h, cache), _ = block((h, cache), (lps, i), True)
            else:
                if p > 1:
                    lps = self._group_layers(lps, p)
                (h, cache), _ = jax.lax.scan(
                    block, (h, cache), (lps, jnp.arange(steps)))
        return h, cache

    # periods of linear and full layers that the cached walk unrolls (a
    # model of window and full layers keeps its scan from two periods on:
    # tests/models/test_decode_cache_carry.py pins that form)
    _UNROLL_STEPS = 2

    # queries a block: the chunk forward's score tensors are ``[B, H,
    # block, keys]`` however long the chunk (a 4,096-token prompt against
    # an 8,192-position horizon would otherwise need 8 GiB)
    _CHUNK_Q_BLOCK = 512
    # ...and how many static horizons a latent layer's chunk forward has a
    # branch for: the cache's length, its half, quarter and eighth
    _LATENT_HORIZON_BUCKETS = 4

    def decode_chunk(self, params, tokens, pos0, cache, n_valid=None):
        """Cached forward over a BLOCK of ``S`` tokens at absolute positions
        ``pos0..pos0+S-1`` → ``(logits [B, S, V] f32, new_cache)``.

        The verification primitive for speculative decoding: the target
        model scores all drafted positions in one matrix-matrix pass
        instead of ``S`` sequential decode steps; and the prefill-insert
        (:meth:`prefill_slot`), chunked or whole. Each query attends cache
        positions ``0..its own position`` — so a chunk starting at the
        first stale cache position also *repairs* it (see
        :meth:`generate_speculative`'s invariant). ``pos0`` may be traced,
        and may be per-row ``[B]`` (batched speculative verification).
        Like :meth:`decode_step`, the MoE variant routes the chunk as its
        own dispatch group. The whole cache rides the layer scan's carry
        (:meth:`_walk_cached`), as in :meth:`decode_step`.

        A layer whose cache is a HORIZON stack writes the chunk's rows at
        ``pos0..`` of its layer and attends rows ``0..its own position``
        (within its window, where it has one), ``_CHUNK_Q_BLOCK`` queries
        at a time.

        A layer whose cache is a RING (a window layer's own stack under
        ``window_cache="ring"``; the one rolling stack of a model whose
        every layer is windowed) never attends the ring in place: a chunk
        longer than the ring would overwrite rows its own earlier queries
        need. It lays the ring out in position order (row ``r`` = position
        ``pos0 - R + r``), appends the chunk's K/V, and lets each block of
        queries see the band of ``block + window - 1`` rows that ends at
        its last query: exact, ``O(S * window)``, and in need of no chunk
        margin in the ring. The window in effect is ``min(window, R)``: a
        horizon-bounded ring (``R <= window``) holds the whole rollout and
        nothing older exists. Then the ring takes, slot by slot, the LAST
        position ``<= pos0 + n_valid - 1`` that maps there, from the chunk
        if the chunk holds it: ``n_valid`` (default: all ``S``) is how many
        of the chunk's tokens are real. Bucket padding past them would
        otherwise push real keys out of the ring, and unlike a horizon
        cache's padding rows they would never be repaired."""
        B, S = tokens.shape
        H, Hkv, Dh, cd = (self.n_heads, self.n_kv_heads, self.head_dim,
                          self.compute_dtype)
        G = H // Hkv
        f32 = jnp.float32
        pos0 = jnp.asarray(pos0)
        per_row = pos0.ndim == 1
        pos0_b = jnp.broadcast_to(pos0.reshape(-1), (B,))
        pos_b = pos0_b[:, None] + jnp.arange(S)[None, :]   # [B, S]
        n_valid = S if n_valid is None else n_valid
        h = self._embed(params, tokens, pos_b)  # [B, S, D]
        rope = self._rope_for(pos_b)
        qb = self._CHUNK_Q_BLOCK
        qb = qb if (S > qb and S % qb == 0) else S
        nb = S // qb
        scale = Dh ** -0.5 if self.attn_scale is None else self.attn_scale

        def attend_blocks(qg, keys_for, mask_for):
            """``qg`` ``[B, Hkv, G, S, Dh]`` → ``[B, Hkv, G, S, Dh]`` (the
            values' head size where it is another):
            softmax(q k / sqrt(Dh)) v, ``qb`` queries at a time, over
            ``keys_for(i0)`` → ``(k, v [B, Hkv, n, Dh])`` under
            ``mask_for(i0)`` → ``[B, qb, n]`` for the block at ``i0``."""
            def one(i0):
                qs = jax.lax.dynamic_slice_in_dim(qg, i0, qb, axis=3)
                k, v = keys_for(i0)
                scores = jnp.einsum(
                    "bkgsd,bktd->bkgst", qs, k,
                    preferred_element_type=f32,
                    precision=jax.lax.Precision.HIGHEST) * scale
                scores = jnp.where(mask_for(i0)[:, None, None], scores,
                                   -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                return jnp.einsum(
                    "bkgst,bktd->bkgsd", probs, v,
                    preferred_element_type=f32,
                    precision=jax.lax.Precision.HIGHEST).astype(cd)

            if nb == 1:
                return one(0)
            out = jax.lax.map(one, jnp.arange(nb) * qb)  # [nb, B,Hkv,G,qb,Dh]
            return jnp.moveaxis(out, 0, 3).reshape(B, Hkv, G, S,
                                                   out.shape[-1])

        def block_pos(i0):                  # [B, qb] the block's positions
            return jax.lax.dynamic_slice_in_dim(pos_b, i0, qb, axis=1)

        def full_layer(qg, k_new, v_new, ck, cv, layer, window):
            kc = jax.lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)
            with jax.named_scope("kv_write"):
                kc = _cache_update_rows(kc, k_new, pos0, per_row)
                vc = _cache_update_rows(vc, v_new, pos0, per_row)
                ck = jax.lax.dynamic_update_index_in_dim(ck, kc, layer, 0)
                cv = jax.lax.dynamic_update_index_in_dim(cv, vc, layer, 0)
            slots = jnp.arange(kc.shape[2])[None, None, :]

            def mask_for(i0):
                at = block_pos(i0)[:, :, None]
                m = slots <= at
                if window is not None:
                    m &= slots > at - window
                return m

            with jax.named_scope("attn_core"), jax.named_scope(
                    "attn_full" if window is None else "attn_window"):
                a = attend_blocks(qg, lambda i0: (kc, vc), mask_for)
            return a, ck, cv

        def ring_layer(qg, k_new, v_new, ck, cv, layer, window):
            kr = jax.lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
            vr = jax.lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)
            R = kr.shape[2]
            window = min(window, R)
            # the ring in position order: row r holds position pos0-R+r
            src = jnp.mod(pos0_b[:, None] - R + jnp.arange(R)[None, :], R)
            src = src[:, None, :, None]
            k_all = jnp.concatenate(
                [jnp.take_along_axis(kr, src, axis=2), k_new], axis=2)
            v_all = jnp.concatenate(
                [jnp.take_along_axis(vr, src, axis=2), v_new], axis=2)
            band = qb + window - 1           # rows a block of queries sees

            def keys_for(i0):
                lo = R + i0 - window + 1
                return (jax.lax.dynamic_slice_in_dim(k_all, lo, band, axis=2),
                        jax.lax.dynamic_slice_in_dim(v_all, lo, band, axis=2))

            def mask_for(i0):
                # band row c is row lo+c of k_all, position pos0+i0-window+1+c
                c = jnp.arange(band)[None, None, :]
                i = jnp.arange(qb)[None, :, None]
                at = pos0_b[:, None, None] + i0 - window + 1 + c
                return (c <= i + window - 1) & (c > i - 1) & (at >= 0)

            with jax.named_scope("attn_core"), jax.named_scope(
                    "attn_window"):
                a = attend_blocks(qg, keys_for, mask_for)
            with jax.named_scope("kv_write"):
                # slot s takes the last position <= the last REAL one that
                # maps to it, if the chunk holds that position
                last = (pos0_b + n_valid - 1)[:, None]
                slot = jnp.arange(R)[None, :]
                j = last - jnp.mod(last - slot, R) - pos0_b[:, None]
                take = (j >= 0)[:, None, :, None]
                jj = jnp.clip(j, 0, S - 1)[:, None, :, None]
                kr = jnp.where(take, jnp.take_along_axis(k_new, jj, axis=2),
                               kr)
                vr = jnp.where(take, jnp.take_along_axis(v_new, jj, axis=2),
                               vr)
                ck = jax.lax.dynamic_update_index_in_dim(ck, kr, layer, 0)
                cv = jax.lax.dynamic_update_index_in_dim(cv, vr, layer, 0)
            return a, ck, cv

        def one_layer(h, lp, cache, window, names, layer, dense=False):
            kn, vn = names
            q, k_new, v_new = self._qkv_heads(
                lp, h, rope if self._rope_on(window) else None)
            qg = q.transpose(0, 2, 1, 3).reshape(B, Hkv, G, S, Dh)
            k_new = k_new.transpose(0, 2, 1, 3).astype(cache[kn].dtype)
            v_new = v_new.transpose(0, 2, 1, 3).astype(cache[vn].dtype)
            attend = ring_layer if self._is_ring(kn) else full_layer
            a, ck, cv = attend(qg, k_new, v_new, cache[kn], cache[vn],
                               layer, window)
            a = a.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)
            return self._cached_layer_tail(
                lp, h, a.reshape(B, S, self.d_attn),
                {**cache, kn: ck, vn: cv}, dense, 1)

        def latent_layer(h, lp, cache, window, names, layer, dense=False):
            # the PUBLISHED form: the chunk's rows go into the stack, keys
            # and values are multiplied out of the rows a query can see,
            # and those are attended. Which rows that is, is static: the
            # horizon in power-of-two buckets from the chunk's length up,
            # one branch each in this one program, chosen by ``pos0 + S``
            # as it runs, so a prompt at the start of a long horizon
            # neither multiplies out nor attends the dead rest of it
            (kn,) = names
            q_nope, q_pe, rows = self._latent_qc(lp, h, rope)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            qg = q.transpose(0, 2, 1, 3)[:, :, None]       # [B, H, 1, S, Dh]
            kc = jax.lax.dynamic_index_in_dim(cache[kn], layer, 0,
                                              keepdims=False)
            with jax.named_scope("kv_write"):
                kc = _cache_update_rows(
                    kc, rows[:, None].astype(kc.dtype), pos0, per_row)
                ck = jax.lax.dynamic_update_index_in_dim(cache[kn], kc,
                                                         layer, 0)
            buckets = [kc.shape[2]]
            while (len(buckets) < self._LATENT_HORIZON_BUCKETS
                   and buckets[0] % 2 == 0 and buckets[0] // 2 >= S):
                buckets.insert(0, buckets[0] // 2)

            def attend_first(n):
                with jax.named_scope("attn"):
                    k, v = self._latent_kv(lp, kc[:, 0, :n])
                    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
                slots = jnp.arange(n)[None, None, :]
                with jax.named_scope("attn_core"), jax.named_scope(
                        "attn_latent"):
                    return attend_blocks(
                        qg, lambda i0: (k, v),
                        lambda i0: slots <= block_pos(i0)[:, :, None])

            need = jnp.max(pos0_b) + S
            a = jax.lax.switch(
                sum((need > n).astype(jnp.int32) for n in buckets[:-1])
                if len(buckets) > 1 else 0,
                [partial(attend_first, n) for n in buckets])
            a = a[:, :, 0].transpose(0, 2, 1, 3)           # [B, S, H, Dv]
            return self._cached_layer_tail(
                lp, h, a.reshape(B, S, self.d_attn), {**cache, kn: ck},
                dense, 1)

        def linear_layer(h, lp, cache, window, names, layer, dense=False):
            # the chunkwise form from the slot's own state and tail, or
            # from ZERO where the chunk starts at position 0 (a new
            # occupant: what the slot's last one left is not its past);
            # nothing past ``n_valid`` enters either
            sn, cn = names
            xc, z, g, beta = self._linear_in(lp, h)
            fresh = pos0_b == 0
            tail = jax.lax.dynamic_index_in_dim(cache[cn], layer, 0,
                                                keepdims=False)
            s0 = unpack_state(jax.lax.dynamic_index_in_dim(
                cache[sn], layer, 0, keepdims=False), self.lin_heads)
            tail = jnp.where(fresh[:, None], 0, tail)
            s0 = jnp.where(fresh[:, None, None, None], 0,
                           s0.astype(jnp.float32))
            with jax.named_scope("attn"), jax.named_scope("conv"):
                y, tail = conv_chunk(xc, tail, lp["lin_conv"], n_valid)
            q, k, v = self._linear_qkv(y)
            with jax.named_scope("attn_core"), jax.named_scope(
                    "attn_linear"):
                o, s1 = gdn_chunk(q, k, v, g, beta, s0, n_valid)
            with jax.named_scope("kv_write"):
                cs = jax.lax.dynamic_update_index_in_dim(
                    cache[sn], pack_state(s1).astype(cache[sn].dtype),
                    layer, 0)
                cc = jax.lax.dynamic_update_index_in_dim(
                    cache[cn], tail, layer, 0)
            return self._cached_ffn(lp, self._linear_out(lp, h, o, z),
                                    {**cache, sn: cs, cn: cc}, dense, 1)

        h, cache = self._walk_cached(
            params, h, cache,
            self._layer_body(one_layer, latent_layer, linear_layer))
        h = self._norm_h(params, "lnf", h)
        return self._logits(params, h), cache

    def _refuse_layout(self, what: str) -> None:
        """The parallel builders (tensor, FSDP, pipeline, the sharded
        generators) walk ``[L, ...]`` stacks of ONE kind of pre-norm layer
        and K/V caches alone; each calls this first."""
        if self.hybrid:
            raise NotImplementedError(
                f"{what}: a model with linear-attention layers stacks its "
                "mixer leaves by kind of layer ([n_linear, ...] beside "
                "[n_full, ...]) and carries a recurrent state a sequence, "
                "and this builder walks [L, ...] stacks of one kind and K/V "
                "caches alone: run it on one device (apply, generate, "
                "ServingEngine)")
        if self.passes > 1:
            raise NotImplementedError(
                f"{what}: a looped stack (passes > 1) runs its layers "
                "several times a token into a cache of a layer a pass, and "
                "this builder walks each layer once: run it on one device "
                "(apply, generate, ServingEngine)")
        if (self.norm_order != "pre" or self.qk_norm == "whole"
                or self.rope_layers == "none"):
            raise NotImplementedError(
                f"{what}: norm_order='post' or 'sandwich', qk_norm='whole' "
                "and rope_layers='none' are read by the single-device "
                "forwards only, and this builder has its own block")

    def _refuse_paged(self, what: str) -> None:
        """The paged forms walk one pool of every layer as scanned input;
        they know neither a second kind of cache nor leading layers, and
        a page holds K and V rows of one head size."""
        if self.latent:
            raise NotImplementedError(
                f"{what}: a latent-attention model caches one latent row a "
                "position, and the paged pool holds pages of per-head K and "
                "V rows: there is no latent page pool yet")
        if self.hybrid:
            raise NotImplementedError(
                f"{what}: a linear-attention layer keeps a recurrent state a "
                "slot, and the paged pool holds pages of K and V rows alone: "
                "there is no state pool beside the pages yet")
        if self._two_kind:
            raise NotImplementedError(
                f"{what}: a ring of the window's length beside the horizon "
                "(window_cache='ring') has no paged pool or block table yet")
        if self.n_lead:
            raise NotImplementedError(
                f"{what}: leading layers outside the layer scan are not "
                "taught to the paged pool")
        if self.passes > 1:
            raise NotImplementedError(
                f"{what}: a looped stack (passes > 1) keeps a cache layer a "
                "pass and layer, and the paged pool holds pages of one "
                "layer a weight layer: there is no looped page pool yet")

    def decode_step_paged(self, params, token, pos, pool, table,
                          page: int):
        """One cached decode step DIRECTLY over a paged KV pool: ``token``
        ``[B]`` at per-row positions ``pos`` ``[B]`` (scalar broadcasts)
        against ``pool`` ``{"k"/"v": [L, P, Hkv, page, Dh]}`` read through
        ``table`` ``[B, M]`` int32 (row ``b`` is slot ``b``'s block table)
        → ``(logits [B, V] f32, new_pool)``.

        The paged sibling of :meth:`decode_step`: same layer body, but
        each layer scatters ONLY the newly produced K/V row into its
        owning page (``pool[table[b, pos_b // page], :, pos_b % page]`` —
        O(new tokens), not a gather/scatter of the whole context) and
        attends through the block table with the fused paged kernel
        (``ops/paged_attention.py`` — Pallas on TPU; on CPU the reference
        gathers a transient view and applies the exact dense math, which
        keeps paged logits BITWISE equal to :meth:`decode_step` on the
        equivalent dense cache). Rows whose table cell at the write
        position is unmapped (parked/freed slots) scatter into the
        per-partition trash page (id 0) — finite garbage the position
        mask keeps invisible. Rolling (all-windowed) caches are refused
        (pages are linear-horizon only, like ``PagedKVCache``)."""
        if self._ring_cache:
            raise ValueError(
                "decode_step_paged: paged pools are linear-horizon; "
                "rolling (all-windowed) caches have no paged layout")
        self._refuse_paged("decode_step_paged")
        B = token.shape[0]
        H = self.n_heads
        Hkv = self.n_kv_heads
        Dh = self.head_dim
        cd = self.compute_dtype
        M = table.shape[1]
        pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
        h = self._embed(params, token, pos_b)  # [B, D]
        rope = self._rope_step(pos_b)

        # write coordinates, shared by every layer: positions past the
        # logical capacity (never produced by the serving engine) and
        # unmapped cells both land in the trash page
        mcell = jnp.clip(pos_b // page, 0, M - 1)
        pids = jnp.where(
            pos_b < M * page,
            jnp.take_along_axis(table, mcell[:, None], axis=1)[:, 0], 0)
        offs = pos_b % page

        def one_layer(h, lp, kp, vp, window):
            q, k_new, v_new = self._qkv_heads(
                lp, h, rope if self._rope_on(window) else None)
            with jax.named_scope("kv_write"):
                kp = kp.at[pids, :, offs].set(k_new, mode="drop")
                vp = vp.at[pids, :, offs].set(v_new, mode="drop")
            with jax.named_scope("attn_core"):
                a = paged_decode_attention(
                    q.reshape(B, Hkv, H // Hkv, Dh), kp, vp, table, pos_b,
                    page, window=window).astype(cd)
            h = self._attn_out(lp, h, a.reshape(B, self.d_attn))
            h, _ = self._ffn_residual(lp, h, "dense", SEQ_AXIS, 1)
            return h, kp, vp

        p = self._window_period()

        def block(h, inputs):
            lp, kp, vp = inputs
            if p == 1:
                h, kp, vp = one_layer(h, lp, kp, vp, self.attn_windows[0])
                return h, (kp, vp)
            kps, vps = [], []
            for g in range(p):
                h, kp_g, vp_g = one_layer(
                    h, {k: v[g] for k, v in lp.items()}, kp[g], vp[g],
                    self.attn_windows[g])
                kps.append(kp_g)
                vps.append(vp_g)
            return h, (jnp.stack(kps), jnp.stack(vps))

        lps = {k: params[k] for k in self._block_keys()}
        ck, cv = pool["k"], pool["v"]
        if p > 1:
            lps = _period_group(lps, p)
            ck = _period_group(ck, p)
            cv = _period_group(cv, p)
        with jax.named_scope("layers"):
            h, (kc_new, vc_new) = jax.lax.scan(block, h, (lps, ck, cv))
        if p > 1:
            kc_new = _period_ungroup(kc_new, self.n_layers)
            vc_new = _period_ungroup(vc_new, self.n_layers)
        h = self._norm_h(params, "lnf", h)
        return self._logits(params, h), {"k": kc_new, "v": vc_new}

    def decode_chunk_paged(self, params, tokens, pos0, pool, table,
                           page: int):
        """Cached forward of a BLOCK of ``S`` tokens per row DIRECTLY over
        a paged pool: the paged sibling of :meth:`decode_chunk`, serving
        paged prefill-insert, chunked-prefill continuations, and
        speculative verify. Each layer scatters the chunk's ``S`` new K/V
        rows through the block table (O(chunk), never the whole row of
        pages — already-shared prefix pages are never rewritten), then
        attends all queries through the table with the fused multi-row
        kernel; the CPU reference applies :meth:`decode_chunk`'s exact
        attention math to a transient gathered view, so logits stay
        BITWISE equal to the dense chunk path. Positions past the logical
        capacity or without a mapped page (bucket padding, parked rows)
        write to the trash page; the staleness-repair invariant
        (:meth:`generate_speculative`) covers them exactly as it covers
        the dense cache's stale rows."""
        if self._ring_cache:
            raise ValueError(
                "decode_chunk_paged: paged pools are linear-horizon; "
                "rolling (all-windowed) caches have no paged layout")
        self._refuse_paged("decode_chunk_paged")
        B, S = tokens.shape
        H = self.n_heads
        Hkv = self.n_kv_heads
        Dh = self.head_dim
        cd = self.compute_dtype
        M = table.shape[1]
        pos0 = jnp.asarray(pos0)
        pos_b = jnp.broadcast_to(pos0.reshape(-1, 1), (B, 1)) + \
            jnp.arange(S)[None, :]  # [B, S] absolute positions per row
        h = self._embed(params, tokens, pos_b)  # [B, S, D]
        rope = self._rope_for(pos_b)

        mcell = jnp.clip(pos_b // page, 0, M - 1)
        pids = jnp.where(pos_b < M * page,
                         jnp.take_along_axis(table, mcell, axis=1), 0)
        offs = pos_b % page                     # [B, S]
        pos0_b = pos_b[:, 0]

        def one_layer(h, lp, kp, vp, window):
            q, k_new, v_new = self._qkv_heads(
                lp, h, rope if self._rope_on(window) else None)
            with jax.named_scope("kv_write"):
                kp = kp.at[pids, :, offs].set(k_new, mode="drop")
                vp = vp.at[pids, :, offs].set(v_new, mode="drop")
            with jax.named_scope("attn_core"):
                qg = q.transpose(0, 2, 1, 3).reshape(
                    B, Hkv, H // Hkv, S, Dh)
                a = paged_chunk_attention(
                    qg, kp, vp, table, pos0_b, page, window=window
                ).astype(cd)
                a = a.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)
            h = self._attn_out(lp, h, a.reshape(B, S, self.d_attn))
            h, _ = self._ffn_residual(lp, h, "dense", SEQ_AXIS, 1)
            return h, kp, vp

        p = self._window_period()

        def block(h, inputs):
            lp, kp, vp = inputs
            if p == 1:
                h, kp, vp = one_layer(h, lp, kp, vp, self.attn_windows[0])
                return h, (kp, vp)
            kps, vps = [], []
            for g in range(p):
                h, kp_g, vp_g = one_layer(
                    h, {k: v[g] for k, v in lp.items()}, kp[g], vp[g],
                    self.attn_windows[g])
                kps.append(kp_g)
                vps.append(vp_g)
            return h, (jnp.stack(kps), jnp.stack(vps))

        lps = {k: params[k] for k in self._block_keys()}
        ck, cv = pool["k"], pool["v"]
        if p > 1:
            lps = _period_group(lps, p)
            ck = _period_group(ck, p)
            cv = _period_group(cv, p)
        with jax.named_scope("layers"):
            h, (kc_new, vc_new) = jax.lax.scan(block, h, (lps, ck, cv))
        if p > 1:
            kc_new = _period_ungroup(kc_new, self.n_layers)
            vc_new = _period_ungroup(vc_new, self.n_layers)
        h = self._norm_h(params, "lnf", h)
        return self._logits(params, h), {"k": kc_new, "v": vc_new}

    def _generate_speculative_device(self, params, prompt, n_new: int,
                                     draft, draft_params, spec_k: int,
                                     with_stats: bool,
                                     temperature: float = 0.0,
                                     seed: int = 0):
        """Speculative decoding as ONE compiled program.

        The host loops (:meth:`generate_speculative` batch-1 and
        `_generate_speculative_batched`) pay ``spec_k + 2`` host
        dispatches per round, which can cost more than the drafted
        tokens save. Here the whole
        draft→verify→accept round loop is a ``lax.while_loop`` inside one
        jit: greedy acceptance (accept while the target's argmax agrees;
        `_spec_accept_row`'s ``temperature<=0`` branch) as a cumprod over
        the match mask, or — round 5 — the sampled rejection rule in f32
        with on-device RNG (see ``_spec_rollout_device``); variable-length
        emissions land in a per-row token buffer via masked writes, and
        finished rows freeze exactly like the batched host loop. ONE
        dispatch for the entire rollout (after the two prefills) —
        dispatches per emitted token < 1 by construction. Greedy output is
        pinned equal to the host loops and the target's own greedy
        rollout; sampled output matches the host driver's f64 rule in
        DISTRIBUTION (``tests/models/test_speculative.py`` pins the
        per-position frequencies against the target's own sampling).
        """
        B, T0 = prompt.shape
        total = T0 + int(n_new)
        horizon = total + spec_k + 1
        t_logits, t_cache = _prefill_jit(self, params, prompt, horizon,
                                         spec_k + 1)
        _, d_cache = _prefill_jit(draft, draft_params, prompt, horizon,
                                  spec_k + 1)
        key = jax.random.PRNGKey(seed)
        if temperature > 0.0:
            key, k0 = jax.random.split(key)
            carry0 = jax.random.categorical(
                k0, t_logits[:, -1].astype(jnp.float32) / temperature,
                axis=-1).astype(jnp.int32)
        else:
            carry0 = jnp.argmax(t_logits[:, -1], axis=-1).astype(jnp.int32)
        buf0 = jnp.zeros((B, total + spec_k + 1), jnp.int32)
        buf0 = buf0.at[:, :T0].set(prompt).at[:, T0].set(carry0)
        pos0 = jnp.full((B,), T0, jnp.int32)
        buf, (rounds, proposed, accepted) = _spec_rollout_device(
            self, draft, params, draft_params, t_cache, d_cache,
            carry0, buf0, pos0, spec_k=spec_k, total=total,
            sampled=temperature > 0.0,
            temperature=float(temperature) if temperature > 0.0 else 1.0,
            key0=key)
        tokens = buf[:, :total]
        if with_stats:
            proposed = int(proposed)
            return tokens, {
                "rounds": int(rounds),
                "proposed": proposed,
                "accepted": int(accepted),
                "acceptance_rate": int(accepted) / max(proposed, 1),
                "tokens_emitted": int(B * (total - T0)),
            }
        return tokens

    def generate_speculative(self, params, prompt, n_new: int,
                             draft: "TransformerLM", draft_params,
                             spec_k: int = 4, temperature: float = 0.0,
                             seed: int = 0, with_stats: bool = False,
                             host_loop: bool = False):
        """Speculative decoding (Leviathan/Chen et al.): a small ``draft``
        model proposes ``spec_k`` tokens per round with cheap cached decode
        steps; the target model scores all of them in ONE
        :meth:`decode_chunk` pass and accepts a prefix. ``temperature=0``
        accepts while the target's greedy choice matches the draft — the
        output then EQUALS the target's own greedy :meth:`generate` exactly
        (verified in tests); ``>0`` uses the standard rejection rule
        (accept ``d`` w.p. ``min(1, p_t(d)/p_d(d))``, resample rejections
        from ``(p_t − p_d)+``, bonus token from ``p_t``), which preserves
        the target's sampling distribution.

        Cache-staleness invariant: a rejected round leaves wrong K/V for
        the rejected positions in BOTH caches, but every round's writes
        start at the first such position and span far enough to repair all
        of them before any query can attend there (chunk length
        ``spec_k+1``, acceptance advances by at most ``n+1``).

        Batches of any size: ``B > 1`` routes to the per-row-position
        batched loop (:meth:`_generate_speculative_batched` — rows accept
        different prefix lengths, so each carries its own absolute
        position through the caches; greedy per-row output still equals
        the target's own rollout). The draft shares the target's
        vocabulary; proposals use plain temperature sampling
        (no top-k/top-p). Latency-oriented: fewer sequential target steps
        per emitted token at the cost of draft work — the win grows with
        the target/draft size ratio. Both greedy AND sampled requests
        execute as one compiled on-device round loop (``host_loop=True``
        forces the host-driver path instead — for greedy that path is the
        bit-exact oracle, for sampled it carries the f64 rejection math
        the device's f32 rule is distribution-checked against).
        ``with_stats=True`` additionally
        returns ``{rounds, proposed, accepted, acceptance_rate,
        tokens_emitted}`` — ``rounds`` is the number of sequential target
        passes, vs ``n_new`` for plain cached decode (the algorithmic
        win).

        Exactness caveat: "equals greedy generate" is bit-for-bit where the
        verify and rollout paths share attention numerics (the CPU/einsum
        path, which the tests pin). On TPU ``decode_step`` uses the
        flash-decode kernel while ``decode_chunk`` uses a dense einsum; an
        exact tie in the target's top-2 logits could in principle resolve
        differently between them. The MoE family participates when expert
        capacity provably never binds (``capacity_factor·k >= n_experts`` —
        the hf_import pin): chunked verification then routes every token
        identically to per-position decode (see
        ``MoETransformerLM._supports_speculative``); capacity-bound MoE
        configs are rejected below because a binding capacity makes chunk
        and per-position keep/drop decisions diverge."""
        if self.hybrid or getattr(draft, "hybrid", False):
            raise NotImplementedError(
                "speculative decoding rolls rejected drafts back by writing "
                "over their cache rows, and a linear-attention layer's state "
                "has folded them in: a verify chunk cannot be rolled back "
                "without a snapshot of the state, which is not in the program")
        if self.passes > 1 or getattr(draft, "passes", 1) > 1:
            raise NotImplementedError(
                "speculative decoding over a looped stack (passes > 1): the "
                "verify chunk and its pin against sequential decode are "
                "written for a stack walked once a token")
        if not self._supports_speculative:
            raise NotImplementedError(
                "speculative decoding needs chunk routing == per-position "
                "routing: for the MoE family that holds only when expert "
                "capacity never binds (capacity_factor * k >= n_experts — "
                "the pin hf_import applies; raise capacity_factor, or use "
                "the dense family)"
            )
        if not draft._supports_speculative:
            raise NotImplementedError(
                "the draft model's routing must also be chunk-stable "
                "(dense, or MoE with capacity_factor * k >= n_experts)"
            )
        prompt = jnp.asarray(prompt, jnp.int32)
        B, T0 = prompt.shape
        if draft.vocab != self.vocab:
            raise ValueError(
                f"draft vocab {draft.vocab} != target vocab {self.vocab}"
            )
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        total = T0 + int(n_new)
        if total > self.max_len or total > draft.max_len:
            raise ValueError(
                f"prompt {T0} + n_new {n_new} exceeds max_len "
                f"(target {self.max_len}, draft {draft.max_len})"
            )
        if n_new < 1:
            return prompt
        if not host_loop:
            # Rounds run as ONE compiled while_loop program — dispatches
            # per emitted token < 1 (the wall-clock win on a
            # dispatch-latency-dominated rig). Greedy: pinned equal to
            # the host loops and the target's own greedy rollout.
            # Sampled (round 5): the rejection rule on-device in f32 —
            # the host driver below stays the f64 distributional oracle
            # (host_loop=True forces it).
            return self._generate_speculative_device(
                params, prompt, int(n_new), draft, draft_params,
                int(spec_k), with_stats, temperature=float(temperature),
                seed=int(seed))
        if B != 1:
            return self._generate_speculative_batched(
                params, prompt, int(n_new), draft, draft_params,
                int(spec_k), float(temperature), int(seed), with_stats,
            )

        horizon = total + spec_k + 1
        t_logits, t_cache = self.prefill(
            params, prompt,
            self.init_cache(1, horizon, chunk=spec_k + 1))
        # chunk margin for the DRAFT too: after a rejection its decode
        # resumes up to spec_k+1 positions behind its last write, and the
        # ring age mask (unlike the causal slot<=pos mask) would otherwise
        # see those stale future slots
        _, d_cache = draft.prefill(
            draft_params, prompt,
            draft.init_cache(1, horizon, chunk=spec_k + 1))
        rng = np.random.default_rng(seed)

        def choose(logits_row):
            if temperature <= 0.0:
                return int(np.argmax(np.asarray(logits_row)))
            return int(rng.choice(
                self.vocab, p=_spec_probs(logits_row, temperature)))

        draft_step = jax.jit(draft.decode_step)
        verify = jax.jit(self.decode_chunk)

        out = list(np.asarray(prompt[0]))
        carry = choose(t_logits[0, -1])
        out.append(carry)
        pos = T0  # absolute position of `carry`, not yet in either cache
        rounds = proposed = accepted = 0

        while len(out) < total:
            rounds += 1
            # -- draft spec_k proposals (cheap sequential steps) ----------
            d_toks, d_probs = [], []
            tok, p = carry, pos
            for _ in range(spec_k):
                dl, d_cache = draft_step(draft_params,
                                         jnp.asarray([tok], jnp.int32),
                                         p, d_cache)
                if temperature > 0.0:
                    row = _spec_probs(dl[0], temperature)
                    tok = int(rng.choice(self.vocab, p=row))
                    d_probs.append(row)
                else:
                    tok = int(np.argmax(np.asarray(dl[0])))
                d_toks.append(tok)
                p += 1

            # -- target verifies the whole block in one pass --------------
            chunk = jnp.asarray([[carry] + d_toks], jnp.int32)
            vl, t_cache = verify(params, chunk, pos, t_cache)
            vl = np.asarray(vl[0], np.float32)  # [spec_k+1, V]

            emitted, n = _spec_accept_row(
                vl, d_toks, d_probs, spec_k, self.vocab, temperature, rng)
            if n == spec_k and len(emitted) == spec_k + 1:
                # Full acceptance: the last draft token d_k was PROPOSED but
                # never ingested by the draft (its K/V at position pos+k
                # would stay a hole forever, corrupting later proposals and
                # collapsing the acceptance rate). Ingest it now; the next
                # round then starts at the bonus token's position.
                _, d_cache = draft_step(draft_params,
                                        jnp.asarray([d_toks[-1]], jnp.int32),
                                        pos + spec_k, d_cache)
            proposed += spec_k
            accepted += n
            out.extend(emitted)
            pos += len(emitted)
            carry = emitted[-1]

        tokens = jnp.asarray([out[:total]], jnp.int32)
        if with_stats:
            # rounds = sequential target (verify) passes; plain cached
            # decode would need n_new sequential target steps — the ratio
            # is the algorithmic win, independent of dispatch overheads.
            return tokens, {
                "rounds": rounds,
                "proposed": proposed,
                "accepted": accepted,
                "acceptance_rate": accepted / max(proposed, 1),
                "tokens_emitted": int(total - T0),
            }
        return tokens

    def _generate_speculative_batched(self, params, prompt, n_new: int,
                                      draft, draft_params, spec_k: int,
                                      temperature: float, seed: int,
                                      with_stats: bool):
        """Batched (B>1) speculative decoding via per-row positions.

        Rows accept different prefix lengths per round, so each row carries
        its OWN absolute position: the draft steps and the verify chunk run
        batched with per-row ``pos`` (``decode_step``/``decode_chunk``
        accept ``[B]`` positions; the flash-decode kernel takes a per-row
        visibility bound). A finished row freezes: its position clamps to
        ``total-1`` (keeping every later round's cache writes inside the
        allocated horizon, with no reliance on update-slice index
        clamping) and later rounds rewrite that span in place — harmless,
        the row's output is already final — while unfinished rows keep
        proposing, so every round costs one verify pass for the whole
        batch.

        The last draft proposal is ingested into the draft cache for EVERY
        row each round (the batch-1 path ingests only on full acceptance):
        for rows that rejected earlier, the write lands beyond their next
        round's start and is overwritten by that round's own draft steps
        before any query can attend it — the same staleness-repair
        invariant :meth:`generate_speculative` documents, extended one slot.

        Greedy (``temperature=0``) output equals per-row batch-1 greedy
        speculative decoding (= the target's own greedy rollout). Sampling
        uses an independent stream per row (``default_rng([seed, row])``) —
        deterministic per seed, but not the batch-1 stream.
        """
        B, T0 = prompt.shape
        total = T0 + n_new
        horizon = total + spec_k + 1
        t_logits, t_cache = self.prefill(
            params, prompt,
            self.init_cache(B, horizon, chunk=spec_k + 1))
        _, d_cache = draft.prefill(
            draft_params, prompt,
            draft.init_cache(B, horizon, chunk=spec_k + 1))
        rngs = [np.random.default_rng([seed, b]) for b in range(B)]

        out = [list(np.asarray(prompt[b])) for b in range(B)]
        carry = np.empty((B,), np.int64)
        last = np.asarray(t_logits[:, -1])
        for b in range(B):
            carry[b] = (
                int(np.argmax(last[b])) if temperature <= 0.0
                else int(rngs[b].choice(
                    self.vocab, p=_spec_probs(last[b], temperature)))
            )
            out[b].append(int(carry[b]))
        pos = np.full((B,), T0, np.int64)
        rounds = proposed = accepted = 0

        draft_step = jax.jit(draft.decode_step)
        verify = jax.jit(self.decode_chunk)

        while min(len(o) for o in out) < total:
            rounds += 1
            active = np.array([len(o) < total for o in out])

            # -- draft proposals, batched, per-row positions --------------
            d_toks = np.empty((B, spec_k), np.int64)
            d_probs = [[None] * spec_k for _ in range(B)]
            tok, p = carry.copy(), pos.copy()
            for i in range(spec_k):
                dl, d_cache = draft_step(
                    draft_params, jnp.asarray(tok, jnp.int32),
                    jnp.asarray(p), d_cache)
                dlh = np.asarray(dl)
                for b in range(B):
                    if temperature > 0.0:
                        row = _spec_probs(dlh[b], temperature)
                        d_probs[b][i] = row
                        tok[b] = int(rngs[b].choice(self.vocab, p=row))
                    else:
                        tok[b] = int(np.argmax(dlh[b]))
                d_toks[:, i] = tok
                p += 1

            # -- target verifies every row's block in one pass ------------
            chunk = np.concatenate([carry[:, None], d_toks], 1)
            vl, t_cache = verify(params, jnp.asarray(chunk, jnp.int32),
                                 jnp.asarray(pos), t_cache)
            vlh = np.asarray(vl, np.float32)  # [B, spec_k+1, V]

            # -- per-row acceptance (the SAME rule function as batch 1) ---
            for b in range(B):
                emitted, n = _spec_accept_row(
                    vlh[b], d_toks[b], d_probs[b], spec_k, self.vocab,
                    temperature, rngs[b])
                if active[b]:
                    proposed += spec_k
                    accepted += n
                    out[b].extend(emitted)
                    # clamp a row that just finished: later rounds keep
                    # writing its (now-final) span without growing past
                    # the allocated cache horizon
                    pos[b] = min(pos[b] + len(emitted), total - 1)
                    carry[b] = emitted[-1]
                # frozen rows: position, carry, and output stay put

            # -- ingest the last proposal into the draft cache for ALL
            # rows (see docstring for why spurious writes are safe)
            _, d_cache = draft_step(draft_params,
                                    jnp.asarray(d_toks[:, -1], jnp.int32),
                                    jnp.asarray(p), d_cache)

        tokens = jnp.asarray([o[:total] for o in out], jnp.int32)
        if with_stats:
            return tokens, {
                "rounds": rounds,
                "proposed": proposed,
                "accepted": accepted,
                "acceptance_rate": accepted / max(proposed, 1),
                "tokens_emitted": int(B * (total - T0)),
            }
        return tokens

    def generate(self, params, prompt, n_new: int,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, seed: int = 0):
        """Autoregressive continuation: ``prompt`` ``[B, T0]`` int →
        ``[B, T0 + n_new]``. Single-device inference on full (gathered)
        params: one batched :meth:`prefill` over the prompt, then a
        ``lax.scan`` of KV-cached decode steps — the cache is sized to the
        decode horizon, not ``max_len``.

        ``temperature=0`` (default) is greedy — for the dense model the
        output then equals the uncached argmax rollout exactly; ``>0``
        samples from ``softmax(logits / temperature)``, optionally
        restricted to the ``top_k`` highest-probability tokens and/or the
        nucleus of tokens whose cumulative probability reaches ``top_p``
        (the most-probable token always survives; with both set, top-k
        truncates first, then the nucleus is taken within it),
        deterministically per ``seed``. The MoE variant decodes too, with
        per-position routing (see :meth:`decode_step`)."""
        prompt = jnp.asarray(prompt, jnp.int32)
        B, T0 = prompt.shape
        total = T0 + int(n_new)
        if total > self.max_len:
            raise ValueError(
                f"prompt {T0} + n_new {n_new} exceeds max_len {self.max_len}"
            )
        if top_k is not None and not 1 <= int(top_k) <= self.vocab:
            raise ValueError(
                f"top_k must be in [1, vocab={self.vocab}], got {top_k}"
            )
        if top_p is not None and not 0.0 < float(top_p) <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if n_new < 1:
            return prompt

        # The whole rollout (prefill + decode scan) compiles as ONE
        # program: an eager lax.scan dispatches per construct instead of
        # once per rollout.
        return _generate_rollout(
            self, params, prompt, jax.random.PRNGKey(seed), int(n_new),
            float(temperature),
            None if top_k is None else int(top_k),
            None if top_p is None else float(top_p))


class MoETransformerLM(TransformerLM):
    """Mixture-of-experts transformer: every block's FFN is a top-k routed
    expert layer, experts sharded over the SAME ``"seq"`` mesh axis the
    sequence rides (the standard overlap of sp and ep groups — no third
    axis needed, and the MoE all_to_alls stay inside the sequence group).
    One ``shard_map`` program therefore combines dp×sp×ep.

    ``ep_groups`` only matters on the dense (oracle) path: it emulates the
    per-source-shard dispatch groups of a ``seq``-axis size it should match
    (the sharded path gets the group size from the axis itself). Total
    parameters scale with ``n_experts`` while per-token FLOPs stay constant;
    the Switch load-balancing aux (weighted ``aux_weight``) enters the
    training objective.
    """

    @property
    def _supports_speculative(self):
        # Chunked verification routes a whole spec_k+1 chunk as ONE
        # competing dispatch group while the rollout routes per position —
        # keep/drop decisions could differ wherever expert capacity BINDS.
        # An expert receives at most n claims per n-token group (each
        # token claims it at most once), so capacity never binds iff
        # cap(n) = ceil(cf·k·n/E) ≥ n for every n, i.e. cf·k ≥ E —
        # exactly the pin models/hf_import.py applies for HF routing
        # parity (cf = E/k). Then every (token, expert) claim is kept in
        # BOTH formulations and the renormalized combine weights
        # coincide, so chunk routing == per-position routing by
        # construction and speculative decoding is exact (round 5;
        # pinned in tests/models/test_speculative.py).
        return (self.moe.capacity_factor * self.moe.k
                >= self.moe.n_experts)

    def __init__(self, vocab: int, d_model: int, n_heads: int, n_layers: int,
                 d_ff: int, max_len: int, n_experts: int, k: int = 2,
                 capacity_factor: float = 1.25, aux_weight: float = 1e-2,
                 ep_groups: int = 1, compute_dtype: str = "float32",
                 routing: str = "token_choice", pos_encoding: str = "learned",
                 tie_embeddings: bool = False,
                 n_kv_heads: Optional[int] = None, activation: str = "relu",
                 norm: str = "layernorm", norm_eps: float = 1e-5,
                 attn_bias: bool = False, ffn_bias: bool = True,
                 rope_theta: float = 10000.0,
                 attn_window: Optional[int] = None,
                 moe_dispatch: str = "slots", param_dtype: str = "float32",
                 head_dim: Optional[int] = None, qk_norm: bool = False,
                 rope_layers: str = "all", window_cache: str = "horizon",
                 dense_layers: int = 0, d_ff_dense: Optional[int] = None,
                 scoring: str = "softmax", select_bias: bool = False,
                 norm_topk: bool = True, routed_scale: float = 1.0,
                 n_shared: int = 0, held=None, mtp_layers: int = 0,
                 q_lora_rank: Optional[int] = None,
                 kv_lora_rank: Optional[int] = None,
                 qk_nope_head_dim: Optional[int] = None,
                 qk_rope_head_dim: Optional[int] = None,
                 v_head_dim: Optional[int] = None,
                 rope_scaling: Optional[Dict[str, Any]] = None):
        # ``activation``/``ffn_bias`` configure the EXPERTS (the MoE block
        # replaces the dense FFN); the remaining knobs hit the attention/
        # norm stack via the base class — together they cover the
        # Mixtral-family shape (swiglu experts, rmsnorm, rotary, GQA).
        # The DeepSeek-V3-shaped families add: ``dense_layers`` leading
        # layers with a dense FFN of width ``d_ff_dense`` (``d_ff`` is the
        # width of ONE expert); the router of ``scoring`` / ``select_bias``
        # / ``norm_topk`` / ``routed_scale``, ``n_shared`` shared experts
        # and a ``held=(e0, n)`` share of each layer's experts
        # (``MoEFeedForward``); ``mtp_layers`` multi-token-prediction
        # modules (:meth:`mtp_logits`; 0 for a model that only serves);
        # the latent-attention arguments are the base class's.
        super().__init__(vocab, d_model, n_heads, n_layers, d_ff, max_len,
                         compute_dtype=compute_dtype,
                         pos_encoding=pos_encoding,
                         tie_embeddings=tie_embeddings,
                         n_kv_heads=n_kv_heads, activation=activation,
                         norm=norm, norm_eps=norm_eps, attn_bias=attn_bias,
                         ffn_bias=ffn_bias, rope_theta=rope_theta,
                         attn_window=attn_window, head_dim=head_dim,
                         qk_norm=qk_norm, rope_layers=rope_layers,
                         window_cache=window_cache, q_lora_rank=q_lora_rank,
                         kv_lora_rank=kv_lora_rank,
                         qk_nope_head_dim=qk_nope_head_dim,
                         qk_rope_head_dim=qk_rope_head_dim,
                         v_head_dim=v_head_dim, rope_scaling=rope_scaling)
        from ..parallel.expert import MoEFeedForward

        if not 0 <= int(dense_layers) < n_layers:
            raise ValueError(
                f"dense_layers {dense_layers} not in [0, {n_layers})")
        self.n_lead = int(dense_layers)
        self.d_ff_dense = int(d_ff if d_ff_dense is None else d_ff_dense)
        if int(mtp_layers) not in (0, 1):
            raise ValueError("mtp_layers is 0 or 1 (one module)")
        self.mtp_layers = int(mtp_layers)

        if routing == "expert_choice":
            # Expert-choice makes token t's routing depend on FUTURE tokens
            # (experts pick top-C across the whole block), so training-time
            # routing differs from autoregressive inference — the EC paper
            # itself flags it as unsuitable for decoder LMs.
            raise ValueError(
                "routing='expert_choice' breaks causality in a decoder LM "
                "(routing would depend on future tokens); use "
                "'token_choice' here, or MoEFeedForward directly for "
                "non-causal workloads"
            )
        # param_dtype="bfloat16" stores the EXPERT stacks (the ~E×3·D·F
        # bulk of the model) in bf16: use-site casts become no-ops and the
        # per-step f32→bf16 convert traffic disappears; optimizer math
        # stays f32 (adam_compact upcasts) with one bf16 rounding per
        # update. The router and the attention/embedding stack remain f32.
        self.moe = MoEFeedForward(d_model, d_ff, n_experts, k=k,
                                  capacity_factor=capacity_factor,
                                  routing=routing, activation=activation,
                                  bias=ffn_bias, param_dtype=param_dtype,
                                  scoring=scoring, select_bias=select_bias,
                                  norm_topk=norm_topk,
                                  routed_scale=routed_scale,
                                  n_shared=n_shared, held=held)
        if moe_dispatch not in ("slots", "gmm", "onehot"):
            raise ValueError(f"Unknown moe_dispatch: {moe_dispatch!r}")
        self.n_experts = n_experts
        self.aux_weight = aux_weight
        self.ep_groups = int(ep_groups)
        # Single-device FFN executor (routing decisions are identical in
        # all three; only execution strategy differs):
        #   "slots"  (default) — index-form gather dispatch into capacity
        #            slots (MoEFeedForward.apply_slots; no [N, E, C]
        #            products, bf16 expert matmuls, gather-only AD
        #            transposes);
        #   "gmm"    — Pallas tile-aligned grouped matmul (apply_gmm;
        #            k·N rows + ≤E·128 tile padding, recompute-backward
        #            swiglu FFN; no cell has measured it against the
        #            slot path inside a whole step);
        #   "onehot" — the GShard one-hot einsum oracle (apply_reference).
        # The sharded (all_to_all) path always uses the slot dispatch.
        self.moe_dispatch = moe_dispatch

    def param_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        shapes = super().param_shapes()
        sds_ = jax.ShapeDtypeStruct
        L, Ld = self.n_layers - self.n_lead, self.n_lead
        dense = {k_: shapes[k_] for k_ in self._lead_keys()}
        # replace the dense FFN stacks with per-layer expert stacks
        for k_ in EXPERT_STACKS:
            shapes.pop(k_, None)
        sparse = dict(self.moe.param_shapes())
        for k_, sds in sparse.items():
            shapes[k_] = sds_((L,) + sds.shape, sds.dtype)
        # the leading dense layers' own leaves: a whole dense block each,
        # its FFN ``d_ff_dense`` wide
        D, Fd = self.d_model, self.d_ff_dense
        ffn = {"w1": (D, Fd), "w3": (D, Fd), "w2": (Fd, D), "b1": (Fd,)}
        for k_, sds in dense.items():
            shapes[self.LEAD + k_] = sds_(
                (Ld,) + ffn.get(k_, sds.shape[1:]), sds.dtype)
        if self.mtp_layers:
            # one multi-token-prediction module: two norms, the projection
            # of [hidden ; next token's embedding], one sparse layer
            D = self.d_model
            shapes["mtp_hn_s"] = sds_((D,), jnp.float32)
            shapes["mtp_en_s"] = sds_((D,), jnp.float32)
            shapes["mtp_wp"] = sds_((2 * D, D), jnp.float32)
            for k_ in self._block_keys():
                shapes["mtp_" + k_] = sds_((1,) + shapes[k_].shape[1:],
                                           shapes[k_].dtype)
        return shapes

    def _lead_keys(self):
        return TransformerLM._block_keys(self) if self.n_lead else ()

    def _stacked_keys(self):
        # the dropless executor's kernel reads a layer of the stack in place
        return self.moe.expert_keys() if self.moe.dropless else ()

    def specs(self) -> Dict[str, P]:
        specs = {k: P() for k in self.param_shapes()}
        for k_ in self.moe.expert_keys():
            specs[k_] = P(None, SEQ_AXIS)  # [L, E, ...]: E over "seq"
        return specs

    def _block_keys(self):
        base = [k for k in super()._block_keys() if k not in EXPERT_STACKS]
        return tuple(base) + tuple(self.moe.param_shapes())

    def init_cache(self, batch: int, length: Optional[int] = None,
                   chunk: int = 1) -> Dict[str, Any]:
        """The base model's cache; a layer on the dropless executor also
        counts its work there, in ``moe_counts`` (int32 ``[2, 5]``: the
        decode steps' and the chunk forwards' :data:`MOE_COUNTS`): the
        cached forwards add to it on the device and nothing fetches it
        but ``ServingEngine.snapshot()``."""
        cache = super().init_cache(batch, length, chunk)
        if self.moe.dropless:
            cache["moe_counts"] = jnp.zeros((2, len(MOE_COUNTS)), jnp.int32)
        return cache

    def mtp_logits(self, params, hidden, next_tokens, positions,
                   attn: str = "dense"):
        """The multi-token-prediction module (the DeepSeek-V3 form) on
        whole sequences: ``hidden`` ``[B, T, D]`` is the main model's last
        layer output BEFORE its final norm (``apply_hidden(...,
        final_norm=False)``), ``next_tokens`` ``[B, T]`` the tokens at
        ``t + 1``; ``h' = Wp [ Nh(hidden) ; Ne(Emb(next_tokens)) ]``, one
        decoder layer (full attention, the sparse FFN), then the main
        model's final norm and head → logits ``[B, T, V]`` for the tokens
        at ``t + 2``. Its leaves carry the ``mtp_`` prefix and exist only
        under ``mtp_layers=1``."""
        if not self.mtp_layers:
            raise ValueError("this model was built with mtp_layers=0")
        cd = self.compute_dtype
        emb = self._embed(params, next_tokens, positions)
        with jax.named_scope("mtp"):
            x = jnp.concatenate(
                [self._norm_h(params, "mtp_hn", hidden),
                 self._norm_h(params, "mtp_en", emb)], axis=-1).astype(cd)
            x = x @ params["mtp_wp"].astype(cd)
        lp = {k_: params["mtp_" + k_][0] for k_ in self._block_keys()}
        rope = self._rope_for(positions) if self._rope_on(None) else None
        h, _, _, _ = self._block_fwd(
            x, lp, lambda q, k, v, rp=None: self._attend(
                q, k, v, attn, SEQ_AXIS, rope=rp, window=None),
            attn, SEQ_AXIS, ep_groups=1, rope=rope)
        return self._logits(params, self._norm_h(params, "lnf", h))

    def _ffn(self, lp, x, attn: str, seq_axis: str,
             ep_groups: Optional[int] = None, stats: Optional[list] = None):
        B, T = x.shape[0], x.shape[1]
        moe_params = {k_: lp[k_] for k_ in self.moe.param_shapes()}
        layer = None
        if isinstance(moe_params["w1"], tuple):
            # the cached layer walk's (whole stack, layer index)
            layer = moe_params["w1"][1]
            moe_params.update({k_: moe_params[k_][0]
                               for k_ in self._stacked_keys()})
        if self.moe.dropless:
            # sigmoid scores, a shared expert, a held share: the one
            # dropless executor, whatever ``moe_dispatch`` and ``attn`` say
            # (no token dispatch group, so nothing to regroup; a held share
            # computes its own part and has no exchange on one chip)
            if attn != "dense" and axis_size(seq_axis) > 1:
                raise NotImplementedError(
                    "the dropless expert layer has no exchange across a "
                    "mesh axis yet: run it on one chip's share")
            y, aux = self.moe.apply_dropless(
                moe_params, x.reshape(B * T, self.d_model), stats=stats,
                layer=layer)
            return y.reshape(B, T, self.d_model), aux
        if attn != "dense":
            flat = x.reshape(B * T, self.d_model)
            # axis_size is static at trace time: on a size-1 axis
            # the all_to_alls are identities and the per-shard dispatch
            # group is the whole local block, so the requested
            # single-device executor is exactly equivalent there.
            alone = axis_size(seq_axis) == 1
            if alone and self.moe_dispatch == "gmm":
                y, aux = self.moe.apply_gmm(moe_params, flat)
            elif alone and self.moe_dispatch == "onehot":
                y, aux = self.moe.apply_reference(moe_params, flat)
            else:
                y, aux = self.moe.apply(moe_params, flat,
                                        axis_name=seq_axis)
            return y.reshape(B, T, self.d_model), aux
        # dense oracle path: each seq-axis dispatch group is one sequence
        # chunk flattened batch-major (exactly how a shard flattens its
        # local block) — re-layout so MoEFeedForward.apply_reference's
        # contiguous per-group emulation sees the same token groups.
        # ``ep_groups=1`` (decode/prefill) treats the block as one group.
        G = self.ep_groups if ep_groups is None else ep_groups
        if T % G:
            raise ValueError(f"T={T} not divisible by ep_groups={G}")
        # (moe_params collected above)
        tl = T // G
        D = self.d_model
        xg = x.reshape(B, G, tl, D).transpose(1, 0, 2, 3).reshape(G * B * tl, D)
        if self.moe_dispatch == "slots":
            y, aux = self.moe.apply_slots(moe_params, xg, ep=G)
        elif self.moe_dispatch == "gmm":
            y, aux = self.moe.apply_gmm(moe_params, xg, ep=G)
        else:
            y, aux = self.moe.apply_reference(moe_params, xg, ep=G)
        y = y.reshape(G, B, tl, D).transpose(1, 0, 2, 3).reshape(B, T, D)
        return y, aux


def make_lm_batches(token_rows: np.ndarray):
    """Host-side prep: ``[B, T+1]`` int rows → ``(tokens, positions,
    targets)`` each ``[B, T]``, targets pre-shifted so sequence sharding
    needs no cross-shard halo."""
    tokens = token_rows[:, :-1]
    targets = token_rows[:, 1:]
    positions = np.broadcast_to(
        np.arange(tokens.shape[1], dtype=np.int32), tokens.shape
    )
    return tokens.astype(np.int32), positions.copy(), targets.astype(np.int32)


def _validate_lm_step(model: TransformerLM, mesh: Mesh, attn: str) -> int:
    """Shared build-time validation for the LM train/eval builders; returns
    the seq-axis size."""
    sp = mesh.shape[SEQ_AXIS]
    if attn not in ("dense", "flash", "ring", "ulysses"):
        raise ValueError(f"Unknown attn: {attn}")
    if attn == "ulysses" and model.n_heads % sp:
        raise ValueError(
            f"attn='ulysses' needs head count {model.n_heads} divisible by "
            f"the seq axis size {sp} (use attn='ring' for few-head models)"
        )
    if model.max_len % sp:
        raise ValueError(
            f"max_len {model.max_len} not divisible by seq axis size {sp}"
        )
    if attn in ("dense", "flash") and sp > 1:
        raise ValueError(
            f"attn={attn!r} is a whole-sequence-per-shard path: under a seq "
            f"axis of size {sp} it would attend within each sequence chunk "
            "only (silently wrong) — use attn='ring' or 'ulysses'"
        )
    moe = getattr(model, "moe", None)
    if moe is not None and moe.n_experts % sp:
        raise ValueError(
            f"n_experts {moe.n_experts} not divisible by seq axis size {sp} "
            "(experts shard over the sequence axis)"
        )
    return sp


def _check_seq_len(model: TransformerLM, sp: int, t: int) -> None:
    """Call-time guard shared by the train/eval steps: JAX clamps
    out-of-range gathers under jit, so an over-long sequence would silently
    reuse the last positional-embedding row."""
    if t > model.max_len:
        raise ValueError(
            f"sequence length {t} exceeds max_len {model.max_len}"
        )
    if t % sp:
        raise ValueError(
            f"sequence length {t} not divisible by seq axis size {sp}"
        )


def _lm_step_parts(model: TransformerLM, mesh: Mesh, optimizer,
                   attn: str, accum_steps: int, vocab_block: Optional[int],
                   overlap_grads, fused_apply: bool, remat: str):
    """The internals of :func:`build_lm_train_step`: validation, specs, and
    the whole step (forward objective, backward, gradient reduction,
    optimizer apply), written to run INSIDE the dp×sp ``shard_map``. A
    profile tells the phases apart by name: backward operations read
    ``transpose(``, the reduction is scoped ``grad_reduce`` and the apply
    ``optimizer``. Returns ``(sp, pspecs, sspecs, tok_spec, step_impl)``."""
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if overlap_grads not in (False, True, "ring"):
        raise ValueError(
            f"overlap_grads must be False, True, or 'ring', "
            f"got {overlap_grads!r}")
    if remat not in ("none", "dots", "full"):
        raise ValueError(f"Unknown remat policy: {remat!r} (none|dots|full)")
    if fused_apply and not hasattr(optimizer, "fused_apply"):
        raise ValueError(
            "fused_apply=True needs an optimizer exposing "
            "fused_apply(grads, opt_state, params) — use adam_compact / "
            "fused_adam from models/optimizers.py")
    sp = _validate_lm_step(model, mesh, attn)
    from ..parallel.param_utils import opt_state_specs

    pspecs = model.specs()
    sspecs = opt_state_specs(optimizer, model.param_shapes(), pspecs)
    tok_spec = P(DATA_AXIS, SEQ_AXIS)
    # Params whose spec mentions the seq axis (MoE expert stacks) are OWNED
    # per seq rank: their gradients arrive locally through the all_to_all
    # transpose and must NOT be summed over "seq".
    def _mentions_seq(spec):
        for ax in spec:
            axes = ax if isinstance(ax, tuple) else (ax,)
            if SEQ_AXIS in axes:
                return True
        return False

    seq_sharded = {k for k, s in pspecs.items() if _mentions_seq(s)}
    dp = mesh.shape[DATA_AXIS]

    @jax.named_scope("grad_reduce")
    def reduce_block(grads):
        """The monolithic post-backward reduction (the baseline path): one
        serialized psum block over every gradient leaf after the full
        backward completes."""
        return {
            k: jax.lax.psum(
                g if k in seq_sharded else jax.lax.psum(g, SEQ_AXIS),
                DATA_AXIS,
            )
            for k, g in grads.items()
        }

    grad_reduce = None
    if overlap_grads:
        use_ring = overlap_grads == "ring"

        def _axis_sum(g, axis):
            if use_ring and g.size >= _RING_MIN_ELEMS:
                return ring_psum(g, axis)
            return jax.lax.psum(g, axis)

        def _reduce_leaf(k, g):
            if k not in seq_sharded:
                g = _axis_sum(g, SEQ_AXIS)
            return _axis_sum(g, DATA_AXIS)

        grad_reduce = _reduce_on_backward(jax.named_scope("grad_reduce")(
            lambda ct: {k: _reduce_leaf(k, g) for k, g in ct.items()}))

    # Non-block params (embeddings, final norm, untied head) are not part
    # of the layer scan; under overlap their reduce-on-backward tag sits at
    # the top of the loss so each cotangent's collective fires where AD
    # produces it (the head/final-norm grads early in the backward — their
    # psums overlap the entire block-scan backward).
    top_keys = tuple(k for k in model.param_shapes()
                     if k not in set(model._block_keys()))

    def make_loss_fn(ntok_total):
        def loss_fn(p, tk, ps, tg):
            # per-microbatch pieces SUM to the full-batch objective:
            # CE is normalized by the global token count, the aux term
            # additionally by accum_steps (it is a per-call mean).
            if grad_reduce is not None:
                p = {**p, **grad_reduce({k: p[k] for k in top_keys})}
            if vocab_block is None:
                logits, aux = model.apply_with_aux(
                    p, tk, ps, attn=attn, grad_reduce=grad_reduce,
                    remat=remat)
                ce = _summed_xent(logits, tg)
            else:
                h, aux = model.apply_hidden(
                    p, tk, ps, attn=attn, grad_reduce=grad_reduce,
                    remat=remat)
                ce = chunked_summed_xent(h, model.head_weight(p), tg,
                                         vocab_block)
            return ce / ntok_total + (
                model.aux_weight / (dp * sp * accum_steps)
            ) * aux
        return loss_fn

    def _foreach_micro(fn, zero_carry, params, tokens, positions, targets):
        """Run ``fn(params, tk, ps, tg)`` over the accum microbatches and
        sum the results (one full-batch call at ``accum_steps == 1``)."""
        if accum_steps == 1:
            return fn(params, tokens, positions, targets)
        B = tokens.shape[0]
        if B % accum_steps:
            raise ValueError(
                f"local batch {B} not divisible by accum_steps "
                f"{accum_steps}"
            )
        micro = B // accum_steps
        split = lambda a: a.reshape(accum_steps, micro, *a.shape[1:])

        def body(carry, xs):
            out = fn(params, *xs)
            return jax.tree_util.tree_map(jnp.add, carry, out), None

        acc, _ = jax.lax.scan(
            body, zero_carry,
            (split(tokens), split(positions), split(targets)),
        )
        return acc

    def _ntok(tokens):
        # token count is static, so normalization can live INSIDE the
        # differentiated scalar: psum of per-shard objectives IS the global
        # objective (the aux term is identical across a data group's seq
        # ranks, so /(dp·sp) de-duplicates its sp copies).
        return float(tokens.shape[0] * tokens.shape[1] * dp * sp)

    def grad_impl(params, tokens, positions, targets):
        """Backward including gradient reduction — in-scan collectives
        under overlap, the post-backward :func:`reduce_block` otherwise.
        Returns ``(objective, fully reduced grads)``."""
        loss_fn = make_loss_fn(_ntok(tokens))
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        objective, grads = _foreach_micro(
            jax.value_and_grad(loss_fn),
            (jnp.zeros((), jnp.float32), zeros),
            params, tokens, positions, targets)
        if grad_reduce is None:
            grads = reduce_block(grads)
        return objective, grads

    @jax.named_scope("optimizer")
    def apply_impl(params, opt_state, grads):
        """Optimizer update + parameter apply."""
        if fused_apply:
            return optimizer.fused_apply(grads, opt_state, params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        # dtype-preserving apply: bf16-stored params add in f32 (updates
        # are f32 from the optimizer) and round ONCE; f32 params unchanged
        params = jax.tree_util.tree_map(
            lambda p, u: (p + u).astype(p.dtype), params, updates)
        return params, opt_state

    def step_impl(params, opt_state, tokens, positions, targets):
        objective, grads = grad_impl(params, tokens, positions, targets)
        loss = jax.lax.psum(
            jax.lax.psum(objective, SEQ_AXIS), DATA_AXIS
        )
        params, opt_state = apply_impl(params, opt_state, grads)
        return params, opt_state, loss

    return sp, pspecs, sspecs, tok_spec, step_impl


def build_lm_train_step(model: TransformerLM, mesh: Mesh, optimizer,
                        attn: str = "ring", accum_steps: int = 1,
                        vocab_block: Optional[int] = None,
                        overlap_grads=False, fused_apply: bool = False,
                        remat: str = "none"):
    """Compile one dp×sp (×ep for the MoE variant) LM training step.

    ``vocab_block`` streams the loss head in that many vocab columns per
    chunk (:func:`chunked_summed_xent`) so the ``[B, T, V]`` logits — and
    their cotangent — never materialize; essential at the imported-
    checkpoint vocab sizes (V = 32k–152k). ``None`` keeps the dense head.

    Returns ``(step, opt_init)``: ``step(params, opt_state, tokens,
    positions, targets) -> (params, opt_state, loss)`` with all three int
    arrays ``[B, T]`` — batch dim sharded over ``"data"``, sequence dim over
    ``"seq"``. Params and optimizer state follow ``model.specs()``: fully
    replicated for the dense model; for :class:`MoETransformerLM` the expert
    stacks (and their optimizer state) shard over ``"seq"`` and their
    gradients skip the seq-axis sum (each seq rank owns its experts — the
    all_to_all transpose already delivered their gradients locally).
    ``loss`` is the optimized objective: token-mean CE plus the
    ``aux_weight``-scaled load-balancing term (zero for the dense model).

    ``accum_steps > 1`` runs gradient accumulation: the local batch splits
    into that many microbatches, a ``lax.scan`` accumulates their gradients,
    and ONE optimizer step applies the sum — activation memory drops to one
    microbatch's worth (the long-context lever that composes with remat and
    sequence parallelism). For the dense model the accumulated step is
    mathematically identical to the full-batch step (pinned in tests); the
    MoE variant routes each microbatch as its own dispatch group, so its
    routing (not its math) differs from whole-batch routing.

    Hot-path knobs (all off by default; token/loss parity pinned in
    ``tests/models/test_train_overlap.py``):

    - ``overlap_grads=True`` buckets the gradient reduction by LAYER
      instead of firing one serialized psum block after the full backward:
      each block-scan step's param slice carries a reduce-on-backward
      custom-vjp tag (:func:`_reduce_on_backward`), so its seq/data
      collectives issue as soon as that layer's backward segment produces
      its cotangent and overlap the remaining backward compute.  Non-scan
      params (embeddings, final norm, head) are tagged at the top of the
      loss, which places the head/final-norm reductions BEFORE the block
      backward in program order.  The psum placement is value-identical
      (bit-identical at ``accum_steps=1``; with accumulation the
      per-microbatch reduction reassociates the cross-device sum — allclose
      parity, at ``accum_steps``× the communication volume).
      ``overlap_grads="ring"`` additionally lowers large buckets
      (≥ ``_RING_MIN_ELEMS`` elements) through :func:`ring_psum`'s chunked
      ``ppermute`` ring instead of one monolithic psum.
    - ``fused_apply=True`` collapses ``optimizer.update`` + the
      dtype-preserving apply into one fused pass per param leaf
      (``optimizer.fused_apply``) so moments and params stream through
      VMEM once instead of materializing a full ``updates`` tree; needs a
      fused-capable optimizer (``adam_compact``/``fused_adam``).
    - ``remat="none"|"dots"|"full"`` sets the block-scan rematerialization
      policy (:func:`_remat_wrap`).
    """
    sp, pspecs, sspecs, tok_spec, step_impl = _lm_step_parts(
        model, mesh, optimizer, attn, accum_steps, vocab_block,
        overlap_grads, fused_apply, remat)
    jit_step = jax.jit(
        shard_map(
            step_impl, mesh=mesh,
            in_specs=(pspecs, sspecs, tok_spec, tok_spec, tok_spec),
            out_specs=(pspecs, sspecs, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )

    def step(params, opt_state, tokens, positions, targets):
        _check_seq_len(model, sp, tokens.shape[1])
        return jit_step(params, opt_state, tokens, positions, targets)

    # Donation is verified at lowering (tests/models/test_donation.py);
    # expose it so the guard doesn't pay backend compilation.
    step.lower = jit_step.lower
    return step, make_opt_init(optimizer, mesh, sspecs)


def build_lm_eval_step(model: TransformerLM, mesh: Mesh, attn: str = "ring"):
    """Compile a dp×sp evaluation step: ``eval_fn(params, tokens, positions,
    targets) -> mean next-token cross-entropy`` (perplexity =
    ``exp(result)``) over the same shardings the train step uses — batch
    over ``"data"``, sequence over ``"seq"``. Same validation rules as
    :func:`build_lm_train_step`."""
    sp = _validate_lm_step(model, mesh, attn)
    pspecs = model.specs()
    tok_spec = P(DATA_AXIS, SEQ_AXIS)
    dp = mesh.shape[DATA_AXIS]

    def eval_impl(params, tokens, positions, targets):
        ntok_total = float(tokens.shape[0] * tokens.shape[1] * dp * sp)
        local = model.loss(params, tokens, positions, targets, attn=attn)
        return jax.lax.psum(
            jax.lax.psum(local, SEQ_AXIS), DATA_AXIS
        ) / ntok_total

    jit_eval = jax.jit(
        shard_map(
            eval_impl, mesh=mesh,
            in_specs=(pspecs, tok_spec, tok_spec, tok_spec),
            out_specs=P(),
            check_vma=False,
        )
    )

    def eval_fn(params, tokens, positions, targets):
        _check_seq_len(model, sp, tokens.shape[1])
        return jit_eval(params, tokens, positions, targets)

    return eval_fn


def shard_lm_batch(mesh: Mesh, tokens, positions, targets):
    """Place host ``[B, T]`` arrays on the dp×sp mesh."""
    sharding = NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS))
    return tuple(jax.device_put(a, sharding) for a in (tokens, positions, targets))
