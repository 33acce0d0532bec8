"""Tensor-parallel TransformerLM: shard the MODEL, not just the data.

EXTENSION BEYOND THE REFERENCE (which has no model-parallel story at all
— SURVEY.md §2.3 lists TP as explicitly absent). The dp×sp trainer
(``build_lm_train_step``) replicates parameters; this module shards them
Megatron-style over a ``("data", "model")`` mesh so a model larger than
one chip's HBM trains AND generates with every matrix split:

- ``wq``/``wk``/``wv`` column-sharded by ATTENTION HEAD groups over
  ``"model"`` (rank r owns heads ``[r·H/tp, (r+1)·H/tp)``) — attention is
  embarrassingly parallel across heads, so the whole attention block runs
  on local heads with no communication;
- ``wo`` row-sharded (its rows are the local heads' outputs) with ONE
  ``psum`` restoring the replicated residual;
- ``w1``/``b1`` column-, ``w2`` row-sharded: one more ``psum`` per block
  after the FFN — the classic two-collectives-per-layer schedule;
- layernorms, embeddings, and the logits head stay replicated (they are
  O(D) and O(V·D); the O(D²)/O(D·F) layer stacks carry the memory).

Autodiff reuses ``parallel.tensor``'s Megatron operator pair: the
replicated activation entering a sharded branch goes through
``identity_psum_grad`` (identity forward, ``psum`` backward — the *f*
operator) so each rank's partial cotangent is summed and the replicated
parameters (layernorms, embeddings) see identical, correct gradients on
every rank; the forward ``psum`` after ``wo``/``w2`` is
``psum_identity_grad`` (its output cotangent is already replicated —
shard_map's untracked-replication default transpose would psum it again
and scale gradients by ``tp``). Sharded parameters' gradients are
naturally local; everything then ``psum``s over ``"data"`` only.

Inference: :func:`build_lm_tp_generate` keeps the KV cache sharded by
heads — cache memory drops by ``tp`` (complementing
``models/sharded_generate.py``'s time-axis sharding) — and every rank
samples the same token from identical post-psum logits.

Dense family only (the MoE variant shards experts over ``"seq"`` — a
different axis plan). Exactness contract: forward logits, training
trajectories, and greedy rollouts all equal the replicated single-device
model's (``tests/models/test_tensor_lm.py``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.flash_attention import flash_attention
from ..ops.flash_decode import aligned_cache_length, decode_attention
from ..ops.pallas_ops import is_tpu_backend
from ..ops.ring_attention import attention_reference
from ..parallel.mesh import DATA_AXIS, build_mesh_2axis
from ..parallel.tensor import identity_psum_grad, psum_identity_grad
from ..parallel.param_utils import make_opt_init, opt_state_specs, \
    shard_by_specs
from .transformer import (
    TransformerLM,
    _rope_angles,
    write_prompt_cache,
    _rope_rotate,
    _summed_xent,
    select_tokens,
)

TP_AXIS = "model"


def build_mesh_tp(data: Optional[int] = None, model: int = 1,
                  devices=None) -> Mesh:
    """A 2-D ``("data", "model")`` mesh; ``model`` = tensor-parallel
    degree."""
    return build_mesh_2axis(TP_AXIS, data=data, second=model,
                            devices=devices)


def _refuse_latent(model, what: str) -> None:
    """The tensor-parallel layouts split ``wq``/``wk``/``wv`` and the K and
    V caches by head; a latent model has neither those leaves nor a cache
    with a head axis."""
    if getattr(model, "latent", False):
        raise NotImplementedError(
            f"{what} shards wq/wk/wv and per-head K and V caches over the "
            "model axis, and a latent-attention model has one joint latent "
            "projection and a cache of latent rows that no head owns")


def _validate_tp(model: TransformerLM, mesh: Mesh) -> int:
    if type(model).__name__ == "MoETransformerLM" or model.aux_weight != 0.0:
        raise NotImplementedError(
            "tensor parallelism covers the dense TransformerLM family; the "
            "MoE variant shards its experts over the seq axis instead "
            "(build_lm_train_step)"
        )
    if getattr(model, "mixed_window", False):
        raise NotImplementedError(
            "per-layer (mixed) attn_window models are single-device only "
            "for now: the tp builders assume one model-wide window for "
            "their ring-cache sizing and masks"
        )
    _refuse_latent(model, "tensor parallelism")
    model._refuse_layout("tensor parallelism")
    if DATA_AXIS not in mesh.shape or TP_AXIS not in mesh.shape:
        raise ValueError(
            f"mesh must carry ({DATA_AXIS!r}, {TP_AXIS!r}) axes, got "
            f"{dict(mesh.shape)}"
        )
    tp = mesh.shape[TP_AXIS]
    for name, val in (("n_heads", model.n_heads),
                      ("n_kv_heads", model.n_kv_heads),
                      ("d_ff", model.d_ff)):
        if val % tp:
            raise ValueError(
                f"{name}={val} must divide by the tensor axis size {tp}"
            )
    return tp


def tp_specs(model: TransformerLM) -> Dict[str, P]:
    """PartitionSpecs for TP over ``("data", "model")`` — layer stacks
    sharded on their head/ffn dimension, everything else replicated."""
    specs = {k: P() for k in model.param_shapes()}
    specs.update({
        "wq": P(None, None, TP_AXIS),
        "wk": P(None, None, TP_AXIS),
        "wv": P(None, None, TP_AXIS),
        "wo": P(None, TP_AXIS, None),
        "w1": P(None, None, TP_AXIS),
        "w2": P(None, TP_AXIS, None),
    })
    # architecture-conditional stacks (hf_import families): the swiglu
    # gate is column-sharded like w1; q/k/v biases shard with their
    # columns' heads; o/ffn output biases stay replicated — they add
    # AFTER the psum (adding a sharded copy before it would scale by tp)
    if model.ffn_bias:
        specs["b1"] = P(None, TP_AXIS)
    if model.activation == "swiglu":
        specs["w3"] = P(None, None, TP_AXIS)
    if model.attn_bias:
        specs["bq"] = P(None, TP_AXIS)
        specs["bk"] = P(None, TP_AXIS)
        specs["bv"] = P(None, TP_AXIS)
    return specs


def shard_tp_params(mesh: Mesh, model: TransformerLM,
                    params: Dict[str, Any]) -> Dict[str, Any]:
    """Place full (host/replicated) params into the TP layout."""
    return shard_by_specs(mesh, tp_specs(model), params)


def _tp_block(model: TransformerLM, h, lp, rope, attend, grad_mode: bool,
              fused_rope: bool = False):
    """One transformer block on rank-local head/ffn shards.

    ``h`` ``[B, T, D]`` replicated over the tensor axis; ``lp`` holds this
    layer's (sharded) matrices. Two psums: after ``wo`` and after ``w2``.
    ``grad_mode`` routes the collectives through ``parallel.tensor``'s
    Megatron operator pair — ``identity_psum_grad`` at branch entries
    (backward sums each rank's partial cotangent) and
    ``psum_identity_grad`` after ``wo``/``w2`` (the forward psum's output
    cotangent is already replicated, so its transpose is the identity —
    shard_map's untracked-replication default would psum it AGAIN and
    scale gradients by tp). Inference paths use the plain psum.
    """
    cd = model.compute_dtype
    B, T, D = h.shape
    Dh = model.d_model // model.n_heads
    if grad_mode:
        enter = lambda x: identity_psum_grad(x, TP_AXIS)
        tp_sum = lambda x: psum_identity_grad(x, TP_AXIS)
    else:
        enter = lambda x: x
        tp_sum = lambda x: jax.lax.psum(x, TP_AXIS)

    x = model._norm_h(lp, "ln1", h).astype(cd)
    x_in = enter(x)
    hl = lp["wq"].shape[-1] // Dh  # local query heads
    q = model._attn_proj(lp, "q", x_in).reshape(B, T, hl, Dh)
    kvl = lp["wk"].shape[-1] // Dh  # local KV heads
    k = model._attn_proj(lp, "k", x_in).reshape(B, T, kvl, Dh)
    v = model._attn_proj(lp, "v", x_in).reshape(B, T, kvl, Dh)
    if rope is not None and not fused_rope:
        # fused_rope: the attend closure rotates q/k inside the Pallas
        # kernel from once-built tables (training path; the returned k is
        # then UNROTATED, which is fine because training discards it).
        q = _rope_rotate(q, *rope)
        k = _rope_rotate(k, *rope)
    a = attend(q, k, v).astype(cd)
    part = a.reshape(B, T, hl * Dh) @ lp["wo"].astype(cd)
    h = h + tp_sum(part)
    if model.attn_bias:  # replicated o-bias adds once, post-psum
        h = h + lp["bo"].astype(cd)

    x = model._norm_h(lp, "ln2", h).astype(cd)
    x_in = enter(x)
    out = _tp_ffn(model, lp, x_in, cd, tp_sum)
    return h + out.astype(cd), (k, v)


def _tp_ffn(model: TransformerLM, lp, x_in, cd, tp_sum):
    """The FFN half of a TP block on column/row shards: ``w1``(+``w3``)
    column-sharded (their bias shards ride along), ``w2`` row-sharded,
    ONE psum, replicated ``b2`` added after it. The activation/bias
    dispatch itself lives in ``TransformerLM._ffn`` (the ``reduce``
    hook) — one home for the math, shards or not."""
    del cd  # _ffn works in x_in's dtype
    out, _ = model._ffn(lp, x_in, "dense", "seq", reduce=tp_sum)
    return out


def _tp_attend(model: TransformerLM, attn: str, rope, grad_mode: bool):
    """Shared attend-dispatch closure for the TP builders (the dp×tp
    forward and the pp×tp stage): flash on TPU (rope fused from
    once-built tables under ``grad_mode`` — XLA cannot hoist them from a
    scan body; inference callers need the pre-rotated k for the cache),
    dense reference elsewhere, the model-wide window throughout. Returns
    ``(attend, tables)`` — ``tables is not None`` ⇔ the caller must skip
    its own rope rotation (``fused_rope``)."""
    on_tpu_flash = attn == "flash" and is_tpu_backend()
    tables = None
    if rope is not None and on_tpu_flash and grad_mode:
        from ..ops.pallas_flash import make_rope_tables

        cos, sin = rope
        tables = make_rope_tables(cos[..., 0, :], sin[..., 0, :])

    def attend(q, k, v):
        w = model.attn_window
        if tables is not None:
            from ..ops.pallas_flash import flash_attention_rope

            return flash_attention_rope(q, k, v, *tables, True, window=w)
        if on_tpu_flash:
            return flash_attention(q, k, v, causal=True, window=w)
        return attention_reference(q, k, v, causal=True, window=w)

    return attend, tables


def _tp_forward(model: TransformerLM, params, tokens, positions, attn: str,
                grad_mode: bool):
    """Full TP forward → (logits [B, T, V] f32, (ks, vs) local-head K/V
    stacks [L, B, T, kvl, Dh])."""
    h = model._embed(params, tokens, positions)
    rope = model._rope_for(positions)
    attend, tables = _tp_attend(model, attn, rope, grad_mode)

    def block(h, lp):
        h, kv = _tp_block(model, h, lp, rope, attend, grad_mode,
                          fused_rope=tables is not None)
        return h, kv

    lps = {k: params[k] for k in model._block_keys()}
    h, (ks, vs) = jax.lax.scan(block, h, lps)
    h = model._norm_h(params, "lnf", h)
    return model._logits(params, h), (ks, vs)


def build_lm_tp_train_step(model: TransformerLM, mesh: Mesh, optimizer,
                           attn: str = "flash"):
    """Compile one dp×tp LM training step.

    Returns ``(step, opt_init)`` with the same calling convention as
    :func:`build_lm_train_step`: ``step(params, opt_state, tokens,
    positions, targets)`` with int ``[B, T]`` arrays, batch sharded over
    ``"data"``; params/optimizer state live in the :func:`tp_specs`
    layout. The loss is global-token-mean CE, identical to the replicated
    trainer's objective.
    """
    tp = _validate_tp(model, mesh)
    del tp
    pspecs = tp_specs(model)
    sspecs = opt_state_specs(optimizer, model.param_shapes(), pspecs)
    tok_spec = P(DATA_AXIS, None)
    dp = mesh.shape[DATA_AXIS]

    # Params sharded over "model" own their gradient shard locally; only
    # replicated params need their (identical-by-construction) gradients
    # left alone. Everything psums over "data".
    def step_impl(params, opt_state, tokens, positions, targets):
        ntok_total = float(tokens.shape[0] * tokens.shape[1] * dp)

        def loss_fn(p):
            logits, _ = _tp_forward(model, p, tokens, positions, attn,
                                    grad_mode=True)
            return _summed_xent(logits, targets) / ntok_total

        objective, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, DATA_AXIS), grads)
        loss = jax.lax.psum(objective, DATA_AXIS)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, opt_state, loss

    jit_step = jax.jit(
        shard_map(
            step_impl, mesh=mesh,
            in_specs=(pspecs, sspecs, tok_spec, tok_spec, tok_spec),
            out_specs=(pspecs, sspecs, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )
    return jit_step, make_opt_init(optimizer, mesh, sspecs)


def build_lm_tp_generate(model: TransformerLM, mesh: Mesh,
                         temperature: float = 0.0,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None,
                         attn: str = "flash"):
    """Compile dp×tp generation with the KV cache sharded BY HEADS.

    ``generate_fn(params, prompt, n_new, seed=0) -> [B, T0+n_new]`` —
    params in the :func:`tp_specs` layout (training output works as-is),
    batch over ``"data"``, each rank's cache holding only its
    ``Hkv/tp`` heads. Greedy output equals the replicated
    :meth:`TransformerLM.generate` token-for-token.
    """
    tp = _validate_tp(model, mesh)
    if top_k is not None and not 1 <= int(top_k) <= model.vocab:
        raise ValueError(
            f"top_k must be in [1, vocab={model.vocab}], got {top_k}"
        )
    if top_p is not None and not 0.0 < float(top_p) <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    dp = mesh.shape[DATA_AXIS]
    H = model.n_heads
    Hkv = model.n_kv_heads
    Dh = model.d_model // H
    hl, kvl = H // tp, Hkv // tp
    cd = model.compute_dtype
    pspecs = tp_specs(model)
    programs: Dict[Any, Any] = {}

    def _gen_impl(total: int, Tc: int, params, prompt, key):
        B, T0 = prompt.shape
        row0 = jax.lax.axis_index(DATA_AXIS) * B

        # -- prefill on local heads, cache [L, B, kvl, Tc, Dh]
        positions = jnp.broadcast_to(jnp.arange(T0), (B, T0))
        logits, (ks, vs) = _tp_forward(model, params, prompt, positions,
                                       attn, grad_mode=False)
        # ks/vs [L, B, T0, kvl, Dh] → cache layout [L, B, kvl, Tc, Dh];
        # windowed models roll: only the prompt's last Tc positions land,
        # at their p mod Tc slots (see TransformerLM.prefill)
        kc = jnp.zeros((model.n_layers, B, kvl, Tc, Dh), cd)
        vc = jnp.zeros_like(kc)
        kc, vc = write_prompt_cache(
            kc, vc, ks.transpose(0, 1, 3, 2, 4),
            vs.transpose(0, 1, 3, 2, 4), model.attn_window is not None)

        key, k0 = jax.random.split(key)
        first = select_tokens(logits[:, -1], k0, temperature, top_k, top_p,
                              row_offset=row0)
        buf = jnp.zeros((B, total), jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
        buf = buf.at[:, T0].set(first)

        lps = {k: params[k] for k in model._block_keys()}

        def decode_step(token, p, kc, vc):
            B = token.shape[0]
            pos_b = jnp.broadcast_to(p, (B,))
            h = model._embed(params, token, pos_b)  # [B, D]
            if model.pos_encoding == "rotary":
                r_cos, r_sin = _rope_angles(pos_b, Dh, model.rope_theta)
                r_cos, r_sin = r_cos[:, None, :], r_sin[:, None, :]

            ring = model.attn_window is not None
            tp_sum = lambda x: jax.lax.psum(x, TP_AXIS)

            def block(h, inputs):
                lp, kcl, vcl = inputs  # kcl/vcl [B, kvl, Tc, Dh]
                x = model._norm_h(lp, "ln1", h).astype(cd)
                q = model._attn_proj(lp, "q", x).reshape(B, hl, Dh)
                k_new = model._attn_proj(lp, "k", x).reshape(B, kvl, 1, Dh)
                v_new = model._attn_proj(lp, "v", x).reshape(B, kvl, 1, Dh)
                if model.pos_encoding == "rotary":
                    q = _rope_rotate(q, r_cos, r_sin)
                    k_new = _rope_rotate(k_new, r_cos[:, None],
                                         r_sin[:, None])
                widx = jnp.mod(p, kcl.shape[2]) if ring else p
                kcl = jax.lax.dynamic_update_slice_in_dim(
                    kcl, k_new, widx, axis=2)
                vcl = jax.lax.dynamic_update_slice_in_dim(
                    vcl, v_new, widx, axis=2)
                qg = q.reshape(B, kvl, hl // kvl, Dh)
                a = decode_attention(qg, kcl, vcl, p,
                                     window=model.attn_window,
                                     ring=ring).astype(cd)
                part = a.reshape(B, hl * Dh) @ lp["wo"].astype(cd)
                h = h + tp_sum(part)
                if model.attn_bias:
                    h = h + lp["bo"].astype(cd)
                x = model._norm_h(lp, "ln2", h).astype(cd)
                out = _tp_ffn(model, lp, x, cd, tp_sum)
                return h + out.astype(cd), (kcl, vcl)

            h, (kc, vc) = jax.lax.scan(block, h, (lps, kc, vc))
            h = model._norm_h(params, "lnf", h)
            return model._logits(params, h), kc, vc

        def step(carry, t):
            buf, kc, vc, token, key = carry
            logits, kc, vc = decode_step(token, t, kc, vc)
            key, kt = jax.random.split(key)
            nxt = select_tokens(logits, kt, temperature, top_k, top_p,
                                row_offset=row0)
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, nxt[:, None], t + 1, axis=1)
            return (buf, kc, vc, nxt, key), None

        (buf, _, _, _, _), _ = jax.lax.scan(
            step, (buf, kc, vc, first, key), jnp.arange(T0, total - 1))
        return buf

    def generate_fn(params, prompt, n_new: int, seed: int = 0):
        prompt = jnp.asarray(prompt, jnp.int32)
        B, T0 = prompt.shape
        total = T0 + int(n_new)
        if total > model.max_len:
            raise ValueError(
                f"prompt {T0} + n_new {n_new} exceeds max_len "
                f"{model.max_len}"
            )
        if B % dp:
            raise ValueError(f"batch {B} not divisible by data axis {dp}")
        if n_new < 1:
            return prompt
        Tc_req = total
        if model.attn_window is not None:
            Tc_req = min(total, model.attn_window) + 1  # ring + margin
        Tc = aligned_cache_length(Tc_req)
        geom = (B, T0, int(n_new))
        if geom not in programs:
            programs[geom] = jax.jit(
                shard_map(
                    functools.partial(_gen_impl, total, Tc),
                    mesh=mesh,
                    in_specs=(pspecs, P(DATA_AXIS, None), P()),
                    out_specs=P(DATA_AXIS, None),
                    check_vma=False,
                )
            )
        key = jax.random.PRNGKey(seed)
        return programs[geom](params, prompt, key)

    return generate_fn
