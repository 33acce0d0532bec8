"""Pipeline-parallel :class:`TransformerLM` training (dp×pp).

EXTENSION BEYOND THE REFERENCE (SURVEY.md §2.3: pipeline parallelism
"explicitly ABSENT"). ``parallel/pipeline.py`` ships the generic GPipe
ring (``pipeline_apply``: microbatches hop stages via ``ppermute``; the
backward pass is the reverse pipeline because XLA transposes the scan +
ppermute); its stage contract is shape-homogeneous ``[mb, ...] ->
[mb, ...]`` — and transformer blocks are exactly that
(``[mb, T, D] -> [mb, T, D]``), so LM DEPTH shards the same way width
(``models/tensor_lm.py``) and state (``models/fsdp_lm.py``) already do.

Layout: the ``[L, ...]`` stacked block params shard their leading axis
over ``"pipe"`` — rank ``r`` owns layers ``[r·G, (r+1)·G)`` (G =
``n_layers / pipe``), applied as a ``lax.scan`` inside its stage tick.
Embeddings, final norm, and the logits head replicate (every rank
computes them; the loss is masked to the LAST pipe rank and their
gradients are restored to the replicated invariant with one pipe-axis
``psum`` — the ``build_staged_train_step`` convention). The batch axis
composes as usual: one ``shard_map`` program, batch over ``"data"``,
stages over ``"pipe"``.

Positions must be row-uniform (every batch row carries the same position
vector — what ``make_lm_batches`` produces): all microbatches then share
one RoPE table, which is closure-captured instead of hopping the ring
with the activations.

GPipe over batch rows is mathematically exact for the dense LM (rows are
independent through attention; the loss is a token sum), so the 3-step
trajectory equals the unpipelined oracle to float tolerance
(``tests/models/test_pipeline_lm.py``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS
from ..parallel.param_utils import make_opt_init, opt_state_specs
from ..parallel.pipeline import PIPE_AXIS, build_mesh_pp, pipeline_apply
from .transformer import (
    SEQ_AXIS,
    TransformerLM,
    _summed_xent,
    chunked_summed_xent,
    is_tpu_backend,
)

__all__ = ["build_lm_pp_train_step", "build_mesh_pp"]


def build_lm_pp_train_step(model: TransformerLM, mesh: Mesh, optimizer,
                           n_micro: int, attn: str = "flash",
                           vocab_block: Optional[int] = None,
                           remat: bool = False,
                           schedule: str = "gpipe",
                           shard_edges: bool = False):
    """Compile one dp×pp LM training step.

    ``mesh`` must carry ``("data", "pipe")``; ``model.n_layers`` must
    divide by the pipe size (one contiguous group of layers per stage).
    ``n_micro`` microbatches stream the ring — bubble fraction
    ``(P-1)/(M+P-1)``, so choose ``n_micro >> pipe``. ``attn`` is
    ``"flash"`` or ``"dense"`` (the sequence stays whole; sp composes via
    a separate mesh, not here). ``vocab_block`` streams the loss head
    (``chunked_summed_xent``).

    ``schedule`` (round 5):

    - ``"gpipe"`` — the scan+transpose formulation: all-microbatch
      forward, then XLA's reversed scan as the backward pipeline.
      ``remat=True`` wraps each stage tick in :func:`jax.checkpoint`, so
      the stash holds tick INPUTS only (``≈ n_micro`` microbatch
      activations per rank instead of every layer internal).
    - ``"1f1b"`` — the hand-scheduled one-forward-one-backward loop
      (:func:`_pp_1f1b_grads`): activation stash bounded at ``2P−1``
      microbatch INPUTS regardless of ``n_micro`` (the recompute-style
      1F1B — inputs are stored, stage internals rebuilt at the backward
      tick), same bubble, and — the layout fix — embeddings run ONLY on
      pipe rank 0 and the norm+head+loss ONLY on the last rank
      (``lax.cond``-gated: the ``[D, V]`` head matmul's FLOPs and its
      activation stash no longer replicate across all ``P`` ranks).
      ``remat`` is implied (the backward tick is a recompute by
      construction).

    ``shard_edges`` (1F1B only): the token embedding (rows) and the
    untied head (columns) STORE sharded over ``"pipe"`` — params and
    their adam moments at rest divide by ``P``, the tensors a large
    vocab makes dominant — and are all-gathered ONCE per step into
    transients (the ZeRO-3 convention; gradient transpose is one
    ``psum_scatter``). Requires ``vocab % pipe == 0``.

    Returns ``(step, opt_init)`` with the ``build_lm_train_step``
    contract: ``step(params, opt_state, tokens, positions, targets)``,
    int arrays ``[B, T]`` sharded over ``"data"`` only, params per
    :func:`lm_pp_specs` (block stacks over ``"pipe"``, the rest
    replicated), ``loss`` = global token-mean CE.
    """
    model._refuse_layout("the pipeline train step")
    if getattr(model, "n_experts", None):
        raise NotImplementedError(
            "dp×pp covers the dense TransformerLM family; MoE experts "
            "shard over the seq axis (build_lm_train_step) instead"
        )
    if attn not in ("dense", "flash"):
        raise ValueError(
            f"attn={attn!r}: the pipelined LM keeps sequences whole — "
            "use 'flash' (TPU) or 'dense'"
        )
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"Unknown schedule: {schedule!r}")
    pp = mesh.shape[PIPE_AXIS]
    dp = mesh.shape[DATA_AXIS]
    if model.n_layers % pp:
        raise ValueError(
            f"n_layers {model.n_layers} not divisible by pipe axis {pp}"
        )
    if n_micro < 1:
        raise ValueError(f"n_micro must be >= 1, got {n_micro}")
    if shard_edges:
        if schedule != "1f1b":
            raise ValueError(
                "shard_edges requires schedule='1f1b' (the GPipe path "
                "replicates edge compute)")
        if model.vocab % pp:
            raise ValueError(
                f"shard_edges needs vocab {model.vocab} divisible by the "
                f"pipe axis {pp}")

    block_keys = set(model._block_keys())
    edge_keys = _edge_keys(model) if shard_edges else frozenset()
    pspecs = lm_pp_specs(model, shard_edges=shard_edges)
    sspecs = opt_state_specs(optimizer, model.param_shapes(), pspecs)
    tok_spec = P(DATA_AXIS)

    def _mk_attend_and_stage(mb, positions):
        """Shared stage construction (GPipe and 1F1B): the per-microbatch
        rope closure + the G-layer stage scan body (params bind at the
        stage_fn CALL, so nothing here enters differentiation)."""
        rope = model._rope_for(positions)
        # row-uniform positions ⇒ every microbatch shares the first
        # mb rows' table (the documented contract)
        rope_mb = None if rope is None else (rope[0][:mb], rope[1][:mb])
        tables = None
        if rope_mb is not None and attn == "flash" and is_tpu_backend():
            from ..ops.pallas_flash import make_rope_tables

            cos, sin = rope_mb
            tables = make_rope_tables(cos[..., 0, :], sin[..., 0, :])

        def attend(q, k, v, rp=None):
            return model._attend(q, k, v, attn, SEQ_AXIS, rope=rp,
                                 rope_tables=tables)

        def stage_fn(stage_params, x):
            def one(hh, lp):
                hh, _, _, _ = model._block_fwd(
                    hh, lp, attend, attn, SEQ_AXIS, rope=rope_mb)
                return hh, None

            out, _ = jax.lax.scan(one, x, stage_params)
            return out

        return stage_fn, rope_mb

    def _head_ce(p, h, tgt):
        """Final norm + logits head + summed CE on one block."""
        h = model._norm_h(p, "lnf", h)
        if vocab_block is not None:
            return chunked_summed_xent(h, model.head_weight(p), tgt,
                                       vocab_block)
        return _summed_xent(model._logits(p, h), tgt)

    def step_impl(params, opt_state, tokens, positions, targets):
        prank = jax.lax.axis_index(PIPE_AXIS)
        ntok_total = float(tokens.shape[0] * tokens.shape[1] * dp)
        B = tokens.shape[0]
        if B % n_micro:
            raise ValueError(
                f"local batch {B} not divisible by n_micro={n_micro}")
        mb = B // n_micro

        if schedule == "1f1b":
            full = params
            if edge_keys:
                # gather the pipe-sharded edge tensors into per-step
                # transients (storage + adam state stay ÷P at rest)
                full = dict(params)
                full["tok"] = jax.lax.all_gather(
                    params["tok"], PIPE_AXIS, axis=0, tiled=True)
                if "head" in params:
                    full["head"] = jax.lax.all_gather(
                        params["head"], PIPE_AXIS, axis=1, tiled=True)
            objective, grads = _pp_1f1b_grads(
                model, full, tokens, positions, targets, n_micro,
                ntok_total, block_keys, _mk_attend_and_stage, _head_ce)
            for k in edge_keys:
                # transpose of the all_gather: sum ranks' partials and
                # return THIS rank's shard (also completes the pipe
                # reduction for these keys)
                grads[k] = jax.lax.psum_scatter(
                    grads[k], PIPE_AXIS,
                    scatter_dimension=0 if k == "tok" else 1, tiled=True)
        else:
            def loss_fn(p):
                h = model._embed(p, tokens, positions)
                stage_fn, _ = _mk_attend_and_stage(mb, positions)
                if remat:
                    # stash tick INPUTS only; stage internals recompute
                    # in the reversed scan
                    stage_fn = jax.checkpoint(stage_fn)
                lp_stage = {k: p[k] for k in block_keys}  # local [G, ...]
                h = pipeline_apply(stage_fn, lp_stage, h, n_micro)
                ce = _head_ce(p, h, targets)
                # count the pipe-replicated loss once: mask to last rank
                return jnp.where(prank == pp - 1, ce / ntok_total, 0.0)

            objective, grads = jax.value_and_grad(loss_fn)(params)
        # stage params are pipe-OWNED (the reverse pipeline delivered their
        # cotangents locally) and sharded edges were psum_scattered above;
        # remaining replicated params need the pipe psum to restore the
        # identical-across-ranks invariant.
        no_pipe_psum = block_keys | edge_keys
        grads = {
            k: jax.lax.psum(
                g if k in no_pipe_psum else jax.lax.psum(g, PIPE_AXIS),
                DATA_AXIS,
            )
            for k, g in grads.items()
        }
        loss = jax.lax.psum(jax.lax.psum(objective, PIPE_AXIS), DATA_AXIS)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, opt_state, loss

    step = jax.jit(
        shard_map(
            step_impl, mesh=mesh,
            in_specs=(pspecs, sspecs, tok_spec, tok_spec, tok_spec),
            out_specs=(pspecs, sspecs, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )
    return step, make_opt_init(optimizer, mesh, sspecs)


def _pp_1f1b_grads(model, params, tokens, positions, targets, n_micro,
                   ntok_total, block_keys, mk_stage, head_ce):
    """Hand-scheduled 1F1B pipeline: loss partial + grads, INSIDE shard_map.

    Timing (M microbatches, P ranks, ``2(P−1) + M`` ticks): rank ``r``
    runs microbatch ``i``'s FORWARD at tick ``i + r`` and its BACKWARD at
    tick ``i + 2(P−1) − r`` — the last rank's backward follows its
    forward immediately (the 1F1B property), cotangents hop the ring in
    reverse one tick behind. Each rank stores only its stage INPUT per
    in-flight microbatch, in a ``2P−1``-deep rotating stash (the gap
    between a microbatch's forward and backward at rank ``r`` is
    ``2(P−1−r)`` ticks) — activation memory is O(P) microbatches however
    large ``n_micro`` grows; the backward tick recomputes the stage via
    ``jax.vjp`` (the remat trade, same FLOPs as GPipe+remat).

    Rank-edge work is ``lax.cond``-gated, not replicated: rank 0's
    composite embeds its token microbatch (the ring input is ignored);
    the LAST rank's composite runs final-norm + head + CE and seeds its
    own h-cotangent from the loss (its ring cotangent input is zero) —
    so the ``[D, V]`` head matmul and its stash exist on ONE rank.
    Gradients accumulate across backward ticks into a zeros-like(params)
    carry; the caller applies the usual pipe/data psum convention
    (edge-param grads are nonzero only on their owning rank here, and
    the pipe psum restores the replicated invariant).
    """
    p = axis_size(PIPE_AXIS)
    rank = jax.lax.axis_index(PIPE_AXIS)
    B, T = tokens.shape
    mb = B // n_micro
    D = model.d_model
    cd = model.compute_dtype
    stage_fn, _ = mk_stage(mb, positions)

    toks_m = tokens.reshape(n_micro, mb, T)
    pos_m = positions.reshape(n_micro, mb, T)
    tgt_m = targets.reshape(n_micro, mb, T)

    def composite(prm, x, toks, pos, tgt):
        """One rank's whole tick work for one microbatch: (embed |
        identity) → stage → (norm+head+CE | identity). Returns
        ``(h_out, loss_partial)``; the loss output's cotangent seeds the
        last rank's backward."""
        h_in = jax.lax.cond(
            rank == 0,
            lambda: model._embed(prm, toks, pos).astype(cd),
            lambda: x,
        )
        h_out = stage_fn({k: prm[k] for k in block_keys}, h_in)
        ce = jax.lax.cond(
            rank == p - 1,
            lambda: head_ce(prm, h_out, tgt) / ntok_total,
            lambda: jnp.asarray(0.0, jnp.float32),
        )
        return h_out, ce

    S = 2 * p - 1  # stash depth: ≥ max fwd→bwd gap (2(P−1)) + 1
    ticks = n_micro + 2 * (p - 1)
    fwd_perm = [(i, (i + 1) % p) for i in range(p)]
    bwd_perm = [(i, (i - 1) % p) for i in range(p)]
    zero_h = jnp.zeros((mb, T, D), cd)
    g0 = jax.tree_util.tree_map(jnp.zeros_like, params)

    def slice_mb(a, i):
        return jax.lax.dynamic_index_in_dim(
            a, jnp.clip(i, 0, n_micro - 1), axis=0, keepdims=False)

    def tick(carry, t):
        fwd_act, bwd_cot, stash, gacc, lacc = carry
        recv_f = jax.lax.ppermute(fwd_act, PIPE_AXIS, fwd_perm)
        recv_b = jax.lax.ppermute(bwd_cot, PIPE_AXIS, bwd_perm)

        # ---- forward slot: microbatch f = t - rank ----
        f = t - rank
        do_f = (f >= 0) & (f < n_micro)
        x_in = jnp.where(rank == 0, zero_h, recv_f)  # rank 0 embeds
        h_out, ce = composite(params, x_in, slice_mb(toks_m, f),
                              slice_mb(pos_m, f), slice_mb(tgt_m, f))
        fwd_act = jnp.where(do_f, h_out, fwd_act)
        lacc = lacc + jnp.where(do_f, ce, 0.0)
        stash = jax.lax.dynamic_update_index_in_dim(
            stash, jnp.where(do_f, x_in, stash[jnp.clip(f % S, 0, S - 1)]),
            jnp.clip(f % S, 0, S - 1), axis=0)

        # ---- backward slot: microbatch b = t - (2(P−1) − rank) ----
        b = t - (2 * (p - 1) - rank)
        do_b = (b >= 0) & (b < n_micro)
        x_b = stash[jnp.clip(b % S, 0, S - 1)]
        h_ct = jnp.where(rank == p - 1, jnp.zeros_like(recv_b), recv_b)

        def run_bwd():
            _, pull = jax.vjp(
                lambda prm, xx: composite(prm, xx, slice_mb(toks_m, b),
                                          slice_mb(pos_m, b),
                                          slice_mb(tgt_m, b)),
                params, x_b)
            dprm, dx = pull((h_ct, jnp.asarray(1.0, jnp.float32)))
            return dprm, dx

        def skip_bwd():
            return g0, jnp.zeros_like(zero_h)

        dprm, dx = jax.lax.cond(do_b, run_bwd, skip_bwd)
        gacc = jax.tree_util.tree_map(jnp.add, gacc, dprm)
        bwd_cot = jnp.where(do_b, dx.astype(cd), bwd_cot)
        return (fwd_act, bwd_cot, stash, gacc, lacc), None

    stash0 = jnp.zeros((S, mb, T, D), cd)
    carry0 = (zero_h, jnp.zeros_like(zero_h), stash0, g0,
              jnp.asarray(0.0, jnp.float32))
    (fwd_act, bwd_cot, stash, gacc, lacc), _ = jax.lax.scan(
        tick, carry0, jnp.arange(ticks))
    return lacc, gacc


def lm_pp_tp_specs(model: TransformerLM) -> Dict[str, P]:
    """PartitionSpecs for the 3-D dp×pp×tp layout: block stacks shard
    their leading layer dim over ``"pipe"`` AND their head/ffn dim over
    ``"model"`` (the :func:`~.tensor_lm.tp_specs` plan per layer);
    embeddings/final-norm/head replicate."""
    from .tensor_lm import tp_specs

    block_keys = set(model._block_keys())
    tspecs = tp_specs(model)
    specs: Dict[str, P] = {}
    for k in model.param_shapes():
        if k not in block_keys:
            specs[k] = P()
            continue
        t = tuple(tspecs.get(k, P()))
        specs[k] = P(PIPE_AXIS, *t[1:]) if t else P(PIPE_AXIS)
    return specs


def build_lm_pp_tp_train_step(model: TransformerLM, mesh: Mesh, optimizer,
                              n_micro: int, attn: str = "flash"):
    """Compile one REAL-LM 3-D training step on ``("data","pipe","model")``
    (round 5 — replaces the toy ``TensorPipelineStack``-only composition
    for transformer depth × width).

    GPipe microbatches stream transformer blocks over ``"pipe"``
    (:func:`~..parallel.pipeline.pipeline_apply`; the backward is the
    reverse pipeline by transposition) while every block computes on
    Megatron column/row shards over ``"model"``
    (:func:`~.tensor_lm._tp_block`: attention by local head groups, the
    classic two psums per layer through the ``identity_psum_grad`` /
    ``psum_identity_grad`` operator pair). Batch shards over ``"data"``.
    Embeddings/final-norm/head replicate (their gradients are identical
    across ``"model"`` by the operator-pair argument and restored across
    ``"pipe"`` with one psum — the GPipe convention); block gradients are
    owned per (pipe, model) shard with no collective beyond the data
    psum. Same contract as :func:`build_lm_pp_train_step`; params follow
    :func:`lm_pp_tp_specs`. Trajectory equals the unpipelined replicated
    oracle (``tests/models/test_pipeline_lm.py``).
    """
    model._refuse_layout("the pipeline x tensor train step")
    from .tensor_lm import TP_AXIS, _tp_block, _validate_tp

    if getattr(model, "n_experts", None):
        raise NotImplementedError(
            "dp×pp×tp covers the dense TransformerLM family")
    if attn not in ("dense", "flash"):
        raise ValueError(
            f"attn={attn!r}: the pipelined LM keeps sequences whole — "
            "use 'flash' (TPU) or 'dense'")
    _validate_tp(model, mesh)
    if PIPE_AXIS not in mesh.shape:
        raise ValueError(
            f"mesh must carry a {PIPE_AXIS!r} axis, got "
            f"{dict(mesh.shape)}")
    pp = mesh.shape[PIPE_AXIS]
    dp = mesh.shape[DATA_AXIS]
    if model.n_layers % pp:
        raise ValueError(
            f"n_layers {model.n_layers} not divisible by pipe axis {pp}")
    if n_micro < 1:
        raise ValueError(f"n_micro must be >= 1, got {n_micro}")

    block_keys = set(model._block_keys())
    pspecs = lm_pp_tp_specs(model)
    sspecs = opt_state_specs(optimizer, model.param_shapes(), pspecs)
    tok_spec = P(DATA_AXIS)

    def step_impl(params, opt_state, tokens, positions, targets):
        prank = jax.lax.axis_index(PIPE_AXIS)
        ntok_total = float(tokens.shape[0] * tokens.shape[1] * dp)
        B = tokens.shape[0]
        if B % n_micro:
            raise ValueError(
                f"local batch {B} not divisible by n_micro={n_micro}")
        mb = B // n_micro

        def loss_fn(p):
            from .tensor_lm import _tp_attend

            h = model._embed(p, tokens, positions)
            rope = model._rope_for(positions)
            # row-uniform positions ⇒ microbatches share the first mb
            # rows' rope (the pipeline contract)
            rope_mb = None if rope is None else (rope[0][:mb],
                                                 rope[1][:mb])
            attend, tables = _tp_attend(model, attn, rope_mb, True)

            def stage_fn(stage_params, x):
                def one(hh, lp):
                    hh, _ = _tp_block(model, hh, lp, rope_mb, attend,
                                      grad_mode=True,
                                      fused_rope=tables is not None)
                    return hh, None

                out, _ = jax.lax.scan(one, x, stage_params)
                return out

            lp_stage = {k: p[k] for k in block_keys}
            h = pipeline_apply(stage_fn, lp_stage, h, n_micro)
            h = model._norm_h(p, "lnf", h)
            ce = _summed_xent(model._logits(p, h), targets)
            return jnp.where(prank == pp - 1, ce / ntok_total, 0.0)

        objective, grads = jax.value_and_grad(loss_fn)(params)
        # block grads: owned per (pipe, model) shard; replicated params:
        # identical across "model" (operator pair) — one PIPE psum
        # restores replication, then everything psums over "data".
        grads = {
            k: jax.lax.psum(
                g if k in block_keys else jax.lax.psum(g, PIPE_AXIS),
                DATA_AXIS,
            )
            for k, g in grads.items()
        }
        loss = jax.lax.psum(jax.lax.psum(objective, PIPE_AXIS), DATA_AXIS)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda prm, u: (prm + u).astype(prm.dtype), params, updates)
        return params, opt_state, loss

    step = jax.jit(
        shard_map(
            step_impl, mesh=mesh,
            in_specs=(pspecs, sspecs, tok_spec, tok_spec, tok_spec),
            out_specs=(pspecs, sspecs, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )
    return step, make_opt_init(optimizer, mesh, sspecs)


def _edge_keys(model: TransformerLM):
    """The vocab-sized edge tensors ``shard_edges`` splits over the pipe
    axis: the token embedding, plus the untied head."""
    return frozenset(
        ["tok"] + ([] if model.tie_embeddings else ["head"]))


def lm_pp_specs(model: TransformerLM, shard_edges: bool = False):
    """PartitionSpecs for the dp×pp layout (block stacks over ``"pipe"``;
    with ``shard_edges``, the embedding rows / head columns too)."""
    block_keys = set(model._block_keys())
    specs = {k: P(PIPE_AXIS) if k in block_keys else P()
             for k in model.param_shapes()}
    if shard_edges:
        specs["tok"] = P(PIPE_AXIS)
        if not model.tie_embeddings:
            specs["head"] = P(None, PIPE_AXIS)
    return specs
