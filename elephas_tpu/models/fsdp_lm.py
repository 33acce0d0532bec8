"""ZeRO-3 / FSDP for the :class:`TransformerLM` family.

EXTENSION BEYOND THE REFERENCE (SURVEY.md §2.3: ZeRO/FSDP "explicitly
absent" — every reference executor replicates the whole model). The generic
flat-buffer FSDP (``parallel/fsdp.py``) gathers ALL params every step —
fine for MLPs, fatal for a 7B-class LM whose full f32 params alone exceed
one chip's HBM. This module is the LM-shaped ZeRO-3:

- **at rest** every parameter — and therefore the optimizer state built
  over the same layout — is sharded over the combined ``("data", "seq")``
  mesh axes. Per-device params + opt state are ``total / P`` (+ padding).
- **in compute** the per-layer block stacks are gathered ONE LAYER AT A
  TIME inside the ``lax.scan`` over layers (all_gather of that layer's
  chunk row), so transient full-param memory is one block + the
  embedding/head group, never the whole model. The AD transpose of each
  per-layer gather is a per-layer ``psum_scatter``: gradients arrive
  chunked and already summed over the mesh — the classic
  all_gather/reduce_scatter pair, per layer, same bytes on the wire as
  replicated DP's allreduce.
- **update** the (elementwise) optimizer steps on the local chunk: 1/P of
  the update FLOPs and state bandwidth. ``adam_compact`` halves the state
  bytes again.

The schedule is mathematically the replicated gradient-synchronous step in
a different storage layout; ``tests/models/test_fsdp_lm.py`` pins the
3-step trajectory against ``build_lm_train_step``'s replicated oracle, the
per-device memory bound, and sharded-checkpoint resume through
``utils/checkpoint.save_sharded_pytree``.

Same LIMITATION as ``parallel/fsdp.py``: the optimizer must be elementwise
(sgd/momentum/adam/rmsprop/… — anything reducing across the parameter
vector would see one chunk).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS
from ..parallel.param_utils import make_opt_init, opt_state_specs
from .transformer import (
    SEQ_AXIS,
    TransformerLM,
    _summed_xent,
    _validate_lm_step,
    is_tpu_backend,
)

BLOCKS_KEY = "blocks"
OTHER_KEY = "other"
EXPERTS_KEY = "experts"
FSDP_AXES = (DATA_AXIS, SEQ_AXIS)


def _pad_chunk(total: int, p: int) -> Tuple[int, int]:
    padded = int(math.ceil(total / p) * p) if total else p
    return padded, padded // p


def _flat_geometry(keys, shapes, lead: int, pad_to: int):
    """Shared flat-buffer geometry for a key group: per-key shapes (with
    ``lead`` leading stack dims dropped), element sizes, running offsets,
    the packed total, and its ``pad_to``-padded chunking. THE single home
    of the layout arithmetic the blocks/other/experts buffers all use."""
    gshapes = {k: shapes[k][lead:] for k in keys}
    sizes = {k: int(np.prod(s)) if s else 1 for k, s in gshapes.items()}
    offsets: Dict[str, int] = {}
    off = 0
    for k in keys:
        offsets[k] = off
        off += sizes[k]
    padded, chunk = _pad_chunk(off, pad_to)
    return gshapes, sizes, offsets, off, padded, chunk


class LMFsdpLayout:
    """Chunked ⇄ named views of a :class:`TransformerLM` param dict.

    Two buffers:

    - ``"blocks"`` ``[L, P, cb]``: per layer, the flattened concatenation
      of that layer's block params (order = ``model._block_keys()``),
      zero-padded to a multiple of ``P`` — sharded ``P(None, ("data",
      "seq"))`` so each device keeps one ``[L, 1, cb]`` sliver and the
      scan gathers one ``[cb·P]`` layer at a time.
    - ``"other"`` ``[P, co]``: everything else (embeddings, final norm,
      untied head) as one flat buffer, sharded over the same combined
      axis.
    - ``"experts"`` ``[L, E, dp, ce]`` (:class:`MoETransformerLM` only,
      round 5): the expert stacks keep their NATURAL sharding over the
      expert/``"seq"`` axis (dim 1, ``E/sp`` experts per seq rank — the
      layout the dispatch all_to_alls require) and are additionally
      ZeRO-chunked over ``"data"`` (dim 2), so at rest they too divide by
      the full ``dp·sp``. The per-layer gather is over ``"data"`` ONLY
      (transient = this rank's ``E/sp`` experts, never the full stack),
      and its AD transpose is the data-axis psum_scatter — exactly the
      "expert grads psum over data only" convention the replicated MoE
      step uses. Router (``wg``) and attention params ride ``"blocks"``.
    """

    def __init__(self, model: TransformerLM, n_shards: int,
                 data_shards: Optional[int] = None,
                 expert_shards: Optional[int] = None):
        moe = getattr(model, "moe", None)
        if moe is not None:
            if data_shards is None or expert_shards is None:
                raise ValueError(
                    "MoE FSDP needs the mesh split: pass data_shards (dp) "
                    "and expert_shards (sp) — experts shard E over 'seq' "
                    "and chunk over 'data'")
            if data_shards * expert_shards != int(n_shards):
                raise ValueError(
                    f"data_shards {data_shards} x expert_shards "
                    f"{expert_shards} != n_shards {n_shards}")
            if moe.n_experts % expert_shards:
                raise ValueError(
                    f"n_experts {moe.n_experts} not divisible by "
                    f"expert_shards {expert_shards}")
            if jnp.dtype(moe.param_dtype) != jnp.float32:
                raise NotImplementedError(
                    "MoE FSDP chunks flatten to f32 buffers; "
                    "param_dtype='bfloat16' is a single-chip storage "
                    "option, not an FSDP layout")
        self.n_shards = int(n_shards)
        self.dp = int(data_shards) if data_shards else self.n_shards
        self.ep = int(expert_shards) if expert_shards else 1
        self.expert_keys = tuple(moe.expert_keys()) if moe is not None \
            else ()
        self.n_experts = moe.n_experts if moe is not None else 0
        shapes = {k: tuple(s.shape) for k, s in model.param_shapes().items()}
        self.block_keys = tuple(k for k in model._block_keys()
                                if k not in self.expert_keys)
        self.other_keys = tuple(
            k for k in shapes
            if k not in self.block_keys and k not in self.expert_keys)
        # per-expert payload geometry: shapes[k] = [L, E, ...]
        (self.eshapes, self.esizes, self.eoffsets, self.etotal,
         self.epadded, self.ce) = _flat_geometry(
            self.expert_keys, shapes, 2, self.dp)
        if not self.expert_keys:
            self.epadded = self.ce = 0
        self.n_layers = model.n_layers
        # per-layer geometry of the stacked block params (leading L dropped)
        (self.bshapes, self.bsizes, self.boffsets, self.btotal,
         self.bpadded, self.cb) = _flat_geometry(
            self.block_keys, shapes, 1, self.n_shards)
        (self.oshapes, self.osizes, self.ooffsets, self.ototal,
         self.opadded, self.co) = _flat_geometry(
            self.other_keys, shapes, 0, self.n_shards)

    # -- host-side layout ----------------------------------------------
    def chunk_host(self, params: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Full host params → ``{"blocks": [L, P, cb], "other": [P, co]}``
        plus, for the MoE family, ``"experts": [L, E, dp, ce]``."""
        want = set(self.block_keys) | set(self.other_keys) | set(
            self.expert_keys)
        if set(params) != want:
            raise ValueError(
                f"param keys {sorted(params)} != layout keys {sorted(want)}"
            )
        blocks = np.zeros((self.n_layers, self.bpadded), np.float32)
        for k in self.block_keys:
            o = self.boffsets[k]
            blocks[:, o:o + self.bsizes[k]] = np.asarray(
                params[k], np.float32).reshape(self.n_layers, -1)
        other = np.zeros((self.opadded,), np.float32)
        for k in self.other_keys:
            o = self.ooffsets[k]
            other[o:o + self.osizes[k]] = np.asarray(
                params[k], np.float32).reshape(-1)
        out = {
            BLOCKS_KEY: blocks.reshape(self.n_layers, self.n_shards, self.cb),
            OTHER_KEY: other.reshape(self.n_shards, self.co),
        }
        if self.expert_keys:
            ex = np.zeros((self.n_layers, self.n_experts, self.epadded),
                          np.float32)
            for k in self.expert_keys:
                o = self.eoffsets[k]
                ex[:, :, o:o + self.esizes[k]] = np.asarray(
                    params[k], np.float32).reshape(
                        self.n_layers, self.n_experts, -1)
            out[EXPERTS_KEY] = ex.reshape(
                self.n_layers, self.n_experts, self.dp, self.ce)
        return out

    def unchunk_host(self, chunks: Dict[str, Any]) -> Dict[str, np.ndarray]:
        blocks = np.asarray(chunks[BLOCKS_KEY]).reshape(self.n_layers, -1)
        other = np.asarray(chunks[OTHER_KEY]).reshape(-1)
        out = {
            k: blocks[:, o:o + self.bsizes[k]].reshape(
                (self.n_layers,) + self.bshapes[k])
            for k, o in self.boffsets.items()
        }
        out.update({
            k: other[o:o + self.osizes[k]].reshape(self.oshapes[k])
            for k, o in self.ooffsets.items()
        })
        if self.expert_keys:
            ex = np.asarray(chunks[EXPERTS_KEY]).reshape(
                self.n_layers, self.n_experts, -1)
            out.update({
                k: ex[:, :, o:o + self.esizes[k]].reshape(
                    (self.n_layers, self.n_experts) + self.eshapes[k])
                for k, o in self.eoffsets.items()
            })
        return out

    def specs(self) -> Dict[str, P]:
        out = {BLOCKS_KEY: P(None, FSDP_AXES), OTHER_KEY: P(FSDP_AXES)}
        if self.expert_keys:
            out[EXPERTS_KEY] = P(None, SEQ_AXIS, DATA_AXIS, None)
        return out

    def chunk_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        out = {
            BLOCKS_KEY: jax.ShapeDtypeStruct(
                (self.n_layers, self.n_shards, self.cb), jnp.float32),
            OTHER_KEY: jax.ShapeDtypeStruct(
                (self.n_shards, self.co), jnp.float32),
        }
        if self.expert_keys:
            out[EXPERTS_KEY] = jax.ShapeDtypeStruct(
                (self.n_layers, self.n_experts, self.dp, self.ce),
                jnp.float32)
        return out

    def shard(self, mesh: Mesh, chunks: Dict[str, Any]) -> Dict[str, Any]:
        specs = self.specs()
        return {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in chunks.items()
        }

    # -- inside shard_map ----------------------------------------------
    def gather_other(self, local_other) -> Dict[str, Any]:
        """Local ``[1, co]`` → the full non-layer params (ONE all_gather)."""
        flat = jax.lax.all_gather(local_other[0], FSDP_AXES, tiled=True)
        return {
            k: jax.lax.dynamic_slice_in_dim(
                flat, o, self.osizes[k]).reshape(self.oshapes[k])
            for k, o in self.ooffsets.items()
        }

    def gather_layer(self, local_row) -> Dict[str, Any]:
        """One layer's local ``[1, cb]`` chunk → that layer's full block
        params in the per-layer shapes ``_block_fwd`` consumes (ONE
        all_gather per scanned layer; its AD transpose is that layer's
        psum_scatter)."""
        flat = jax.lax.all_gather(local_row[0], FSDP_AXES, tiled=True)
        return {
            k: jax.lax.dynamic_slice_in_dim(
                flat, o, self.bsizes[k]).reshape(self.bshapes[k])
            for k, o in self.boffsets.items()
        }

    def gather_layer_experts(self, local_erow) -> Dict[str, Any]:
        """One layer's local ``[E/sp, 1, ce]`` expert sliver → this seq
        rank's LOCAL expert stacks ``[E/sp, ...]`` (one ``"data"``-axis
        all_gather; the full ``E`` never materializes — the dispatch
        all_to_alls expect exactly these seq-sharded stacks). AD
        transpose = the data-axis psum_scatter, i.e. the replicated MoE
        step's "expert grads psum over data only" convention."""
        e_l = local_erow.shape[0]
        flat = jax.lax.all_gather(
            local_erow[:, 0], DATA_AXIS, axis=1, tiled=True)  # [E/sp, dp·ce]
        return {
            k: jax.lax.dynamic_slice_in_dim(
                flat, o, self.esizes[k], axis=1).reshape(
                    (e_l,) + self.eshapes[k])
            for k, o in self.eoffsets.items()
        }


def build_lm_fsdp_train_step(model: TransformerLM, mesh: Mesh, optimizer,
                             attn: str = "flash", accum_steps: int = 1,
                             remat: bool = True,
                             vocab_block: Optional[int] = None):
    """Compile one ZeRO-3 LM training step over ``mesh``'s combined
    ``("data", "seq")`` axes.

    Same data contract as ``build_lm_train_step`` (tokens/positions/targets
    ``[B, T]`` sharded ``P("data", "seq")``); params and optimizer state
    are chunked per :class:`LMFsdpLayout` instead of replicated. ``remat``
    checkpoints each scanned block, so the backward re-gathers the layer
    and recomputes its activations — the standard FSDP + activation-
    checkpointing trade that keeps both transient params AND activations
    at one layer's footprint. ``vocab_block`` streams the loss head in
    vocab-column chunks (``chunked_summed_xent``) — no ``[B, T, V]``
    logits — completing the big-model memory story for imported
    large-vocab checkpoints.

    Round 5: the :class:`MoETransformerLM` family works too — expert
    stacks shard E over ``"seq"`` (their dispatch-native layout) and
    ZeRO-chunk over ``"data"`` (see :class:`LMFsdpLayout`'s ``"experts"``
    buffer), everything else chunks over the combined axes; the per-layer
    transient is one attention block + this rank's ``E/sp`` experts. The
    objective gains the ``aux_weight``-scaled load-balancing term with
    the replicated step's exact counting convention, so a Mixtral-class
    import's full params + adam state divide by ``dp·sp`` at rest with
    the trajectory unchanged.

    Returns ``(step, opt_init, layout)``; ``step(chunks, opt_state, tokens,
    positions, targets) -> (chunks, opt_state, loss)`` where ``loss`` is
    the global token-mean cross-entropy (+ the MoE aux term).
    """
    model._refuse_layout("the FSDP train step")
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    sp = _validate_lm_step(model, mesh, attn)
    dp = mesh.shape[DATA_AXIS]
    is_moe = getattr(model, "moe", None) is not None
    layout = LMFsdpLayout(model, dp * sp, data_shards=dp,
                          expert_shards=sp) if is_moe else \
        LMFsdpLayout(model, dp * sp)
    chunk_specs = layout.specs()
    sspecs = opt_state_specs(optimizer, layout.chunk_shapes(), chunk_specs)
    tok_spec = P(DATA_AXIS, SEQ_AXIS)
    aux_w = float(getattr(model, "aux_weight", 0.0))

    def step_impl(chunks, opt_state, tokens, positions, targets):
        ntok_total = float(tokens.shape[0] * tokens.shape[1] * dp * sp)

        def loss_fn(ch, tk, ps, tg):
            other = layout.gather_other(ch[OTHER_KEY])
            h = model._embed(other, tk, ps)
            rope = model._rope_for(ps)
            tables = None
            if rope is not None and attn == "flash" and is_tpu_backend():
                from ..ops.pallas_flash import make_rope_tables

                cos, sin = rope
                tables = make_rope_tables(cos[..., 0, :], sin[..., 0, :])

            def block(hh, row):
                if is_moe:
                    brow, erow = row
                    lp = layout.gather_layer(brow)
                    lp.update(layout.gather_layer_experts(erow))
                else:
                    lp = layout.gather_layer(row)
                hh, aux, _, _ = model._block_fwd(
                    hh, lp,
                    lambda q, k, v, rp=None: model._attend(
                        q, k, v, attn, SEQ_AXIS, rope=rp,
                        rope_tables=tables),
                    attn, SEQ_AXIS, rope=rope,
                )
                return hh, aux

            body = jax.checkpoint(block) if remat else block
            xs = (ch[BLOCKS_KEY], ch[EXPERTS_KEY]) if is_moe \
                else ch[BLOCKS_KEY]
            h, auxes = jax.lax.scan(body, h, xs)
            h = model._norm_h(other, "lnf", h)
            if vocab_block is not None:
                from .transformer import chunked_summed_xent

                ce = chunked_summed_xent(h, model.head_weight(other), tg,
                                         vocab_block)
            else:
                ce = _summed_xent(model._logits(other, h), tg)
            # MoE objective mirrors build_lm_train_step: token-mean CE
            # plus the aux term counted once per (data, seq) group
            obj = ce / ntok_total
            if is_moe:
                obj = obj + (
                    aux_w / (dp * sp * accum_steps)) * jnp.sum(auxes)
            return obj

        if accum_steps == 1:
            objective, grads = jax.value_and_grad(loss_fn)(
                chunks, tokens, positions, targets)
        else:
            B = tokens.shape[0]
            if B % accum_steps:
                raise ValueError(
                    f"local batch {B} not divisible by accum_steps "
                    f"{accum_steps}")
            micro = B // accum_steps
            split = lambda a: a.reshape(accum_steps, micro, *a.shape[1:])

            def body(carry, xs):
                obj_acc, grad_acc = carry
                obj, g = jax.value_and_grad(loss_fn)(chunks, *xs)
                return (obj_acc + obj,
                        jax.tree_util.tree_map(jnp.add, grad_acc, g)), None

            zeros = jax.tree_util.tree_map(jnp.zeros_like, chunks)
            (objective, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros),
                (split(tokens), split(positions), split(targets)))
        # Gradients arrived chunked + summed (the gathers' psum_scatter
        # transposes); only the scalar loss still needs the cross-device sum.
        loss = jax.lax.psum(objective, FSDP_AXES)
        updates, opt_state = optimizer.update(grads, opt_state, chunks)
        chunks = jax.tree_util.tree_map(jnp.add, chunks, updates)
        return chunks, opt_state, loss

    step = jax.jit(
        shard_map(
            step_impl, mesh=mesh,
            in_specs=(chunk_specs, sspecs, tok_spec, tok_spec, tok_spec),
            out_specs=(chunk_specs, sspecs, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )
    return step, make_opt_init(optimizer, mesh, sspecs), layout
