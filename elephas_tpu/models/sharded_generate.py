"""Sharded LM inference: generate without gathering to one device.

EXTENSION BEYOND THE REFERENCE (whose inference story is ``model.predict``
on a driver-local replica — SURVEY.md §2.5). A model trained dp×sp
(``build_lm_train_step``) used to require gathering onto ONE chip to call
:meth:`TransformerLM.generate`; for the long-context models that axis
exists to serve, the KV cache is exactly the object that does not fit.

``build_lm_generate`` compiles generation as one ``shard_map`` program over
the same ``("data", "seq")`` mesh the training step uses:

- **batch** shards over ``"data"`` — each data rank decodes its rows;
- **the KV cache** shards over ``"seq"`` along the time axis — rank ``r``
  owns cache positions ``[r·Tl, (r+1)·Tl)``, so per-chip cache memory drops
  by the seq-axis size; the decode horizon scales with the mesh.

Each decode step, every seq rank attends the query against its local cache
slice with the lse-exposing flash-decode kernel
(``ops/flash_decode.flash_decode_lse``) and the partials merge by
logsumexp — the ring-attention merge applied across the cache:

    lse  = logsumexp_r lse_r            (pmax + psum over "seq")
    out  = Σ_r exp(lse_r − lse) · out_r (psum over "seq")

Three collectives on ``[B, Hkv, G(, Dh)]`` tensors per layer — tiny
ICI traffic compared to the cache reads they shard. The new position's K/V
is written ONLY by its owner rank (non-owners rewrite their current row
with itself, keeping the update statically shaped); sampling runs
replicated on every seq rank from identical merged logits, so the ranks
stay in lockstep without a broadcast.

Prefill runs the full (matrix-matrix) forward per data rank, then each seq
rank keeps only its slice of the prompt K/V — prompt-length activations
appear transiently on every rank (same as single-chip prefill), but the
*standing* cache is sharded. The MoE variant works too: its expert
stacks already shard over this same ``"seq"`` axis, and every FFN call
runs under a non-``"dense"`` tag so routing dispatches through the two
``all_to_all``s against the LOCAL expert shards (each rank routes its
identical replicated tokens, so the combined outputs stay replicated and
no expert weights are ever gathered). MoE capacity semantics are
per-rank dispatch groups — identical keep/drop to the gathered rollout
whenever capacity does not bind (see the tests).

Exactness: the logsumexp merge is algebraically the same softmax attention
the single-device path computes, so greedy sharded generation reproduces
:meth:`TransformerLM.generate` token-for-token
(``tests/models/test_sharded_generate.py``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.flash_decode import (
    aligned_cache_length,
    decode_attention_lse,
)
from ..ops.paged_attention import paged_decode_attention_lse, paged_view_rows
from ..parallel.mesh import DATA_AXIS
from .transformer import (
    SEQ_AXIS,
    TransformerLM,
    _adapter_ctx,
    _period_group,
    _period_ungroup,
    _rope_angles,
    _rope_rotate,
    select_slot_tokens,
    select_tokens,
    spec_verify_select,
)


def _local_cache_len(total: int, sp: int) -> int:
    """Per-rank cache capacity: the horizon split over ranks, aligned so the
    flash-decode kernel never pads (a pad would recopy the slice in HBM
    every step)."""
    return aligned_cache_length(-(-total // sp))


def _check_mesh_and_specs(model: TransformerLM, mesh: Mesh) -> None:
    """Shared build-time validation for every sharded inference builder:
    the mesh must carry the (``"data"``, ``"seq"``) axes and params may be
    replicated or sharded over ``"seq"`` only (the MoE expert stacks)."""
    if getattr(model, "latent", False):
        raise NotImplementedError(
            "the sharded generators split K and V stacks along the sequence "
            "and merge per-rank attention by logsumexp, and a "
            "latent-attention model caches one stack of latent rows that "
            "its absorbed decode kernel reads whole: serve it unsharded")
    model._refuse_layout("the sharded generators and serving ops")
    for name, spec in model.specs().items():
        for ax in spec:
            axes = ax if isinstance(ax, tuple) else (ax,)
            for a in axes:
                if a not in (None, SEQ_AXIS):
                    raise NotImplementedError(
                        f"sharded generate shards over {SEQ_AXIS!r}; param "
                        f"{name!r} has spec {spec}"
                    )
    if DATA_AXIS not in mesh.shape or SEQ_AXIS not in mesh.shape:
        raise ValueError(
            f"mesh must carry ({DATA_AXIS!r}, {SEQ_AXIS!r}) axes, got "
            f"{dict(mesh.shape)}"
        )
    n_experts = getattr(model, "n_experts", None)
    sp = mesh.shape[SEQ_AXIS]
    if n_experts is not None and n_experts % sp:
        # same build-time clarity the training builder gives — otherwise
        # this surfaces as a cryptic all_to_all divisibility error later
        raise ValueError(
            f"n_experts={n_experts} not divisible by seq axis size {sp}"
        )


def _merged_decode_attention(qg, kc, vc, pos_local, Tl, window):
    """Local flash-decode partial + logsumexp merge over "seq".

    ``pos_local`` is a scalar or per-row ``[B]`` (the serving engine's
    slots sit at independent depths). ``window`` is THIS layer's sliding
    window (static; None = full). The local kernel masks ``slot ≤
    pos_local`` and ``slot > pos_local − w``; since both slot and pos
    share the rank's global offset ``r·Tl``, that IS the global window
    mask — including for ranks whose slice the window has partially left,
    which pass their true (past-the-end) ``pos_local`` so the lower bound
    stays global. Ranks with nothing visible — not yet reached, or wholly
    expired — clamp pos into valid kernel range and drop out of the merge
    with −inf lse (per ROW when pos is per-row)."""
    if window is None:
        pos_cl = jnp.clip(pos_local, 0, Tl - 1)
        invalid = pos_local < 0
    else:
        w = int(window)
        # upper clamp keeps ≥1 visible slot (valid arithmetic);
        # genuinely expired ranks are overridden below anyway
        pos_cl = jnp.clip(pos_local, 0, Tl + w - 2)
        invalid = (pos_local < 0) | (pos_local - w + 1 >= Tl)
    o_r, lse_r = decode_attention_lse(qg, kc, vc, pos_cl,
                                      window=window)
    invalid = jnp.asarray(invalid)
    if invalid.ndim == 1:                        # per-row → [B, 1, 1]
        invalid = invalid[:, None, None]
    lse_r = jnp.where(invalid, -jnp.inf, lse_r)
    m = jax.lax.pmax(lse_r, SEQ_AXIS)
    w_r = jnp.exp(lse_r - m)                     # [B, Hkv, G]
    num = jax.lax.psum(w_r[..., None] * o_r, SEQ_AXIS)
    den = jax.lax.psum(w_r, SEQ_AXIS)
    return num / den[..., None]                  # [B, Hkv, G, Dh]


def _owner_write(c, new, idx, is_owner, per_row: bool):
    """Owner-masked statically-shaped cache write: ``new`` ``[B, Hkv, 1,
    Dh]`` into ``c`` ``[B, Hkv, Tl, Dh]`` at time ``idx``. The owner rank
    writes the new row; everyone else re-writes its current row with
    itself — one ``[B, Hkv, 1, Dh]`` gather keeps the update statically
    shaped without copying the whole slice through a select. ``idx`` /
    ``is_owner`` are scalars, or per-row ``[B]`` (vmapped — serving slots
    advance independently, so different rows can have different owner
    ranks)."""
    if not per_row:
        cur = jax.lax.dynamic_slice_in_dim(c, idx, 1, axis=2)
        return jax.lax.dynamic_update_slice_in_dim(
            c, jnp.where(is_owner, new, cur), idx, axis=2)

    def row(cb, nb, ib, ob):
        cur = jax.lax.dynamic_slice_in_dim(cb, ib, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            cb, jnp.where(ob, nb, cur), ib, axis=1)

    return jax.vmap(row)(c, new, idx, is_owner)


def _decode_step_sharded(model: TransformerLM, params, token, p,
                         kcache, vcache, Tl: int):
    """One merged decode step on the local batch/cache shards.

    ``token [B_local]`` at absolute position ``p`` — a traced scalar (the
    lockstep generate rollout) or per-row ``[B_local]`` (the serving
    engine's slots each sit at their own depth); ``kcache/vcache
    [L, B_local, Hkv, Tl, Dh]``. Mirrors ``TransformerLM.decode_step``
    with the attention and cache write swapped for their sharded forms
    (including the per-layer window period scan).
    """
    B = token.shape[0]
    H = model.n_heads
    Hkv = model.n_kv_heads
    Dh = model.d_model // H
    cd = model.compute_dtype
    p = jnp.asarray(p)
    per_row = p.ndim == 1
    r = jax.lax.axis_index(SEQ_AXIS)
    pos_local = p - r * Tl                       # scalar or [B]
    is_owner = (pos_local >= 0) & (pos_local < Tl)
    idx = jnp.clip(pos_local, 0, Tl - 1)
    if per_row:
        # [B] → broadcastable against the [B, Hkv, 1, Dh] row updates
        is_owner_w = is_owner[:, None, None, None]
    else:
        is_owner_w = is_owner

    pos_b = jnp.broadcast_to(p, (B,))
    h = model._embed(params, token, pos_b)       # [B, D]
    if model.pos_encoding == "rotary":
        r_cos, r_sin = _rope_angles(pos_b, Dh, model.rope_theta)
        r_cos, r_sin = r_cos[:, None, :], r_sin[:, None, :]

    def one_layer(h, lp, kc, vc, window):
        # kc/vc [B, Hkv, Tl, Dh]; ``window`` static for this layer
        x = model._norm_h(lp, "ln1", h).astype(cd)
        q = model._attn_proj(lp, "q", x).reshape(B, H, Dh)
        k_new = model._attn_proj(lp, "k", x).reshape(B, Hkv, 1, Dh)
        v_new = model._attn_proj(lp, "v", x).reshape(B, Hkv, 1, Dh)
        if model.pos_encoding == "rotary":
            q = _rope_rotate(q, r_cos, r_sin)
            k_new = _rope_rotate(k_new, r_cos[:, None], r_sin[:, None])
        kc = _owner_write(kc, k_new, idx, is_owner_w, per_row)
        vc = _owner_write(vc, v_new, idx, is_owner_w, per_row)
        qg = q.reshape(B, Hkv, H // Hkv, Dh)
        a = _merged_decode_attention(qg, kc, vc, pos_local, Tl, window)
        a = a.astype(cd).reshape(B, H, Dh)
        h = h + model._attn_proj(lp, "o", a.reshape(B, model.d_model))
        x = model._norm_h(lp, "ln2", h).astype(cd)
        # Non-"dense" tag: the MoE variant's experts dispatch over the
        # LIVE seq axis (all_to_all against the local expert shards —
        # every rank routes its identical replicated tokens, so the
        # combined outputs stay replicated); the dense FFN ignores the
        # tag entirely.
        out, _ = model._ffn(lp, x[:, None, :], "ring", SEQ_AXIS,
                            ep_groups=1)
        return h + out[:, 0].astype(cd), kc, vc

    pp = model._window_period()

    def block(h, inputs):
        lp, kc, vc = inputs
        if pp == 1:
            h, kc, vc = one_layer(h, lp, kc, vc, model.attn_windows[0])
            return h, (kc, vc)
        kcs, vcs = [], []
        for g in range(pp):
            h, kc_g, vc_g = one_layer(
                h, {k: v[g] for k, v in lp.items()}, kc[g], vc[g],
                model.attn_windows[g])
            kcs.append(kc_g)
            vcs.append(vc_g)
        return h, (jnp.stack(kcs), jnp.stack(vcs))

    lps = {k: params[k] for k in model._block_keys()}
    kcache_s, vcache_s = kcache, vcache
    if pp > 1:
        lps = _period_group(lps, pp)
        kcache_s = _period_group(kcache, pp)
        vcache_s = _period_group(vcache, pp)
    h, (kc_new, vc_new) = jax.lax.scan(
        block, h, (lps, kcache_s, vcache_s))
    if pp > 1:
        kc_new = _period_ungroup(kc_new, model.n_layers)
        vc_new = _period_ungroup(vc_new, model.n_layers)
    h = model._norm_h(params, "lnf", h)
    return model._logits(params, h), kc_new, vc_new


def build_lm_generate(model: TransformerLM, mesh: Mesh,
                      temperature: float = 0.0,
                      top_k: Optional[int] = None,
                      top_p: Optional[float] = None):
    """Compile sharded generation over ``mesh`` (axes ``"data"``, ``"seq"``).

    Returns ``generate_fn(params, prompt, n_new, seed=0) -> [B, T0+n_new]``
    with ``prompt [B, T0]`` int; ``B`` must divide by the data-axis size.
    ``params`` are the (replicated) training-layout params —
    ``model.shard_params(mesh, ...)`` output works as-is; nothing is
    gathered. One program is compiled per ``(B, T0, n_new)`` geometry and
    cached on the returned function.
    """
    # Params may be replicated or sharded over THIS program's "seq" axis
    # (the MoE expert stacks) — anything else has no home here.
    # Sliding windows (uniform or per-layer): the cache stays
    # horizon-sharded (memory already divided by sp), each rank masks its
    # local partial on GLOBAL window arithmetic — positions past a rank's
    # slice end keep the offset identity (see _merged_decode_attention) —
    # and wholly-expired ranks drop out of the logsumexp merge with −inf
    # weight, exactly like not-yet-reached ranks.
    _check_mesh_and_specs(model, mesh)
    if top_k is not None and not 1 <= int(top_k) <= model.vocab:
        raise ValueError(
            f"top_k must be in [1, vocab={model.vocab}], got {top_k}"
        )
    if top_p is not None and not 0.0 < float(top_p) <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")

    sp = mesh.shape[SEQ_AXIS]
    dp = mesh.shape[DATA_AXIS]
    Hkv = model.n_kv_heads
    Dh = model.d_model // model.n_heads
    cd = model.compute_dtype
    programs: Dict[Any, Any] = {}

    def _gen_impl(total: int, Tl: int, params, prompt, key):
        """The per-rank program: local prompt ``[B_local, T0]``."""
        B, T0 = prompt.shape
        r = jax.lax.axis_index(SEQ_AXIS)

        # Prefill the full prompt (matrix-matrix; attention replicated per
        # data rank, the FFN under a non-"dense" tag so MoE experts
        # dispatch over the live seq axis against their LOCAL shards), then
        # keep only this rank's cache slice. The prefill K/V is padded to a
        # multiple of Tl so every slice start is exact: ranks at or past the
        # padded length slice garbage that position masking keeps invisible
        # until a decode write lands there.
        p_up = -(-T0 // Tl) * Tl
        tmp = {
            "k": jnp.zeros((model.n_layers, B, Hkv, p_up, Dh), cd),
            "v": jnp.zeros((model.n_layers, B, Hkv, p_up, Dh), cd),
        }
        logits, tmp = model.prefill(params, prompt, tmp, ffn_tag="ring")
        start = jnp.minimum(r * Tl, p_up - Tl)
        kcache = jax.lax.dynamic_slice_in_dim(tmp["k"], start, Tl, axis=3)
        vcache = jax.lax.dynamic_slice_in_dim(tmp["v"], start, Tl, axis=3)
        # Ranks wholly past the prefilled span must not keep a stale copy of
        # the last covered slice (its rows would alias real positions): zero
        # them. Slices are distinct per rank otherwise, so this is the only
        # aliasing case.
        past = r * Tl >= p_up
        kcache = jnp.where(past, jnp.zeros_like(kcache), kcache)
        vcache = jnp.where(past, jnp.zeros_like(vcache), vcache)

        # Global first row of this data shard: sampling folds the key per
        # GLOBAL row, so the sharded draw equals the gathered one.
        row0 = jax.lax.axis_index(DATA_AXIS) * B

        key, k0 = jax.random.split(key)
        first = select_tokens(logits[:, -1], k0, temperature, top_k, top_p,
                              row_offset=row0)
        buf = jnp.zeros((B, total), jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
        buf = buf.at[:, T0].set(first)

        def step(carry, t):
            buf, kcache, vcache, token, key = carry
            logits, kcache, vcache = _decode_step_sharded(
                model, params, token, t, kcache, vcache, Tl
            )
            key, kt = jax.random.split(key)
            nxt = select_tokens(logits, kt, temperature, top_k, top_p,
                                row_offset=row0)
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, nxt[:, None], t + 1, axis=1
            )
            return (buf, kcache, vcache, nxt, key), None

        (buf, _, _, _, _), _ = jax.lax.scan(
            step, (buf, kcache, vcache, first, key),
            jnp.arange(T0, total - 1),
        )
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
        Tl = _local_cache_len(total, sp)
        geom = (B, T0, int(n_new))
        if geom not in programs:
            pspecs = model.specs()  # replicated; MoE experts over "seq"
            programs[geom] = jax.jit(
                shard_map(
                    functools.partial(_gen_impl, total, Tl),
                    mesh=mesh,
                    in_specs=(pspecs, P(DATA_AXIS, None), P()),
                    out_specs=P(DATA_AXIS, None),
                    check_vma=False,
                )
            )
        key = jax.random.PRNGKey(seed)
        return programs[geom](params, prompt, key)

    return generate_fn


def _prefill_slice_sharded(model: TransformerLM, capacity: int, Tl: int,
                           params, tokens, aid=None):
    """Replicated full prefill of ``tokens`` ``[1, Tb]`` into a transient
    full-``capacity`` K/V buffer, sliced down to THIS seq rank's
    ``[r·Tl, (r+1)·Tl)`` rows → ``(logits [1, Tb, V], new_k, new_v)`` with
    ``new_k/new_v [L, 1, Hkv, Tl, Dh]``. The shared front half of the
    dense insert and the paged insert: tokens are replicated, so the
    logits come back replicated on every rank with no collective. ``aid``
    (replicated scalar, optional) selects the adapter for multi-tenant
    models — it must be replicated or the logits stop being."""
    L = model.n_layers
    Hkv = model.n_kv_heads
    Dh = model.d_model // model.n_heads
    cd = model.compute_dtype
    r_seq = jax.lax.axis_index(SEQ_AXIS)
    tmp = {
        "k": jnp.zeros((L, 1, Hkv, capacity, Dh), cd),
        "v": jnp.zeros((L, 1, Hkv, capacity, Dh), cd),
    }
    with _adapter_ctx(model,
                      None if aid is None else jnp.reshape(aid, (1,))):
        logits, tmp = model.prefill(params, tokens, tmp, ffn_tag="ring")
    new_k = jax.lax.dynamic_slice_in_dim(tmp["k"], r_seq * Tl, Tl, axis=3)
    new_v = jax.lax.dynamic_slice_in_dim(tmp["v"], r_seq * Tl, Tl, axis=3)
    return logits, new_k, new_v


def _chunk_row_sharded(model: TransformerLM, Tl: int, params, row, tokens,
                       t_last, pos0, own):
    """Chunk-continuation forward of ``tokens`` ``[1, C]`` at absolute
    positions ``pos0..`` against ONE slot row's local time slice ``row``
    ``{"k"/"v": [L, 1, Hkv, Tl, Dh]}``: scatter the chunk's K/V into the
    slice (out-of-slice and non-owner writes drop), matrix-matrix scores
    against it under the global causal/window mask, logsumexp-merge the
    partials over ``"seq"``, and replicate the owner's ``t_last`` logits
    by a masked ``psum`` over ``"data"``. The shared middle of the dense
    chunk insert and the paged chunk insert; ``own`` is this data rank's
    ownership predicate (non-owners run on a surrogate row whose writes
    all drop, so their returned row is bitwise the input). Returns
    ``(last [V], {"k"/"v": new row})``."""
    C = tokens.shape[1]
    H = model.n_heads
    Hkv = model.n_kv_heads
    Dh = model.d_model // H
    cd = model.compute_dtype
    r_seq = jax.lax.axis_index(SEQ_AXIS)

    pos_b = pos0 + jnp.arange(C)[None, :]           # [1, C] absolute
    h = model._embed(params, tokens, pos_b)         # [1, C, D]
    rope = model._rope_for(pos_b)
    # chunk→slice write coordinates: unique, consecutive; anything
    # out of this rank's slice — or on a non-owner data rank — is
    # redirected to Tl, which scatter mode="drop" discards (NEVER a
    # negative index: numpy-style wrap would corrupt the slice tail)
    local_t = pos_b[0] - r_seq * Tl                 # [C]
    write_t = jnp.where((local_t >= 0) & (local_t < Tl) & own,
                        local_t, Tl)
    slots_g = r_seq * Tl + jnp.arange(Tl)           # [Tl] global pos

    def mask_for(window):
        # [1, C, Tl]: query i (global pos0+i) sees global slots
        # <= its position, window-clamped below for this layer
        m = slots_g[None, None, :] <= pos_b[:, :, None]
        if window is not None:
            m &= slots_g[None, None, :] > pos_b[:, :, None] - window
        return m

    def one_layer(h, lp, kc, vc, window):
        # kc/vc [1, Hkv, Tl, Dh] — this rank's slice of the slot row
        x = model._norm_h(lp, "ln1", h).astype(cd)
        q = model._attn_proj(lp, "q", x).reshape(1, C, H, Dh)
        k_new = model._attn_proj(lp, "k", x).reshape(1, C, Hkv, Dh)
        v_new = model._attn_proj(lp, "v", x).reshape(1, C, Hkv, Dh)
        if rope is not None:
            q = _rope_rotate(q, *rope)
            k_new = _rope_rotate(k_new, *rope)
        kc = kc.at[:, :, write_t, :].set(
            k_new.transpose(0, 2, 1, 3), mode="drop")
        vc = vc.at[:, :, write_t, :].set(
            v_new.transpose(0, 2, 1, 3), mode="drop")
        # matrix-matrix scores against the local slice, then the
        # logsumexp merge over "seq" (same identity as the decode
        # step's flash-decode merge; exp(-inf)=0 drops masked slots,
        # and the global max is finite — every query at least sees
        # its own just-written position on its owner rank)
        qg = q.transpose(0, 2, 1, 3).reshape(1, Hkv, H // Hkv, C, Dh)
        scores = jnp.einsum(
            "bkgsd,bktd->bkgst", qg, kc,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ) * (Dh ** -0.5)
        scores = jnp.where(mask_for(window)[:, None, None], scores,
                           -jnp.inf)
        m_r = jnp.max(scores, axis=-1)              # [1, Hkv, G, C]
        m = jax.lax.pmax(m_r, SEQ_AXIS)
        w = jnp.exp(scores - m[..., None])
        s_r = jnp.sum(w, axis=-1)
        o_r = jnp.einsum(
            "bkgst,bktd->bkgsd", w, vc,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        den = jax.lax.psum(s_r, SEQ_AXIS)
        num = jax.lax.psum(o_r, SEQ_AXIS)
        a = (num / den[..., None]).astype(cd)       # [1, Hkv, G, C, Dh]
        a = a.reshape(1, H, C, Dh).transpose(0, 2, 1, 3)
        h = h + model._attn_proj(lp, "o", a.reshape(1, C, model.d_model))
        x = model._norm_h(lp, "ln2", h).astype(cd)
        out, _ = model._ffn(lp, x, "ring", SEQ_AXIS, ep_groups=1)
        return h + out.astype(cd), kc, vc

    pp = model._window_period()

    def block(h, inputs):
        lp, kc, vc = inputs
        if pp == 1:
            h, kc, vc = one_layer(h, lp, kc, vc, model.attn_windows[0])
            return h, (kc, vc)
        kcs, vcs = [], []
        for g in range(pp):
            h, kc_g, vc_g = one_layer(
                h, {k: v[g] for k, v in lp.items()}, kc[g], vc[g],
                model.attn_windows[g])
            kcs.append(kc_g)
            vcs.append(vc_g)
        return h, (jnp.stack(kcs), jnp.stack(vcs))

    lps = {k: params[k] for k in model._block_keys()}
    ck, cv = row["k"], row["v"]
    if pp > 1:
        lps = _period_group(lps, pp)
        ck = _period_group(ck, pp)
        cv = _period_group(cv, pp)
    h, (kc_new, vc_new) = jax.lax.scan(block, h, (lps, ck, cv))
    if pp > 1:
        kc_new = _period_ungroup(kc_new, model.n_layers)
        vc_new = _period_ungroup(vc_new, model.n_layers)
    h = model._norm_h(params, "lnf", h)
    logits = model._logits(params, h)               # [1, C, V]
    last = jax.lax.dynamic_index_in_dim(logits[0], t_last, axis=0,
                                        keepdims=False)
    # replicate the OWNER's logits (non-owner data ranks computed on
    # surrogate rows — garbage h, masked out of the sum)
    last = jax.lax.psum(jnp.where(own, last, 0.0), DATA_AXIS)
    return last, {"k": kc_new, "v": vc_new}


def _verify_rows_sharded(model: TransformerLM, Tl: int, params, kc_all,
                         vc_all, chunk, pos):
    """Speculative-verify forward over EVERY local slot row at once:
    ``chunk`` ``[S, C]`` (carry + drafts per row) at per-row absolute
    positions ``pos..pos+C-1`` against the local cache slices ``kc_all``/
    ``vc_all`` ``[L, S, Hkv, Tl, Dh]``. The batched sibling of
    :func:`_chunk_row_sharded` — same scatter-then-score shape, same
    global causal/window mask, same ``"seq"`` logsumexp merge, same
    ``"ring"`` FFN tag — but with NO data-rank owner masking: every rank
    verifies its OWN slot rows (the verify batch is the whole ``"data"``-
    sharded slot axis, like the decode step). Chunk writes land at
    ``pos..pos+C-1`` per row, out-of-slice coordinates dropping on
    non-owner seq ranks. Returns ``(logits [S, C, V] f32 — replicated
    across "seq", local to each data rank — new kc_all, new vc_all)``."""
    S, C = chunk.shape
    H = model.n_heads
    Hkv = model.n_kv_heads
    Dh = model.d_model // H
    cd = model.compute_dtype
    r_seq = jax.lax.axis_index(SEQ_AXIS)

    pos_b = pos[:, None] + jnp.arange(C)[None, :]   # [S, C] absolute
    h = model._embed(params, chunk, pos_b)          # [S, C, D]
    rope = model._rope_for(pos_b)
    local_t = pos_b - r_seq * Tl                    # [S, C]
    write_t = jnp.where((local_t >= 0) & (local_t < Tl), local_t, Tl)
    slots_g = r_seq * Tl + jnp.arange(Tl)           # [Tl] global pos

    def mask_for(window):
        # [S, C, Tl]: query j of row s (global pos[s]+j) sees global
        # slots <= its position, window-clamped below for this layer
        m = slots_g[None, None, :] <= pos_b[:, :, None]
        if window is not None:
            m &= slots_g[None, None, :] > pos_b[:, :, None] - window
        return m

    def row_write(c, wt, new):
        # c [Hkv, Tl, Dh]; wt [C]; new [Hkv, C, Dh] — per-row scatter,
        # out-of-slice coordinates redirected to Tl and dropped
        return c.at[:, wt, :].set(new, mode="drop")

    def one_layer(h, lp, kc, vc, window):
        # kc/vc [S, Hkv, Tl, Dh] — this rank's slices of every slot row
        x = model._norm_h(lp, "ln1", h).astype(cd)
        q = model._attn_proj(lp, "q", x).reshape(S, C, H, Dh)
        k_new = model._attn_proj(lp, "k", x).reshape(S, C, Hkv, Dh)
        v_new = model._attn_proj(lp, "v", x).reshape(S, C, Hkv, Dh)
        if rope is not None:
            q = _rope_rotate(q, *rope)
            k_new = _rope_rotate(k_new, *rope)
        kc = jax.vmap(row_write)(kc, write_t, k_new.transpose(0, 2, 1, 3))
        vc = jax.vmap(row_write)(vc, write_t, v_new.transpose(0, 2, 1, 3))
        qg = q.transpose(0, 2, 1, 3).reshape(S, Hkv, H // Hkv, C, Dh)
        scores = jnp.einsum(
            "bkgsd,bktd->bkgst", qg, kc,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ) * (Dh ** -0.5)
        scores = jnp.where(mask_for(window)[:, None, None], scores,
                           -jnp.inf)
        m_r = jnp.max(scores, axis=-1)              # [S, Hkv, G, C]
        m = jax.lax.pmax(m_r, SEQ_AXIS)
        w = jnp.exp(scores - m[..., None])
        s_r = jnp.sum(w, axis=-1)
        o_r = jnp.einsum(
            "bkgst,bktd->bkgsd", w, vc,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        den = jax.lax.psum(s_r, SEQ_AXIS)
        num = jax.lax.psum(o_r, SEQ_AXIS)
        a = (num / den[..., None]).astype(cd)       # [S, Hkv, G, C, Dh]
        a = a.reshape(S, H, C, Dh).transpose(0, 2, 1, 3)
        h = h + model._attn_proj(lp, "o", a.reshape(S, C, model.d_model))
        x = model._norm_h(lp, "ln2", h).astype(cd)
        out, _ = model._ffn(lp, x, "ring", SEQ_AXIS, ep_groups=1)
        return h + out.astype(cd), kc, vc

    pp = model._window_period()

    def block(h, inputs):
        lp, kc, vc = inputs
        if pp == 1:
            h, kc, vc = one_layer(h, lp, kc, vc, model.attn_windows[0])
            return h, (kc, vc)
        kcs, vcs = [], []
        for g in range(pp):
            h, kc_g, vc_g = one_layer(
                h, {k: v[g] for k, v in lp.items()}, kc[g], vc[g],
                model.attn_windows[g])
            kcs.append(kc_g)
            vcs.append(vc_g)
        return h, (jnp.stack(kcs), jnp.stack(vcs))

    lps = {k: params[k] for k in model._block_keys()}
    ck, cv = kc_all, vc_all
    if pp > 1:
        lps = _period_group(lps, pp)
        ck = _period_group(ck, pp)
        cv = _period_group(cv, pp)
    h, (kc_new, vc_new) = jax.lax.scan(block, h, (lps, ck, cv))
    if pp > 1:
        kc_new = _period_ungroup(kc_new, model.n_layers)
        vc_new = _period_ungroup(vc_new, model.n_layers)
    h = model._norm_h(params, "lnf", h)
    logits = model._logits(params, h)               # [S, C, V]
    return logits, kc_new, vc_new


def _merged_paged_attention(qg, kp, vp, table, pos_local, Tl, page,
                            window):
    """Paged flash-decode partial + logsumexp merge over "seq": the paged
    sibling of :func:`_merged_decode_attention`, reading K/V straight out
    of this partition's page pool slice through the local block table
    instead of a gathered dense view. Same clamp/invalid handling — ranks
    with nothing visible drop out of the merge with −inf lse per row —
    and on CPU :func:`paged_decode_attention_lse` resolves to the
    gather-through-table reference whose math is bitwise the dense
    kernel's, so the merged output equals the dense path's exactly."""
    if window is None:
        pos_cl = jnp.clip(pos_local, 0, Tl - 1)
        invalid = pos_local < 0
    else:
        w = int(window)
        pos_cl = jnp.clip(pos_local, 0, Tl + w - 2)
        invalid = (pos_local < 0) | (pos_local - w + 1 >= Tl)
    o_r, lse_r = paged_decode_attention_lse(qg, kp, vp, table, pos_cl,
                                            page, window=window)
    invalid = jnp.asarray(invalid)
    if invalid.ndim == 1:                        # per-row → [B, 1, 1]
        invalid = invalid[:, None, None]
    lse_r = jnp.where(invalid, -jnp.inf, lse_r)
    m = jax.lax.pmax(lse_r, SEQ_AXIS)
    w_r = jnp.exp(lse_r - m)                     # [B, Hkv, G]
    num = jax.lax.psum(w_r[..., None] * o_r, SEQ_AXIS)
    den = jax.lax.psum(w_r, SEQ_AXIS)
    return num / den[..., None]                  # [B, Hkv, G, Dh]


def _paged_decode_step_sharded(model: TransformerLM, params, token, p,
                               pool, table, page: int, Tl: int):
    """One merged decode step DIRECTLY over the local page-pool shard:
    the paged sibling of :func:`_decode_step_sharded`. ``pool``
    ``{"k"/"v": [L, Pl, Hkv, page, Dh]}`` is this partition's slice,
    ``table`` ``[Sl, Ml]`` its local block-table block. Each layer
    scatters the one new K/V row of every OWNER slot into its owning page
    (non-owner seq ranks and unmapped cells write into the trash page —
    finite garbage the mask never shows) and attends through the table
    with :func:`_merged_paged_attention`; no dense view is ever
    materialized. Returns ``(logits [Sl, V], new_pool)``."""
    B = token.shape[0]
    H = model.n_heads
    Hkv = model.n_kv_heads
    Dh = model.d_model // H
    cd = model.compute_dtype
    r = jax.lax.axis_index(SEQ_AXIS)
    pos_local = p - r * Tl                       # [B]
    own_seq = (pos_local >= 0) & (pos_local < Tl)
    idx = jnp.clip(pos_local, 0, Tl - 1)
    pids = jnp.where(
        own_seq,
        jnp.take_along_axis(table, (idx // page)[:, None], axis=1)[:, 0],
        0)
    offs = idx % page

    pos_b = jnp.broadcast_to(p, (B,))
    h = model._embed(params, token, pos_b)       # [B, D]
    if model.pos_encoding == "rotary":
        r_cos, r_sin = _rope_angles(pos_b, Dh, model.rope_theta)
        r_cos, r_sin = r_cos[:, None, :], r_sin[:, None, :]

    def one_layer(h, lp, kp, vp, window):
        # kp/vp [Pl, Hkv, page, Dh] — this partition's pool slice
        x = model._norm_h(lp, "ln1", h).astype(cd)
        q = model._attn_proj(lp, "q", x).reshape(B, H, Dh)
        k_new = model._attn_proj(lp, "k", x).reshape(B, Hkv, Dh)
        v_new = model._attn_proj(lp, "v", x).reshape(B, Hkv, Dh)
        if model.pos_encoding == "rotary":
            q = _rope_rotate(q, r_cos, r_sin)
            k_new = _rope_rotate(k_new, r_cos, r_sin)
        kp = kp.at[pids, :, offs].set(k_new, mode="drop")
        vp = vp.at[pids, :, offs].set(v_new, mode="drop")
        qg = q.reshape(B, Hkv, H // Hkv, Dh)
        a = _merged_paged_attention(qg, kp, vp, table, pos_local, Tl,
                                    page, window)
        a = a.astype(cd).reshape(B, H, Dh)
        h = h + model._attn_proj(lp, "o", a.reshape(B, model.d_model))
        x = model._norm_h(lp, "ln2", h).astype(cd)
        out, _ = model._ffn(lp, x[:, None, :], "ring", SEQ_AXIS,
                            ep_groups=1)
        return h + out[:, 0].astype(cd), kp, vp

    pp = model._window_period()

    def block(h, inputs):
        lp, kp, vp = inputs
        if pp == 1:
            h, kp, vp = one_layer(h, lp, kp, vp, model.attn_windows[0])
            return h, (kp, vp)
        kps, vps = [], []
        for g in range(pp):
            h, kp_g, vp_g = one_layer(
                h, {k: v[g] for k, v in lp.items()}, kp[g], vp[g],
                model.attn_windows[g])
            kps.append(kp_g)
            vps.append(vp_g)
        return h, (jnp.stack(kps), jnp.stack(vps))

    lps = {k: params[k] for k in model._block_keys()}
    ck, cv = pool["k"], pool["v"]
    if pp > 1:
        lps = _period_group(lps, pp)
        ck = _period_group(ck, pp)
        cv = _period_group(cv, pp)
    h, (kc_new, vc_new) = jax.lax.scan(block, h, (lps, ck, cv))
    if pp > 1:
        kc_new = _period_ungroup(kc_new, model.n_layers)
        vc_new = _period_ungroup(vc_new, model.n_layers)
    h = model._norm_h(params, "lnf", h)
    return model._logits(params, h), {"k": kc_new, "v": vc_new}


def _paged_chunk_row_sharded(model: TransformerLM, Tl: int, page: int,
                             params, pool, trow, tokens, t_last, pos0,
                             own):
    """Chunk-continuation forward of ``tokens`` ``[1, C]`` DIRECTLY over
    the partition's pool slice through ONE slot's local block-table row
    ``trow`` ``[1, Ml]``: the paged sibling of :func:`_chunk_row_sharded`.
    Each layer scatters only the chunk's own K/V rows into their owning
    pages (out-of-slice and non-owner writes land in the trash page), then
    scores against a TRANSIENT gathered view of the slot's local slice —
    the view's time axis equals ``Tl``, so the score/psum block below is
    verbatim the dense chunk's and the merged logits stay bitwise
    identical. Adopted prefix pages are attended but never rewritten.
    Returns ``(last [V], new_pool)``."""
    C = tokens.shape[1]
    H = model.n_heads
    Hkv = model.n_kv_heads
    Dh = model.d_model // H
    cd = model.compute_dtype
    Ml = trow.shape[1]
    r_seq = jax.lax.axis_index(SEQ_AXIS)

    pos_b = pos0 + jnp.arange(C)[None, :]           # [1, C] absolute
    h = model._embed(params, tokens, pos_b)         # [1, C, D]
    rope = model._rope_for(pos_b)
    local_t = pos_b[0] - r_seq * Tl                 # [C]
    valid = (local_t >= 0) & (local_t < Tl) & own
    lt = jnp.clip(local_t, 0, Tl - 1)
    pids = jnp.where(valid, jnp.take(trow[0], lt // page), 0)
    offs = lt % page
    slots_g = r_seq * Tl + jnp.arange(Tl)           # [Tl] global pos

    def mask_for(window):
        m = slots_g[None, None, :] <= pos_b[:, :, None]
        if window is not None:
            m &= slots_g[None, None, :] > pos_b[:, :, None] - window
        return m

    def one_layer(h, lp, kp, vp, window):
        # kp/vp [Pl, Hkv, page, Dh] — this partition's pool slice
        x = model._norm_h(lp, "ln1", h).astype(cd)
        q = model._attn_proj(lp, "q", x).reshape(1, C, H, Dh)
        k_new = model._attn_proj(lp, "k", x).reshape(1, C, Hkv, Dh)
        v_new = model._attn_proj(lp, "v", x).reshape(1, C, Hkv, Dh)
        if rope is not None:
            q = _rope_rotate(q, *rope)
            k_new = _rope_rotate(k_new, *rope)
        kp = kp.at[pids, :, offs].set(k_new[0], mode="drop")
        vp = vp.at[pids, :, offs].set(v_new[0], mode="drop")
        # transient per-layer gather of the slot's local slice: content
        # is exactly what the dense path's carried view holds here, so
        # the einsum/psum block below is bitwise the dense chunk's
        kc = paged_view_rows(kp, trow, page)        # [1, Hkv, Tl, Dh]
        vc = paged_view_rows(vp, trow, page)
        qg = q.transpose(0, 2, 1, 3).reshape(1, Hkv, H // Hkv, C, Dh)
        scores = jnp.einsum(
            "bkgsd,bktd->bkgst", qg, kc,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ) * (Dh ** -0.5)
        scores = jnp.where(mask_for(window)[:, None, None], scores,
                           -jnp.inf)
        m_r = jnp.max(scores, axis=-1)              # [1, Hkv, G, C]
        m = jax.lax.pmax(m_r, SEQ_AXIS)
        w = jnp.exp(scores - m[..., None])
        s_r = jnp.sum(w, axis=-1)
        o_r = jnp.einsum(
            "bkgst,bktd->bkgsd", w, vc,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        den = jax.lax.psum(s_r, SEQ_AXIS)
        num = jax.lax.psum(o_r, SEQ_AXIS)
        a = (num / den[..., None]).astype(cd)       # [1, Hkv, G, C, Dh]
        a = a.reshape(1, H, C, Dh).transpose(0, 2, 1, 3)
        h = h + model._attn_proj(lp, "o", a.reshape(1, C, model.d_model))
        x = model._norm_h(lp, "ln2", h).astype(cd)
        out, _ = model._ffn(lp, x, "ring", SEQ_AXIS, ep_groups=1)
        return h + out.astype(cd), kp, vp

    pp = model._window_period()

    def block(h, inputs):
        lp, kp, vp = inputs
        if pp == 1:
            h, kp, vp = one_layer(h, lp, kp, vp, model.attn_windows[0])
            return h, (kp, vp)
        kps, vps = [], []
        for g in range(pp):
            h, kp_g, vp_g = one_layer(
                h, {k: v[g] for k, v in lp.items()}, kp[g], vp[g],
                model.attn_windows[g])
            kps.append(kp_g)
            vps.append(vp_g)
        return h, (jnp.stack(kps), jnp.stack(vps))

    lps = {k: params[k] for k in model._block_keys()}
    ck, cv = pool["k"], pool["v"]
    if pp > 1:
        lps = _period_group(lps, pp)
        ck = _period_group(ck, pp)
        cv = _period_group(cv, pp)
    h, (kc_new, vc_new) = jax.lax.scan(block, h, (lps, ck, cv))
    if pp > 1:
        kc_new = _period_ungroup(kc_new, model.n_layers)
        vc_new = _period_ungroup(vc_new, model.n_layers)
    h = model._norm_h(params, "lnf", h)
    logits = model._logits(params, h)               # [1, C, V]
    last = jax.lax.dynamic_index_in_dim(logits[0], t_last, axis=0,
                                        keepdims=False)
    # replicate the OWNER's logits (non-owner data ranks computed on an
    # unwritten view — garbage h, masked out of the sum)
    last = jax.lax.psum(jnp.where(own, last, 0.0), DATA_AXIS)
    return last, {"k": kc_new, "v": vc_new}


def _paged_verify_rows_sharded(model: TransformerLM, Tl: int, page: int,
                               params, pool, table, chunk, pos):
    """Speculative-verify forward over EVERY local slot row DIRECTLY over
    the partition's pool slice: the paged sibling of
    :func:`_verify_rows_sharded`, writing each layer's chunk K/V through
    the block table (O(chunk) rows — rejected-tail rows included, exactly
    the dense path's stale-dead rows; decode-era pages are never shared,
    see ``serving/memory.py``) and scoring against a transient gathered
    view whose time axis equals ``Tl`` — the einsum/psum block is
    verbatim the dense verify's, keeping logits bitwise identical.
    Returns ``(logits [S, C, V], new_pool)``."""
    S, C = chunk.shape
    H = model.n_heads
    Hkv = model.n_kv_heads
    Dh = model.d_model // H
    cd = model.compute_dtype
    r_seq = jax.lax.axis_index(SEQ_AXIS)

    pos_b = pos[:, None] + jnp.arange(C)[None, :]   # [S, C] absolute
    h = model._embed(params, chunk, pos_b)          # [S, C, D]
    rope = model._rope_for(pos_b)
    local_t = pos_b - r_seq * Tl                    # [S, C]
    valid = (local_t >= 0) & (local_t < Tl)
    lt = jnp.clip(local_t, 0, Tl - 1)
    pids = jnp.where(valid,
                     jnp.take_along_axis(table, lt // page, axis=1), 0)
    offs = lt % page
    slots_g = r_seq * Tl + jnp.arange(Tl)           # [Tl] global pos

    def mask_for(window):
        m = slots_g[None, None, :] <= pos_b[:, :, None]
        if window is not None:
            m &= slots_g[None, None, :] > pos_b[:, :, None] - window
        return m

    def one_layer(h, lp, kp, vp, window):
        # kp/vp [Pl, Hkv, page, Dh] — this partition's pool slice
        x = model._norm_h(lp, "ln1", h).astype(cd)
        q = model._attn_proj(lp, "q", x).reshape(S, C, H, Dh)
        k_new = model._attn_proj(lp, "k", x).reshape(S, C, Hkv, Dh)
        v_new = model._attn_proj(lp, "v", x).reshape(S, C, Hkv, Dh)
        if rope is not None:
            q = _rope_rotate(q, *rope)
            k_new = _rope_rotate(k_new, *rope)
        kp = kp.at[pids, :, offs].set(k_new, mode="drop")
        vp = vp.at[pids, :, offs].set(v_new, mode="drop")
        kc = paged_view_rows(kp, table, page)       # [S, Hkv, Tl, Dh]
        vc = paged_view_rows(vp, table, page)
        qg = q.transpose(0, 2, 1, 3).reshape(S, Hkv, H // Hkv, C, Dh)
        scores = jnp.einsum(
            "bkgsd,bktd->bkgst", qg, kc,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ) * (Dh ** -0.5)
        scores = jnp.where(mask_for(window)[:, None, None], scores,
                           -jnp.inf)
        m_r = jnp.max(scores, axis=-1)              # [S, Hkv, G, C]
        m = jax.lax.pmax(m_r, SEQ_AXIS)
        w = jnp.exp(scores - m[..., None])
        s_r = jnp.sum(w, axis=-1)
        o_r = jnp.einsum(
            "bkgst,bktd->bkgsd", w, vc,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        den = jax.lax.psum(s_r, SEQ_AXIS)
        num = jax.lax.psum(o_r, SEQ_AXIS)
        a = (num / den[..., None]).astype(cd)       # [S, Hkv, G, C, Dh]
        a = a.reshape(S, H, C, Dh).transpose(0, 2, 1, 3)
        h = h + model._attn_proj(lp, "o", a.reshape(S, C, model.d_model))
        x = model._norm_h(lp, "ln2", h).astype(cd)
        out, _ = model._ffn(lp, x, "ring", SEQ_AXIS, ep_groups=1)
        return h + out.astype(cd), kp, vp

    pp = model._window_period()

    def block(h, inputs):
        lp, kp, vp = inputs
        if pp == 1:
            h, kp, vp = one_layer(h, lp, kp, vp, model.attn_windows[0])
            return h, (kp, vp)
        kps, vps = [], []
        for g in range(pp):
            h, kp_g, vp_g = one_layer(
                h, {k: v[g] for k, v in lp.items()}, kp[g], vp[g],
                model.attn_windows[g])
            kps.append(kp_g)
            vps.append(vp_g)
        return h, (jnp.stack(kps), jnp.stack(vps))

    lps = {k: params[k] for k in model._block_keys()}
    ck, cv = pool["k"], pool["v"]
    if pp > 1:
        lps = _period_group(lps, pp)
        ck = _period_group(ck, pp)
        cv = _period_group(cv, pp)
    h, (kc_new, vc_new) = jax.lax.scan(block, h, (lps, ck, cv))
    if pp > 1:
        kc_new = _period_ungroup(kc_new, model.n_layers)
        vc_new = _period_ungroup(vc_new, model.n_layers)
    h = model._norm_h(params, "lnf", h)
    logits = model._logits(params, h)               # [S, C, V]
    return logits, {"k": kc_new, "v": vc_new}


class ServingOps(NamedTuple):
    """The sharded programs the serving engine drives (plus the cache
    factory matching their layout). Signatures are identical to the
    engine's single-device kernels, so ``ServingEngine`` swaps them in
    without touching its loop — including the chunked-prefill insert
    (``pos0``) and the fused K-step decode."""

    init_cache: Any   # () -> {"k"/"v": [L, S, Hkv, capacity, Dh]} placed
    insert: Any       # (params, cache, tokens[1,Tb], t_last, slot, pos0) -> (last[V], cache)
    decode: Any       # (params, cache, tok[S], pos[S], temps[S], keys[S,2], live[S]) -> (emit[S], tok, pos, cache)
    decode_fused: Any  # (..., live[S], n_steps=K) -> (emit[S,K], tok, pos, cache)
    verify: Any       # (params, cache, drafts[S,W], tok, pos, temps, keys, live) -> (sel[S,W+1], n[S], tok, pos, cache)
    max_len: int
    capacity: int     # cache time axis = sp · aligned(ceil(max_len / sp))


def build_serving_ops(model: TransformerLM, mesh: Mesh, n_slots: int,
                      max_len: Optional[int] = None) -> ServingOps:
    """Compile the serving engine's two device programs over ``mesh``:
    SLOTS shard over ``"data"`` (each data rank owns ``n_slots/dp``
    contiguous slot rows) and the KV cache time axis over ``"seq"`` —
    per-chip cache memory drops by ``dp × sp`` while the driver loop stays
    the single-device one.

    **Insert** (``pos0 == 0``: a whole prompt, or a chunk train's FIRST
    chunk) mirrors ``_gen_impl``'s prefill-then-slice: the padded prompt
    ``[1, Tb]`` prefills replicated into a FULL-capacity transient K/V
    buffer (every seq rank then slices exactly ``[r·Tl, (r+1)·Tl)`` — no
    clamping, so no aliasing case), and each data rank owner-masks the
    write into its local slot row: the owner replaces the whole row, every
    other rank rewrites one of its rows with itself (statically shaped —
    the same trick as the decode step's owner write). Ranks past the
    prompt span write the transient buffer's zeros, wiping the previous
    occupant wholesale.

    **Chunked insert** (``pos0 > 0``: a chunk train continuation) CANNOT
    reuse that path — the chunk must attend the slot's existing sharded
    K/V, and a transient-buffer rewrite would wipe it. Instead each rank
    gathers its slice of the slot row, scatter-writes the chunk positions
    that land in its slice (unique indices, out-of-slice and non-owner
    writes drop), attends the chunk against the slice under the global
    causal/window mask, and merges partials across ``"seq"`` by the same
    logsumexp identity the decode step uses — just with matrix-matrix
    score blocks instead of flash-decode. Non-owner data ranks compute on
    a surrogate row and write nothing; the final logits replicate from
    the owner by a masked ``psum`` over ``"data"``.

    **Decode** is ``_decode_step_sharded`` with PER-ROW positions (each
    slot at its own depth, free slots parked at 0) + per-slot selection;
    sampling runs replicated on every seq rank from identical merged
    logits and identical per-slot keys, so ranks stay in lockstep with no
    broadcast — ``row_offset`` folding is unnecessary because every slot
    carries its own key. The carry token/position advance in-program for
    ``live`` rows (the engine's device-resident step state), and
    **decode_fused** wraps the same body in a ``lax.scan`` of ``n_steps``
    — one launch, K tokens, identical streams.

    One decode program per fuse width; one insert program per
    prompt-length bucket (``t_last``/``slot``/``pos0`` stay traced). The
    cache is donated through every program so the sharded buffer updates
    in place.
    """
    _check_mesh_and_specs(model, mesh)
    if model._ring_cache:
        raise NotImplementedError(
            "serving needs a linear (horizon) cache; all-windowed models "
            "allocate rolling buffers (see TransformerLM.prefill_slot)"
        )
    sp = mesh.shape[SEQ_AXIS]
    dp = mesh.shape[DATA_AXIS]
    if n_slots % dp:
        raise ValueError(
            f"n_slots={n_slots} not divisible by data axis size {dp}")
    max_len = int(model.max_len if max_len is None else max_len)
    Tl = _local_cache_len(max_len, sp)
    capacity = sp * Tl
    L = model.n_layers
    Hkv = model.n_kv_heads
    Dh = model.d_model // model.n_heads
    cd = model.compute_dtype
    cspec = P(None, DATA_AXIS, None, SEQ_AXIS, None)
    cache_specs = {"k": cspec, "v": cspec}
    pspecs = model.specs()

    def init_cache():
        # two DISTINCT buffers (the engine donates the cache through every
        # program; XLA refuses aliased donations), each shard zeroed on
        # its own device: a cache sized for the mesh need not fit on one
        zeros = jax.jit(
            lambda: jnp.zeros((L, n_slots, Hkv, capacity, Dh), cd),
            out_shardings=NamedSharding(mesh, cspec))
        return {"k": zeros(), "v": zeros()}

    def _insert_impl(params, cache, tokens, t_last, slot):
        # local cache [L, S_local, Hkv, Tl, Dh]; tokens [1, Tb] replicated
        S_local = cache["k"].shape[1]
        r_data = jax.lax.axis_index(DATA_AXIS)
        logits, new_k, new_v = _prefill_slice_sharded(
            model, capacity, Tl, params, tokens)
        slot_local = slot - r_data * S_local
        own = (slot_local >= 0) & (slot_local < S_local)
        idx = jnp.clip(slot_local, 0, S_local - 1)
        out = {}
        for n, new in (("k", new_k), ("v", new_v)):
            cur = jax.lax.dynamic_slice_in_dim(cache[n], idx, 1, axis=1)
            out[n] = jax.lax.dynamic_update_slice_in_dim(
                cache[n], jnp.where(own, new, cur), idx, axis=1)
        last = jax.lax.dynamic_index_in_dim(logits[0], t_last, axis=0,
                                            keepdims=False)
        return last, out

    def _chunk_impl(params, cache, tokens, t_last, slot, pos0):
        # Chunk-train continuation: ``tokens`` [1, C] at absolute
        # positions pos0.. against slot ``slot``'s EXISTING sharded row.
        # Local cache [L, S_local, Hkv, Tl, Dh]; everything but the cache
        # is replicated. The forward itself lives in _chunk_row_sharded
        # (shared with the paged path); this wrapper only gathers and
        # re-scatters the slot row.
        S_local = cache["k"].shape[1]
        r_data = jax.lax.axis_index(DATA_AXIS)
        slot_local = slot - r_data * S_local
        own = (slot_local >= 0) & (slot_local < S_local)
        idx = jnp.clip(slot_local, 0, S_local - 1)
        # non-owner data ranks gather a surrogate row they write back
        # unchanged (their chunk writes all drop inside)
        row = {n: jax.lax.dynamic_slice_in_dim(cache[n], idx, 1, axis=1)
               for n in ("k", "v")}        # [L, 1, Hkv, Tl, Dh]
        last, new_row = _chunk_row_sharded(model, Tl, params, row, tokens,
                                           t_last, pos0, own)
        out = {
            n: jax.lax.dynamic_update_slice_in_dim(cache[n], new_row[n],
                                                   idx, axis=1)
            for n in ("k", "v")
        }
        return last, out

    def _decode_impl(params, cache, tokens, pos, temps, keys, live):
        # local: tokens/pos/temps/live [S_local], keys [S_local, 2]
        logits, kc, vc = _decode_step_sharded(
            model, params, tokens, pos, cache["k"], cache["v"], Tl)
        emit = select_slot_tokens(logits, pos + 1, temps, keys)
        tokens = jnp.where(live, emit, tokens)
        pos = jnp.where(live, pos + 1, pos)
        return emit, tokens, pos, {"k": kc, "v": vc}

    def _fused_impl(n_steps, params, cache, tokens, pos, temps, keys, live):
        def body(carry, _):
            tok, p, kc, vc = carry
            logits, kc, vc = _decode_step_sharded(
                model, params, tok, p, kc, vc, Tl)
            emit = select_slot_tokens(logits, p + 1, temps, keys)
            tok = jnp.where(live, emit, tok)
            p = jnp.where(live, p + 1, p)
            return (tok, p, kc, vc), emit

        (tokens, pos, kc, vc), emitted = jax.lax.scan(
            body, (tokens, pos, cache["k"], cache["v"]), None,
            length=n_steps)
        return emitted.T, tokens, pos, {"k": kc, "v": vc}

    def _verify_impl(params, cache, drafts, tokens, pos, temps, keys, live):
        # speculative verify: ONE chunk forward scores carry + drafts for
        # every local row; selection/acceptance runs replicated on every
        # seq rank from identical merged logits and identical per-slot
        # keys, so the ranks stay in lockstep (same argument as decode)
        chunk = jnp.concatenate([tokens[:, None], drafts], axis=1)
        logits, kc, vc = _verify_rows_sharded(
            model, Tl, params, cache["k"], cache["v"], chunk, pos)
        sel, n_acc = spec_verify_select(logits, drafts, pos, temps, keys)
        corr = jnp.take_along_axis(sel, n_acc[:, None], axis=1)[:, 0]
        tokens = jnp.where(live, corr, tokens)
        pos = jnp.where(live, pos + n_acc + 1, pos)
        return sel, n_acc, tokens, pos, {"k": kc, "v": vc}

    insert_programs: Dict[int, Any] = {}
    chunk_programs: Dict[int, Any] = {}

    def insert(params, cache, tokens, t_last, slot, pos0=0):
        Tb = int(tokens.shape[1])
        if int(pos0) == 0:
            # whole prompt, or a chunk train's first chunk: prefill-then-
            # slice (also wipes the previous occupant wholesale)
            if Tb not in insert_programs:
                insert_programs[Tb] = jax.jit(
                    shard_map(
                        _insert_impl,
                        mesh=mesh,
                        in_specs=(pspecs, cache_specs, P(None, None), P(),
                                  P()),
                        out_specs=(P(), cache_specs),
                        check_vma=False,
                    ),
                    donate_argnums=(1,),
                )
            return insert_programs[Tb](
                params, cache, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(t_last, jnp.int32), jnp.asarray(slot, jnp.int32))
        if Tb not in chunk_programs:
            chunk_programs[Tb] = jax.jit(
                shard_map(
                    _chunk_impl,
                    mesh=mesh,
                    in_specs=(pspecs, cache_specs, P(None, None), P(), P(),
                              P()),
                    out_specs=(P(), cache_specs),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
        return chunk_programs[Tb](
            params, cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(t_last, jnp.int32), jnp.asarray(slot, jnp.int32),
            jnp.asarray(pos0, jnp.int32))

    state_specs = (P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                   P(DATA_AXIS, None), P(DATA_AXIS))
    decode = jax.jit(
        shard_map(
            _decode_impl,
            mesh=mesh,
            in_specs=(pspecs, cache_specs) + state_specs,
            out_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                       cache_specs),
            check_vma=False,
        ),
        donate_argnums=(1,),
    )

    fused_programs: Dict[int, Any] = {}

    def decode_fused(params, cache, tokens, pos, temps, keys, live,
                     n_steps: int):
        K = int(n_steps)
        if K not in fused_programs:
            fused_programs[K] = jax.jit(
                shard_map(
                    functools.partial(_fused_impl, K),
                    mesh=mesh,
                    in_specs=(pspecs, cache_specs) + state_specs,
                    out_specs=(P(DATA_AXIS, None), P(DATA_AXIS),
                               P(DATA_AXIS), cache_specs),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
        return fused_programs[K](params, cache, tokens, pos, temps, keys,
                                 live)

    verify_programs: Dict[int, Any] = {}

    def verify(params, cache, drafts, tokens, pos, temps, keys, live):
        W = int(drafts.shape[1])
        if W not in verify_programs:
            verify_programs[W] = jax.jit(
                shard_map(
                    _verify_impl,
                    mesh=mesh,
                    in_specs=(pspecs, cache_specs, P(DATA_AXIS, None))
                    + state_specs,
                    out_specs=(P(DATA_AXIS, None), P(DATA_AXIS),
                               P(DATA_AXIS), P(DATA_AXIS), cache_specs),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
        return verify_programs[W](params, cache,
                                  jnp.asarray(drafts, jnp.int32), tokens,
                                  pos, temps, keys, live)

    return ServingOps(init_cache=init_cache, insert=insert, decode=decode,
                      decode_fused=decode_fused, verify=verify,
                      max_len=max_len, capacity=capacity)


class PagedServingOps(NamedTuple):
    """The PAGED serving programs (see ``serving/memory.py``): same loop
    contract as :class:`ServingOps`, but the KV lives in a refcounted page
    pool read through per-slot block tables, and every program carries the
    device table (plus per-slot adapter ids on the decode paths). The
    pool is donated through every program; the table/aids are small,
    host-cached, and never donated."""

    init_pool: Any     # () -> {"k"/"v": [L, dp·sp·Pl, Hkv, page, Dh]} placed
    upload_table: Any  # np [S, M] -> placed device table
    upload_aids: Any   # np [S] -> placed device adapter ids
    scatter_table_row: Any  # (table_dev, slot, row[M]) -> table_dev (donated)
    scatter_aids_row: Any   # (aids_dev, slot, aid) -> aids_dev (donated)
    insert: Any        # (params, pool, table, tokens[1,Tb], t_last, slot, pos0, aid) -> (last[V], pool)
    decode: Any        # (params, pool, table, aids, tok, pos, temps, keys, live) -> (emit, tok, pos, pool)
    decode_fused: Any  # (..., live, n_steps=K) -> (emit[S,K], tok, pos, pool)
    verify: Any        # (params, pool, table, aids, drafts, tok, pos, temps, keys, live) -> (sel, n, tok, pos, pool)
    max_len: int
    capacity: int      # logical per-slot horizon = sp · Tl
    Tl: int            # per-partition time slice
    page: int
    Ml: int            # logical pages per partition slice = Tl // page
    pages_per_partition: int
    dp: int
    sp: int


def build_paged_serving_ops(model: TransformerLM, mesh: Mesh, n_slots: int,
                            max_len: Optional[int] = None,
                            page_size: int = 16,
                            pages_per_partition: Optional[int] = None
                            ) -> PagedServingOps:
    """Compile the paged serving programs over ``mesh``: slots shard over
    ``"data"`` and each slot's LOGICAL time axis over ``"seq"`` exactly as
    in :func:`build_serving_ops` — but physical KV rows live in a page
    pool of ``pages_per_partition`` pages per ``(data, seq)`` partition
    (pool row ``p·Pl + i`` is page ``i`` of partition ``p = d·sp + q``;
    page 0 of each partition is the trash page). Block tables hold LOCAL
    page ids; cell ``(s, m)`` of the global ``[S, M]`` table belongs to
    partition ``(s // Sl)·sp + (m // Ml)``.

    Every program runs DIRECTLY over the pool through the table — decode
    and fused decode via :func:`_paged_decode_step_sharded` (per-layer
    single-row page scatter + :func:`_merged_paged_attention`), chunk
    continuations via :func:`_paged_chunk_row_sharded` and speculative
    verify via :func:`_paged_verify_rows_sharded` (per-layer O(chunk)
    page scatter, scores against a transient gathered view whose time
    axis equals ``Tl``), insert via replicated prefill-then-slice
    scattering only the pages the prompt actually covers. Non-owner and
    unmapped writes land in the trash page. No per-step dense-layout
    round trip remains, and the attention reduction trees match the dense
    programs' exactly. ``page_size`` must divide ``Tl`` — that equality
    of time axes IS the bit-identity contract with the dense engine
    (on CPU every paged attention resolves to the gather-through-table
    reference applying the dense math verbatim). Adapter ids
    ride along: the insert paths take one replicated scalar (logits must
    stay replicated), the decode paths a ``"data"``-sharded ``[S]``
    vector, both applied via the model's ``adapter_context`` when it has
    one (:class:`MultiTenantLM`)."""
    _check_mesh_and_specs(model, mesh)
    if model._ring_cache:
        raise NotImplementedError(
            "serving needs a linear (horizon) cache; all-windowed models "
            "allocate rolling buffers (see TransformerLM.prefill_slot)"
        )
    sp = mesh.shape[SEQ_AXIS]
    dp = mesh.shape[DATA_AXIS]
    if n_slots % dp:
        raise ValueError(
            f"n_slots={n_slots} not divisible by data axis size {dp}")
    max_len = int(model.max_len if max_len is None else max_len)
    Tl = _local_cache_len(max_len, sp)
    capacity = sp * Tl
    page = int(page_size)
    if page < 1 or Tl % page:
        raise ValueError(
            f"page_size {page} must divide the per-shard cache length {Tl} "
            f"(the dense-view bit-identity contract)")
    Ml = Tl // page
    Sl = n_slots // dp
    if pages_per_partition is None:
        pages_per_partition = Sl * Ml + 1
    Pl = int(pages_per_partition)
    if Pl < 2:
        raise ValueError(f"pages_per_partition must be >= 2, got {Pl}")
    L = model.n_layers
    Hkv = model.n_kv_heads
    Dh = model.d_model // model.n_heads
    cd = model.compute_dtype
    pool_spec = P(None, (DATA_AXIS, SEQ_AXIS), None, None, None)
    pool_specs = {"k": pool_spec, "v": pool_spec}
    table_spec = P(DATA_AXIS, SEQ_AXIS)
    aids_spec = P(DATA_AXIS)
    pspecs = model.specs()

    def init_pool():
        # two DISTINCT buffers (XLA refuses donation of aliased inputs),
        # each shard zeroed on its own device: a pool sized for the mesh
        # need not fit on one
        zeros = jax.jit(
            lambda: jnp.zeros((L, dp * sp * Pl, Hkv, page, Dh), cd),
            out_shardings=NamedSharding(mesh, pool_spec))
        return {"k": zeros(), "v": zeros()}

    def upload_table(table_np):
        return jax.device_put(jnp.asarray(table_np, jnp.int32),
                              NamedSharding(mesh, table_spec))

    def upload_aids(aids_np):
        return jax.device_put(jnp.asarray(aids_np, jnp.int32),
                              NamedSharding(mesh, aids_spec))

    # device-resident table maintenance: one dirty slot row patched in
    # place (donated) instead of re-uploading the whole host table
    scatter_table_row = jax.jit(
        lambda t, s, row: t.at[s].set(row),
        donate_argnums=(0,),
        out_shardings=NamedSharding(mesh, table_spec))
    scatter_aids_row = jax.jit(
        lambda a, s, aid: a.at[s].set(aid),
        donate_argnums=(0,),
        out_shardings=NamedSharding(mesh, aids_spec))

    def _paged_insert_impl(params, pool, table, tokens, t_last, slot, aid):
        # local: pool [L, Pl, Hkv, page, Dh], table [Sl, Ml]
        Sl_, Ml_ = table.shape
        Tb = tokens.shape[1]                        # static chunk length
        r_data = jax.lax.axis_index(DATA_AXIS)
        r_seq = jax.lax.axis_index(SEQ_AXIS)
        logits, new_k, new_v = _prefill_slice_sharded(
            model, capacity, Tl, params, tokens, aid=aid)
        slot_local = slot - r_data * Sl_
        own = (slot_local >= 0) & (slot_local < Sl_)
        idx = jnp.clip(slot_local, 0, Sl_ - 1)
        trow = jax.lax.dynamic_slice(table, (idx, 0), (1, Ml_))
        # scatter ONLY pages whose global span intersects the prompt —
        # pages wholly past Tb are unmapped (cell 0) and would have
        # carried zeros into the trash page; non-owner data ranks and
        # unmapped cells redirect to the trash page. Duplicate trash
        # coordinates are undefined-pick — trash is never read unmasked.
        starts = r_seq * Tl + jnp.arange(Ml_) * page
        ids = jnp.where(own & (starts < Tb), trow[0], 0)
        for n, new in (("k", new_k), ("v", new_v)):
            vals = new[:, 0].reshape(L, Hkv, Ml_, page, Dh)
            vals = vals.transpose(0, 2, 1, 3, 4)    # [L, Ml, Hkv, pg, Dh]
            pool[n] = pool[n].at[:, ids].set(vals, mode="drop")
        last = jax.lax.dynamic_index_in_dim(logits[0], t_last, axis=0,
                                            keepdims=False)
        return last, pool

    def _paged_chunk_impl(params, pool, table, tokens, t_last, slot, pos0,
                          aid):
        Sl_, Ml_ = table.shape
        r_data = jax.lax.axis_index(DATA_AXIS)
        slot_local = slot - r_data * Sl_
        own = (slot_local >= 0) & (slot_local < Sl_)
        idx = jnp.clip(slot_local, 0, Sl_ - 1)
        trow = jax.lax.dynamic_slice(table, (idx, 0), (1, Ml_))
        with _adapter_ctx(model, jnp.reshape(aid, (1,))):
            last, pool = _paged_chunk_row_sharded(
                model, Tl, page, params, pool, trow, tokens, t_last,
                pos0, own)
        return last, pool

    def _paged_decode_impl(params, pool, table, aids, tokens, pos, temps,
                           keys, live):
        # local: tokens/pos/temps/live/aids [Sl], keys [Sl, 2] — one
        # fused step straight over the pool, no dense view round trip
        with _adapter_ctx(model, aids):
            logits, pool = _paged_decode_step_sharded(
                model, params, tokens, pos, pool, table, page, Tl)
        emit = select_slot_tokens(logits, pos + 1, temps, keys)
        tokens = jnp.where(live, emit, tokens)
        pos = jnp.where(live, pos + 1, pos)
        return emit, tokens, pos, pool

    def _paged_fused_impl(n_steps, params, pool, table, aids, tokens, pos,
                          temps, keys, live):
        # the POOL itself is the scan carry: each step's layers write
        # their one new row per slot into the owning page, so the whole
        # window moves O(Sl · n_steps) rows
        def body(carry, _):
            tok, p, pk, pv = carry
            with _adapter_ctx(model, aids):
                logits, new = _paged_decode_step_sharded(
                    model, params, tok, p, {"k": pk, "v": pv}, table,
                    page, Tl)
            emit = select_slot_tokens(logits, p + 1, temps, keys)
            tok = jnp.where(live, emit, tok)
            p = jnp.where(live, p + 1, p)
            return (tok, p, new["k"], new["v"]), emit

        (tokens_out, pos_out, pk, pv), emitted = jax.lax.scan(
            body, (tokens, pos, pool["k"], pool["v"]), None,
            length=n_steps)
        return emitted.T, tokens_out, pos_out, {"k": pk, "v": pv}

    def _paged_verify_impl(params, pool, table, aids, drafts, tokens, pos,
                           temps, keys, live):
        # speculative verify straight over the pool: ONE chunk forward
        # writing O(chunk) rows through the table (rejected-tail rows
        # included — decode-era pages are never shared, and the
        # staleness-repair invariant rewrites them before any read)
        chunk = jnp.concatenate([tokens[:, None], drafts], axis=1)
        with _adapter_ctx(model, aids):
            logits, pool = _paged_verify_rows_sharded(
                model, Tl, page, params, pool, table, chunk, pos)
        sel, n_acc = spec_verify_select(logits, drafts, pos, temps, keys)
        corr = jnp.take_along_axis(sel, n_acc[:, None], axis=1)[:, 0]
        tokens = jnp.where(live, corr, tokens)
        pos = jnp.where(live, pos + n_acc + 1, pos)
        return sel, n_acc, tokens, pos, pool

    insert_programs: Dict[int, Any] = {}
    chunk_programs: Dict[int, Any] = {}

    def insert(params, pool, table, tokens, t_last, slot, pos0, aid):
        Tb = int(tokens.shape[1])
        if int(pos0) == 0:
            if Tb not in insert_programs:
                insert_programs[Tb] = jax.jit(
                    shard_map(
                        _paged_insert_impl,
                        mesh=mesh,
                        in_specs=(pspecs, pool_specs, table_spec,
                                  P(None, None), P(), P(), P()),
                        out_specs=(P(), pool_specs),
                        check_vma=False,
                    ),
                    donate_argnums=(1,),
                )
            return insert_programs[Tb](
                params, pool, table, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(t_last, jnp.int32),
                jnp.asarray(slot, jnp.int32), jnp.asarray(aid, jnp.int32))
        if Tb not in chunk_programs:
            chunk_programs[Tb] = jax.jit(
                shard_map(
                    _paged_chunk_impl,
                    mesh=mesh,
                    in_specs=(pspecs, pool_specs, table_spec,
                              P(None, None), P(), P(), P(), P()),
                    out_specs=(P(), pool_specs),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
        return chunk_programs[Tb](
            params, pool, table, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(t_last, jnp.int32), jnp.asarray(slot, jnp.int32),
            jnp.asarray(pos0, jnp.int32), jnp.asarray(aid, jnp.int32))

    state_specs = (P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                   P(DATA_AXIS, None), P(DATA_AXIS))
    decode = jax.jit(
        shard_map(
            _paged_decode_impl,
            mesh=mesh,
            in_specs=(pspecs, pool_specs, table_spec, aids_spec)
            + state_specs,
            out_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                       pool_specs),
            check_vma=False,
        ),
        donate_argnums=(1,),
    )

    fused_programs: Dict[int, Any] = {}

    def decode_fused(params, pool, table, aids, tokens, pos, temps, keys,
                     live, n_steps: int):
        K = int(n_steps)
        if K not in fused_programs:
            fused_programs[K] = jax.jit(
                shard_map(
                    functools.partial(_paged_fused_impl, K),
                    mesh=mesh,
                    in_specs=(pspecs, pool_specs, table_spec, aids_spec)
                    + state_specs,
                    out_specs=(P(DATA_AXIS, None), P(DATA_AXIS),
                               P(DATA_AXIS), pool_specs),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
        return fused_programs[K](params, pool, table, aids, tokens, pos,
                                 temps, keys, live)

    verify_programs: Dict[int, Any] = {}

    def verify(params, pool, table, aids, drafts, tokens, pos, temps, keys,
               live):
        W = int(drafts.shape[1])
        if W not in verify_programs:
            verify_programs[W] = jax.jit(
                shard_map(
                    _paged_verify_impl,
                    mesh=mesh,
                    in_specs=(pspecs, pool_specs, table_spec, aids_spec,
                              P(DATA_AXIS, None)) + state_specs,
                    out_specs=(P(DATA_AXIS, None), P(DATA_AXIS),
                               P(DATA_AXIS), P(DATA_AXIS), pool_specs),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
        return verify_programs[W](params, pool, table, aids,
                                  jnp.asarray(drafts, jnp.int32), tokens,
                                  pos, temps, keys, live)

    return PagedServingOps(init_pool=init_pool, upload_table=upload_table,
                           upload_aids=upload_aids,
                           scatter_table_row=scatter_table_row,
                           scatter_aids_row=scatter_aids_row,
                           insert=insert,
                           decode=decode, decode_fused=decode_fused,
                           verify=verify,
                           max_len=max_len, capacity=capacity, Tl=Tl,
                           page=page, Ml=Ml,
                           pages_per_partition=Pl, dp=dp, sp=sp)
