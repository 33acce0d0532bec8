"""Flash-decode: fused KV-cache attention for autoregressive inference.

One decode step attends a single query position per sequence against the
whole cache — a bandwidth-bound op (every step re-reads B·Hkv·T·Dh of K and
V from HBM). Naive lowering materializes the [B, Hkv, G, T] score tensor in
HBM twice (scores, probabilities); this kernel streams the cache through
VMEM in T-blocks with flash-style online softmax, touching K/V once and
never materializing probabilities off-chip.

Grouped-query attention is native: the cache carries ``Hkv`` heads and the
``G = H/Hkv`` query heads of a group share each K/V block from the same VMEM
visit — the kernel's arithmetic intensity grows with G for free.

The decode position ``pos`` is a *traced* scalar (it advances inside the
generation ``lax.scan``), delivered via Pallas scalar prefetch so block
index maps can see it: K/V blocks past ``pos`` are not even DMA'd — their
index map clamps to the last live block and ``pl.when`` skips the compute.

Cache layout is ``[B, Hkv, T, Dh]`` (T on the sublane axis) so each
(batch, kv-head) grid cell streams contiguous ``[BT, Dh]`` tiles.

Used by ``TransformerLM.decode_step`` via :func:`decode_attention` — Pallas
on TPU, the jnp reference elsewhere (also the test oracle; the kernel runs
under ``interpret=True`` on CPU in tests). No reference (b13n3rd/elephas)
analog: the reference has no inference engine beyond ``model.predict``
(SURVEY.md §2.5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_ops import _LANE, _pad_up, is_tpu_backend

_BLOCK_T = 256
_SUBLANE = 8
_NEG = -1e30


def aligned_cache_length(length: int) -> int:
    """Smallest cache length >= ``length`` whose T axis the kernel can
    block without padding (pads in the decode hot loop would recopy the
    whole cache in HBM every step). Extra positions are masked by ``pos``."""
    bt = min(_BLOCK_T, _pad_up(int(length), _SUBLANE))
    return _pad_up(int(length), bt)


# -- reference (fallback / oracle) implementation ----------------------------


def decode_attention_reference(q, k, v, pos, window=None,
                               ring: bool = False):
    """Grouped decode attention against a cache.

    ``q`` [B, Hkv, G, Dh]; ``k``/``v`` [B, Hkv, T, Dh]; ``pos`` scalar int
    or per-row ``[B]`` int (batched speculative decoding advances rows at
    different positions) — row b sees positions ``0..pos[b]`` inclusive,
    restricted to the last ``window`` of them under sliding-window
    attention. Returns [B, Hkv, G, Dh] float32, softmax in f32. One body
    serves this and the lse-exposing variant (same dedup rationale as the
    Pallas side).
    """
    return decode_attention_reference_lse(q, k, v, pos, window, ring)[0]


# -- pallas kernel ------------------------------------------------------------


def flash_decode(q, k, v, pos, interpret: bool = False, window=None,
                 ring: bool = False):
    """Fused decode attention (Pallas). Same contract as
    :func:`decode_attention_reference`; ``pos`` may be a traced scalar.

    One kernel serves both this and :func:`flash_decode_lse` — this entry
    discards the (tiny, lane-broadcast) lse output rather than keeping a
    second copy of the online-softmax kernel in sync."""
    return flash_decode_lse(q, k, v, pos, interpret=interpret,
                            window=window, ring=ring)[0]


def decode_attention(q, k, v, pos, window=None, ring: bool = False):
    """Dispatcher: Pallas flash-decode on TPU, jnp reference elsewhere."""
    if is_tpu_backend():
        return flash_decode(q, k, v, pos, window=window, ring=ring)
    return decode_attention_reference(q, k, v, pos, window, ring)


# -- lse-exposing variant (sequence-parallel decode) --------------------------
#
# When the KV cache is sharded over a mesh axis, each rank attends its local
# slice and the partials merge by logsumexp — exactly the ring-attention
# merge (ops/ring_attention.py), applied across the decode cache instead of
# around a ring:  o = Σ_r exp(lse_r − lse) · o_r,  lse = logsumexp_r lse_r.
# These variants return that per-rank ``lse`` alongside the normalized
# output; the cross-rank merge itself lives in models/sharded_generate.py
# (psum/pmax over the axis — three tiny collectives on [B, Hkv, G] tensors).


def decode_attention_reference_lse(q, k, v, pos, window=None,
                                   ring: bool = False):
    """Like :func:`decode_attention_reference` but also returns
    ``lse [B, Hkv, G] f32`` — the log of the softmax denominator (shifted by
    nothing: ``logsumexp`` of the masked scaled scores).

    ``ring=True`` (requires ``window``): the cache is a ROLLING buffer of
    ``Tc`` slots — slot ``s`` holds absolute position ``pos - ((pos - s)
    mod Tc)`` (writes land at ``p mod Tc``). A slot is visible iff its age
    ``(pos - s) mod Tc`` is ``< min(window, pos+1)`` — one formula that
    covers warm-up (ages past ``pos`` wrap high and mask out) and steady
    state (expired slots age out), for scalar and per-row positions alike.
    """
    dh = q.shape[-1]
    scores = jnp.einsum(
        "bkgd,bktd->bkgt", q, k, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ) * (dh ** -0.5)
    pos_rows = jnp.asarray(pos).reshape(-1, 1, 1, 1)  # scalar or per-row [B]
    slots = jnp.arange(k.shape[2])[None, None, None, :]
    if ring:
        if window is None:
            raise ValueError("ring cache attention requires a window")
        age = jnp.mod(pos_rows - slots, k.shape[2])
        mask = age < jnp.minimum(int(window), pos_rows + 1)
    else:
        mask = slots <= pos_rows
        if window is not None:
            mask &= slots > pos_rows - int(window)
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum(
        "bkgt,bktd->bkgd", p, v, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ) / l[..., None]
    return out, m + jnp.log(l)


def _decode_kernel_lse(d_true: int, block_t: int, window, t_ring,
                       t_live, pos_ref,
                       q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s,
                       acc_s):
    """Online-softmax decode kernel with an lse output (lane-broadcast).

    ``pos_ref`` is per-row ``[B]`` (scalar callers broadcast): the batch
    grid dimension picks its own visibility bound, which is what batched
    speculative decoding needs when rows sit at different positions."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    start = t * block_t
    if t_ring is not None:
        # rolling cache: the whole (window-sized) buffer is live
        live = True
    else:
        live = start <= pos_ref[b]
        if window is not None:
            # blocks wholly below the window contribute nothing
            live = jnp.logical_and(
                live, start + block_t - 1 >= pos_ref[b] - (int(window) - 1))

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ) * (d_true ** -0.5)
        j = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if t_ring is not None:
            # slot age under the rolling buffer (see the reference impl)
            age = jnp.mod(pos_ref[b] - j, t_ring)
            keep = age < jnp.minimum(int(window), pos_ref[b] + 1)
            keep = jnp.logical_and(keep, j < t_ring)  # alignment padding
        else:
            keep = j <= pos_ref[b]
            if window is not None:
                keep = jnp.logical_and(keep, j > pos_ref[b] - int(window))
                # windowed callers may pass pos PAST the cache end (a
                # sequence-sharded rank whose slice is partially expired
                # keeps global window arithmetic that way) — alignment
                # padding rows must then be masked explicitly
                keep = jnp.logical_and(keep, j < t_live)
        s = jnp.where(keep, s, _NEG)
        m_prev = m_s[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_s[:] = alpha * l_s[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[:] = alpha * acc_s[:] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        m_s[:] = jnp.broadcast_to(m_cur, m_s.shape)

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_s[:] / l_s[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[:] + jnp.log(l_s[:])


def flash_decode_lse(q, k, v, pos, interpret: bool = False, window=None,
                     ring: bool = False):
    """Fused decode attention returning ``(out, lse)``; ``pos`` (scalar or
    per-row ``[B]``) must be ``>= 0`` (a rank with nothing visible clamps
    pos and overrides its lse to −inf outside the kernel — see
    models/sharded_generate.py)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hkv, G, Dh = q.shape
    T = k.shape[2]
    Gp = _pad_up(G, _SUBLANE)
    bt = min(_BLOCK_T, _pad_up(T, _SUBLANE))
    Tp = _pad_up(T, bt)
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Tp - T), (0, 0))) if Tp != T else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Tp - T), (0, 0))) if Tp != T else v
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    n_t = Tp // bt

    if ring:
        if window is None:
            raise ValueError("ring cache attention requires a window")
        # the buffer IS the window: every block is live, nothing to skip
        kv_ix = lambda b, h, t, s: (b, h, t, 0)
    elif window is None:
        # blocks past row b's pos are never DMA'd
        kv_ix = lambda b, h, t, s: (b, h, jnp.minimum(t, s[b] // bt), 0)
    else:
        # ...nor, under a sliding window, blocks wholly before it (the
        # upper clip also bounds positions past the cache end — see the
        # padding mask in the kernel)
        w = int(window)
        kv_ix = lambda b, h, t, s: (
            b, h,
            jnp.clip(t, jnp.maximum((s[b] - w + 1) // bt, 0),
                     jnp.minimum(s[b] // bt, n_t - 1)),
            0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, Gp, Dh), lambda b, h, t, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bt, Dh), kv_ix),
            pl.BlockSpec((1, 1, bt, Dh), kv_ix),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Gp, Dh), lambda b, h, t, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Gp, _LANE), lambda b, h, t, s: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Gp, _LANE), jnp.float32),
            pltpu.VMEM((Gp, _LANE), jnp.float32),
            pltpu.VMEM((Gp, Dh), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_decode_kernel_lse, Dh, bt, window,
                          T if ring else None, T),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Gp, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, Gp, _LANE), jnp.float32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
        name="flash_decode",
    )(pos_arr, qp, kp, vp)
    return out[:, :, :G, :], lse[:, :, :G, 0]


def decode_attention_lse(q, k, v, pos, window=None, ring: bool = False):
    """Dispatcher for the lse-exposing decode attention."""
    if is_tpu_backend():
        return flash_decode_lse(q, k, v, pos, window=window, ring=ring)
    return decode_attention_reference_lse(q, k, v, pos, window, ring)
