"""Fused paged attention: decode straight over the page pool.

The paged serving path used to *gather* every slot's pages into the dense
``[S, Hkv, T, Dh]`` layout, run the unchanged dense decode programs, and
scatter the written span back —
a full per-slot KV memcpy in each direction per decode step. This module
is the vLLM-style replacement: attention reads K/V pages *directly out of
the pool* ``[P, Hkv, page, Dh]`` through the ``[S, M]`` block table, and
the serving kernels write only the *newly produced* rows into their owning
pages (O(new tokens), not O(context)).

Two kernel families, mirroring ``ops/flash_decode.py``:

* **single-token decode** (:func:`paged_flash_decode_lse`) — grid
  ``(S, Hkv, M)``; the K/V block index map dereferences the block table via
  scalar prefetch (``pid = table[s, min(m, pos[s] // page)]``), so pages
  past a slot's ``pos`` are never even DMA'd and each live page streams
  through VMEM exactly once under flash-style online softmax. Unmapped
  table cells hold 0 — the per-partition trash page — whose finite garbage
  is masked by ``j <= pos`` exactly like the dense kernel's tail.
* **chunked / verify multi-row** (:func:`paged_flash_chunk`) — the same
  page walk with ``C`` queries per slot at positions ``pos0 .. pos0+C-1``
  (chunked prefill continuations and speculative verify), per-query causal
  masks built from a 2-D iota.

The jnp references are also the CPU path: they read the pool through the
table into a transient per-call view and then apply the *exact* dense
attention math (same einsums, same ``HIGHEST`` precision, same masking),
so on CPU — where the dense programs use their own jnp references — paged
and dense logits are **bitwise identical**. That is the identity contract
the serving tests pin. The Pallas kernels accumulate at page granularity
(vs the dense kernel's 256-wide blocks), so across *backends* they are
allclose, not bitwise; within a backend the contract holds because both
engines run the same implementation family.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_ops import _LANE, _pad_up, is_tpu_backend

_SUBLANE = 8
_NEG = -1e30


def paged_view_rows(pool, table, page: int):
    """Dense per-slot view of one layer's page pool: ``pool``
    ``[P, Hkv, page, Dh]`` read through ``table`` ``[S, M]`` int32 →
    ``[S, Hkv, M·page, Dh]``. Unmapped cells (id 0) read the trash page,
    whose finite garbage sits at masked positions only. This is the read
    the references below make — XLA fuses it into the attention consumer,
    so on CPU it is a transient, not a carried buffer."""
    g = pool[table]                        # [S, M, Hkv, page, Dh]
    S, M, Hkv, pg, Dh = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(S, Hkv, M * pg, Dh)


# -- jnp references (CPU path / oracles) -------------------------------------


def paged_decode_reference_lse(q, kp, vp, table, pos, page: int,
                               window=None):
    """Single-token paged decode attention, reference path.

    ``q`` [S, Hkv, G, Dh]; ``kp``/``vp`` [P, Hkv, page, Dh]; ``table``
    [S, M]; ``pos`` scalar or per-row [S]. Returns ``(out [S, Hkv, G, Dh]
    f32, lse [S, Hkv, G] f32)``. Exactly
    :func:`~elephas_tpu.ops.flash_decode.decode_attention_reference_lse`
    applied to the table-gathered view — the masked (> pos, trash-page)
    positions contribute exactly zero, so the result is bitwise what the
    dense path computes on its own cache."""
    from .flash_decode import decode_attention_reference_lse

    k = paged_view_rows(kp, table, page)
    v = paged_view_rows(vp, table, page)
    return decode_attention_reference_lse(q, k, v, pos, window=window)


def paged_decode_reference(q, kp, vp, table, pos, page: int, window=None):
    return paged_decode_reference_lse(q, kp, vp, table, pos, page, window)[0]


def paged_chunk_reference(q, kp, vp, table, pos0, page: int, window=None):
    """Multi-row (chunk / verify) paged attention, reference path.

    ``q`` [S, Hkv, G, C, Dh] — C queries per slot at absolute positions
    ``pos0[s] .. pos0[s]+C-1`` — against the table-gathered view. The math
    is verbatim ``TransformerLM.decode_chunk``'s attention over a horizon
    layer, the chunk as one block of queries (same einsums, same mask,
    ``jax.nn.softmax``), so it is bitwise the dense chunk path on CPU.
    Returns ``[S, Hkv, G, C, Dh]`` f32."""
    S, Hkv, G, C, Dh = q.shape
    kc = paged_view_rows(kp, table, page)   # [S, Hkv, T, Dh]
    vc = paged_view_rows(vp, table, page)
    T = kc.shape[2]
    pos_b = jnp.asarray(pos0).reshape(-1, 1) + jnp.arange(C)[None, :]
    slots = jnp.arange(T)[None, None, :]
    mask = slots <= pos_b[:, :, None]
    if window is not None:
        mask &= slots > pos_b[:, :, None] - int(window)
    scores = jnp.einsum(
        "bkgsd,bktd->bkgst", q, kc,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ) * (Dh ** -0.5)
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bkgst,bktd->bkgsd", probs, vc,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


# -- pallas kernels -----------------------------------------------------------


def _paged_decode_kernel_lse(d_true: int, page: int, window, pos_ref,
                             tbl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                             m_s, l_s, acc_s):
    """Online-softmax decode over one slot's page chain. Grid
    ``(S, Hkv, M)``: step ``m`` sees the page the index map dereferenced
    from the block table (clamped to the last live page, so dead steps
    re-see a live block and skip compute)."""
    from jax.experimental import pallas as pl

    s_i = pl.program_id(0)
    m_i = pl.program_id(2)

    @pl.when(m_i == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    start = m_i * page
    live = start <= pos_ref[s_i]
    if window is not None:
        live = jnp.logical_and(
            live, start + page - 1 >= pos_ref[s_i] - (int(window) - 1))

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
        keep = j <= pos_ref[s_i]
        if window is not None:
            keep = jnp.logical_and(keep, j > pos_ref[s_i] - int(window))
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

    @pl.when(m_i == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_s[:] / l_s[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[:] + jnp.log(l_s[:])


def paged_flash_decode_lse(q, kp, vp, table, pos, page: int, window=None,
                           interpret: bool = False):
    """Fused paged decode attention (Pallas): same contract as
    :func:`paged_decode_reference_lse`, no dense-layout materialization.

    The block table and per-slot positions ride in via scalar prefetch so
    the K/V index maps can dereference them: grid step ``(s, h, m)`` DMAs
    pool page ``table[s, min(m, pos[s] // page)]`` — logical pages past a
    slot's write head are never fetched (their grid steps clamp onto the
    last live page and ``pl.when`` skips the compute), and unmapped cells
    fetch the trash page whose garbage the position mask zeroes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, Hkv, G, Dh = q.shape
    M = table.shape[1]
    Gp = _pad_up(G, _SUBLANE)
    qp = jnp.pad(q.astype(jnp.float32),
                 ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (S,))
    tbl = jnp.asarray(table, jnp.int32)

    if window is None:
        kv_ix = lambda s, h, m, p_r, t_r: (
            t_r[s, jnp.minimum(m, p_r[s] // page)], h, 0, 0)
    else:
        w = int(window)
        kv_ix = lambda s, h, m, p_r, t_r: (
            t_r[s, jnp.clip(m, jnp.maximum((p_r[s] - w + 1) // page, 0),
                            jnp.minimum(p_r[s] // page, M - 1))],
            h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, Hkv, M),
        in_specs=[
            pl.BlockSpec((1, 1, Gp, Dh), lambda s, h, m, p_r, t_r:
                         (s, h, 0, 0)),
            pl.BlockSpec((1, 1, page, Dh), kv_ix),
            pl.BlockSpec((1, 1, page, Dh), kv_ix),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Gp, Dh), lambda s, h, m, p_r, t_r:
                         (s, h, 0, 0)),
            pl.BlockSpec((1, 1, Gp, _LANE), lambda s, h, m, p_r, t_r:
                         (s, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Gp, _LANE), jnp.float32),
            pltpu.VMEM((Gp, _LANE), jnp.float32),
            pltpu.VMEM((Gp, Dh), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_paged_decode_kernel_lse, Dh, page, window),
        out_shape=[
            jax.ShapeDtypeStruct((S, Hkv, Gp, Dh), jnp.float32),
            jax.ShapeDtypeStruct((S, Hkv, Gp, _LANE), jnp.float32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_decode",
    )(pos_arr, tbl, qp, kp, vp)
    return out[:, :, :G, :], lse[:, :, :G, 0]


def paged_flash_decode(q, kp, vp, table, pos, page: int, window=None,
                       interpret: bool = False):
    return paged_flash_decode_lse(q, kp, vp, table, pos, page,
                                  window=window, interpret=interpret)[0]


def _paged_chunk_kernel(d_true: int, page: int, C: int, window, pos_ref,
                        tbl_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s,
                        acc_s):
    """Multi-row paged online softmax: query row ``r = g·C + c`` of slot
    ``s`` sits at absolute position ``pos0[s] + c`` — the per-row causal
    bound is rebuilt from a 2-D iota each page step."""
    from jax.experimental import pallas as pl

    s_i = pl.program_id(0)
    m_i = pl.program_id(2)

    @pl.when(m_i == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    start = m_i * page
    live = start <= pos_ref[s_i] + C - 1
    if window is not None:
        live = jnp.logical_and(
            live, start + page - 1 >= pos_ref[s_i] - (int(window) - 1))

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
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % C
        qpos = pos_ref[s_i] + c
        keep = j <= qpos
        if window is not None:
            keep = jnp.logical_and(keep, j > qpos - int(window))
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

    @pl.when(m_i == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_s[:] / l_s[:, :1]).astype(o_ref.dtype)


def paged_flash_chunk(q, kp, vp, table, pos0, page: int, window=None,
                      interpret: bool = False):
    """Fused paged chunk/verify attention (Pallas): same contract as
    :func:`paged_chunk_reference`. The G·C query rows of a slot flatten
    onto the sublane axis and walk the slot's page chain once; the index
    map clamps at ``(pos0[s] + C - 1) // page``, so pages past the last
    query's position are never DMA'd."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, Hkv, G, C, Dh = q.shape
    M = table.shape[1]
    R = G * C
    Rp = _pad_up(R, _SUBLANE)
    qf = q.reshape(S, Hkv, R, Dh).astype(jnp.float32)
    qf = jnp.pad(qf, ((0, 0), (0, 0), (0, Rp - R), (0, 0)))
    pos_arr = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (S,))
    tbl = jnp.asarray(table, jnp.int32)

    if window is None:
        kv_ix = lambda s, h, m, p_r, t_r: (
            t_r[s, jnp.minimum(m, (p_r[s] + C - 1) // page)], h, 0, 0)
    else:
        w = int(window)
        kv_ix = lambda s, h, m, p_r, t_r: (
            t_r[s, jnp.clip(m, jnp.maximum((p_r[s] - w + 1) // page, 0),
                            jnp.minimum((p_r[s] + C - 1) // page, M - 1))],
            h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, Hkv, M),
        in_specs=[
            pl.BlockSpec((1, 1, Rp, Dh), lambda s, h, m, p_r, t_r:
                         (s, h, 0, 0)),
            pl.BlockSpec((1, 1, page, Dh), kv_ix),
            pl.BlockSpec((1, 1, page, Dh), kv_ix),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Rp, Dh), lambda s, h, m, p_r, t_r:
                         (s, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Rp, _LANE), jnp.float32),
            pltpu.VMEM((Rp, _LANE), jnp.float32),
            pltpu.VMEM((Rp, Dh), jnp.float32),
        ],
    )
    (out,) = pl.pallas_call(
        functools.partial(_paged_chunk_kernel, Dh, page, C, window),
        out_shape=[jax.ShapeDtypeStruct((S, Hkv, Rp, Dh), jnp.float32)],
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_chunk",
    )(pos_arr, tbl, qf, kp, vp)
    return out[:, :, :R, :].reshape(S, Hkv, G, C, Dh)


# -- dispatchers --------------------------------------------------------------
#
# Off the TPU every call takes the reference path, which is also the bitwise
# CPU contract (tests use page 4/8 there). On the TPU the kernels read one
# page per block. Mosaic on the v5e compiled them, with parity to the
# references, for pages of 8 and 16 rows in f32 AND bf16 pools (Dh 64, 128
# and 256; the page is the block's full second-minor extent, so a bf16 page
# of 8 rows — half a packed tile — is padded, not refused). Any other page
# size is refused here: it never falls quietly to the gathered reference.
# One switch per call keeps the serving kernels free of backend conditionals.


def _use_pallas(page: int) -> bool:
    if not is_tpu_backend():
        return False
    if page % _SUBLANE:
        raise ValueError(
            f"page_size {page} is not a multiple of {_SUBLANE}: the paged "
            f"attention kernels cannot read such pages on the TPU")
    return True


def paged_decode_attention(q, kp, vp, table, pos, page: int, window=None):
    """Dispatcher: Pallas paged flash-decode on TPU (sublane-aligned
    pages), bitwise jnp reference elsewhere."""
    if _use_pallas(page):
        return paged_flash_decode(q, kp, vp, table, pos, page,
                                  window=window)
    return paged_decode_reference(q, kp, vp, table, pos, page, window)


def paged_decode_attention_lse(q, kp, vp, table, pos, page: int,
                               window=None):
    """Dispatcher for the lse-exposing paged decode attention (the
    sequence-parallel partial the mesh path logsumexp-merges)."""
    if _use_pallas(page):
        return paged_flash_decode_lse(q, kp, vp, table, pos, page,
                                      window=window)
    return paged_decode_reference_lse(q, kp, vp, table, pos, page, window)


def paged_chunk_attention(q, kp, vp, table, pos0, page: int, window=None):
    """Dispatcher for the multi-row (chunk/verify) paged attention."""
    if _use_pallas(page):
        return paged_flash_chunk(q, kp, vp, table, pos0, page,
                                 window=window)
    return paged_chunk_reference(q, kp, vp, table, pos0, page, window)
