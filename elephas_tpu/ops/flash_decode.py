"""Flash-decode: fused KV-cache attention for autoregressive inference.

One decode step attends a single query position per sequence against that
sequence's part of the cache — a bandwidth-bound op (every step re-reads
the live K and V from HBM). Naive lowering materializes the [B, Hkv, G, T]
score tensor in HBM twice (scores, probabilities); this kernel streams the
cache through VMEM in T-blocks with flash-style online softmax, touching
K/V once and never materializing probabilities off-chip.

**The walk.** The grid is the batch rows, one step a row. Inside a step
the kernel loops over the cache blocks (256 positions; the latent kernel
below walks the same way in blocks of up to 1,024) that row attends,
in ascending order, and over no others: the trip count comes from the
row's own ``pos`` (and the layer's window or ring), delivered by scalar
prefetch — :func:`kv_block_walk`, the one function the serving engine also
counts visits with. A visit is a block for ALL ``Hkv`` heads: K and V stay
in HBM and the kernel copies the ``[Hkv, 256, Dh]`` tiles itself into a
double buffer, the next visit's (or the next row's first) while this one
computes. So a batch whose rows are a quarter full costs a quarter of the
visits: a static grid over every block of the cache would step over the
dead ones at a fixed cost each even with their fetch skipped, three
quarters of its steps at a long horizon. Of a full layer's last block a
row's copy brings in the live rows alone, rounded up to whole packed
tiles (:func:`kv_copied_positions`); whole, it was half dead on average.

**What a visit multiplies.** The tiles go to the MXU in the cache's dtype,
as they lie in VMEM: a bf16 tile is never converted up. A bf16 x bf16
product is exact in the float32 accumulator, so the few-row operand alone
sets the passes (:func:`_small_times_tile`): a bf16 ``q`` is one, the
float32 probabilities ``p`` are split into three bf16 pieces that sum to
them exactly. Those are all the products a float32 ``HIGHEST`` matmul
makes of an upcast bf16 tile; the ones it makes besides multiply the
tile's zero low pieces. Scores, softmax and every sum stay float32, so
the output is the float32 one (the reference on the same arrays, to the
order of summation). A float32 cache is multiplied at ``HIGHEST``. Which
it is follows from the arrays' dtypes at trace time, nothing else.

Grouped-query attention is native: the cache carries ``Hkv`` heads and the
``G = H/Hkv`` query heads of a group share each K/V block from the same
VMEM visit. The decode position ``pos`` is a *traced* scalar or per-row
vector (it advances inside the generation ``lax.scan``).

Cache layout is ``[B, Hkv, T, Dh]`` (T on the sublane axis) so a visit's
tile is ``Hkv`` contiguous ``[BT, Dh]`` runs. Every entry also takes the
model's whole STACKED cache ``[L, B, Hkv, T, Dh]`` with a (traced)
``layer`` index: a second scalar-prefetch operand that the kernel's copies
put in front, so it reads layer ``l`` straight out of the buffer the
decode step carries and updates in place — no per-layer slice of the cache
is ever materialised.

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
import numpy as np

from .pallas_ops import _LANE, _pad_up, is_tpu_backend

_BLOCK_T = 256
_LATENT_BLOCK_T = 1024
_SUBLANE = 8
_NEG = -1e30
# rows a full layer's copy of a row's last block is rounded up to: one
# packed tile of a bf16 cache, two of a float32 one
_TAIL_GRANULE = 16


def _block_t(cache_len: int) -> int:
    """Positions a visit of the decode kernel covers."""
    return min(_BLOCK_T, _pad_up(int(cache_len), _SUBLANE))


def latent_block_t(cache_len: int) -> int:
    """Positions a visit of the LATENT decode kernel covers: the largest of
    :func:`_block_t`'s block and its doubles up to ``_LATENT_BLOCK_T`` that
    the cache is whole blocks of (1,024 for a cache of 8,192 rows, 512 for
    one of 4,608, the plain 256 for one of 768). The kernel, the model's
    ``decode_walks`` and through it the engine's visit counters all ask
    here (the comment above :func:`mla_decode_reference` says why a latent
    visit is wider)."""
    bt = _block_t(cache_len)
    while 2 * bt <= _LATENT_BLOCK_T and int(cache_len) % (2 * bt) == 0:
        bt *= 2
    return bt


def aligned_cache_length(length: int) -> int:
    """Smallest cache length >= ``length`` whose T axis the kernel can
    block without padding (pads in the decode hot loop would recopy the
    whole cache in HBM every step). Extra positions are masked by ``pos``."""
    return _pad_up(int(length), _block_t(length))


# -- reference (fallback / oracle) implementation ----------------------------


def decode_attention_reference(q, k, v, pos, window=None,
                               ring: bool = False, layer=None):
    """Grouped decode attention against a cache.

    ``q`` [B, Hkv, G, Dh]; ``k``/``v`` [B, Hkv, T, Dh], or the stacked
    ``[L, B, Hkv, T, Dh]`` with ``layer`` (int, may be traced) naming the
    layer to attend; ``pos`` scalar int
    or per-row ``[B]`` int (batched speculative decoding advances rows at
    different positions) — row b sees positions ``0..pos[b]`` inclusive,
    restricted to the last ``window`` of them under sliding-window
    attention. Returns [B, Hkv, G, Dh] float32, softmax in f32. One body
    serves this and the lse-exposing variant (same dedup rationale as the
    Pallas side).
    """
    return decode_attention_reference_lse(q, k, v, pos, window, ring,
                                          layer)[0]


# -- pallas kernel ------------------------------------------------------------


def flash_decode(q, k, v, pos, interpret: bool = False, window=None,
                 ring: bool = False, layer=None):
    """Fused decode attention (Pallas). Same contract as
    :func:`decode_attention_reference`; ``pos`` and ``layer`` may be traced
    scalars.

    One kernel serves both this and :func:`flash_decode_lse` — this entry
    discards the (tiny, lane-broadcast) lse output rather than keeping a
    second copy of the online-softmax kernel in sync."""
    return flash_decode_lse(q, k, v, pos, interpret=interpret,
                            window=window, ring=ring, layer=layer)[0]


def decode_attention(q, k, v, pos, window=None, ring: bool = False,
                     layer=None):
    """Dispatcher: Pallas flash-decode on TPU, jnp reference elsewhere."""
    if is_tpu_backend():
        return flash_decode(q, k, v, pos, window=window, ring=ring,
                            layer=layer)
    return decode_attention_reference(q, k, v, pos, window, ring, layer)


# -- the decode step's cache write ---------------------------------------------
#
# One new K row and one new V row per batch row and layer, written into the
# stacked cache the decode step carries. As a plain XLA scatter the per-row
# write made the TPU compiler re-lay the whole carried cache out around it
# (a whole-cache copy a layer); as a kernel whose outputs alias the cache
# operands it moves the one tile that holds the row and leaves the buffer's
# layout to the attention kernel's, so nothing of cache size is copied.


def cache_write_row_reference(k, v, k_new, v_new, layer, pos):
    """Write ``k_new``/``v_new`` ``[B, Hkv, Dh]`` — one position per batch
    row — into layer ``layer`` (int, may be traced) of the stacked caches
    ``k``/``v`` ``[L, B, Hkv, T, Dh]`` at time offset ``pos`` (scalar: one
    dynamic_update_slice; per-row ``[B]``: one scatter of ``B`` windows).
    Offsets clamp to the cache like ``dynamic_update_slice``'s. Returns the
    updated ``(k, v)``."""
    if jnp.ndim(pos) == 0:
        at = (layer, 0, 0, pos, 0)
        return (jax.lax.dynamic_update_slice(
                    k, k_new.astype(k.dtype)[None, :, :, None, :], at),
                jax.lax.dynamic_update_slice(
                    v, v_new.astype(v.dtype)[None, :, :, None, :], at))
    rows = jnp.arange(k_new.shape[0])
    put = dict(mode="clip", indices_are_sorted=True, unique_indices=True)
    return (k.at[layer, rows, :, pos, :].set(k_new, **put),
            v.at[layer, rows, :, pos, :].set(v_new, **put))


def _write_row_kernel(rows: int, pos_ref, layer_ref, *refs):
    """Replace row ``pos[b] mod rows`` of batch row ``b``'s ``[Hkv, rows,
    Dh]`` tile of each stack (the tile the index maps picked: the one that
    holds ``pos[b]``) and write the tile back over itself. ``refs``: the
    stacks' new rows, then their tiles, then the tiles' outputs."""
    from jax.experimental import pallas as pl

    del layer_ref
    n = len(refs) // 3
    r = pos_ref[pl.program_id(0)] % rows
    hit = jax.lax.broadcasted_iota(jnp.int32, refs[n].shape[1:], 1) == r
    for new_ref, old_ref, out_ref in zip(refs[:n], refs[n:2 * n],
                                         refs[2 * n:]):
        # through f32 (exact for bf16): the v5e's vector unit selects there
        out_ref[0] = jnp.where(
            hit, new_ref[0].astype(jnp.float32),
            old_ref[0].astype(jnp.float32)).astype(out_ref.dtype)


def _flash_write_rows(stacks, news, layer, pos, interpret: bool, name: str):
    """One new row a batch row into layer ``layer`` of each of ``stacks``
    (``[L, B, Hkv, T, Dh]``, all of one shape) from ``news`` (``[B, Hkv,
    Dh]``), as ONE Pallas kernel whose outputs ALIAS the stacks: per batch
    row it reads the one sublane tile of ``[Hkv, rows, Dh]`` that holds
    ``pos[b]``, replaces that row and writes the tile back, in the buffer
    it was given. ``layer`` and ``pos`` ride scalar prefetch, like
    :func:`flash_decode_lse`'s."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k = stacks[0]
    n = len(stacks)
    L, B, Hkv, T, Dh = k.shape
    # one packed sublane tile of the cache dtype (8 rows of f32, 16 of
    # bf16); a short cache not made of such tiles is one block, as it is
    # for the attention kernel (aligned_cache_length)
    rows = _SUBLANE * max(1, 4 // k.dtype.itemsize)
    if T % rows:
        rows = T
    pos_arr = jnp.clip(
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)), 0, T - 1)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    new_spec = pl.BlockSpec((1, Hkv, 1, Dh), lambda b, s, l: (b, 0, 0, 0))
    tile_spec = pl.BlockSpec((None, 1, Hkv, rows, Dh),
                             lambda b, s, l: (l[0], b, 0, s[b] // rows, 0))
    return tuple(pl.pallas_call(
        functools.partial(_write_row_kernel, rows),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in stacks],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[new_spec] * n + [tile_spec] * n,
            out_specs=[tile_spec] * n,
        ),
        # operands count from the two scalar-prefetch arrays and the new
        # rows: the first stack is operand 2 + n
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=interpret,
        name=name,
    )(pos_arr, layer_arr,
      *(new.astype(c.dtype)[:, :, None, :] for new, c in zip(news, stacks)),
      *stacks))


def flash_cache_write_row(k, v, k_new, v_new, layer, pos,
                          interpret: bool = False):
    """:func:`cache_write_row_reference` as the aliasing Pallas write
    (:func:`_flash_write_rows`) over the K and the V stack."""
    return _flash_write_rows((k, v), (k_new, v_new), layer, pos, interpret,
                             "kv_write_row")


def cache_write_row(k, v, k_new, v_new, layer, pos):
    """Dispatcher: the aliasing Pallas write on TPU, jnp reference
    elsewhere."""
    if is_tpu_backend():
        return flash_cache_write_row(k, v, k_new, v_new, layer, pos)
    return cache_write_row_reference(k, v, k_new, v_new, layer, pos)


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
                                   ring: bool = False, layer=None):
    """Like :func:`decode_attention_reference` but also returns
    ``lse [B, Hkv, G] f32`` — the log of the softmax denominator (shifted by
    nothing: ``logsumexp`` of the masked scaled scores). With ``layer`` the
    caches are the stacked ``[L, B, Hkv, T, Dh]`` and this attends
    ``k[layer]``, ``v[layer]``.

    ``ring=True`` (requires ``window``): the cache is a ROLLING buffer of
    ``Tc`` slots — slot ``s`` holds absolute position ``pos - ((pos - s)
    mod Tc)`` (writes land at ``p mod Tc``). A slot is visible iff its age
    ``(pos - s) mod Tc`` is ``< min(window, pos+1)`` — one formula that
    covers warm-up (ages past ``pos`` wrap high and mask out) and steady
    state (expired slots age out), for scalar and per-row positions alike.
    """
    if layer is not None:
        k = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
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


def kv_block_walk(pos, cache_len: int, window=None, ring: bool = False,
                  block=None):
    """How a decode kernel walks one row's cache of ``cache_len``
    positions, in blocks of ``block`` (default ``_block_t(cache_len)``,
    :func:`flash_decode_lse`'s; the latent kernel's is
    :func:`latent_block_t`): ``(first, walked, live)``. The kernel visits
    blocks ``first .. first + walked - 1``; ``live`` of the cache's
    blocks hold a key the row attends. ``pos`` is
    the row's position (it attends ``0 .. pos``, the last ``window`` of
    them under a window): a Python or NumPy integer (arrays too: the
    engine's counters sum the result over its rows) or, inside the kernel,
    a traced scalar. One function for both, so what the engine counts is
    what the kernel walks.

    Full layer: ``0 .. pos // bt``. Window on a horizon cache: from the
    block of ``pos - window + 1`` to the block of ``pos``, clipped to the
    cache (``pos`` may lie past its end: sharded decode). Ring: every
    block (the buffer is the window); live are the blocks of the newest
    ``min(window, pos + 1)`` slots, a run that ends at slot ``pos mod
    cache_len`` and may wrap."""
    xp = jnp if isinstance(pos, jax.Array) else np
    bt = _block_t(cache_len) if block is None else int(block)
    n_t = -(-int(cache_len) // bt)
    if ring:
        seen = xp.minimum(xp.minimum(int(window), pos + 1), cache_len)
        end = pos % cache_len // bt
        start = (pos - seen + 1) % cache_len // bt
        live = xp.where((pos - seen + 1) % cache_len <= pos % cache_len,
                        end - start + 1,
                        xp.minimum(end + 1 + n_t - start, n_t))
        return 0 * pos, 0 * pos + n_t, live
    last = xp.minimum(pos // bt, n_t - 1)
    # (never past ``last``: a window wholly beyond the cache end, which no
    # caller passes, still walks one block, all of it masked)
    first = (0 * pos if window is None else xp.minimum(
        xp.maximum((pos - int(window) + 1) // bt, 0), last))
    walked = last - first + 1
    return first, walked, walked


def _trims_tail(block: int, window, ring: bool, granule) -> bool:
    """Does a walk copy its last block's live rows only? Only a full
    layer's: its live run ends inside the last block (a window's starts
    inside the first, a ring's wraps), and only where the block is more
    than one whole ``granule`` of rows."""
    return (granule is not None and window is None and not ring
            and block % granule == 0 and block > granule)


def _copy_rows(live, block: int, granule: int):
    """Rows a trimmed copy of a block covers when its first ``live`` rows
    (``>= 1``) are live: ``live`` up to whole ``granule``s (a power of
    two), at most the block."""
    xp = jnp if isinstance(live, jax.Array) else np
    return xp.minimum((live + granule - 1) & -granule, block)


def kv_copied_positions(pos, cache_len: int, window=None, ring: bool = False,
                        block=None, granule=_TAIL_GRANULE):
    """Positions of one row's cache (``kv_block_walk``'s arguments) that
    :func:`flash_decode_lse`'s copies bring into VMEM for ``pos``: every
    walked block whole, but a full layer's last block only up to its
    live rows, rounded up to whole ``granule``s (:func:`_copy_rows`, which
    the kernel sizes its copies by). ``granule=None``, a latent kernel's
    walk, a ring's and a window's copy whole blocks. Like
    :func:`kv_block_walk`, for integers, NumPy arrays (the engine's
    counters) and traced scalars alike."""
    bt = _block_t(cache_len) if block is None else int(block)
    _, walked, _ = kv_block_walk(pos, cache_len, window, ring, bt)
    if not _trims_tail(bt, window, ring, granule):
        return walked * bt
    return (walked - 1) * bt + _copy_rows(pos - (walked - 1) * bt + 1, bt,
                                          granule)


class _CopyIf:
    """Async copies started, and waited for, only where ``when`` holds:
    both from the one :func:`_decode_kernel_lse` ``copies`` call, so the
    wait names the pieces the start issued."""

    def __init__(self, when, copies):
        self.when, self.copies = when, copies

    def start(self):
        from jax.experimental import pallas as pl

        @pl.when(self.when)
        def _():
            for c in self.copies:
                c.start()

    def wait(self):
        from jax.experimental import pallas as pl

        @pl.when(self.when)
        def _():
            for c in self.copies:
                c.wait()


def _small_times_tile(x, tile, axis: int):
    """``x`` ``[Hkv, rows, C]`` (a few rows a head: ``q``, or the
    probabilities ``p``) times the heads' cache tiles ``[Hkv, bt, Dh]``
    over the tiles' ``axis``, one product a head, to float32 and with no
    bit of a float32 product given up (the module docstring says why).

    A bf16 tile is multiplied as it lies in VMEM and ``x`` decides the
    passes: a bf16 ``x`` is one; a float32 ``x`` is the sum of three bf16
    pieces ``hi + mid + lo`` (8 + 8 + 8 mantissa bits, exact), stacked
    along the rows so the tile is the stationary operand once, and the
    three partial results are added. Any other tile (float32) keeps the
    package's rule for float32 inputs: ``Precision.HIGHEST``."""
    bf16 = jnp.bfloat16
    dims = (((2,), (axis,)), ((0,), (0,)))
    if tile.dtype != bf16:
        return jax.lax.dot_general(
            x.astype(jnp.float32), tile.astype(jnp.float32), dims,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
    if x.dtype == bf16:
        return jax.lax.dot_general(x, tile, dims,
                                   preferred_element_type=jnp.float32)
    heads, rows, cols = x.shape
    x = x.astype(jnp.float32)
    mid = x - x.astype(bf16).astype(jnp.float32)
    lo = mid - mid.astype(bf16).astype(jnp.float32)
    # stacked in float32 (whole 8-row tiles) and rounded once: the rows of
    # ``x`` round to hi, those of the remainders to mid and lo; bf16 packs
    # 16 rows a tile, so the stack is padded to whole ones
    stack = [x, mid, lo]
    pad = _pad_up(3 * rows, 2 * _SUBLANE) - 3 * rows
    if pad:
        stack.append(jnp.zeros((heads, pad, cols), jnp.float32))
    out = jax.lax.dot_general(
        jnp.concatenate(stack, axis=1).astype(bf16), tile, dims,
        preferred_element_type=jnp.float32)
    return (out[:, :rows] + out[:, rows:2 * rows]
            + out[:, 2 * rows:3 * rows])


def _attend_block(scale: float, q_ref, k_ref, v_ref, keep, m_s, l_s, acc_s):
    """One visit's arithmetic: every KV head's ``[bt, Dh]`` tile of K and V
    against its group's queries, folded into the heads' running softmax
    (``m_s``/``l_s`` lane-broadcast, ``acc_s``). ``keep`` ``[Gp, bt]`` is
    the visibility mask of the block's positions, the same for all heads;
    ``scale`` multiplies the float32 scores. (The latent kernel hands the
    same buffer twice: ``v_ref`` a window of ``k_ref``'s columns.)

    Both products are :func:`_small_times_tile`: the tiles are multiplied
    in the cache's dtype, ``q`` in the dtype it arrives in, and the scores,
    the softmax, ``p`` and every accumulation are float32, so the result
    is the float32 one whatever the cache holds. The heads are one batched
    product and one softmax over ``[Hkv, Gp, bt]``, not ``Hkv`` chains one
    after the other: with the tiles left as stored the chains, not the
    MXU, set a visit's pace, and side by side they hide behind the next
    visit's copy."""
    gp = keep.shape[0]
    # (a bf16 ``q`` block carries 16 rows a head, a packed tile: the scores
    # of the first ``Gp`` are the group's)
    s = _small_times_tile(q_ref[0], k_ref[...], 2)[:, :gp] * scale
    s = jnp.where(keep[None], s, _NEG)
    m_prev = m_s[:, :, :1]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = alpha * acc_s[...] + _small_times_tile(p, v_ref[...], 1)
    m_s[...] = jnp.broadcast_to(m_cur, m_s.shape)


def _reset_softmax(m_s, l_s, acc_s):
    """A row's running softmax before its first block."""
    m_s[:] = jnp.full_like(m_s, _NEG)
    l_s[:] = jnp.zeros_like(l_s)
    acc_s[:] = jnp.zeros_like(acc_s)


def _walk_row(b, last_row, pos_ref, t_live: int, window, ring: bool,
              copies, slot_s, reset, attend, block=None):
    """Grid step ``b``'s walk over the cache blocks its row attends
    (:func:`kv_block_walk` of ``pos_ref[b]``, in blocks of ``block``),
    shared by the decode kernels. ``copies(row, t, slot)`` gives the async
    copies of block ``t`` of batch row ``row`` into buffer ``slot`` of a
    double buffer: a visit's
    tiles are copied while the visit before computes out of the other
    buffer. The copy of a row's FIRST block is started by the row before
    it (row 0 starts its own), so only the very first copy of a call is
    waited for idle; ``slot_s`` carries which buffer that block went to
    from one grid step to the next. ``reset()`` clears the row's running
    softmax; ``attend(t, slot, pos)`` folds block ``t`` into it."""
    from jax.experimental import pallas as pl

    pos = pos_ref[b]
    first, walked, _ = kv_block_walk(pos, t_live, window, ring, block)

    @pl.when(b == 0)
    def _first_copy():
        slot_s[0] = 0
        for c in copies(0, first, 0):
            c.start()

    slot0 = slot_s[0]
    reset()

    def visit(i, carry):
        t = first + i
        slot = (slot0 + i) % 2
        more = i + 1 < walked

        @pl.when(jnp.logical_or(more, b < last_row))
        def _next_copy():
            row = jnp.where(more, b, jnp.minimum(b + 1, last_row))
            nxt = jnp.where(more, t + 1, kv_block_walk(
                pos_ref[row], t_live, window, ring, block)[0])
            for c in copies(row, nxt, 1 - slot):
                c.start()

        for c in copies(b, t, slot):
            c.wait()
        attend(t, slot, pos)
        return carry

    jax.lax.fori_loop(0, walked, visit, None)
    slot_s[0] = (slot0 + walked) % 2


def _decode_kernel_lse(d_true: int, t_live: int, window, ring: bool,
                       granule, pos_ref, layer_ref, q_ref, k_hbm, v_hbm,
                       o_ref, lse_ref, k_buf, v_buf, sem, slot_s,
                       m_s, l_s, acc_s):
    """Online-softmax decode kernel with an lse output (lane-broadcast):
    grid step ``b`` is batch row ``b``, and inside it a loop over the
    blocks :func:`kv_block_walk` gives for ``pos[b]`` (per-row: batched
    speculative decoding has rows at different positions), all KV heads of
    a block a visit (:func:`_walk_row`).

    K and V stay in HBM (``[L, B, Hkv, T, Dh]``); a visit's ``[Hkv, bt,
    Dh]`` tiles are copied into one of two VMEM buffers while the visit
    before computes out of the other. On a full layer the copy of a row's
    last block stops after its live rows, rounded up to whole ``granule``s
    (:func:`kv_copied_positions`), issued as static pieces of 128, 64, ...,
    ``granule`` rows under ``pl.when`` by the row's ``pos`` (on the v5e
    quicker than one copy of the rounded size chosen out of ``bt /
    granule``). The rows it leaves hold an earlier visit's copy (the V
    buffers are zeroed at the call's first row), finite, masked, and
    multiplied by a ``p`` of exactly 0, so the output is the whole
    block's bit for bit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    last_row = pl.num_programs(0) - 1
    bt = k_buf.shape[2]
    layer = layer_ref[0]
    trim = _trims_tail(bt, window, ring, granule)

    def copies(row, t, slot):
        at = pl.ds(pl.multiple_of(t * bt, bt), bt)
        whole = (pltpu.make_async_copy(k_hbm.at[layer, row, :, at, :],
                                       k_buf.at[slot], sem.at[0, slot]),
                 pltpu.make_async_copy(v_hbm.at[layer, row, :, at, :],
                                       v_buf.at[slot], sem.at[1, slot]))
        if not trim:
            return whole
        # the block whole, or its first n rows as the pieces of n's binary
        # form, each where it lies: at most one piece of each size
        n = _copy_rows(pos_ref[row] - t * bt + 1, bt, granule)
        out = [_CopyIf(n == bt, whole)]
        rows = granule
        while rows < bt:
            off = n & -(2 * rows)
            at = pl.ds(pl.multiple_of(t * bt + off, rows), rows)
            to = pl.ds(pl.multiple_of(off, rows), rows)
            out.append(_CopyIf(
                jnp.logical_and(n < bt, (n & rows) != 0),
                (pltpu.make_async_copy(k_hbm.at[layer, row, :, at, :],
                                       k_buf.at[slot, :, to, :],
                                       sem.at[0, slot]),
                 pltpu.make_async_copy(v_hbm.at[layer, row, :, at, :],
                                       v_buf.at[slot, :, to, :],
                                       sem.at[1, slot]))))
            rows *= 2
        return tuple(out)

    if trim:
        @pl.when(b == 0)
        def _zero_values():
            v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

    def attend(t, slot, pos):
        j = t * bt + jax.lax.broadcasted_iota(
            jnp.int32, (acc_s.shape[1], bt), 1)
        if ring:
            # slot age under the rolling buffer (see the reference impl)
            keep = jnp.mod(pos - j, t_live) < jnp.minimum(int(window),
                                                          pos + 1)
            keep = jnp.logical_and(keep, j < t_live)  # alignment padding
        else:
            keep = j <= pos
            if window is not None:
                keep = jnp.logical_and(keep, j > pos - int(window))
                # windowed callers may pass pos PAST the cache end (a
                # sequence-sharded rank whose slice is partially expired
                # keeps global window arithmetic that way) — alignment
                # padding rows must then be masked explicitly
                keep = jnp.logical_and(keep, j < t_live)
        _attend_block(d_true ** -0.5, q_ref, k_buf.at[slot], v_buf.at[slot],
                      keep, m_s, l_s, acc_s)

    _walk_row(b, last_row, pos_ref, t_live, window, ring, copies, slot_s,
              functools.partial(_reset_softmax, m_s, l_s, acc_s), attend)
    o_ref[0] = (acc_s[:] / l_s[:, :, :1]).astype(o_ref.dtype)
    lse_ref[0] = m_s[:] + jnp.log(l_s[:])


def flash_decode_lse(q, k, v, pos, interpret: bool = False, window=None,
                     ring: bool = False, layer=None):
    """Fused decode attention returning ``(out, lse)``; ``pos`` (scalar or
    per-row ``[B]``) must be ``>= 0`` (a rank with nothing visible clamps
    pos and overrides its lse to −inf outside the kernel — see
    models/sharded_generate.py).

    ``k``/``v`` are one layer's ``[B, Hkv, T, Dh]`` cache (``layer=None``),
    or the whole stacked ``[L, B, Hkv, T, Dh]`` cache with ``layer`` (int,
    may be traced) the layer to attend. The layer index rides scalar
    prefetch next to ``pos`` and the kernel's own copies address ``[layer,
    row]`` of the buffer it is handed, which stays in HBM whole:
    ``TransformerLM.decode_step`` carries the stack through its layer scan
    and never slices it. The one-layer form is the same call over a
    one-layer stack (a reshape, layer 0)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if ring and window is None:
        raise ValueError("ring cache attention requires a window")
    if layer is None:
        k, v, layer = k[None], v[None], 0
    B, Hkv, G, Dh = q.shape
    T = k.shape[3]
    Gp, Dp = _pad_up(G, _SUBLANE), _pad_up(Dh, _LANE)
    bt = _block_t(T)
    Tp = _pad_up(T, bt)
    if not q.dtype == k.dtype == jnp.bfloat16:
        q = q.astype(jnp.float32)
    # q's block is whole packed tiles of its dtype: 16 rows of bf16
    Gq = _pad_up(G, _SUBLANE * 4 // q.dtype.itemsize)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Gq - G), (0, Dp - Dh)))
    if (Tp, Dp) != (T, Dh):
        # never in a served model's decode loop: init_cache aligns T, and
        # a head size is whole lanes (the kernel's copies slice whole
        # tiles; zero columns change no product)
        pad = ((0, 0),) * 3 + ((0, Tp - T), (0, Dp - Dh))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    # (clamped: every row walks at least its block 0, whatever it is handed)
    pos_arr = jnp.maximum(
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)), 0)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    row_ix = lambda b, s, l: (b, 0, 0, 0)
    tiles = 2 * Hkv * bt * Dp * (k.dtype.itemsize + v.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hkv, Gq, Dp), row_ix),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, Hkv, Gp, Dp), row_ix),
            pl.BlockSpec((1, Hkv, Gp, _LANE), row_ix),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, Hkv, bt, Dp), k.dtype),
            pltpu.VMEM((2, Hkv, bt, Dp), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((Hkv, Gp, _LANE), jnp.float32),
            pltpu.VMEM((Hkv, Gp, _LANE), jnp.float32),
            pltpu.VMEM((Hkv, Gp, Dp), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_decode_kernel_lse, Dh, T, window, ring,
                          _TAIL_GRANULE),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Gp, Dp), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, Gp, _LANE), jnp.float32),
        ],
        grid_spec=grid_spec,
        # rows in order: each starts the next one's first copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 2 * tiles)),
        interpret=interpret,
        name="flash_decode",
    )(pos_arr, layer_arr, qp, k, v)
    return out[:, :, :G, :Dh], lse[:, :, :G, 0]


def decode_attention_lse(q, k, v, pos, window=None, ring: bool = False,
                         layer=None):
    """Dispatcher for the lse-exposing decode attention."""
    if is_tpu_backend():
        return flash_decode_lse(q, k, v, pos, window=window, ring=ring,
                                layer=layer)
    return decode_attention_reference_lse(q, k, v, pos, window, ring, layer)


# -- latent (MLA) decode: one stack of rows that are key and value at once ---
#
# A latent-attention model caches ONE row a position a layer: the
# compressed ``c_kv`` (``rank`` numbers) and, after it, the one rotary key
# all heads share. With ``W_UK`` absorbed into the query and ``W_UV`` into
# the output (``TransformerLM.decode_step``), every head attends that row
# itself: the score is the query against ALL its columns, the value its
# first ``rank`` columns. So the cache is a stack of keys alone, ``[L, B,
# 1, T, Dc]`` (one "KV head"; ``Dc`` the row padded to whole lanes with
# zero columns, which change no product), and a visit copies ONE ``[bt,
# Dc]`` tile for both products. The walk, the double buffer and the
# arithmetic are :func:`flash_decode_lse`'s (:func:`_walk_row`,
# :func:`_attend_block`): what differs is one copy a visit where that
# kernel makes two, ``H`` query rows a sequence where it has ``G``, and
# the block.
#
# **A latent visit covers up to 1,024 positions** (:func:`latent_block_t`),
# four of ``flash_decode``'s blocks. That kernel's visit costs what its
# copy costs; this one's does not: 64 query rows against one shared tile
# make a visit two dependent passes through the MXU (scores, then the three
# exact pieces of ``p`` against the values) with the softmax between them,
# and on the chip that chain costs about 0.4 us a visit whatever the block,
# as much again as the products of 256 positions (0.42 us, themselves at
# the MXU's and the copy's pace). So the fixed part is paid once per 1,024
# positions: 0.84 -> 0.51 us per 256 (PERF.md, PR 33). The price is the
# over-read: a row's last visit is a whole block, half of it dead on
# average, masked as before.


def mla_decode_reference(q, c, pos, layer=None, rank=None, scale=None):
    """Absorbed latent decode attention against the latent cache.

    ``q`` ``[B, H, Dc]`` (per head: the query in latent space, then its
    rotary part, then zeros up to ``Dc``); ``c`` ``[B, 1, T, Dc]``, or the
    stacked ``[L, B, 1, T, Dc]`` with ``layer`` (int, may be traced);
    ``pos`` scalar or per-row ``[B]``: row ``b`` sees positions
    ``0..pos[b]``. Scores are ``scale * q . row`` over all ``Dc`` columns,
    the values a row's first ``rank`` columns. Returns ``[B, H, rank]``
    float32, softmax in float32."""
    if layer is not None:
        c = jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
    rows = c[:, 0]                                         # [B, T, Dc]
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    scores = jnp.einsum(
        "bhd,btd->bht", q, rows, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST) * scale
    seen = (jnp.arange(rows.shape[1])[None, None, :]
            <= jnp.asarray(pos).reshape(-1, 1, 1))
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum(
        "bht,btr->bhr", probs, rows[:, :, :rank],
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _mla_kernel(scale: float, t_live: int, pos_ref, layer_ref, q_ref,
                c_hbm, o_ref, c_buf, sem, slot_s, m_s, l_s, acc_s):
    """Grid step ``b`` is batch row ``b``: its ``H`` latent queries against
    the blocks of ``c_hbm`` ``[L, B, 1, T, Dc]`` that :func:`kv_block_walk`
    gives for ``pos[b]``, ONE ``[1, bt, Dc]`` tile a visit
    (:func:`_walk_row`), used as keys whole and as values through its
    first ``rank`` columns (``acc_s``'s width). ``bt`` is the buffer's:
    :func:`latent_block_t` of the cache."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    last_row = pl.num_programs(0) - 1
    bt = c_buf.shape[2]
    rank = acc_s.shape[2]
    layer = layer_ref[0]

    def copies(row, t, slot):
        at = pl.ds(pl.multiple_of(t * bt, bt), bt)
        return (pltpu.make_async_copy(c_hbm.at[layer, row, :, at, :],
                                      c_buf.at[slot], sem.at[slot]),)

    def attend(t, slot, pos):
        j = t * bt + jax.lax.broadcasted_iota(
            jnp.int32, (acc_s.shape[1], bt), 1)
        _attend_block(scale, q_ref, c_buf.at[slot],
                      c_buf.at[slot, :, :, pl.ds(0, rank)], j <= pos,
                      m_s, l_s, acc_s)

    _walk_row(b, last_row, pos_ref, t_live, None, False, copies, slot_s,
              functools.partial(_reset_softmax, m_s, l_s, acc_s), attend,
              block=bt)
    o_ref[0] = (acc_s[:] / l_s[:, :, :1]).astype(o_ref.dtype)


def mla_decode(q, c, pos, layer=None, rank=None, scale=None,
               interpret: bool = False):
    """:func:`mla_decode_reference` as a Pallas kernel (``name=
    "mla_decode"``); ``pos`` (``>= 0``) and ``layer`` may be traced. The
    stack stays in HBM whole and the kernel's own copies address ``[layer,
    row]`` of it, as :func:`flash_decode_lse`'s do, a block of
    :func:`latent_block_t` positions a visit. ``Dc`` and ``rank`` are
    whole lanes and ``T`` whole blocks (``init_cache`` sees to both);
    a bf16 ``q`` beside a bf16 cache is multiplied as it arrives, the
    float32 probabilities are split (:func:`_small_times_tile`), and no
    tile is converted up."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if layer is None:
        c, layer = c[None], 0
    B, H, Dc = q.shape
    T = c.shape[3]
    rank = Dc if rank is None else int(rank)
    bt = latent_block_t(T)
    if Dc % _LANE or rank % _LANE or T % bt or c.shape[2:] != (1, T, Dc):
        raise ValueError(
            f"mla_decode: rows of {Dc} columns, {rank} of them values, in a "
            f"cache {c.shape}: the row and its value part must be whole "
            f"{_LANE}-column lanes, the cache one KV head of whole "
            f"{bt}-position blocks")
    scale = float(Dc ** -0.5 if scale is None else scale)
    if not q.dtype == c.dtype == jnp.bfloat16:
        q = q.astype(jnp.float32)
    Hp = _pad_up(H, _SUBLANE)
    # q's block is whole packed tiles of its dtype: 16 rows of bf16
    Hq = _pad_up(H, _SUBLANE * 4 // q.dtype.itemsize)
    qp = jnp.pad(q, ((0, 0), (0, Hq - H), (0, 0)))[:, None]
    pos_arr = jnp.maximum(
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)), 0)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    row_ix = lambda b, s, l: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, Hq, Dc), row_ix),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec((1, 1, Hp, rank), row_ix)],
        scratch_shapes=[
            pltpu.VMEM((2, 1, bt, Dc), c.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((1, Hp, _LANE), jnp.float32),
            pltpu.VMEM((1, Hp, _LANE), jnp.float32),
            pltpu.VMEM((1, Hp, rank), jnp.float32),
        ],
    )
    (out,) = pl.pallas_call(
        functools.partial(_mla_kernel, scale, T),
        out_shape=[jax.ShapeDtypeStruct((B, 1, Hp, rank), jnp.float32)],
        grid_spec=grid_spec,
        # rows in order: each starts the next one's first copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="mla_decode",
    )(pos_arr, layer_arr, qp, c)
    return out[:, 0, :H]


def latent_decode_attention(q, c, pos, layer=None, rank=None, scale=None):
    """Dispatcher: the ``mla_decode`` Pallas kernel on TPU, the jnp
    reference elsewhere."""
    if is_tpu_backend():
        return mla_decode(q, c, pos, layer=layer, rank=rank, scale=scale)
    return mla_decode_reference(q, c, pos, layer, rank, scale)


def latent_write_row_reference(c, new, layer, pos):
    """Write ``new`` ``[B, Dc]``, one latent row per batch row, into layer
    ``layer`` of the stacked latent cache ``c`` ``[L, B, 1, T, Dc]`` at
    time offset ``pos`` (scalar or per-row ``[B]``); offsets clamp like
    ``dynamic_update_slice``'s. Returns the updated stack."""
    new = new.astype(c.dtype)
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice(
            c, new[None, :, None, None, :], (layer, 0, 0, pos, 0))
    rows = jnp.arange(new.shape[0])
    return c.at[layer, rows, 0, pos, :].set(
        new, mode="clip", indices_are_sorted=True, unique_indices=True)


def flash_latent_write_row(c, new, layer, pos, interpret: bool = False):
    """:func:`latent_write_row_reference` as the aliasing Pallas write
    (:func:`_flash_write_rows`) over the one latent stack."""
    return _flash_write_rows((c,), (new[:, None, :],), layer, pos,
                             interpret, "latent_write_row")[0]


def latent_write_row(c, new, layer, pos):
    """Dispatcher: the aliasing Pallas write on TPU, jnp reference
    elsewhere."""
    if is_tpu_backend():
        return flash_latent_write_row(c, new, layer, pos)
    return latent_write_row_reference(c, new, layer, pos)
