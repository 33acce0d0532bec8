"""Pallas flash-attention TRAINING kernels (forward + backward) for TPU.

The pure-JAX blockwise implementation in ``flash_attention.py`` is exact but
HBM-bound on TPU: XLA materializes every ``[T, block]`` score tile to HBM
(measured ~14 ms/layer at B8·H16·T2048·Dh64 — ~10× the matmul-roofline
time), because a ``lax.scan`` body is not fused into a single attention
kernel. These kernels keep each score tile in VMEM for its whole life:
one HBM read of Q/K/V per tile pair, no score/probability traffic at all.

Layout convention — scores are computed K-MAJOR (``s^T: [bk, bq]``): the
online-softmax statistics (running max ``m``, denominator ``l``, and the
saved ``lse``) are then indexed by *query* position along the LANE axis,
where cross-block broadcasts (``s^T - m``) are native sublane broadcasts.
The output accumulator is kept transposed (``[Dh, bq]``) for the same
reason; it is flipped once per query block at epilogue. This avoids every
lane→sublane relayout in the hot loop.

Grouped-query attention is native: K/V keep their ``Hkv`` heads and the
BlockSpec index maps divide the query-head index (``h // G``) — the
repeated heads are never materialized. Causality skips work at two levels:
invisible tile pairs are skipped by ``pl.when`` AND their K/V DMAs never
issue (the index map clamps to the last visible tile, the same trick as
``flash_decode.py``).

Backward follows FlashAttention-2: the forward saves only
``lse = m + log l`` (``[B, H, T]``); ``Δ = Σ_d dO·O`` is precomputed in
XLA (one fused elementwise+reduce). One pass (``flash_bwd_dkv``, KV tile
outer, Q tile inner) computes each visible score tile, its ``exp`` and
dPᵀ once: ``dk``/``dv`` accumulate over the Q tiles, and ``dq`` over the
KV tiles in a ``[Tq, Dh]`` f32 VMEM scratch that lives for the whole
``(b, h)`` sweep, each query tile stored at its last visible KV tile.
Where that scratch and the resident dq block pass ``_ONE_PASS_VMEM``
(8,192 bf16 positions at head size 128), ``dq`` gets a kernel of its own
(``flash_bwd_dq``) that recomputes the scores. Per-query-head ``dk``/``dv``
partials are summed across each GQA group outside.

No reference (b13n3rd/elephas) analog: the reference has no attention ops
at all (SURVEY.md §2) — this is TPU-first infrastructure for the LM family.
Used via ``flash_attention`` (``flash_attention.py``), which routes here on
TPU and to the scan implementation elsewhere; tests run these kernels in
``interpret=True`` mode against the dense oracle, gradients included.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_ops import _pad_up

_NEG = -1e30
_BQ = 512
_BK = 512
# VMEM the one-pass backward may add to the dk/dv kernel for dq: its f32
# [Tq, Dh] scratch and the double-buffered [Tq, Dh] dq output block. The
# kernel asks for that much scoped VMEM beyond the compiler's 16 MiB
# default, within which the dk/dv kernel alone compiles.
_ONE_PASS_VMEM = 8 << 20
_ONE_PASS_VMEM_LIMIT = (16 << 20) + _ONE_PASS_VMEM


def _one_pass_bwd(Tq: int, Dh: int, dtype) -> bool:
    """Does dq of a whole ``(b, h)`` sweep fit beside dk/dv? Then one
    kernel makes all three gradients; longer sequences fall back to the
    separate dq kernel."""
    return Tq * Dh * (4 + 2 * jnp.dtype(dtype).itemsize) <= _ONE_PASS_VMEM


def _prec(*refs):
    """f32 inputs get HIGHEST (true f32 products — the package-wide rule,
    see flash_attention.py); bf16 inputs are exact on the MXU either way."""
    import jax
    if any(r.dtype == jnp.float32 for r in refs):
        return jax.lax.Precision.HIGHEST
    return None


def _visible(causal: bool, i, j, bq: int, bk: int, window=None):
    """May query tile ``i`` see any of KV tile ``j``? (causal only; with a
    sliding ``window``, tiles wholly below every query's window are skipped
    too — the compute saving that makes long-context SWA O(T·window))."""
    if not causal:
        return True
    vis = j * bk <= i * bq + bq - 1
    if window is not None:
        vis = jnp.logical_and(
            vis, j * bk + bk - 1 >= i * bq - (int(window) - 1))
    return vis


def _mask_t(sT, causal: bool, i, j, bq: int, bk: int, t_true: int,
            window=None):
    """Causal (+ sliding-window) + length masking on a k-major ``[bk, bq]``
    score tile.

    Length masks apply only when T was padded up to the tile size. Padded
    *query* rows must be masked too (not just sliced off after): backward
    folds every row's ``p^T`` into dk/dv, so an unmasked garbage row would
    corrupt real gradients.
    """
    keep = None
    if causal:
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, sT.shape, 0)
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, sT.shape, 1)
        keep = kpos <= qpos
        if window is not None:
            keep &= kpos > qpos - int(window)
    if t_true % bk:
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, sT.shape, 0)
        m = kpos < t_true
        keep = m if keep is None else keep & m
    if t_true % bq:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, sT.shape, 1)
        m = qpos < t_true
        keep = m if keep is None else keep & m
    return sT if keep is None else jnp.where(keep, sT, _NEG)


# -- forward ------------------------------------------------------------------


def _roll_half(x):
    """Swap the two lane-halves: ``[x1, x2] → [x2, x1]`` (RoPE helper)."""
    h = x.shape[-1] // 2
    return jnp.concatenate([x[..., h:], x[..., :h]], axis=-1)


def _rot(x, c2, s2, neg: bool = False):
    """Half-split RoPE as ``x·C2 + roll(x)·S2`` with ``C2 = [cos|cos]``,
    ``S2 = [−sin|sin]`` (both [tiles, Dh] f32). ``neg=True`` applies the
    INVERSE rotation (derotation — the transform is orthogonal), used to
    map the backward kernels' d(q_rot)/d(k_rot) back to dq/dk. Rotation in
    f32, result in ``x``'s dtype (same contract as the jnp `_rope_rotate`).
    """
    xf = x.astype(jnp.float32)
    s2 = -s2 if neg else s2
    return (xf * c2 + _roll_half(xf) * s2).astype(x.dtype)


def _fwd_kernel(causal: bool, bq: int, bk: int, t_true: int, scale: float,
                rope: bool, window, *refs):
    from jax.experimental import pallas as pl

    if rope:
        (q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref,
         o_ref, lse_ref, m_s, l_s, acc_s, qr_s) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs

    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)
        if rope:
            # q is invariant across the KV sweep: rotate ONCE per window
            qr_s[:] = _rot(q_ref[0, 0].astype(jnp.float32),
                           cq_ref[0], sq_ref[0])

    @pl.when(_visible(causal, i, j, bq, bk, window))
    def _compute():
        if rope:
            q = qr_s[:].astype(q_ref.dtype)
            k = _rot(k_ref[0, 0], ck_ref[0], sk_ref[0])
        else:
            q = q_ref[0, 0]                  # [bq, Dh]
            k = k_ref[0, 0]                  # [bk, Dh]
        prec = _prec(q_ref, k_ref)
        sT = jax.lax.dot_general(            # k-major scores [bk, bq]
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ) * scale
        sT = _mask_t(sT, causal, i, j, bq, bk, t_true, window)
        m_prev = m_s[:1]                     # [1, bq]
        m_cur = jnp.maximum(m_prev, jnp.max(sT, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)      # [1, bq]
        p = jnp.exp(sT - m_cur)              # [bk, bq] f32
        l_s[:1] = alpha * l_s[:1] + jnp.sum(p, axis=0, keepdims=True)
        acc_s[:] = alpha * acc_s[:] + jax.lax.dot_general(
            v_ref[0, 0], p.astype(v_ref.dtype),  # [Dh, bq] += v^T @ p
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        m_s[:1] = m_cur

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_s[:1], 1e-30)      # [1, bq]
        o_ref[0, 0] = jnp.transpose(acc_s[:] / l).astype(o_ref.dtype)
        # lse is stored [B, H, 8, T] (T on lanes): the 8 sublane copies are
        # a free broadcast here and let every consumer read a lane-major
        # [1, bq] row without relayout (TPU blocks need sublane dims % 8).
        lse_ref[0, 0] = jnp.broadcast_to(m_s[:1] + jnp.log(l),
                                         lse_ref[0, 0].shape)


def _pad_t(a, Tp, T):
    return a if Tp == T else jnp.pad(
        a, ((0, 0),) * (a.ndim - 2) + ((0, Tp - T), (0, 0))
    )


def _kv_clamp(bq: int, bk: int, window):
    """KV-tile index clamp for query tile ``i``: invisible tiles (future
    ones, and — under a sliding window — wholly-expired ones) are never
    DMA'd; their index maps to the nearest visible tile and ``pl.when``
    skips the compute."""
    last = lambda i: (i * bq + bq - 1) // bk
    if window is None:
        return lambda i, j: jnp.minimum(j, last(i))
    first = lambda i: jnp.maximum((i * bq - (int(window) - 1)) // bk, 0)
    return lambda i, j: jnp.clip(j, first(i), last(i))


def _q_clamp(bq: int, bk: int, window):
    """Query-tile index clamp for KV tile ``j`` (the dkv kernel's inner
    axis): clamp early (pre-causal) tiles up, and — under a window —
    too-late tiles down to the last one whose queries still see tile j."""
    lo = lambda j: (j * bk) // bq
    if window is None:
        return lambda j, i: jnp.maximum(i, lo(j))
    hi = lambda j: ((j + 1) * bk + int(window) - 2) // bq
    return lambda j, i: jnp.clip(i, lo(j), hi(j))


def _flash_fwd_tpu(q, k, v, causal, bq, bk, interpret, rope=None,
                   window=None):
    """``q`` [B, H, T, Dh]; ``k``/``v`` [B, Hkv, T, Dh] → (o, lse).

    ``rope=(c2, s2)`` ([B, T, Dh] f32, the duplicated half-split tables)
    fuses the rotary embedding of q and k into the kernel — the rotated
    tensors never exist in HBM. ``window`` = sliding-window attention
    (causal only): query ``t`` sees keys ``(t-window, t]``.
    """
    if window is not None and not causal:
        # single chokepoint for every public entry (tpu/with_lse/rope):
        # silently ignoring the window would return full bidirectional
        # attention for a caller who asked for a sliding one
        raise ValueError("window requires causal attention")
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, Dh = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    bq, bk = min(bq, _pad_up(T, 8)), min(bk, _pad_up(T, 8))
    Tq, Tk = _pad_up(T, bq), _pad_up(T, bk)
    if Tq != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tq - T), (0, 0)))
    if Tk != T:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Tk - T), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Tk - T), (0, 0)))
    nq, nk = Tq // bq, Tk // bk
    scale = Dh ** -0.5

    # Invisible KV tiles are never DMA'd: clamp their index into the
    # visible range for this query tile (the compute is pl.when-skipped).
    if causal:
        cl = _kv_clamp(bq, bk, window)
        kv_ix = lambda b, h, i, j: (b, h // G, cl(i, j), 0)
        rk_ix = lambda b, h, i, j: (b, cl(i, j), 0)
    else:
        kv_ix = lambda b, h, i, j: (b, h // G, j, 0)
        rk_ix = lambda b, h, i, j: (b, j, 0)
    rq_ix = lambda b, h, i, j: (b, i, 0)

    in_specs = [
        pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, Dh), kv_ix),
        pl.BlockSpec((1, 1, bk, Dh), kv_ix),
    ]
    inputs = [q, k, v]
    if rope is not None:
        c2, s2 = (_pad_t(t, max(Tq, Tk), T) for t in rope)
        in_specs += [pl.BlockSpec((1, bq, Dh), rq_ix),
                     pl.BlockSpec((1, bq, Dh), rq_ix),
                     pl.BlockSpec((1, bk, Dh), rk_ix),
                     pl.BlockSpec((1, bk, Dh), rk_ix)]
        inputs += [c2, s2, c2, s2]

    grid = (B, H, nq, nk)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal, bq, bk, T, scale,
                          rope is not None, window),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 8, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, 8, Tq), jnp.float32),
        ],
        name="flash_fwd",
        scratch_shapes=[
            pltpu.VMEM((8, bq), jnp.float32),    # running max (row 0 live)
            pltpu.VMEM((8, bq), jnp.float32),    # running denominator
            pltpu.VMEM((Dh, bq), jnp.float32),   # transposed accumulator
        ] + ([pltpu.VMEM((bq, Dh), jnp.float32)]  # rotated-q (per window)
             if rope is not None else []),
        interpret=interpret,
    )(*inputs)
    return o[:, :, :T], lse[:, :, :, :T]


# -- backward -----------------------------------------------------------------


def _dq_kernel(causal: bool, bq: int, bk: int, t_true: int, scale: float,
               rope: bool, window, *refs):
    from jax.experimental import pallas as pl

    if rope:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
         cq_ref, sq_ref, ck_ref, sk_ref, dq_ref, dq_s, qr_s) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
         dq_ref, dq_s) = refs

    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)
        if rope:
            qr_s[:] = _rot(q_ref[0, 0].astype(jnp.float32),
                           cq_ref[0], sq_ref[0])

    @pl.when(_visible(causal, i, j, bq, bk, window))
    def _compute():
        if rope:
            q = qr_s[:].astype(q_ref.dtype)
            k = _rot(k_ref[0, 0], ck_ref[0], sk_ref[0])
        else:
            q = q_ref[0, 0]                  # [bq, Dh]
            k = k_ref[0, 0]                  # [bk, Dh]
        v = v_ref[0, 0]
        do = do_ref[0, 0]                    # [bq, Dh]
        prec = _prec(q_ref, k_ref)
        sT = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ) * scale                            # [bk, bq]
        sT = _mask_t(sT, causal, i, j, bq, bk, t_true, window)
        pT = jnp.exp(sT - lse_ref[0, 0, :1])                  # [bk, bq]
        dpT = jax.lax.dot_general(            # v @ do^T → [bk, bq]
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        dsT = pT * (dpT - dl_ref[0, 0, :1]) * scale
        dq_s[:] += jax.lax.dot_general(       # k^T @ ds^T → [Dh, bq]
            k, dsT.astype(k.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq = jnp.transpose(dq_s[:])          # [bq, Dh] f32, w.r.t. q_rot
        if rope:
            # derotate (inverse rotation): d/dq = R(−θ) · d/d(q_rot)
            dq = _rot(dq, cq_ref[0], sq_ref[0], neg=True)
        dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(causal: bool, bq: int, bk: int, t_true: int, scale: float,
                rope: bool, window, one_pass: bool, *refs):
    """dk/dv over the Q sweep of one KV tile. ``one_pass`` (the one-pass
    backward) also accumulates dqᵀ of every query tile into a ``[nq, Dh,
    bq]`` f32 scratch that lives for the whole ``(b, h)`` sweep, and stores
    query tile ``i``'s dq at its last visible KV tile: the score tile, its
    ``exp`` and dPᵀ are computed once for all three gradients."""
    from jax.experimental import pallas as pl

    n_in, n_out = (10 if rope else 6), (3 if one_pass else 2)
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref = refs[:6]
    if rope:
        cq_ref, sq_ref, ck_ref, sk_ref = refs[6:10]
    outs, scratch = refs[n_in:n_in + n_out], refs[n_in + n_out:]
    dk_ref, dv_ref = outs[:2]
    dk_s, dv_s = scratch[:2]
    if one_pass:
        dq_ref, dq_s = outs[2], scratch[2]
    if rope:
        kr_s = scratch[-1]

    j, i = pl.program_id(2), pl.program_id(3)   # KV tile outer, Q inner
    nk = pl.num_programs(2)

    if one_pass:
        @pl.when(jnp.logical_and(j == 0, i == 0))
        def _init_dq():
            dq_s[:] = jnp.zeros_like(dq_s)

    @pl.when(i == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)
        if rope:
            # k is invariant across the Q sweep: rotate ONCE per window
            kr_s[:] = _rot(k_ref[0, 0].astype(jnp.float32),
                           ck_ref[0], sk_ref[0])

    @pl.when(_visible(causal, i, j, bq, bk, window))
    def _compute():
        if rope:
            q = _rot(q_ref[0, 0], cq_ref[0], sq_ref[0])
            k = kr_s[:].astype(k_ref.dtype)
        else:
            q = q_ref[0, 0]
            k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        prec = _prec(q_ref, k_ref)
        sT = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ) * scale                             # [bk, bq]
        sT = _mask_t(sT, causal, i, j, bq, bk, t_true, window)
        pT = jnp.exp(sT - lse_ref[0, 0, :1])
        pTl = pT.astype(do.dtype)
        dv_s[:] += jax.lax.dot_general(       # p^T @ do → [bk, Dh]
            pTl, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        dpT = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        dsT = pT * (dpT - dl_ref[0, 0, :1]) * scale
        dsTl = dsT.astype(q.dtype)
        dk_s[:] += jax.lax.dot_general(       # ds^T @ q → [bk, Dh]
            dsTl, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        if one_pass:
            # the product _dq_kernel makes, summed in the same ascending j
            dq_s[i] += jax.lax.dot_general(   # k^T @ ds^T → [Dh, bq]
                k, dsTl, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            )
            last = (jnp.minimum((i * bq + bq - 1) // bk, nk - 1) if causal
                    else nk - 1)

            @pl.when(j == last)
            def _store_dq():
                dq = jnp.transpose(dq_s[i])  # [bq, Dh] f32, w.r.t. q_rot
                if rope:
                    dq = _rot(dq, cq_ref[0], sq_ref[0], neg=True)
                dq_ref[0, 0, i] = dq.astype(dq_ref.dtype)

    @pl.when(i == pl.num_programs(3) - 1)
    def _finish():
        dk = dk_s[:]                         # [bk, Dh] f32, w.r.t. k_rot
        if rope:
            dk = _rot(dk, ck_ref[0], sk_ref[0], neg=True)
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


def _flash_bwd_tpu(q, k, v, o, lse, do, causal, bq, bk, interpret,
                   delta_minus=None, rope=None, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, Dh = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    bq, bk = min(bq, _pad_up(T, 8)), min(bk, _pad_up(T, 8))
    Tq, Tk = _pad_up(T, bq), _pad_up(T, bk)
    # Δ in the same [B, H, 8, T] sublane-broadcast layout as lse.
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1)[:, :, None, :],
        lse.shape,
    )
    if delta_minus is not None:
        # lse cotangent (see flash_attention_with_lse): ds gains
        # p·g_lse, which is exactly Δ → Δ − g_lse in the backward kernels.
        delta = delta - delta_minus
    if Tq != T:
        pad_q = ((0, 0), (0, 0), (0, Tq - T), (0, 0))
        q, do = jnp.pad(q, pad_q), jnp.pad(do, pad_q)
        # padded q rows: lse=0 and masked scores → p = exp(-1e30) = 0
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, Tq - T)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, 0), (0, Tq - T)))
    if Tk != T:
        pad_k = ((0, 0), (0, 0), (0, Tk - T), (0, 0))
        k, v = jnp.pad(k, pad_k), jnp.pad(v, pad_k)
    if rope is not None:
        c2, s2 = (_pad_t(t, max(Tq, Tk), T) for t in rope)
    nq, nk = Tq // bq, Tk // bk
    scale = Dh ** -0.5

    if causal:
        kcl = _kv_clamp(bq, bk, window)
        qcl = _q_clamp(bq, bk, window)
        kv_ix = lambda b, h, i, j: (b, h // G, kcl(i, j), 0)
        # In the dkv kernel Q is the inner axis: clamp invisible (early,
        # and under a window also too-late) q tiles into the visible range.
        q_ix = lambda b, h, j, i: (b, h, qcl(j, i), 0)
        q_ix_s = lambda b, h, j, i: (b, h, 0, qcl(j, i))
        # rope-table maps (3-D [B, T, Dh] tables, no head axis)
        rkq_ix = lambda b, h, i, j: (b, kcl(i, j), 0)
        rq_ixq = lambda b, h, i, j: (b, i, 0)
        rq_ixk = lambda b, h, j, i: (b, qcl(j, i), 0)
        rk_ixk = lambda b, h, j, i: (b, j, 0)
    else:
        kv_ix = lambda b, h, i, j: (b, h // G, j, 0)
        q_ix = lambda b, h, j, i: (b, h, i, 0)
        q_ix_s = lambda b, h, j, i: (b, h, 0, i)
        rkq_ix = lambda b, h, i, j: (b, j, 0)
        rq_ixq = lambda b, h, i, j: (b, i, 0)
        rq_ixk = lambda b, h, j, i: (b, i, 0)
        rk_ixk = lambda b, h, j, i: (b, j, 0)

    one_pass = _one_pass_bwd(Tq, Dh, q.dtype)
    if not one_pass:
        dq_specs = [
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, Dh), kv_ix),
            pl.BlockSpec((1, 1, bk, Dh), kv_ix),
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 8, bq), lambda b, h, i, j: (b, h, 0, i)),
            pl.BlockSpec((1, 1, 8, bq), lambda b, h, i, j: (b, h, 0, i)),
        ]
        dq_inputs = [q, k, v, do, lse, delta]
        if rope is not None:
            dq_specs += [pl.BlockSpec((1, bq, Dh), rq_ixq),
                         pl.BlockSpec((1, bq, Dh), rq_ixq),
                         pl.BlockSpec((1, bk, Dh), rkq_ix),
                         pl.BlockSpec((1, bk, Dh), rkq_ix)]
            dq_inputs += [c2, s2, c2, s2]
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, causal, bq, bk, T, scale,
                              rope is not None, window),
            grid=(B, H, nq, nk),
            in_specs=dq_specs,
            out_specs=pl.BlockSpec((1, 1, bq, Dh),
                                   lambda b, h, i, j: (b, h, i, 0)),
            out_shape=jax.ShapeDtypeStruct((B, H, Tq, Dh), q.dtype),
            scratch_shapes=[pltpu.VMEM((Dh, bq), jnp.float32)]
            + ([pltpu.VMEM((bq, Dh), jnp.float32)] if rope is not None
               else []),
            interpret=interpret,
            name="flash_bwd_dq",
        )(*dq_inputs)

    # dk/dv per QUERY head; GQA groups summed below.
    dkv_specs = [
        pl.BlockSpec((1, 1, bq, Dh), q_ix),
        pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j, i: (b, h // G, j, 0)),
        pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j, i: (b, h // G, j, 0)),
        pl.BlockSpec((1, 1, bq, Dh), q_ix),
        pl.BlockSpec((1, 1, 8, bq), q_ix_s),
        pl.BlockSpec((1, 1, 8, bq), q_ix_s),
    ]
    dkv_inputs = [q, k, v, do, lse, delta]
    if rope is not None:
        dkv_specs += [pl.BlockSpec((1, bq, Dh), rq_ixk),
                      pl.BlockSpec((1, bq, Dh), rq_ixk),
                      pl.BlockSpec((1, bk, Dh), rk_ixk),
                      pl.BlockSpec((1, bk, Dh), rk_ixk)]
        dkv_inputs += [c2, s2, c2, s2]
    out_specs = [
        pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j, i: (b, h, j, 0)),
        pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j, i: (b, h, j, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, H, Tk, Dh), k.dtype),
        jax.ShapeDtypeStruct((B, H, Tk, Dh), v.dtype),
    ]
    scratch = [pltpu.VMEM((bk, Dh), jnp.float32),
               pltpu.VMEM((bk, Dh), jnp.float32)]
    if one_pass:
        # every query tile's dq, resident for the whole (b, h) sweep
        out_specs.append(pl.BlockSpec((1, 1, nq, bq, Dh),
                                      lambda b, h, j, i: (b, h, 0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, nq, bq, Dh), q.dtype))
        scratch.append(pltpu.VMEM((nq, Dh, bq), jnp.float32))
    if rope is not None:
        scratch.append(pltpu.VMEM((bk, Dh), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_dkv_kernel, causal, bq, bk, T, scale,
                          rope is not None, window, one_pass),
        grid=(B, H, nk, nq),
        in_specs=dkv_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name="flash_bwd_dkv",
        scratch_shapes=scratch,
        compiler_params=(pltpu.CompilerParams(
            vmem_limit_bytes=_ONE_PASS_VMEM_LIMIT) if one_pass else None),
        interpret=interpret,
    )(*dkv_inputs)
    dkh, dvh = outs[:2]
    if one_pass:
        dq = outs[2].reshape(B, H, Tq, Dh)

    dq = dq[:, :, :T]
    dkh, dvh = dkh[:, :, :T], dvh[:, :, :T]
    if G > 1:
        dkh = dkh.reshape(B, Hkv, G, T, Dh).sum(axis=2)
        dvh = dvh.reshape(B, Hkv, G, T, Dh).sum(axis=2)
    return dq, dkh.astype(k.dtype), dvh.astype(v.dtype)


# -- custom-VJP wrapper (model layout [B, T, H, Dh]) --------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_tpu(q, k, v, causal: bool = False, block_q: int = _BQ,
                        block_k: int = _BK, interpret: bool = False,
                        window=None):
    """Fused flash attention: ``q`` [B, T, H, Dh], ``k``/``v`` may carry
    fewer (divisor) KV heads. Exact (online-softmax) attention; returns
    [B, T, H, Dh] in ``q.dtype``. ``window`` = sliding-window attention
    (requires ``causal``): query ``t`` sees keys ``(t-window, t]``."""
    out, _ = _fa_fwd(q, k, v, causal, block_q, block_k, interpret, window)
    return out


# Thin delegates over the (out, lse) variant below — ONE set of
# swapaxes/residual/backward wrappers to keep in sync, not two.
def _fa_fwd(q, k, v, causal, block_q, block_k, interpret, window=None):
    (out, _lse), res = _fal_fwd(q, k, v, causal, block_q, block_k, interpret,
                                window)
    return out, res


def _fa_bwd(causal, block_q, block_k, interpret, window, res, g):
    lse8 = res[4]
    zero_lse = jnp.zeros(
        (lse8.shape[0], lse8.shape[3], lse8.shape[1]), jnp.float32
    )  # Δ − 0 = Δ: the plain variant has no lse cotangent
    return _fal_bwd(causal, block_q, block_k, interpret, window, res,
                    (g, zero_lse))


flash_attention_tpu.defvjp(_fa_fwd, _fa_bwd)


# -- (out, lse) variant: the building block for cross-shard merges ------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_with_lse(q, k, v, causal: bool = False,
                             block_q: int = _BQ, block_k: int = _BK,
                             interpret: bool = False, window=None):
    """Like :func:`flash_attention_tpu` but also returns the per-row
    ``lse = logsumexp(scores)`` as ``[B, T, H]`` float32 — DIFFERENTIABLY.

    This is the primitive a cross-shard softmax merge needs (ring
    attention combines per-visit partial attentions by their lse). The
    lse cotangent costs nothing extra in the backward: ``∂lse_i/∂s_ij =
    p_ij``, so it folds into the FlashAttention-2 ``Δ`` term —
    ``ds = p∘(dp − Δ)`` becomes ``p∘(dp − (Δ − g_lse))`` — and the same
    kernels run unchanged with ``Δ_eff = Δ − g_lse``.
    """
    (out, lse), _ = _fal_fwd(q, k, v, causal, block_q, block_k, interpret,
                             window)
    return out, lse


def _fal_fwd(q, k, v, causal, block_q, block_k, interpret, window=None):
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o, lse8 = _flash_fwd_tpu(qt, kt, vt, causal, block_q, block_k, interpret,
                             window=window)
    lse_out = jnp.transpose(lse8[:, :, 0, :], (0, 2, 1))  # [B, T, H]
    return ((jnp.swapaxes(o, 1, 2), lse_out),
            (qt, kt, vt, o, lse8))


def _fal_bwd(causal, block_q, block_k, interpret, window, res, cts):
    qt, kt, vt, o, lse8 = res
    g, g_lse = cts
    do = jnp.swapaxes(g, 1, 2)
    # [B, T, H] → the kernels' [B, H, 8, T] sublane-broadcast layout
    g_lse8 = jnp.broadcast_to(
        jnp.transpose(g_lse, (0, 2, 1))[:, :, None, :], lse8.shape
    ).astype(jnp.float32)
    dq, dk, dv = _flash_bwd_tpu(qt, kt, vt, o, lse8, do, causal,
                                block_q, block_k, interpret,
                                delta_minus=g_lse8, window=window)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))


flash_attention_with_lse.defvjp(_fal_fwd, _fal_bwd)


def make_rope_tables(cos, sin):
    """(cos, sin) ``[..., Dh/2]`` → duplicated half-split tables
    ``(C2, S2)`` ``[..., Dh]`` f32 (see ``_rot``). Build ONCE per forward
    — inside a scanned layer body XLA cannot hoist the concat, so callers
    must not rebuild per layer."""
    c2 = jnp.concatenate([cos, cos], -1).astype(jnp.float32)
    s2 = jnp.concatenate([-sin, sin], -1).astype(jnp.float32)
    return c2, s2


# -- rope-fused variant (train-path attention with in-kernel rotation) --------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_attention_rope(q, k, v, c2, s2, causal: bool = True,
                         block_q: int = _BQ, block_k: int = _BK,
                         interpret: bool = False, window=None):
    """Flash attention with the rotary embedding FUSED into the kernels.

    ``q`` [B, T, H, Dh] and ``k``/``v`` [B, T, Hkv, Dh] arrive UNROTATED;
    ``c2``/``s2`` are the duplicated half-split RoPE tables ``[B, T, Dh]``
    float32 (``C2 = [cos|cos]``, ``S2 = [−sin|sin]``, see ``_rot``). The
    rotated q/k never exist in HBM: tiles rotate on load in the forward
    AND the backward, and the gradient tiles derotate on store
    (the rotation is orthogonal, so the VJP is the inverse rotation).
    Numerically identical to rotating with ``_rope_rotate`` first — for
    q/k/v gradients. The TABLES are treated as constants (positions are
    not trained): their cotangent is zero by contract, made explicit with
    a ``stop_gradient`` — learned-rotary experiments must not route
    frequency gradients through this op.
    """
    (out, _), _res = _far_fwd(q, k, v, c2, s2, causal, block_q, block_k,
                              interpret, window)
    return out


def _far_fwd(q, k, v, c2, s2, causal, block_q, block_k, interpret,
             window=None):
    c2 = jax.lax.stop_gradient(c2)
    s2 = jax.lax.stop_gradient(s2)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o, lse = _flash_fwd_tpu(qt, kt, vt, causal, block_q, block_k, interpret,
                            rope=(c2, s2), window=window)
    return ((jnp.swapaxes(o, 1, 2), lse),
            (qt, kt, vt, o, lse, c2, s2))


def _far_bwd(causal, block_q, block_k, interpret, window, res, g):
    qt, kt, vt, o, lse, c2, s2 = res
    do = jnp.swapaxes(g, 1, 2)
    dq, dk, dv = _flash_bwd_tpu(qt, kt, vt, o, lse, do, causal,
                                block_q, block_k, interpret,
                                rope=(c2, s2), window=window)
    # positions are constants: zero cotangent for the tables (DCE'd)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2), jnp.zeros_like(c2), jnp.zeros_like(s2))


def _far_fwd_vjp(q, k, v, c2, s2, causal, block_q, block_k, interpret,
                 window=None):
    (out, _lse), res = _far_fwd(q, k, v, c2, s2, causal, block_q, block_k,
                                interpret, window)
    return out, res


flash_attention_rope.defvjp(_far_fwd_vjp, _far_bwd)
