"""Gated DeltaNet: the linear-attention layer whose memory of the past is a
recurrent STATE, not rows of a cache (Yang et al., "Gated Delta Networks").

Per head, with keys and queries of ``dk`` numbers and values of ``dv``, the
state is a matrix ``S`` ``[dk, dv]``, zero before the first position, and a
position folds itself into it::

    S~  = alpha_t S_{t-1}                 decay, alpha_t = exp(g_t) in (0, 1]
    u_t = beta_t (v_t - S~^T k_t)         the delta rule's correction
    S_t = S~ + k_t u_t^T
    o_t = S_t^T q_t

(that is ``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``;
``beta`` in (0, 2) where the transition may have a negative eigenvalue).
THREE forms of this one recurrence live here, all float32:

- :func:`gdn_recurrence`: one position at a time under ``lax.scan``. The
  definition; the oracle of the tests.
- :func:`gdn_chunk`: the chunkwise (WY) form over blocks of 64 positions,
  what a prompt or a prefill chunk runs: inside a block the pseudo-values
  ``u`` solve a unit lower-triangular system in ``K K^T`` (scaled by
  ``beta`` and the decay ratios), the state is read once for the
  cross-block term and updated once a block. ``jax.numpy`` under a
  ``lax.scan`` over blocks; positions past ``n_valid`` leave the state as
  it was (bucket padding must not touch it: it is never repaired, as a
  cache's padding rows are).
- :func:`gdn_decode`: one position a row against the STACKED state of every
  linear layer, updated in place. On the TPU a Pallas kernel
  (``name="gdn_decode"``): a live row's state is read once and written
  once a layer, rows that are not live are neither read nor written (a
  free slot and a parked partial prefill keep their state), and the stack
  stays in HBM whole with the layer index on scalar prefetch, as
  ``flash_decode``'s does. Elsewhere :func:`gdn_decode_reference`.

THE STATE'S LAYOUT. A head's ``[dk, dv]`` matrix has ``dv`` on the lanes. 192
columns would be padded to 256 by the chip's tiled layout (a third more
memory and a third more bytes a step), so ``g`` heads lie side by side in one
``[dk, g dv]`` tile, ``g`` the fewest that make whole 128-column lanes
(:func:`state_group`; 2 for 192: ``[96, 384]``): the stack is ``[L, B, H/g,
dk, g dv]`` float32, five axes with the slot axis second like every other
cache stack. :func:`pack_state` / :func:`unpack_state` go between that and
``[..., H, dk, dv]``.

Before the recurrence a layer's q, k and v channels pass a causal depthwise
convolution of a few taps (:func:`conv_chunk`, :func:`conv_step`), whose
memory of the past is the last ``width - 1`` inputs: the TAIL, kept a slot
beside the state as ``[L, B, (width - 1) C]`` in the model's ``act_dtype``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .pallas_ops import _LANE, is_tpu_backend

BLOCK = 64          # positions a block of the chunkwise form
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


# -- the state's layout ---------------------------------------------------------

def state_group(heads: int, dv: int) -> int:
    """Heads side by side in one tile of the stacked state: the fewest that
    make ``g * dv`` whole 128-column lanes, or 1 where the heads do not
    divide into such groups (small test widths)."""
    g = _LANE // math.gcd(dv, _LANE)
    return g if heads % g == 0 else 1


def pack_state(s):
    """``[..., H, dk, dv]`` -> ``[..., H/g, dk, g dv]``."""
    *lead, H, dk, dv = s.shape
    g = state_group(H, dv)
    s = s.reshape(*lead, H // g, g, dk, dv)
    return jnp.moveaxis(s, -3, -2).reshape(*lead, H // g, dk, g * dv)


def unpack_state(sp, heads: int):
    """Inverse of :func:`pack_state`."""
    *lead, Hp, dk, gdv = sp.shape
    g = heads // Hp
    s = sp.reshape(*lead, Hp, dk, g, gdv // g)
    return jnp.moveaxis(s, -2, -3).reshape(*lead, heads, dk, gdv // g)


def l2_normalize(x, eps: float = 1e-6):
    """``x / ||x||_2`` over the last axis in float32 (``eps`` under the
    root, as the published layer has it)."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


# -- the recurrence, three ways ------------------------------------------------

def gdn_step(q, k, v, g, beta, s):
    """ONE position of the recurrence for every row and head: ``q``/``k``
    ``[B, H, dk]``, ``v`` ``[B, H, dv]``, ``g``/``beta`` ``[B, H]``, ``s``
    ``[B, H, dk, dv]`` -> ``(o [B, H, dv], s_new)``, float32, no MXU
    rounding (products and sums on the vector unit)."""
    q, k, v, s = (x.astype(_F32) for x in (q, k, v, s))
    s = s * jnp.exp(g.astype(_F32))[..., None, None]
    u = beta.astype(_F32)[..., None] * (
        v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * u[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def gdn_recurrence(q, k, v, g, beta, s0, state_dtype=None):
    """The definition: positions one at a time. ``q``/``k`` ``[B, T, H,
    dk]``, ``v`` ``[B, T, H, dv]``, ``g``/``beta`` ``[B, T, H]``, ``s0``
    ``[B, H, dk, dv]`` -> ``(o [B, T, H, dv], s_T)``. ``state_dtype``
    rounds the carried state to that type after every position (the tests'
    bfloat16-state variant; ``None`` keeps float32)."""
    def body(s, xs):
        o, s = gdn_step(*xs, s)
        if state_dtype is not None:
            s = s.astype(state_dtype).astype(_F32)
        return s, o

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    s, o = jax.lax.scan(body, s0.astype(_F32), xs)
    return jnp.moveaxis(o, 0, 1), s


def _unit_lower_inverse(a):
    """``(I + A)^-1`` for strictly lower-triangular ``a`` ``[..., C, C]`` by
    forward substitution, a row a step (row ``i`` of the inverse is ``e_i -
    A[i, :] X`` once the rows above it are final), on the vector unit: the
    exact solve, with none of the cancellation a Neumann series of a
    matrix with entries up to 2 would bring."""
    C = a.shape[-1]
    eye = jnp.eye(C, dtype=_F32)

    def row(i, x):
        a_i = jax.lax.dynamic_index_in_dim(a, i, axis=-2, keepdims=False)
        new = eye[i] - jnp.sum(a_i[..., :, None] * x, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(x, new, i, axis=-2)

    return jax.lax.fori_loop(0, C, row, jnp.broadcast_to(eye, a.shape))


@jax.named_scope("gdn_chunk")
def gdn_chunk(q, k, v, g, beta, s0, n_valid=None, block: int = BLOCK):
    """The chunkwise (WY) form: same arguments and result as
    :func:`gdn_recurrence`. ``n_valid`` (scalar or ``[B]``, may be traced):
    positions from ``n_valid`` on fold nothing into the state (``beta`` 0,
    decay 1) and their outputs are garbage the caller does not read.

    With ``G`` the running sum of ``g`` inside a block, ``D[t, i] =
    exp(G_t - G_i)`` for ``i <= t``::

        (I + tril(diag(beta) (D * K K^T), -1)) U = diag(beta) (V - diag(e^G) K S_0)
        O   = diag(e^G) Q S_0 + tril(D * Q K^T) U
        S_C = e^{G_C} S_0 + (diag(e^{G_C - G}) K)^T U

    Everything that does not hold ``S_0`` is computed for all blocks at
    once; the scan over blocks carries the state alone. Matrix products are
    float32 at the highest precision (the MXU's default single bf16 pass
    would round q, k and the state to 8 bits)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = int(block)
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))
    if n_valid is not None:
        valid = (jnp.arange(T)[None, :]
                 < jnp.asarray(n_valid).reshape(-1, 1))[..., None]
        g, beta = jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)
    nb = -(-T // C)
    pad = nb * C - T

    def blocks(x):       # [B, T, H, ...] -> [nb, B, H, C, ...]
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(B, nb, C, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    q, k, v = blocks(q), blocks(k), blocks(v)
    g, beta = blocks(g), blocks(beta)                     # [nb, B, H, C]
    G = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((C, C), bool))
    # (masked before the exponential: above the diagonal G_t - G_i > 0)
    D = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                          -jnp.inf))
    mm = functools.partial(jnp.einsum, precision=_HI,
                           preferred_element_type=_F32)
    a = jnp.tril(beta[..., None] * D * mm("...td,...id->...ti", k, k), -1)
    inv = _unit_lower_inverse(a)
    e_g = jnp.exp(G)[..., None]
    w = mm("...ti,...id->...td", inv, beta[..., None] * e_g * k)
    uv = mm("...ti,...id->...td", inv, beta[..., None] * v)
    qk = D * mm("...td,...id->...ti", q, k)
    qg = q * e_g
    g_end = G[..., -1:]                                   # [nb, B, H, 1]
    k_end = k * jnp.exp(g_end - G)[..., None]

    def one(s, xs):
        w_b, uv_b, qk_b, qg_b, k_b, ge_b = xs
        u = uv_b - mm("bhtd,bhdv->bhtv", w_b, s)
        o = mm("bhtd,bhdv->bhtv", qg_b, s) + mm("bhti,bhiv->bhtv", qk_b, u)
        s = jnp.exp(ge_b)[..., None] * s + mm("bhtd,bhtv->bhdv", k_b, u)
        return s, o

    s, o = jax.lax.scan(one, s0.astype(_F32), (w, uv, qk, qg, k_end, g_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)         # [B, nb, C, H, dv]
    return o.reshape(B, nb * C, H, dv)[:, :T], s


def gdn_decode_reference(q, k, v, g, beta, state, layer, live=None):
    """One position a row against layer ``layer`` (may be traced) of the
    stacked, packed state ``[L, B, H/g, dk, g dv]``: ``q``/``k`` ``[B, H,
    dk]``, ``v`` ``[B, H, dv]``, ``g``/``beta`` ``[B, H]``, ``live`` ``[B]``
    bool or ``None`` (every row) -> ``(o [B, H, dv], state)``. A row that is
    not live keeps its state (its ``o`` is garbage nobody reads)."""
    H = q.shape[1]
    sp = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    o, s = gdn_step(q, k, v, g, beta, unpack_state(sp, H))
    new = pack_state(s).astype(state.dtype)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, sp)
    return o, jax.lax.dynamic_update_index_in_dim(state, new, layer, 0)


_BUFFERS = 3        # a row's state: one arriving, one in work, one leaving


def _gdn_kernel(g_heads: int, order_ref, n_ref, layer_ref, qt_ref, kt_ref,
                v_ref, a_ref, b_ref, s_hbm, o_ref, s_out, buf, sem_in,
                sem_out):
    """Grid step ``i`` is the ``i``-th LIVE row, ``order[i]`` (the wrapper
    sorts the live rows first; steps from ``n_live`` on only clear their
    output). Its whole state ``[H/g, dk, g dv]`` is copied from the stack in
    HBM into one of three VMEM buffers while the row before computes in the
    second and the row before that leaves the third; the update is
    elementwise on the vector unit, a tile of ``g`` heads at a time:
    ``k``'s and ``q``'s columns broadcast along the lanes, ``v``, the
    decay and ``beta`` along the sublanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    n = n_ref[0]
    layer = layer_ref[0]
    Hp, dk, gdv = buf.shape[1:]
    dv = gdv // g_heads

    def copy_in(j, slot):
        return pltpu.make_async_copy(s_hbm.at[layer, order_ref[j]],
                                     buf.at[slot], sem_in.at[slot])

    def copy_out(j, slot):
        return pltpu.make_async_copy(buf.at[slot],
                                     s_out.at[layer, order_ref[j]],
                                     sem_out.at[slot])

    @pl.when(jnp.logical_and(i == 0, n > 0))
    def _first():
        copy_in(0, 0).start()

    def columns(t_ref, p):
        """``[dk, g dv]``: head ``p g + j``'s column of ``t_ref`` ``[1, dk,
        H]`` broadcast over lanes ``j dv .. (j + 1) dv``."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (dk, gdv), 1)
        out = None
        for j in range(g_heads):
            h = p * g_heads + j
            col = jnp.broadcast_to(t_ref[0, :, h:h + 1], (dk, gdv))
            out = col if out is None else jnp.where(lane >= j * dv, col, out)
        return out

    @pl.when(i < n)
    def _row():
        slot = i % _BUFFERS

        @pl.when(i + 1 < n)
        def _next():
            nxt = (i + 1) % _BUFFERS

            @pl.when(i + 1 >= _BUFFERS)
            def _():      # the buffer's last tenant has to have left
                copy_out(i + 1 - _BUFFERS, nxt).wait()

            copy_in(i + 1, nxt).start()

        copy_in(i, slot).wait()
        for p in range(Hp):
            kx = columns(kt_ref, p)
            s = buf[slot, p] * a_ref[0, p:p + 1, :]
            u = b_ref[0, p:p + 1, :] * (
                v_ref[0, p:p + 1, :] - jnp.sum(s * kx, axis=0, keepdims=True))
            s = s + kx * u
            buf[slot, p] = s
            o_ref[0, p:p + 1, :] = jnp.sum(s * columns(qt_ref, p), axis=0,
                                           keepdims=True)
        copy_out(i, slot).start()

    @pl.when(i >= n)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i == pl.num_programs(0) - 1)
    def _drain():         # the rows still leaving when the grid ends
        for d in range(_BUFFERS, 0, -1):
            @pl.when(n - d >= 0)
            def _():
                copy_out(n - d, (n - d) % _BUFFERS).wait()


def gdn_decode(q, k, v, g, beta, state, layer, live=None,
               interpret: bool = False):
    """:func:`gdn_decode_reference` as a Pallas kernel whose second output
    ALIASES the stacked state: per live row one read and one write of the
    row's ``H dk dv`` float32 numbers of layer ``layer``, nothing of a row
    that is not live. ``layer`` and the order of the live rows ride scalar
    prefetch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, dk = q.shape
    dv = v.shape[-1]
    L, _, Hp, _, gdv = state.shape
    gh = H // Hp
    if state.shape != (L, B, Hp, dk, gh * dv) or state.dtype != _F32:
        raise ValueError(
            f"gdn_decode: state {state.shape} {state.dtype} is not the "
            f"packed float32 stack [L, {B}, H/g, {dk}, g x {dv}] of {H} heads")
    alive = (jnp.ones((B,), bool) if live is None else live.astype(bool))
    # the live rows first, in row order; the rest behind them
    order = jnp.argsort(jnp.logical_not(alive), stable=True).astype(jnp.int32)
    n_live = jnp.sum(alive).astype(jnp.int32).reshape(1)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)

    def lanes(x):         # [B, H] or [B, H, dv] -> [B, H/g, g dv]
        x = jnp.broadcast_to(x.astype(_F32).reshape(B, H, -1), (B, H, dv))
        return x.reshape(B, Hp, gdv)

    qt = jnp.swapaxes(q.astype(_F32), 1, 2)               # [B, dk, H]
    kt = jnp.swapaxes(k.astype(_F32), 1, 2)
    col = pl.BlockSpec((1, dk, H), lambda i, o, n, l: (o[i], 0, 0))
    row = pl.BlockSpec((1, Hp, gdv), lambda i, o, n, l: (o[i], 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_gdn_kernel, gh),
        out_shape=[jax.ShapeDtypeStruct((B, Hp, gdv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[col, col, row, row, row,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[row, pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((_BUFFERS, Hp, dk, gdv), _F32),
                pltpu.SemaphoreType.DMA((_BUFFERS,)),
                pltpu.SemaphoreType.DMA((_BUFFERS,)),
            ],
        ),
        # operands count from the three scalar-prefetch arrays: the state
        # is operand 8, and it is the second output
        input_output_aliases={8: 1},
        # rows in order: each starts the next one's copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(
                32 << 20, (_BUFFERS + 1) * Hp * dk * gdv * 4 + (8 << 20))),
        interpret=interpret,
        name="gdn_decode",
    )(order, n_live, layer_arr, qt, kt, lanes(v), lanes(jnp.exp(g)),
      lanes(beta), state)
    return o.reshape(B, H, dv), state


def gdn_decode_update(q, k, v, g, beta, state, layer, live=None):
    """Dispatcher: the ``gdn_decode`` Pallas kernel on TPU (a float32
    state, which is what it moves), the jnp reference elsewhere."""
    if is_tpu_backend() and state.dtype == _F32:
        return gdn_decode(q, k, v, g, beta, state, layer, live)
    return gdn_decode_reference(q, k, v, g, beta, state, layer, live)


# -- the short convolution ------------------------------------------------------

def conv_chunk(x, tail, w, n_valid=None):
    """Causal depthwise convolution and SiLU over a chunk: ``x`` ``[B, S,
    C]`` continuing ``tail`` ``[B, (W - 1) C]`` (the ``W - 1`` inputs before
    the chunk, oldest first; zeros before position 0), ``w`` ``[W, C]``
    (``w[W - 1]`` weighs the position itself) -> ``(y [B, S, C] float32,
    new tail)``: ``y_t = silu(sum_j w_j x_{t - (W - 1) + j})`` as ``W``
    shifted adds. The new tail is the last ``W - 1`` inputs up to
    ``n_valid`` (default ``S``; may be traced), so bucket padding never
    enters it."""
    B, S, C = x.shape
    W = w.shape[0]
    full = jnp.concatenate([tail.reshape(B, W - 1, C).astype(x.dtype), x],
                           axis=1)
    w = w.astype(_F32)
    y = sum(full[:, j:j + S].astype(_F32) * w[j] for j in range(W))
    n = S if n_valid is None else n_valid
    if jnp.ndim(n) == 0:
        new = jax.lax.dynamic_slice_in_dim(full, n, W - 1, axis=1)
    else:
        new = jax.vmap(lambda f, i: jax.lax.dynamic_slice_in_dim(
            f, i, W - 1, axis=0))(full, jnp.asarray(n))
    return jax.nn.silu(y), new.reshape(B, (W - 1) * C).astype(tail.dtype)


def conv_step(x, tail, w):
    """:func:`conv_chunk` for ONE position a row: ``x`` ``[B, C]``, ``tail``
    ``[B, (W - 1) C]`` -> ``(y [B, C] float32, new tail)``; the tail's
    pieces are whole-lane slices where ``C`` is whole lanes."""
    C = x.shape[-1]
    W = w.shape[0]
    w = w.astype(_F32)
    y = x.astype(_F32) * w[W - 1] + sum(
        tail[:, j * C:(j + 1) * C].astype(_F32) * w[j] for j in range(W - 1))
    new = jnp.concatenate([tail[:, C:], x.astype(tail.dtype)], axis=-1)
    return jax.nn.silu(y), new
