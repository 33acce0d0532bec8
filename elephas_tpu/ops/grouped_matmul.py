"""Pallas TPU grouped matmul (``gmm``) for MoE expert FFNs.

EXTENSION BEYOND THE REFERENCE (SURVEY.md §2.3 — expert parallelism is
"explicitly ABSENT" there). The MoE dispatch problem: ``M`` token rows,
each owned by one of ``E`` experts, must multiply that expert's weight
matrix. The other execution strategies all pay for it differently —
one-hot einsums pay O(N·E·C·D) dispatch FLOPs, capacity slots pay
``cf·k·N`` padded rows, and ``jax.lax.ragged_dot`` pays a poor lowering
(on the v5e, one sparse layer of 16 held experts: 4.67-4.71 ms against
this kernel's 2.64-2.83 at the decode shape, 7.29 against 5.70 at the
prefill shape; PERF.md §6, PR 27). This module's strategy:

  * rows are pre-sorted by expert into a TILE-ALIGNED layout — each
    expert's row block is padded up to a multiple of the 128-row MXU
    tile, so every grid tile belongs to exactly ONE expert (worst-case
    padding ``E·(tm−1)`` rows ≈ 6–12 % at bench shapes, vs the capacity
    path's 25 %);
  * a scalar-prefetched ``gmap`` (tile → expert id) steers each tile's
    weight fetch via the BlockSpec index map — no per-row index math in
    the kernel, and Pallas skips the weight DMA when consecutive tiles
    hit the same expert;
  * the contraction dim is tiled with an f32 VMEM accumulator
    (k-innermost grid), so arbitrarily large ``d_model``/``d_ff`` fit.

Three kernels cover training: ``gmm`` (rows × per-group weights),
its transposed-weights twin (used for dL/dx), and ``tgmm`` (per-group
xᵀ·dy weight gradients, accumulated f32 across the row tiles of each
group). ``gmm`` carries a custom VJP wiring the three together;
``gmap`` must be NON-DECREASING (groups contiguous) — the layout
builder in ``parallel.expert`` guarantees it.

A jax.numpy reference (`gmm_reference`) is the test oracle; kernels
run under ``interpret=True`` on CPU in tests (pallas_guide.md
conventions: f32 tiles (8,128), bf16 (16,128), k-tiled accumulation).

DEAD TILES. A dropless caller sizes its row buffer for the worst routing
and fills what the step's routing needs; the tiles past that are marked
``gmap[t] == E`` (one past the last group, still non-decreasing). ``gmm``
fetches nothing for them and skips their matmul: the weight index clamps
to the last group's and the row and output indices to the last LIVE
tile's, so no block index changes and the pipeline copies nothing (a dead
tile that still fetched its ``[tm, K]`` rows cost 2 us a grid step on the
v5e, ten times a skipped step: PERF.md, PR 27). Their output rows are
left as they were and must not be read.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128


def _pick_tile(size: int, prefs=(512, 256, 128)) -> Optional[int]:
    for t in prefs:
        if size % t == 0:
            return t
    return None


def tileable(m: int, k: int, n: int, tm: int) -> bool:
    """True iff the Pallas kernels can run these shapes (every dim splits
    into lane-aligned tiles). The MoE executor falls back to the jnp
    reference otherwise (small test shapes, odd head dims)."""
    return (
        m % tm == 0
        and _pick_tile(k) is not None
        and _pick_tile(n) is not None
        # deep contractions must split into _K_CHUNK kernel calls
        and (k <= 2 * _K_CHUNK or k % _K_CHUNK == 0)
    )


# -- reference (oracle / fallback) -------------------------------------------


def gmm_reference(lhs, rhs, gmap, transpose_rhs: bool = False):
    """``out[r] = lhs[r] @ rhs[gmap[r // tm]]`` in plain jnp (one gather +
    one batched matmul). ``lhs [M, K]``, ``rhs [E, K, N]`` (or ``[E, N, K]``
    when ``transpose_rhs``), ``gmap [M // tm]`` int32 non-decreasing."""
    m = lhs.shape[0]
    tm = m // gmap.shape[0]
    blocks = lhs.reshape(gmap.shape[0], tm, lhs.shape[1])
    # a dead tile (gmap == E) multiplies the last group's weights here:
    # its rows are never read
    w = jnp.take(rhs, jnp.minimum(gmap, rhs.shape[0] - 1),
                 axis=0)  # [nm, K, N] / [nm, N, K]
    dims = (((2,), (2,)), ((0,), (0,))) if transpose_rhs else (
        ((2,), (1,)), ((0,), (0,)))
    out = jax.lax.dot_general(blocks, w, dims,
                              preferred_element_type=jnp.float32)
    return out.reshape(m, -1).astype(lhs.dtype)


def tgmm_reference(lhs, g, gmap, n_groups: int):
    """``out[e] = Σ_{tiles t: gmap[t]=e} lhs_tᵀ @ g_t`` in plain jnp
    (one-hot einsum). ``lhs [M, K]``, ``g [M, N]`` → ``[E, K, N]`` f32."""
    nm = gmap.shape[0]
    tm = lhs.shape[0] // nm
    lb = lhs.reshape(nm, tm, lhs.shape[1]).astype(jnp.float32)
    gb = g.reshape(nm, tm, g.shape[1]).astype(jnp.float32)
    onehot = jax.nn.one_hot(gmap, n_groups, dtype=jnp.float32)  # [nm, E]
    return jnp.einsum("te,tmk,tmn->ekn", onehot, lb, gb)


# -- pallas kernels ----------------------------------------------------------


def _gmm_kernel(gmap_ref, layer_ref, live_ref, lhs_ref, rhs_ref, out_ref, *,
                transpose_rhs: bool, n_groups: int):
    # grid (n, m), m INNERMOST: gmap is non-decreasing, so consecutive
    # row tiles usually hit the same expert and Pallas skips the weight
    # block's DMA (same index → buffer reuse) — each expert's [K, tn]
    # panel crosses HBM once per n-sweep, not once per row tile.
    # ``layer_ref`` is the index maps' alone (the stack's layer dimension
    # is squeezed away before the body sees the block), ``live_ref`` too.
    del layer_ref, live_ref
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
        ((1,), (0,)), ((), ()))

    @pl.when(gmap_ref[pl.program_id(1)] < n_groups)   # not a dead tile
    def _():
        out_ref[:] = jax.lax.dot_general(
            lhs_ref[:], rhs_ref[0], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _gmm_kernel_kloop(gmap_ref, layer_ref, live_ref, lhs_ref, rhs_ref,
                      out_ref, *, transpose_rhs: bool, kc: int,
                      n_groups: int):
    del layer_ref, live_ref
    # deep-K variant: whole-K blocks in VMEM, but the contraction runs as
    # an explicit unrolled loop of kc-deep dots into an f32 accumulator —
    # Mosaic schedules a single K=4k dot poorly (measured 12 GF/s), while
    # the same data as 1k-deep slices runs near peak. Grid (n, m),
    # m innermost for the weight-panel DMA reuse.
    k_dim = lhs_ref.shape[1]
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
        ((1,), (0,)), ((), ()))

    @pl.when(gmap_ref[pl.program_id(1)] < n_groups)   # not a dead tile
    def _():
        acc = None
        for j in range(0, k_dim, kc):
            lj = lhs_ref[:, j:j + kc]
            rj = rhs_ref[0][:, j:j + kc] if transpose_rhs else \
                rhs_ref[0][j:j + kc, :]
            p = jax.lax.dot_general(lj, rj, dims,
                                    preferred_element_type=jnp.float32)
            acc = p if acc is None else acc + p
        out_ref[:] = acc.astype(out_ref.dtype)


def _gmm_kernel_ktiled(gmap_ref, layer_ref, live_ref, lhs_ref, rhs_ref,
                       out_ref, acc_ref, *, transpose_rhs: bool,
                       n_groups: int):
    del layer_ref, live_ref
    # fallback for K too large for whole-K VMEM panels: grid (m, n, k),
    # k innermost, f32 accumulation across k tiles.
    ik = pl.program_id(2)
    live = gmap_ref[pl.program_id(0)] < n_groups      # not a dead tile

    @pl.when(ik == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
        ((1,), (0,)), ((), ()))

    @pl.when(live)
    def _():
        acc_ref[:] += jax.lax.dot_general(
            lhs_ref[:], rhs_ref[0], dims,
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, ik == pl.num_programs(2) - 1))
    def _():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _tgmm_kernel(gmap_ref, lhs_ref, g_ref, out_ref, acc_ref):
    # grid (n, m), m INNERMOST: each group's [K, tn] gradient panel
    # accumulates f32 in VMEM across the group's (contiguous) row tiles
    # and is written back once, on the group's last tile.
    im = pl.program_id(1)
    nm = pl.num_programs(1)
    gcur = gmap_ref[im]
    first = (im == 0) | (gmap_ref[jnp.maximum(im - 1, 0)] != gcur)
    last = (im == nm - 1) | (gmap_ref[jnp.minimum(im + 1, nm - 1)] != gcur)

    @pl.when(first)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        lhs_ref[:], g_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(last)
    def _():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)[None]


_VMEM_BYTES = 12 * 1024 * 1024  # working budget (16 MB VMEM minus slack)
_PANEL_BYTES = 4 * 1024 * 1024  # cap for one whole-K panel (rhs / f32 acc)
_K_CHUNK = 1024  # contraction depth per kernel call (see gmm's K-chunking)


def _panel_tn(n_dim: int, k_dim: int, tm: int, itemsize: int,
              acc_f32: bool = False) -> Optional[int]:
    """Largest N-tile whose whole-K working set fits VMEM: double-buffered
    lhs (tm×K) and rhs (K×tn) blocks, the out block, and (tgmm) the f32
    K×tn accumulator panel."""
    fixed = 2 * tm * k_dim * itemsize
    for t in (1024, 512, 256, 128):
        if n_dim % t:
            continue
        panel = k_dim * t * (4 if acc_f32 else itemsize)
        total = fixed + 2 * k_dim * t * itemsize + 2 * tm * t * itemsize \
            + (panel if acc_f32 else 0)
        if panel <= _PANEL_BYTES and total <= _VMEM_BYTES:
            return t
    return None


def _gmm_dispatch(lhs, rhs, gmap, transpose_rhs: bool, interpret: bool,
                  layer=None):
    """``rhs`` is one layer's ``[E, K, N]`` group weights, or with
    ``layer`` (int, may be traced) a whole STACK ``[L, E, K, N]`` of which
    the kernel reads layer ``layer`` in place: the index rides scalar
    prefetch and the weight blocks' index maps put it in front, so no
    layer of the stack is sliced out (a slice handed to a custom call is a
    copy of that layer's weights every step).

    Deep-contraction front door. Mosaic schedules a single K≳4k dot
    poorly (measured 12 GF/s vs 206 at K=1k, d1024/F4096 bench shapes);
    the default fix is IN-KERNEL K-slicing (``_gmm_kernel_kloop`` — no
    HBM partials). Only when the whole-K panel cannot fit VMEM at all
    does the contraction split into separate kernel calls summed in f32
    here at the XLA level."""
    if layer is None:
        rhs, layer = rhs[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    k_dim = lhs.shape[1]
    if k_dim <= 2 * _K_CHUNK or k_dim % _K_CHUNK:
        return _gmm_call(lhs, rhs, gmap, layer, transpose_rhs, interpret)
    n_dim = rhs.shape[2] if transpose_rhs else rhs.shape[3]
    tm = lhs.shape[0] // gmap.shape[0]
    isz = jnp.dtype(rhs.dtype).itemsize
    if _panel_tn(n_dim, k_dim, tm, isz) is not None:
        return _gmm_call(lhs, rhs, gmap, layer, transpose_rhs, interpret)
    acc = None
    for j in range(0, k_dim, _K_CHUNK):
        lj = jax.lax.slice_in_dim(lhs, j, j + _K_CHUNK, axis=1)
        rj = jax.lax.slice_in_dim(rhs, j, j + _K_CHUNK,
                                  axis=3 if transpose_rhs else 2)
        p = _gmm_call(lj, rj, gmap, layer, transpose_rhs, interpret)
        acc = p.astype(jnp.float32) if acc is None else \
            acc + p.astype(jnp.float32)
    return acc.astype(lhs.dtype)


def _gmm_call(lhs, rhs, gmap, layer, transpose_rhs: bool, interpret: bool):
    """``rhs`` ``[L, E, K, N]`` (``[L, E, N, K]`` transposed), ``layer``
    ``[1]`` int32: see :func:`_gmm_dispatch`."""
    m, k_dim = lhs.shape
    n_dim = rhs.shape[2] if transpose_rhs else rhs.shape[3]
    nm = gmap.shape[0]
    tm = m // nm
    isz = jnp.dtype(rhs.dtype).itemsize
    tn = _panel_tn(n_dim, k_dim, tm, isz)
    last = rhs.shape[1] - 1          # a dead tile keeps the last block
    # ...and the last live tile's rows and output block: ``row(im, lv)``
    live = jnp.sum((gmap <= last).astype(jnp.int32)).reshape(1)
    row = lambda im, lv: jnp.minimum(im, jnp.maximum(lv[0] - 1, 0))
    if tn is not None:
        # whole-K weight panels, row tiles innermost (see _gmm_kernel);
        # the stack's layer dimension is squeezed out of the block
        if transpose_rhs:
            rhs_block = (None, 1, tn, k_dim)
            rhs_index = lambda i_n, im, gm, l, lv: (
                l[0], jnp.minimum(gm[im], last), i_n, 0)
        else:
            rhs_block = (None, 1, k_dim, tn)
            rhs_index = lambda i_n, im, gm, l, lv: (
                l[0], jnp.minimum(gm[im], last), 0, i_n)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_dim // tn, nm),
            in_specs=[
                pl.BlockSpec((tm, k_dim),
                             lambda i_n, im, gm, l, lv: (row(im, lv), 0)),
                pl.BlockSpec(rhs_block, rhs_index),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda i_n, im, gm, l, lv: (row(im, lv), i_n)),
        )
        if k_dim > _K_CHUNK:
            kc = next((c for c in (1024, 512, 256)
                       if k_dim % c == 0 and c < k_dim), k_dim)
            kernel = functools.partial(
                _gmm_kernel_kloop, transpose_rhs=transpose_rhs, kc=kc,
                n_groups=last + 1)
        else:
            kernel = functools.partial(_gmm_kernel,
                                       transpose_rhs=transpose_rhs,
                                       n_groups=last + 1)
        semantics = ("arbitrary", "arbitrary")
    else:
        tk = _pick_tile(k_dim)
        tn = _pick_tile(n_dim, (512, 256, 128))
        if transpose_rhs:
            rhs_block = (None, 1, tn, tk)
            rhs_index = lambda im, i_n, ik, gm, l, lv: (
                l[0], jnp.minimum(gm[im], last), i_n, ik)
        else:
            rhs_block = (None, 1, tk, tn)
            rhs_index = lambda im, i_n, ik, gm, l, lv: (
                l[0], jnp.minimum(gm[im], last), ik, i_n)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nm, n_dim // tn, k_dim // tk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda im, i_n, ik, gm, l, lv: (
                    row(im, lv), ik)),
                pl.BlockSpec(rhs_block, rhs_index),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda im, i_n, ik, gm, l, lv: (row(im, lv), i_n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        )
        kernel = functools.partial(_gmm_kernel_ktiled,
                                   transpose_rhs=transpose_rhs,
                                   n_groups=last + 1)
        semantics = ("arbitrary", "arbitrary", "arbitrary")
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n_dim), lhs.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
        ),
        interpret=interpret,
        # rhs is transposed only in the hand-written backward (dx)
        name="grouped_matmul_bwd_dx" if transpose_rhs
        else "grouped_matmul_fwd",
    )(gmap, layer, live, lhs, rhs)


def gmm_stacked(lhs, rhs, layer, gmap, interpret: bool = False):
    """:func:`gmm` against layer ``layer`` (int, may be traced) of a STACK
    of group weights ``rhs [L, E, K, N]``, read in place (see
    :func:`_gmm_dispatch`). Forward only: what a serving step calls with
    the layer stacks it was given, sliced nowhere."""
    return _gmm_dispatch(lhs, rhs, gmap, False, interpret, layer=layer)


def _tgmm_dispatch(lhs, g, gmap, n_groups: int, out_dtype, interpret: bool):
    """K-chunking for the weight-grad kernel: chunks write DISJOINT
    ``out[:, j:j+kc, :]`` slices, so they concatenate (no summation)."""
    k_dim = lhs.shape[1]
    if k_dim <= 2 * _K_CHUNK or k_dim % _K_CHUNK:
        return _tgmm_call(lhs, g, gmap, n_groups, out_dtype, interpret)
    parts = [
        _tgmm_call(jax.lax.slice_in_dim(lhs, j, j + _K_CHUNK, axis=1),
                   g, gmap, n_groups, out_dtype, interpret)
        for j in range(0, k_dim, _K_CHUNK)
    ]
    return jnp.concatenate(parts, axis=1)


def _tgmm_call(lhs, g, gmap, n_groups: int, out_dtype, interpret: bool):
    m, k_dim = lhs.shape
    n_dim = g.shape[1]
    nm = gmap.shape[0]
    tm = m // nm
    isz = jnp.dtype(g.dtype).itemsize
    tn = _panel_tn(n_dim, k_dim, tm, isz, acc_f32=True)
    if tn is None:
        raise ValueError(
            f"tgmm K={k_dim} too large for a whole-K f32 VMEM panel; "
            "untileable for now"
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_dim // tn, nm),
        in_specs=[
            pl.BlockSpec((tm, k_dim), lambda i_n, im, gm: (im, 0)),
            pl.BlockSpec((tm, tn), lambda i_n, im, gm: (im, i_n)),
        ],
        out_specs=pl.BlockSpec(
            (1, k_dim, tn), lambda i_n, im, gm: (gm[im], 0, i_n)
        ),
        scratch_shapes=[pltpu.VMEM((k_dim, tn), jnp.float32)],
    )
    return pl.pallas_call(
        _tgmm_kernel,
        out_shape=jax.ShapeDtypeStruct((n_groups, k_dim, n_dim), out_dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="grouped_matmul_bwd_dw",
    )(gmap, lhs, g)


# -- differentiable entry points ---------------------------------------------
#
# gmap is an int array (non-differentiable) — its cotangent slot returns
# None, the same convention parallel.expert's gather VJPs use. The
# transposed-weights twin is a separate custom_vjp so each backward can
# call the other without re-entrant tracing.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm(lhs, rhs, gmap, interpret: bool = False):
    """Grouped matmul: ``out[r] = lhs[r] @ rhs[gmap[r // tm]]``.

    ``lhs [M, K]`` (row tiles of size ``tm = M // gmap.size`` each owned by
    one group), ``rhs [E, K, N]``, ``gmap [M//tm]`` int32 NON-DECREASING
    → ``[M, N]`` in ``lhs.dtype`` (f32 accumulation)."""
    return _gmm_dispatch(lhs, rhs, gmap, False, interpret)


def _gmm_fwd(lhs, rhs, gmap, interpret):
    return gmm(lhs, rhs, gmap, interpret), (lhs, rhs, gmap)


def _gmm_bwd(interpret, res, gy):
    lhs, rhs, gmap = res
    dlhs = gmm_t(gy, rhs, gmap, interpret)
    drhs = tgmm(lhs, gy, gmap, rhs.shape[0], rhs.dtype, interpret)
    return dlhs, drhs, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm_t(lhs, rhs, gmap, interpret: bool = False):
    """:func:`gmm` with transposed group weights:
    ``out[r] = lhs[r] @ rhs[gmap[r // tm]]ᵀ`` for ``rhs [E, N, K]`` —
    the dL/dx kernel (weights stay in their forward layout; the BlockSpec
    reads them transposed)."""
    return _gmm_dispatch(lhs, rhs, gmap, True, interpret)


def _gmm_t_fwd(lhs, rhs, gmap, interpret):
    return gmm_t(lhs, rhs, gmap, interpret), (lhs, rhs, gmap)


def _gmm_t_bwd(interpret, res, gy):
    lhs, rhs, gmap = res
    dlhs = gmm(gy, rhs, gmap, interpret)
    drhs = tgmm(gy, lhs, gmap, rhs.shape[0], rhs.dtype, interpret)
    return dlhs, drhs, None


gmm_t.defvjp(_gmm_t_fwd, _gmm_t_bwd)


def tgmm(lhs, g, gmap, n_groups: int, out_dtype=jnp.float32,
         interpret: bool = False):
    """Per-group weight gradient: ``out[e] = Σ_{t: gmap[t]=e} lhs_tᵀ @ g_t``
    over ``tm``-row tiles ``t``. f32 accumulation in VMEM across each
    group's (contiguous) tiles; groups with no tiles come out zero because
    the layout builder gives every group at least one (possibly all-
    sentinel) tile. Not differentiated — it IS the backward."""
    return _tgmm_dispatch(lhs, g, gmap, n_groups, out_dtype, interpret)
