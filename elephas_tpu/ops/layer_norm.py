"""Fused LayerNorm Pallas kernel (forward + backward).

LayerNorm is the transformer LM's second-hottest bandwidth consumer after
attention: naive lowering reads ``x`` for the mean, again for the variance,
and the backward pass re-reads the normalized activations it stored in HBM.
The kernels below do each pass in ONE VMEM visit per 8-row block:

- forward: row mean + variance + normalize + affine in one pass;
- backward: recompute ``x̂`` on-chip (nothing but ``x`` is saved) and emit
  ``dx`` plus per-block partial reductions for ``dscale``/``dbias``, which
  XLA then sums over the (tiny) grid axis.

The dx formula, with ``x̂ = (x − μ)·rstd`` and ``h = g·scale``:
``dx = rstd · (h − mean(h) − x̂·mean(h·x̂))``.

Tile layout (see /opt/skills/guides/pallas_guide.md): float32 tiles are
(8, 128); rows are processed in 8-row blocks with the full feature dimension
resident in VMEM, features zero-padded to a lane multiple. Row statistics
use the centered variance with the padded lanes masked (see ``_stats`` for
why); all other padded terms vanish because padded ``scale``/``bias``/``g``
columns are zero, and padded output columns are sliced off.

Used by the LM family via :func:`elephas_tpu.ops.layer_norm` — Pallas on
TPU, the jnp reference elsewhere (which is also the test oracle; kernels run
under ``interpret=True`` on CPU in tests). No reference (b13n3rd/elephas)
analog: the reference has no custom kernels at all (SURVEY.md §2.2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_ops import _LANE, _pad_up
from .pallas_ops import _BLOCK_B as _BLOCK_N


# -- reference (fallback / oracle) implementation ----------------------------


def layer_norm_reference(x, scale, bias, eps: float = 1e-5):
    """LayerNorm over the last axis of ``[..., D]`` with affine params [D]."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


# -- pallas kernels ----------------------------------------------------------


def _stats(x, d_true: int, eps: float):
    """Row mean + rstd + centered-and-masked x, numerically stable.

    Variance is the CENTERED sum((x−μ)²)/D — the E[x²]−μ² shortcut
    catastrophically cancels in float32 when |μ| ≫ σ (e.g. a residual
    stream riding at 1e4) and can even go negative → rsqrt NaN. Centering
    requires masking the zero-padded lanes, which otherwise contribute μ²
    each to the centered sum.
    """
    mask = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < d_true
    inv_d = 1.0 / d_true
    mu = jnp.sum(x, axis=-1, keepdims=True) * inv_d
    xc = jnp.where(mask, x - mu, 0.0)
    var = jnp.sum(xc * xc, axis=-1, keepdims=True) * inv_d
    return xc, jax.lax.rsqrt(var + eps)


def _fwd_kernel(d_true: int, eps: float, x_ref, s_ref, b_ref, out_ref):
    xc, rstd = _stats(x_ref[:], d_true, eps)
    out_ref[:] = xc * rstd * s_ref[:] + b_ref[:]


def _bwd_kernel(d_true: int, eps: float, x_ref, s_ref, g_ref,
                dx_ref, ds_ref, db_ref):
    from jax.experimental import pallas as pl

    g = g_ref[:]
    inv_d = 1.0 / d_true
    xc, rstd = _stats(x_ref[:], d_true, eps)
    xhat = xc * rstd
    h = g * s_ref[:]
    mean_h = jnp.sum(h, axis=-1, keepdims=True) * inv_d
    mean_hx = jnp.sum(h * xhat, axis=-1, keepdims=True) * inv_d
    dx_ref[:] = rstd * (h - mean_h - xhat * mean_hx)

    # Parameter grads: every grid step revisits the SAME (8, Dp) output
    # block (TPU grids are sequential, the block stays resident in VMEM),
    # accumulating its row-reduced partial into all 8 rows; the caller reads
    # row 0. Cheaper than a [grid, Dp] partials array + host-side sum.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        ds_ref[:] = jnp.zeros_like(ds_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    part_s = jnp.sum(g * xhat, axis=0, keepdims=True)
    part_b = jnp.sum(g, axis=0, keepdims=True)
    ds_ref[:] = ds_ref[:] + jnp.broadcast_to(part_s, ds_ref.shape)
    db_ref[:] = db_ref[:] + jnp.broadcast_to(part_b, db_ref.shape)


def _prepare(x2, scale, bias_or_g):
    N, D = x2.shape
    Np, Dp = _pad_up(N, _BLOCK_N), _pad_up(D, _LANE)
    xp = jnp.pad(x2.astype(jnp.float32), ((0, Np - N), (0, Dp - D)))
    sp = jnp.pad(scale.astype(jnp.float32), (0, Dp - D)).reshape(1, Dp)
    bp = jnp.pad(bias_or_g.astype(jnp.float32), (0, Dp - D)).reshape(1, Dp) \
        if bias_or_g.ndim == 1 else \
        jnp.pad(bias_or_g.astype(jnp.float32), ((0, Np - N), (0, Dp - D)))
    return xp, sp, bp, N, D, Np, Dp


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_layer_norm(x, scale, bias, eps: float = 1e-5, interpret: bool = False):
    """Fused LayerNorm over the last axis (Pallas).

    ``x`` [..., D]; ``scale``/``bias`` [D]. Returns float32 in ``x``'s shape.
    """
    from jax.experimental import pallas as pl

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    xp, sp, bp, N, D, Np, Dp = _prepare(x2, scale, bias)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, D, eps),
        out_shape=jax.ShapeDtypeStruct((Np, Dp), jnp.float32),
        grid=(Np // _BLOCK_N,),
        in_specs=[
            pl.BlockSpec((_BLOCK_N, Dp), lambda n: (n, 0)),
            pl.BlockSpec((1, Dp), lambda n: (0, 0)),
            pl.BlockSpec((1, Dp), lambda n: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_BLOCK_N, Dp), lambda n: (n, 0)),
        interpret=interpret,
        name="layer_norm_fwd",
    )(xp, sp, bp)
    return out[:N, :D].reshape(*lead, D)


def _fused_fwd(x, scale, bias, eps, interpret):
    # bias[:0]: zero-size dtype carrier so the backward pass can cast dbias
    # without saving the whole bias tensor.
    return fused_layer_norm(x, scale, bias, eps, interpret), (x, scale, bias[:0])


def _fused_bwd(eps, interpret, residuals, g):
    from jax.experimental import pallas as pl

    x, scale, bias_dtype_carrier = residuals
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(x2.shape)
    xp, sp, gp, N, D, Np, Dp = _prepare(x2, scale, g2)
    grid = Np // _BLOCK_N
    dx, ds_acc, db_acc = pl.pallas_call(
        functools.partial(_bwd_kernel, D, eps),
        out_shape=[
            jax.ShapeDtypeStruct((Np, Dp), jnp.float32),
            jax.ShapeDtypeStruct((_BLOCK_N, Dp), jnp.float32),
            jax.ShapeDtypeStruct((_BLOCK_N, Dp), jnp.float32),
        ],
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_BLOCK_N, Dp), lambda n: (n, 0)),
            pl.BlockSpec((1, Dp), lambda n: (0, 0)),
            pl.BlockSpec((_BLOCK_N, Dp), lambda n: (n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_BLOCK_N, Dp), lambda n: (n, 0)),
            pl.BlockSpec((_BLOCK_N, Dp), lambda n: (0, 0)),
            pl.BlockSpec((_BLOCK_N, Dp), lambda n: (0, 0)),
        ],
        interpret=interpret,
        name="layer_norm_bwd",
    )(xp, sp, gp)
    dx = dx[:N, :D].reshape(*lead, D).astype(x.dtype)
    dscale = ds_acc[0, :D].astype(scale.dtype)
    dbias = db_acc[0, :D].astype(bias_dtype_carrier.dtype)
    return dx, dscale, dbias


fused_layer_norm.defvjp(_fused_fwd, _fused_bwd)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    """Dispatcher: Pallas kernel on TPU, jnp reference elsewhere.

    Always returns float32 (the kernel's output dtype), so callers see one
    dtype contract regardless of backend.
    """
    from .pallas_ops import is_tpu_backend

    if is_tpu_backend():
        return fused_layer_norm(x, scale, bias, eps)
    return layer_norm_reference(x, scale, bias, eps).astype(jnp.float32)
