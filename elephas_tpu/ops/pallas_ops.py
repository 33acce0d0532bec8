"""Pallas TPU kernels for hot ops.

The framework's compute hot path is XLA-compiled Keras models — matmuls/convs
land on the MXU and elementwise ops fuse without help. The one op worth a
hand-written kernel is the classification loss on wide output layers:
``softmax → log → mask → reduce`` over ``[batch, vocab]`` logits materializes
several HBM-sized intermediates under naive lowering. The fused kernel below
computes per-sample categorical cross-entropy from logits in ONE VMEM pass
(row max, exp, log-sum-exp, dot with labels), with a custom VJP whose backward
pass recomputes softmax on-chip instead of storing it.

Used automatically by ``elephas_tpu.models.losses`` for
``categorical_crossentropy(from_logits=True)`` when running on TPU; a
jax.numpy reference implementation serves as the fallback (and as the test
oracle — the kernel runs under ``interpret=True`` on CPU in tests).

Kernel layout notes (see /opt/skills/guides/pallas_guide.md): float32 tiles
are (8, 128), so the batch is processed in 8-row blocks and the class
dimension is padded to a 128 multiple with -1e30 logits (exp → 0) and zero
labels; the per-sample output rides a [B, 1] block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_BLOCK_B = 8
_LANE = 128


def _pad_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# -- reference (fallback / oracle) implementation ----------------------------


def xent_from_logits_reference(logits, labels):
    """Per-sample CE from logits, one-hot labels: ``lse(x) - <y, x>``."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    return lse - jnp.sum(labels * logits, axis=-1)


# -- pallas kernels ----------------------------------------------------------


def _fwd_kernel(logits_ref, labels_ref, out_ref):
    x = logits_ref[:]
    y = labels_ref[:]
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True)) + m
    out_ref[:] = jnp.sum(y * (lse - x), axis=-1, keepdims=True)


def _bwd_kernel(logits_ref, labels_ref, g_ref, out_ref):
    x = logits_ref[:]
    y = labels_ref[:]
    g = g_ref[:]  # [TB, 1]
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    out_ref[:] = (p - y) * g


# Mosaic's default scoped-VMEM limit. The kernels hold the whole class
# dimension in one block, so past ~130k classes the double-buffered blocks
# outgrow it (on the v5e: "Scoped allocation with size 18.56M and limit
# 16.00M" at C=152064) and the call asks for what its blocks need instead.
_DEFAULT_VMEM_BYTES = 16 * 1024 * 1024


def _pallas_call(kernel, name, n_in, B, Cp, out_cols, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    in_specs = []
    for i in range(n_in):
        cols = Cp if i < 2 else 1  # logits/labels are [B, Cp]; g is [B, 1]
        in_specs.append(
            pl.BlockSpec((_BLOCK_B, cols), lambda b, cols=cols: (b, 0))
        )
    # double-buffered [8, Cp] blocks plus the kernel's [8, Cp] temporaries
    wide_blocks = min(n_in, 2) + (out_cols == Cp)
    need = (2 * wide_blocks + 4) * _BLOCK_B * Cp * 4 + (2 << 20)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, out_cols), jnp.float32),
        grid=(B // _BLOCK_B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((_BLOCK_B, out_cols), lambda b: (b, 0)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(_DEFAULT_VMEM_BYTES, need)),
        interpret=interpret,
        name=name,
    )


def _prepare(logits, labels):
    B, C = logits.shape
    Bp, Cp = _pad_up(B, _BLOCK_B), _pad_up(C, _LANE)
    x = jnp.pad(
        logits.astype(jnp.float32), ((0, Bp - B), (0, Cp - C)),
        constant_values=-1e30,
    )
    y = jnp.pad(labels.astype(jnp.float32), ((0, Bp - B), (0, Cp - C)))
    return x, y, B, Bp, Cp


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fused_xent_from_logits(logits, labels, interpret=False):
    """Fused per-sample categorical cross-entropy from logits (Pallas).

    ``logits`` [B, C] float, ``labels`` [B, C] one-hot. Returns [B] float32.
    """
    x, y, B, Bp, Cp = _prepare(logits, labels)
    out = _pallas_call(_fwd_kernel, "fused_xent_fwd", 2, Bp, Cp, 1,
                       interpret)(x, y)
    return out[:B, 0]


def _fused_fwd(logits, labels, interpret):
    return fused_xent_from_logits(logits, labels, interpret), (logits, labels)


def _fused_bwd(interpret, residuals, g):
    logits, labels = residuals
    x, y, B, Bp, Cp = _prepare(logits, labels)
    gp = jnp.pad(g.astype(jnp.float32), (0, Bp - B)).reshape(Bp, 1)
    dx = _pallas_call(_bwd_kernel, "fused_xent_bwd", 3, Bp, Cp, Cp,
                      interpret)(x, y, gp)
    C = logits.shape[1]
    return dx[:B, :C].astype(logits.dtype), None


fused_xent_from_logits.defvjp(_fused_fwd, _fused_bwd)


def is_tpu_backend() -> bool:
    """True when the default JAX backend is a TPU. A backend that fails to
    initialise raises here: a broken TPU runtime is an error, never "not a
    TPU" — every Pallas dispatcher keys on this answer."""
    return jax.default_backend() == "tpu"


def categorical_crossentropy_from_logits(logits, labels):
    """Dispatcher: Pallas kernel on TPU, jnp reference elsewhere."""
    if is_tpu_backend():
        return fused_xent_from_logits(logits, labels)
    return xent_from_logits_reference(logits, labels)
