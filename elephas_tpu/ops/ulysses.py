"""Ulysses sequence parallelism: all-to-all head/sequence re-sharding.

EXTENSION BEYOND THE REFERENCE (like ``ring_attention`` — the reference has
no long-context support of any kind, SURVEY.md §5.7). DeepSpeed-Ulysses
(Jacobs et al. 2023) is the second canonical sequence-parallel schedule, the
all-to-all complement to the ring: activations arrive sharded over the
SEQUENCE dim, one ``all_to_all`` re-shards them over the HEAD dim (each
device then holds the FULL sequence for ``H/P`` heads), blockwise flash
attention runs locally with no inter-step communication (``O(T · block)``
memory — no ``[T, T]`` matrix; see ``flash_attention.py``), and a second
``all_to_all`` restores sequence sharding. Communication is two all-to-alls of the
activation volume per call — ``O(T·H·D/P)`` per chip — versus the ring's
``P`` nearest-neighbor KV hops; on a TPU torus the ring wins for very long
sequences at small head counts, Ulysses wins when heads are plentiful and
per-step latency matters (no ``P``-step serial chain). Both are exact: this
function equals :func:`~elephas_tpu.ops.ring_attention.attention_reference`
on the gathered sequence.

Constraint unique to Ulysses: the head count must divide by the group size
(``H % P == 0``) — the re-shard has nothing to split otherwise (the ring has
no such constraint; it is the fallback for few-head models).
"""

from __future__ import annotations

from functools import partial

import jax

from jax.lax import axis_size
from ..parallel.mesh import DATA_AXIS
from .flash_attention import flash_attention, repeat_kv_heads
from .ring_attention import sharded_seq_attention


def _ulysses_local(q, k, v, causal: bool, axis_name: str, window=None):
    """Per-shard body INSIDE shard_map. ``q``: local sequence block
    ``[B, T/P, H, D]`` → out ``[B, T/P, H, D]``. ``k``/``v`` may carry
    fewer (divisor) KV heads: when the KV head count still divides the
    group size, the all_to_alls move only the small blocks and flash
    broadcasts locally; otherwise heads broadcast before the re-shard.

    ``window`` (sliding-window attention, causal only) passes straight
    through to the local flash call: after the head↔sequence all-to-all
    each device holds the FULL sequence, so within-sequence positions are
    global and the kernel's windowed mask (and its out-of-window tile
    skipping) applies unchanged."""
    if window is not None and not causal:
        raise ValueError("window requires causal attention")
    p = axis_size(axis_name)
    h = q.shape[2]
    if k.shape[2] % p:
        k = repeat_kv_heads(k, h)
        v = repeat_kv_heads(v, h)
    # seq-sharded/head-full → seq-full/head-sharded: [B, T, H/P, D]
    a2a = partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=2,
        concat_axis=1, tiled=True,
    )
    qh, kh, vh = a2a(q), a2a(k), a2a(v)
    # full sequence per head group here — blockwise flash keeps the local
    # attention O(T·block) instead of materializing [T, T] (and finishes
    # any remaining KV-head broadcast)
    out = flash_attention(qh, kh, vh, causal=causal, window=window)
    # seq-full/head-sharded → seq-sharded/head-full
    return jax.lax.all_to_all(
        out, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    )


# Public alias: the per-shard body for composing Ulysses attention INSIDE a
# larger shard_map program (see ``models/transformer.py``).
ulysses_attention_local = _ulysses_local


def ulysses_attention(q, k, v, mesh=None, causal: bool = False,
                      axis_name: str = DATA_AXIS, window=None):
    """Exact attention over sequences sharded across a mesh axis, via
    head↔sequence all-to-alls.

    ``q``/``k``/``v``: ``[B, T, H, D]`` with ``T`` and ``H`` divisible by the
    group size (the ``axis_name`` extent of ``mesh``). Same contract (and
    shared compile-cache harness) as
    :func:`~elephas_tpu.ops.ring_attention.ring_attention`, including
    sliding ``window`` (causal only).
    """
    if mesh is None:
        from ..parallel.mesh import build_mesh

        mesh = build_mesh()
    p = mesh.shape[axis_name]
    t, h = q.shape[1], q.shape[2]
    if t % p:
        raise ValueError(f"sequence length {t} not divisible by group size {p}")
    if h % p:
        raise ValueError(f"head count {h} not divisible by group size {p}")
    return sharded_seq_attention(
        "ulysses", _ulysses_local, mesh, axis_name, causal, q, k, v,
        window=window,
    )
