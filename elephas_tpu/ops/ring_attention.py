"""Ring attention: sequence-parallel exact attention over the mesh.

EXTENSION BEYOND THE REFERENCE. The reference has no long-context support of
any kind (SURVEY.md §5.7: sequence length scales only as far as one worker's
memory) — this module is the TPU-native answer to that gap, provided as an
explicitly-labeled extension: exact (not approximate) attention over
sequences sharded across the ``"data"`` mesh axis, so maximum sequence length
scales linearly with device count.

Algorithm (Ring Attention, Liu et al. 2023; flash-style online softmax):
queries stay put; key/value blocks rotate around the device ring via
``jax.lax.ppermute`` (nearest-neighbor ICI transfers — the topology TPUs are
built for). Each of the ``P`` steps computes blockwise scores of the local
queries against the visiting KV block and folds them into a running
``(max, sum, weighted-acc)`` softmax state, so no ``[T, T]`` matrix and no
gathered KV ever materialize. Peak memory per chip: ``O(T/P · d)`` for state
plus one visiting block — sequence length scales with the ring size.

Causal masking uses absolute positions derived from each block's origin rank,
so results are bit-comparable to full attention on the unsharded sequence
(``attention_reference``, the test oracle in
``tests/ops/test_ring_attention.py``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size

from ..parallel.mesh import DATA_AXIS
from .flash_attention import fold_softmax_block, repeat_kv_heads


def attention_reference(q, k, v, causal: bool = False, window=None,
                        scale=None):
    """Plain full attention — the single-device test oracle (the Ulysses
    local body uses blockwise ``flash_attention`` instead, avoiding this
    function's ``[T, T]`` score matrix).

    ``q``: ``[B, T, H, D]``; ``k``/``v``: ``[B, T, H, D]`` or fewer
    (divisor) KV heads — grouped-query attention. Returns ``[B, T, H, D]``
    in the input dtype. Scores, softmax, and the value sum accumulate in
    float32 even for bf16 inputs — summing a long sequence's normalizer in
    an 8-bit mantissa loses exactly the precision flash/ring practice warns
    about, so every attention path in the package shares the f32 rule.

    ``window`` (requires ``causal``): sliding-window attention — query
    ``t`` sees keys ``(t-window, t]``, i.e. the last ``window`` positions
    including itself (the Mistral convention). ``scale`` multiplies the
    scores (default ``D ** -0.5``; a latent-attention model's carries its
    rotary scaling's factor); ``v`` may have another head size than ``q``
    and ``k``, and the output then has ``v``'s.
    """
    if window is not None and not causal:
        raise ValueError("window requires causal attention")
    k = repeat_kv_heads(k, q.shape[2])
    v = repeat_kv_heads(v, q.shape[2])
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
    ) * scale
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        mask = jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None]
        if window is not None:
            mask &= jnp.arange(tk)[None, :] > (
                jnp.arange(tq)[:, None] - int(window))
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
    )
    return out.astype(q.dtype)


def _ring_attention_local(q, k, v, causal: bool, axis_name: str,
                          window=None):
    """Per-shard body: runs INSIDE shard_map. ``q``: local sequence block
    ``[B, Tb, H, D]``; ``k``/``v`` may carry fewer (divisor) KV heads —
    the ring's ppermute hops then move only the small blocks, and heads
    broadcast at the local score compute. ``window`` (causal only):
    sliding-window attention masked on ABSOLUTE positions, so windows
    spanning any number of shard boundaries are exact."""
    if window is not None and not causal:
        raise ValueError("window requires causal attention")
    p = axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = d ** -0.5
    qpos = rank * tq + jnp.arange(tq)  # absolute query positions

    def fold_block(j, m, l, acc, kb, vb):
        """Fold the visiting KV block (which started at rank ``rank - j``)
        into the float32 online-softmax state (shared fold — the
        ``isneginf`` guard logic lives once, in
        ``flash_attention.fold_softmax_block``)."""
        src = (rank - j) % p
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, repeat_kv_heads(kb, h),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST
        ) * scale
        if causal:
            kpos = src * tk + jnp.arange(tk)
            mask = kpos[None, :] <= qpos[:, None]  # [Tq, Tk]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - int(window)
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        vb_full = jnp.transpose(repeat_kv_heads(vb, h), (0, 2, 1, 3))
        return fold_softmax_block(scores, vb_full, m, l, acc)

    def step(j, carry):
        m, l, acc, kb, vb = carry
        m, l, acc = fold_block(j, m, l, acc, kb, vb)
        # rotate KV one hop around the ring
        perm = [(i, (i + 1) % p) for i in range(p)]
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return m, l, acc, kb, vb

    # Accumulators in float32 regardless of input dtype (flash/ring practice:
    # bf16 inputs must not accumulate the normalizer in bf16).
    m0 = jnp.full((b, h, tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    acc0 = jnp.zeros((b, h, tq, d), jnp.float32)
    # p-1 rotated steps, then the last visiting block folded without the
    # final (discarded) rotation — saves one ppermute pair per call.
    m, l, acc, kb, vb = jax.lax.fori_loop(0, p - 1, step, (m0, l0, acc0, k, v))
    m, l, acc = fold_block(p - 1, m, l, acc, kb, vb)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B, Tq, H, D]


def _ring_flash_local(q, k, v, causal: bool, axis_name: str,
                      interpret: bool = False, window=None):
    """TPU per-shard ring body: per-visit Pallas flash + lse merge.

    Each visiting KV block is attended with the fused
    :func:`~elephas_tpu.ops.pallas_flash.flash_attention_with_lse` kernel
    (score tiles stay in VMEM — the jnp fold above materializes a
    ``[B, H, Tq, Tk]`` score tensor in HBM per visit), and the per-visit
    normalized partials merge by their logsumexp:

        out_{S∪j} = (out_S·e^{lse_S} + o_j·e^{lse_j}) / e^{logaddexp}

    computed max-shifted. Causality is decided per VISIT from the block's
    origin rank — fully visible (origin < rank, plain flash), the diagonal
    (origin == rank, causal flash), or skipped (origin > rank) via
    ``lax.switch``; within-block positions then need no global offsets.
    Gradients flow through the kernel's custom VJP (the lse cotangent folds
    into its Δ term) and the jnp merge — no hand-written ring backward.
    Autodiff stores per-visit residuals (O(P · local block) — the memory
    the forward saves is the score tensor, not the residual stream).

    ``window`` (causal only) extends the per-visit classification:
    wholly-expired blocks (every key below every query's window) SKIP —
    the compute is O(T·window) as the window shrinks — the diagonal runs
    the kernel's own windowed mask, still-fully-visible blocks run plain
    flash, and the ≤⌈window/Tk⌉ boundary blocks whose visibility is
    PARTIAL fall back to one materialized banded-score fold (the kernel's
    static window mask cannot express a traced cross-block offset).
    """
    p = axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    from .pallas_flash import flash_attention_with_lse

    if window is not None and not causal:
        raise ValueError("window requires causal attention")
    b, tq, h, _ = q.shape
    tk = k.shape[1]
    perm = [(i, (i + 1) % p) for i in range(p)]

    from .pallas_flash import _BK, _BQ

    def full(q, kb, vb):
        return flash_attention_with_lse(q, kb, vb, False, _BQ, _BK,
                                        interpret)

    def diag(q, kb, vb):
        return flash_attention_with_lse(q, kb, vb, True, _BQ, _BK,
                                        interpret, window=window)

    def skip(q, kb, vb):
        return (jnp.zeros(q.shape, q.dtype),
                jnp.full((b, tq, h), -jnp.inf, jnp.float32))

    def visit(acc, lse_acc, kb, vb, j):
        src = (rank - j) % p
        if causal and window is not None:
            w = int(window)
            kpos0 = src * tk  # visiting block's absolute key origin
            # 0 skip: causally invisible OR wholly below every query's
            #   window (max key < min query − (w−1));
            # 1 diag: the resident block — kernel-masked causal+window;
            # 2 full: earlier block, newest-possible-expiry query still
            #   sees its oldest key (min key > max query − w);
            # 3 partial: earlier block crossed by the window boundary —
            #   banded jnp fold on absolute positions.
            earlier = src < rank
            expired = kpos0 + tk - 1 < rank * tq - (w - 1)
            full_vis = kpos0 > (rank * tq + tq - 1) - w

            def partial_blk(q, kb, vb):
                scale = q.shape[-1] ** -0.5
                scores = jnp.einsum(
                    "bqhd,bkhd->bhqk", q, repeat_kv_heads(kb, h),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST
                ) * scale
                qpos = rank * tq + jnp.arange(tq)
                kpos = kpos0 + jnp.arange(tk)
                mask = (kpos[None, :] <= qpos[:, None]) & (
                    kpos[None, :] > qpos[:, None] - w)
                scores = jnp.where(mask[None, None], scores, -jnp.inf)
                m = jnp.max(scores, axis=-1)
                safe = jnp.where(jnp.isneginf(m), 0.0, m)
                e = jnp.exp(scores - safe[..., None])
                e = jnp.where(mask[None, None], e, 0.0)
                l = jnp.sum(e, axis=-1)
                o = jnp.einsum(
                    "bhqk,bkhd->bqhd", e, repeat_kv_heads(vb, h),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST
                ) / jnp.transpose(jnp.maximum(l, 1e-30), (0, 2, 1))[
                    ..., None]
                lse = jnp.where(jnp.isneginf(m), -jnp.inf,
                                safe + jnp.log(jnp.maximum(l, 1e-30)))
                return (o.astype(q.dtype),
                        jnp.transpose(lse, (0, 2, 1)))  # [B, Tq, H]

            idx = jnp.where(
                src == rank, 1,
                jnp.where(~earlier | expired, 0,
                          jnp.where(full_vis, 2, 3))).astype(jnp.int32)
            o_j, lse_j = jax.lax.switch(
                idx, [skip, diag, full, partial_blk], q, kb, vb)
        elif causal:
            # 0: origin > rank (invisible), 1: diagonal, 2: fully visible
            idx = (src < rank).astype(jnp.int32) * 2 + (
                src == rank
            ).astype(jnp.int32)
            o_j, lse_j = jax.lax.switch(idx, [skip, diag, full], q, kb, vb)
        else:
            o_j, lse_j = full(q, kb, vb)
        m = jnp.maximum(lse_acc, lse_j)
        w_acc = jnp.exp(lse_acc - m)   # first visit: exp(-inf − finite) = 0
        w_j = jnp.exp(lse_j - m)
        denom = w_acc + w_j            # ≥ 1 (the max contributes exactly 1)
        acc = (acc * w_acc[..., None]
               + o_j.astype(jnp.float32) * w_j[..., None]) / denom[..., None]
        return acc, m + jnp.log(denom)

    def fold(carry, j):
        acc, lse_acc, kb, vb = carry
        acc, lse_acc = visit(acc, lse_acc, kb, vb, j)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (acc, lse_acc, kb, vb), None

    acc0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full((b, tq, h), -jnp.inf, jnp.float32)
    # p-1 rotated steps, then the last visiting block folded WITHOUT the
    # trailing (discarded) rotation — saves one ppermute pair per call,
    # mirroring the jnp fold above.
    (acc, lse_acc, kb, vb), _ = jax.lax.scan(
        fold, (acc0, lse0, k, v), jnp.arange(p - 1)
    )
    acc, _ = visit(acc, lse_acc, kb, vb, p - 1)
    return acc.astype(q.dtype)


def ring_attention_local(q, k, v, causal: bool, axis_name: str,
                         window=None):
    """Per-shard ring attention body for composing INSIDE a larger
    shard_map program (e.g. the sequence-parallel transformer in
    ``models/transformer.py``): the fused Pallas path on TPU, the jnp
    online-softmax fold elsewhere. Both branches are pinned against the
    dense ``attention_reference`` oracle (the Pallas one in interpret mode,
    ``tests/ops/test_pallas_flash.py``). ``window``: sliding-window
    attention on absolute positions (causal only)."""
    from .pallas_ops import is_tpu_backend

    if is_tpu_backend():
        return _ring_flash_local(q, k, v, causal, axis_name, window=window)
    return _ring_attention_local(q, k, v, causal, axis_name, window=window)

_COMPILED = {}


def sharded_seq_attention(tag: str, local_fn, mesh, axis_name: str,
                          causal: bool, q, k, v, window=None):
    """Shared harness for the sequence-parallel attention schedules (ring,
    Ulysses): shard ``q``/``k``/``v`` along the sequence dim over
    ``axis_name``, run ``local_fn`` (a per-shard body taking
    ``causal``/``axis_name``/``window`` kwargs) inside ``shard_map``, and
    cache the compiled executable per ``(tag, mesh, axis, causal,
    window)`` — shapes/dtypes hit jit's own cache; the dict is
    FIFO-bounded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(None, axis_name)  # shard the sequence dim
    key = (tag, mesh, axis_name, causal, window)
    fn = _COMPILED.get(key)
    if fn is None:
        if len(_COMPILED) >= 16:  # bound the executable cache
            _COMPILED.pop(next(iter(_COMPILED)))
        fn = jax.jit(
            shard_map(
                partial(local_fn, causal=causal, axis_name=axis_name,
                        window=window),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )
        )
        _COMPILED[key] = fn
    shard = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(a, shard) for a in (q, k, v))
    return fn(q, k, v)


def ring_attention(q, k, v, mesh=None, causal: bool = False,
                   axis_name: str = DATA_AXIS, window=None):
    """Exact attention over sequences sharded across a mesh axis.

    ``q``/``k``/``v``: ``[B, T, H, D]`` with ``T`` divisible by the ring size
    (the ``axis_name`` extent of ``mesh``). Inputs may be host arrays (they
    are sharded along ``T``) or already sharded. Equals
    :func:`attention_reference` on the gathered sequence (including
    ``window``, masked on absolute positions); bf16 inputs accumulate in
    float32.
    """
    if mesh is None:
        from ..parallel.mesh import build_mesh

        mesh = build_mesh()
    p = mesh.shape[axis_name]  # ring size = this axis, not the whole mesh
    t = q.shape[1]
    if t % p:
        raise ValueError(f"sequence length {t} not divisible by ring size {p}")
    return sharded_seq_attention(
        "ring", ring_attention_local, mesh, axis_name, causal, q, k, v,
        window=window,
    )
