"""Expert parallelism: mixture-of-experts FFN over an ``"expert"`` mesh axis.

EXTENSION BEYOND THE REFERENCE. Expert parallelism is "explicitly ABSENT"
from the reference (SURVEY.md §2.3) — every executor holds the complete
model. This module scales *parameter count* past one chip the MoE way
(GShard, Lepikhin et al. 2020; Switch, Fedus et al. 2021): ``E`` feed-forward
experts are sharded over an ``"expert"`` mesh axis, a learned router sends
each token to its top-k experts, and the token blocks travel to the experts'
devices and back via two ``all_to_all``s — active FLOPs per token stay
constant while total parameters scale with the mesh.

Dispatch is the GShard einsum formulation: a ``[N, E, C]`` one-hot dispatch
tensor (capacity ``C`` slots per expert) gathers token blocks
``[E, C, D]``, the expert-axis ``all_to_all`` re-shards E→local /
gathers source shards, experts run as one vmapped batched FFN (a single
``[E/P, P·C, D]`` MXU-friendly matmul per projection — no scalar routing
loops anywhere), and the transpose ``all_to_all`` + combine einsum scatter
the outputs home. Tokens beyond an expert's capacity are dropped (their
combine weight is zero → they pass through the residual path untouched);
the oracle (:meth:`MoEFeedForward.apply_reference`) reproduces the same
dispatch math bit-for-bit on one device, which is what the tests check.

Token sharding: the leading token dim may be sharded over BOTH the data and
expert axes (``P(("data", "expert"))``) — dp groups and expert groups then
carry disjoint token blocks, and :func:`build_ep_train_step` restores every
gradient invariant with the minimal collectives (router grads psum over both
axes, expert grads over ``"data"`` only).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import DATA_AXIS, build_mesh_2axis
from .param_utils import (
    gather_host,
    glorot,
    make_opt_init,
    opt_state_specs,
    shard_by_specs,
)

EXPERT_AXIS = "expert"
# the per-expert stacked leaves (everything else a layer has is shared)
EXPERT_STACKS = ("w1", "b1", "w2", "b2", "w3")


def build_mesh_ep(data: Optional[int] = None, expert: int = 1,
                  devices: Optional[Sequence] = None) -> Mesh:
    """A 2-D ``("data", "expert")`` mesh; ``expert`` = expert-parallel degree."""
    return build_mesh_2axis(EXPERT_AXIS, data=data, second=expert,
                            devices=devices)


@jax.named_scope("moe_route")
def _top_k_dispatch(gates, capacity: int, k: int):
    """GShard top-k dispatch from router probabilities.

    ``gates`` ``[N, E]`` (softmax rows) → ``(dispatch [N, E, C] one-hot,
    combine [N, E, C] weights, aux_stats)``. Slots are claimed in token
    order, k-th choices queueing behind all (k-1)-th choices (the GShard
    priority rule), so the result is deterministic and oracle-reproducible.
    Combine weights renormalize over the token's *kept* choices.
    """
    n, e = gates.shape
    masks = []
    g = gates
    for _ in range(k):
        idx = jnp.argmax(g, axis=-1)
        m = jax.nn.one_hot(idx, e, dtype=gates.dtype)
        masks.append(m)
        g = g * (1.0 - m)  # exclude chosen expert from the next round

    # capacity positions: k-th choices come after all earlier choices
    pos, counts = [], jnp.zeros((e,), gates.dtype)
    for m in masks:
        p_ = jnp.cumsum(m, axis=0) - m + counts[None, :]
        pos.append(p_)
        counts = counts + jnp.sum(m, axis=0)

    dispatch = jnp.zeros((n, e, capacity), gates.dtype)
    combine_w = jnp.zeros((n, e), gates.dtype)
    for m, p_ in zip(masks, pos):
        keep = m * (p_ < capacity).astype(gates.dtype)
        slot = jnp.sum(p_ * keep, axis=-1).astype(jnp.int32)  # [N]
        dispatch = dispatch + keep[:, :, None] * jax.nn.one_hot(
            slot, capacity, dtype=gates.dtype
        )[:, None, :]
        combine_w = combine_w + keep * gates
    denom = jnp.maximum(jnp.sum(combine_w, axis=-1, keepdims=True), 1e-9)
    combine = (combine_w / denom)[:, :, None] * dispatch
    # aux-loss ingredients (Switch eq. 4): per-expert dispatch counts of the
    # FIRST choice and summed router probs, plus the token count.
    aux = (jnp.sum(masks[0], axis=0), jnp.sum(gates, axis=0),
           jnp.asarray(float(n), gates.dtype))
    return dispatch, combine, aux


@jax.named_scope("moe_route")
def _top_k_select(gates, capacity: int, k: int):
    """:func:`_top_k_dispatch`'s selection in INDEX form (no ``[N, E, C]``
    tensors): same iterated-argmax choice order, same GShard priority rule
    (k-th choices queue behind all (k-1)-th choices), same keep-if-slot<C
    decision, same renormalized combine weights — so a grouped-matmul
    executor can reproduce the one-hot path's routing bit-for-bit.

    Returns ``(eidx [N, k] int32, slot [N, k] int32, combine [N, k],
    (c1 [E], gsum [E]))`` where ``slot`` is each choice's capacity-queue
    position at its expert (``>= capacity`` ⇔ dropped), ``combine`` is
    zero for dropped choices, and ``c1``/``gsum`` are the aux-loss
    ingredients (first-choice counts, summed router probs).
    """
    n, e = gates.shape
    g = gates
    eidxs, slots, keeps = [], [], []
    counts = jnp.zeros((e,), gates.dtype)
    first = None
    for _ in range(k):
        idx = jnp.argmax(g, axis=-1)
        m = jax.nn.one_hot(idx, e, dtype=gates.dtype)
        if first is None:
            first = m
        pos = jnp.cumsum(m, axis=0) - m + counts[None, :]
        slot = jnp.sum(pos * m, axis=-1)  # [N] queue position at its expert
        keeps.append(slot < capacity)
        slots.append(slot.astype(jnp.int32))
        eidxs.append(idx.astype(jnp.int32))
        counts = counts + jnp.sum(m, axis=0)
        g = g * (1.0 - m)  # exclude chosen expert from the next round
    eidx = jnp.stack(eidxs, axis=1)
    slot = jnp.stack(slots, axis=1)
    keep = jnp.stack(keeps, axis=1)
    gv = jnp.take_along_axis(gates, eidx, axis=1) * keep.astype(gates.dtype)
    denom = jnp.maximum(jnp.sum(gv, axis=1, keepdims=True), 1e-9)
    return eidx, slot, gv / denom, (jnp.sum(first, axis=0),
                                    jnp.sum(gates, axis=0))


@jax.custom_vjp
def _rows_to_slots(x, tos, flat, keep):
    """``blocks_flat[s] = x[tos[s]]`` (sentinel rows → 0), with a GATHER
    backward: TPU scatter-add (the default transpose of a gather) serializes
    on row conflicts, but the slot assignment is injective — token ``n``'s
    kept copies live exactly at ``flat[n, j]`` — so ``dx[n]`` is a gather of
    those ``k`` rows masked by ``keep`` and summed. ``tos [S]`` maps slot →
    token (sentinel = n), ``flat [N, k]`` maps (token, choice) → slot
    (clipped for drops), ``keep [N, k]`` masks dropped choices."""
    with jax.named_scope("moe_dispatch"):
        return jnp.take(x, tos, axis=0, mode="fill", fill_value=0)


def _rows_to_slots_fwd(x, tos, flat, keep):
    return _rows_to_slots(x, tos, flat, keep), (tos, flat, keep)


@jax.named_scope("moe_dispatch")
def _rows_to_slots_bwd(res, g):
    _, flat, keep = res
    n, k = flat.shape
    dx = jnp.take(g, flat.reshape(-1), axis=0).reshape(n, k, -1)
    dx = jnp.sum(dx * keep[..., None].astype(g.dtype), axis=1)
    return dx, None, None, None


_rows_to_slots.defvjp(_rows_to_slots_fwd, _rows_to_slots_bwd)


@jax.custom_vjp
def _slots_to_rows(out_flat, flat, cell):
    """``rows[i] = out_flat[flat[i]]`` for flattened (token, choice) ``i``,
    with a GATHER backward: ``cell [S]`` is the inverse map (slot → claiming
    flat pair, sentinel = N·k ⇒ out-of-bounds ⇒ zero fill). Dropped pairs
    read a clipped slot forward but their cotangent is zero (combine weight
    0), so the inverse covering only KEPT pairs is exact."""
    with jax.named_scope("moe_combine"):
        return jnp.take(out_flat, flat, axis=0)


def _slots_to_rows_fwd(out_flat, flat, cell):
    return _slots_to_rows(out_flat, flat, cell), cell


@jax.named_scope("moe_combine")
def _slots_to_rows_bwd(cell, g):
    return (jnp.take(g, cell, axis=0, mode="fill", fill_value=0),
            None, None)


_slots_to_rows.defvjp(_slots_to_rows_fwd, _slots_to_rows_bwd)


def _ffn_mm(xs, w, gmap, use_kernel: bool, interpret: bool,
            transpose: bool = False):
    """One grouped projection for :func:`_moe_ffn_swiglu` — Pallas kernel
    or jnp reference, forward-only (differentiation is hand-written in
    the caller's VJP)."""
    from ..ops import grouped_matmul as G

    if use_kernel:
        fn = G.gmm_t if transpose else G.gmm
        return fn(xs, w, gmap, interpret)
    return G.gmm_reference(xs, w, gmap, transpose_rhs=transpose)


def _ffn_tgmm(lhs, g, gmap, n_groups: int, dtype, use_kernel: bool,
              interpret: bool):
    from ..ops import grouped_matmul as G

    if use_kernel:
        return G.tgmm(lhs, g, gmap, n_groups, dtype, interpret)
    return G.tgmm_reference(lhs, g, gmap, n_groups).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _moe_ffn_swiglu(xs, w1, w2, w3, gmap, use_kernel, interpret):
    """Grouped swiglu FFN (``out = (silu(xs·w1[g]) ⊙ (xs·w3[g])) · w2[g]``)
    with a RECOMPUTE backward: residuals are ``(xs, weights, gmap)`` only.
    Saving ``u``/``v``/``h`` (three ``[M, F]`` tensors per layer) through
    the layer scan costs more in carry-stacking HBM traffic than the two
    grouped matmuls that rebuild them, and
    keeping the silu-gradient chain inside one VJP lets XLA fuse it as a
    single bf16 elementwise region instead of the generic AD graph."""
    u = _ffn_mm(xs, w1, gmap, use_kernel, interpret)
    v = _ffn_mm(xs, w3, gmap, use_kernel, interpret)
    h = jax.nn.silu(u) * v
    return _ffn_mm(h, w2, gmap, use_kernel, interpret)


def _moe_ffn_swiglu_fwd(xs, w1, w2, w3, gmap, use_kernel, interpret):
    out = _moe_ffn_swiglu(xs, w1, w2, w3, gmap, use_kernel, interpret)
    return out, (xs, w1, w2, w3, gmap)


@jax.named_scope("moe_experts")
def _moe_ffn_swiglu_bwd(use_kernel, interpret, res, dout):
    xs, w1, w2, w3, gmap = res
    E = w1.shape[0]
    u = _ffn_mm(xs, w1, gmap, use_kernel, interpret)
    v = _ffn_mm(xs, w3, gmap, use_kernel, interpret)
    sig = jax.nn.sigmoid(u)
    su = u * sig
    h = su * v
    dh = _ffn_mm(dout, w2, gmap, use_kernel, interpret, transpose=True)
    dv = dh * su
    du = dh * v * (sig + su * (1.0 - sig))  # d silu(u) = σ(u)(1 + u(1-σ))
    dxs = (
        _ffn_mm(du, w1, gmap, use_kernel, interpret, transpose=True)
        + _ffn_mm(dv, w3, gmap, use_kernel, interpret, transpose=True)
    )
    dw1 = _ffn_tgmm(xs, du, gmap, E, w1.dtype, use_kernel, interpret)
    dw3 = _ffn_tgmm(xs, dv, gmap, E, w3.dtype, use_kernel, interpret)
    dw2 = _ffn_tgmm(h, dout, gmap, E, w2.dtype, use_kernel, interpret)
    return dxs, dw1, dw2, dw3, None


_moe_ffn_swiglu.defvjp(_moe_ffn_swiglu_fwd, _moe_ffn_swiglu_bwd)


@jax.named_scope("moe_route")
def _expert_choice_dispatch(gates, capacity: int):
    """Expert-choice routing (Zhou et al. 2022): each EXPERT picks its
    top-``capacity`` tokens by gate score (ties break to the lowest token
    index — ``lax.top_k`` is deterministic, so shard and oracle agree);
    the combine weight is the gate score itself. Load is perfectly balanced
    by construction — every expert processes exactly ``capacity`` slots —
    so no auxiliary loss is needed; tokens may be picked by 0..E experts.

    Returns ``(dispatch [E, C, N] one-hot, combine [E, C, N] weights)``.
    """
    vals, idx = jax.lax.top_k(gates.T, capacity)  # [E, C] over tokens
    dispatch = jax.nn.one_hot(idx, gates.shape[0], dtype=gates.dtype)
    return dispatch, dispatch * vals[..., None]


class MoEFeedForward:
    """Top-k routed expert FFN (``D → F → D`` per expert; relu by
    default, or swiglu/gelu via ``activation`` with optional biases —
    the Mixtral-family expert shape is ``activation="swiglu",
    bias=False``).

    ``capacity_factor`` sizes each expert's buffer PER SOURCE SHARD as
    ``ceil(cf · k · N_shard / E)`` (``N_shard`` = that shard's token count),
    so an expert's total slots across the group are ``≈ cf · k · N_group / E``
    — the GShard budget, paid as ``P`` independent per-shard quotas (slightly
    laxer than one global cumsum, but all_to_all-local: no cross-shard slot
    coordination). :meth:`init` returns FULL host params; :meth:`specs`
    shards the expert stacks over ``"expert"`` and replicates the router.
    """

    def __init__(self, d_model: int, d_ff: int, n_experts: int, k: int = 2,
                 capacity_factor: float = 1.25,
                 routing: str = "token_choice", activation: str = "relu",
                 bias: bool = True, param_dtype="float32",
                 scoring: str = "softmax", select_bias: bool = False,
                 norm_topk: bool = True, routed_scale: float = 1.0,
                 n_shared: int = 0, held=None):
        if n_experts < k:
            raise ValueError(f"need n_experts >= k, got {n_experts} < {k}")
        if routing not in ("token_choice", "expert_choice"):
            raise ValueError(f"Unknown routing: {routing}")
        if activation not in ("relu", "gelu", "swiglu"):
            raise ValueError(f"Unknown activation: {activation}")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"Unknown scoring: {scoring}")
        # The DeepSeek-V3-style router and the held share (the defaults
        # are the GShard layer above, unchanged): ``scoring="sigmoid"``
        # scores each expert on its own; ``select_bias`` adds a learned
        # ``wg_b [E]`` to the scores for the top-k SELECTION only;
        # ``norm_topk`` divides the chosen scores by their sum and
        # ``routed_scale`` multiplies them; ``n_shared`` shared experts
        # (one SwiGLU of width ``n_shared * d_ff``) see every token
        # ungated; ``held=(e0, n)`` tells the layer it holds experts
        # ``e0..e0+n`` of the ``n_experts`` the router scores: its stacks
        # are ``[n, ...]``, routing is over all of them, and it computes
        # its own experts' part of the result (plus the shared expert).
        # Any of these takes the layer off the capacity-slot executors and
        # onto :meth:`apply_dropless`, which drops no token at any load.
        self.scoring = scoring
        self.select_bias = bool(select_bias)
        self.norm_topk = bool(norm_topk)
        self.routed_scale = float(routed_scale)
        self.n_shared = int(n_shared)
        if held is not None:
            e0, nh = (int(v) for v in held)
            if nh < 1 or e0 < 0 or e0 + nh > n_experts:
                raise ValueError(
                    f"held={held} is no range of the {n_experts} experts")
            held = (e0, nh)
        self.held = held
        self.dropless = (scoring != "softmax" or self.select_bias
                         or not self.norm_topk or self.routed_scale != 1.0
                         or self.n_shared > 0 or held is not None)
        if self.dropless and (routing != "token_choice"
                              or activation != "swiglu" or bias):
            raise ValueError(
                "sigmoid scores, a selection bias, a shared expert and a "
                "held share run on the dropless executor, which computes "
                "bias-free SwiGLU experts under token_choice routing")
        self.d_model = d_model
        self.d_ff = d_ff
        self.n_experts = n_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.routing = routing
        self.activation = activation
        self.bias = bool(bias)
        # Storage dtype for the EXPERT stacks only. The router (wg) always
        # stays float32 — routing argmaxes must be bit-stable against the
        # oracle. bf16 storage kills the dominant per-step convert traffic
        # (the stacks are the big tensors: E·3·D·F params): the use-site
        # ``astype(compute_dtype)`` becomes a no-op, and gradients arrive
        # bf16 (optimizer math still runs f32 — adam_compact upcasts, and
        # the update add rounds once per step).
        self.param_dtype = jnp.dtype(param_dtype)

    def param_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        """Full (unsharded) shape/dtype per param — the shape-only source for
        :meth:`init` and the train-step builder's optimizer-state specs."""
        D, F = self.d_model, self.d_ff
        E = self.held_range[1]
        pd = self.param_dtype
        shapes = {
            "wg": jax.ShapeDtypeStruct((D, self.n_experts), jnp.float32),
            "w1": jax.ShapeDtypeStruct((E, D, F), pd),
            "b1": jax.ShapeDtypeStruct((E, F), pd),
            "w2": jax.ShapeDtypeStruct((E, F, D), pd),
            "b2": jax.ShapeDtypeStruct((E, D), pd),
        }
        if self.activation == "swiglu":
            shapes["w3"] = jax.ShapeDtypeStruct((E, D, F), pd)
        if not self.bias:
            del shapes["b1"], shapes["b2"]
        if self.select_bias:
            shapes["wg_b"] = jax.ShapeDtypeStruct((self.n_experts,),
                                                  jnp.float32)
        if self.n_shared:
            Fs = self.n_shared * F
            shapes["ws1"] = jax.ShapeDtypeStruct((D, Fs), pd)
            shapes["ws3"] = jax.ShapeDtypeStruct((D, Fs), pd)
            shapes["ws2"] = jax.ShapeDtypeStruct((Fs, D), pd)
        return shapes

    def init(self, seed: int = 0) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(seed)
        out = {}
        for name, sds in self.param_shapes().items():
            if name == "wg_b":
                out[name] = (rng.normal(size=sds.shape) * 0.02).astype(
                    sds.dtype)
            elif name.startswith("w"):
                out[name] = glorot(rng, *sds.shape, dtype=sds.dtype)
            else:
                out[name] = np.zeros(sds.shape, sds.dtype)
        return out

    def expert_keys(self):
        """The per-expert stacked param names — what shards over the
        expert axis. The router (``wg``, ``wg_b``) and the shared expert
        (``ws*``) are :meth:`shared_keys`."""
        return tuple(k for k in self.param_shapes() if k in EXPERT_STACKS)

    def shared_keys(self):
        """What every holder of a share computes alike: the router, its
        selection bias and the shared expert."""
        return tuple(k for k in self.param_shapes()
                     if k not in EXPERT_STACKS)

    @property
    def held_range(self):
        """``(first, count)`` of the experts this layer holds: all of them
        without ``held``."""
        return (0, self.n_experts) if self.held is None else self.held

    def specs(self) -> Dict[str, P]:
        out = {k: P() for k in self.shared_keys()}
        out.update({k: P(EXPERT_AXIS) for k in self.expert_keys()})
        return out

    def shard_params(self, mesh: Mesh, params: Dict[str, Any]) -> Dict[str, Any]:
        return shard_by_specs(mesh, self.specs(), params)

    def gather_params(self, params: Dict[str, Any]) -> Dict[str, np.ndarray]:
        return gather_host(params)

    def capacity(self, n_shard: int) -> int:
        """Per-(expert, source-shard) slot count for ``n_shard`` local
        tokens: ``ceil(cf · k · n / E)`` for BOTH routings. Under
        token-choice, ``k`` is the per-token expert count the buffer must
        absorb; under expert-choice there is no per-token top-k — ``k``
        instead sets the target MEAN experts per token (the EC paper's
        capacity knob), so ``k=2, cf=1.0`` gives each expert ``2n/E``
        slots."""
        return max(
            1, int(math.ceil(self.capacity_factor * self.k * n_shard
                             / self.n_experts))
        )

    @jax.named_scope("moe_experts")
    def _expert_ffn(self, *args):
        """One expert's FFN over its ``[C, D]`` block (vmapped over E).
        Argument order matches :meth:`_expert_args`."""
        if self.activation == "swiglu":
            if self.bias:
                w1, w2, w3, b1, b2, x = args
                h = jax.nn.silu(jnp.dot(x, w1) + b1) * jnp.dot(x, w3)
                return jnp.dot(h, w2) + b2
            w1, w2, w3, x = args
            h = jax.nn.silu(jnp.dot(x, w1)) * jnp.dot(x, w3)
            return jnp.dot(h, w2)
        act = jax.nn.relu if self.activation == "relu" else             (lambda u: jax.nn.gelu(u, approximate=True))
        if self.bias:
            w1, w2, b1, b2, x = args
            return jnp.dot(act(jnp.dot(x, w1) + b1), w2) + b2
        w1, w2, x = args
        return jnp.dot(act(jnp.dot(x, w1)), w2)

    @jax.named_scope("moe_experts")
    def _expert_args(self, params, dtype=None):
        """Expert stacks in the positional order ``_expert_ffn`` takes
        (weights first, then biases — matching ``expert_keys`` sorted
        w-before-b), cast to ``dtype`` when given: bf16 models run their
        experts on the MXU fast path, f32 models are unchanged."""
        ws = [params[k] for k in self.expert_keys() if k.startswith("w")]
        bs = [params[k] for k in self.expert_keys() if k.startswith("b")]
        if dtype is None:
            return ws + bs
        return [a.astype(dtype) for a in ws + bs]

    @jax.named_scope("moe_route")
    def _gates(self, params, x, f32: bool = False):
        """Router probabilities ``[N, E]`` of tokens ``x`` ``[N, D]``; the
        sort-based executors route in float32 whatever ``x`` is."""
        if self.dropless:
            raise ValueError(
                "this layer (sigmoid scores, selection bias, shared expert "
                "or held share) runs through apply_dropless; the capacity "
                "executors compute the softmax top-k layer only")
        wg = params["wg"]
        if f32:
            x, wg = x.astype(jnp.float32), wg.astype(jnp.float32)
        return jax.nn.softmax(jnp.dot(x, wg), axis=-1)

    @jax.named_scope("moe")
    def apply(self, params: Dict[str, Any], x, axis_name: str = EXPERT_AXIS):
        """Forward INSIDE shard_map. ``x``: local tokens ``[N_l, D]``;
        expert stacks in ``params`` are local ``[E/P, ...]`` shards.
        Returns ``(y [N_l, D], aux_loss scalar)`` — aux is the Switch
        load-balancing loss computed from group-global counts (psummed over
        ``axis_name``), so it equals the oracle's value exactly."""
        n_l = x.shape[0]
        cap = self.capacity(n_l)
        D = self.d_model
        E = self.n_experts
        f32 = jnp.float32
        gates = self._gates(params, x)
        # Dispatch is INDEX-FORM (gather/scatter), not one-hot einsums: the
        # [N, E, C] dispatch/combine products cost O(N·E·C·D) FLOPs and —
        # because the one-hot tensors are f32 — used to promote the token
        # blocks (and therefore the whole expert FFN) to f32. Building
        # blocks by gather keeps them in the compute dtype and spends only
        # O(E·C·D) bandwidth; routing decisions, capacity keeps, and
        # combine weights are bit-identical (same _top_k_select math the
        # one-hot oracle reproduces). Combine math stays f32.
        if self.routing == "expert_choice":
            # an expert cannot pick more tokens than the shard holds
            ec_vals, ec_idx = jax.lax.top_k(gates.T, min(cap, n_l))
            blocks = jnp.take(x, ec_idx.reshape(-1), axis=0).reshape(
                E, -1, D)
        else:
            blocks, cell, flat, combine, c1, gsum = self._slot_dispatch(
                x, gates, cap)
        # E→local experts, gather the P source shards' slots:
        # [E, C, D] → [E/P, P·C, D]
        with jax.named_scope("moe_dispatch"):
            blocks = jax.lax.all_to_all(
                blocks, axis_name, split_axis=0, concat_axis=1, tiled=True
            )
        args = self._expert_args(params, blocks.dtype)
        out = jax.vmap(self._expert_ffn)(*args, blocks)
        # transpose re-shard: [E/P, P·C, D] → [E, C, D]
        with jax.named_scope("moe_combine"):
            out = jax.lax.all_to_all(
                out, axis_name, split_axis=1, concat_axis=0, tiled=True
            )
        if self.routing == "expert_choice":
            # scatter-add each expert's slots home, gate-weighted (f32);
            # perfectly balanced by construction → no aux loss
            y = jnp.zeros((n_l, D), f32).at[ec_idx.reshape(-1)].add(
                out.reshape(-1, D).astype(f32)
                * ec_vals.reshape(-1)[:, None].astype(f32))
            return y, jnp.asarray(0.0, jnp.float32)
        y = self._slot_combine(out, cell, flat, combine, n_l)
        # Switch aux loss on group-global stats: E · Σ_e f_e · p_e
        c1 = jax.lax.psum(c1, axis_name)
        gsum = jax.lax.psum(gsum, axis_name)
        nt = jax.lax.psum(
            jnp.asarray(float(n_l), gates.dtype), axis_name)
        aux = self.n_experts * jnp.sum((c1 / nt) * (gsum / nt))
        return y, aux

    @jax.named_scope("moe_dispatch")
    def _slot_dispatch(self, x, gates, cap: int):
        """token_choice index-form dispatch: ``x [N, D]`` + router ``gates``
        → ``(blocks [E, C, D], cell, flat, combine, c1, gsum)``.

        ONE small int scatter builds the inverse map ``cell[s]`` = the
        flattened (token, choice) pair claiming slot ``s`` (sentinel =
        ``N·k`` for empty cells; over-capacity pairs index out of bounds
        on the slot dim and are dropped). Everything else — the block
        build, the combine, and BOTH their AD transposes — is then pure
        gathers (:func:`_rows_to_slots` / :func:`_slots_to_rows`), and the
        blocks stay in ``x``'s dtype (no f32 promotion through one-hot
        products)."""
        n_l, E = x.shape[0], self.n_experts
        eidx, slot, combine, (c1, gsum) = _top_k_select(gates, cap, self.k)
        sent = n_l * self.k
        pair = jnp.arange(sent, dtype=jnp.int32).reshape(n_l, self.k)
        cell = jnp.full((E, cap), sent, jnp.int32).at[
            eidx.reshape(-1), slot.reshape(-1)
        ].set(pair.reshape(-1), mode="drop").reshape(-1)
        tok_of_cell = jnp.where(cell == sent, n_l, cell // self.k)
        keep = slot < cap
        flat = eidx * cap + jnp.minimum(slot, cap - 1)  # [N, k] slot ids
        # sentinel rows (empty slots) gather as zeros — exactly the
        # one-hot dispatch's zero padding
        blocks = _rows_to_slots(x, tok_of_cell, flat, keep).reshape(
            E, cap, self.d_model)
        return blocks, cell, flat, combine, c1, gsum

    @jax.named_scope("moe_combine")
    def _slot_combine(self, out, cell, flat, combine, n_l: int):
        """Weighted gather of each token's k expert outputs (f32 math)."""
        f32 = jnp.float32
        rows = _slots_to_rows(
            out.reshape(-1, self.d_model), flat.reshape(-1), cell
        ).reshape(n_l, self.k, self.d_model).astype(f32)
        return jnp.sum(rows * combine[..., None].astype(f32), axis=1)

    @jax.named_scope("moe")
    def apply_slots(self, params: Dict[str, Any], x, ep: int = 1):
        """:meth:`apply_reference`'s contract executed by the index-form
        (gather) dispatch — the sharded path's exact math with the
        all_to_alls elided. The fastest single-device executor measured on
        TPU (no ``[N, E, C]`` products, blocks stay in the compute dtype,
        both AD transposes are gathers). ``token_choice`` only."""
        if self.routing != "token_choice":
            raise ValueError(
                "apply_slots implements token_choice routing only; "
                "use apply_reference for expert_choice")
        n = x.shape[0]
        if n % ep:
            raise ValueError(f"{n} tokens not divisible by ep={ep}")
        cap = self.capacity(n // ep)
        args = None
        ys, c1s, gsums = [], [], []
        for blk in jnp.split(x, ep, axis=0):
            gates = self._gates(params, blk)
            blocks, cell, flat, combine, c1, gsum = self._slot_dispatch(
                blk, gates, cap)
            if args is None:
                args = self._expert_args(params, blocks.dtype)
            out = jax.vmap(self._expert_ffn)(*args, blocks)
            ys.append(self._slot_combine(out, cell, flat, combine,
                                         blk.shape[0]))
            c1s.append(c1)
            gsums.append(gsum)
        c1, gsum = sum(c1s), sum(gsums)
        aux = self.n_experts * jnp.sum((c1 / n) * (gsum / n))
        return jnp.concatenate(ys, axis=0), aux

    @jax.named_scope("moe_dispatch")
    def _tile_layout(self, eidx, slot, n: int, tm: int):
        """Tile-aligned sorted-by-expert row layout for the Pallas grouped
        matmul: expert ``e``'s (token, choice) pairs occupy contiguous rows
        ``off[e] + slot`` with ``off`` the exclusive cumsum of per-expert
        claim counts rounded UP to a multiple of ``tm`` (and at least one
        tile, so every expert's weight-grad block gets visited/zeroed —
        the :func:`..ops.grouped_matmul.tgmm` precondition). Static buffer
        height ``M_pad = k·N + E·tm`` bounds the padding at ``E·tm`` rows
        — at bench shapes ~6–12 %, vs the capacity path's ``cf−1`` = 25 %.

        Returns ``(row [N, k], inv [M_pad], tok_of_row [M_pad],
        gmap [M_pad/tm])``: ``row`` maps pair → buffer row (injective),
        ``inv`` its inverse (sentinel ``N·k`` for padding rows),
        ``tok_of_row`` the gather index building the buffer (sentinel
        ``N`` → zero fill), ``gmap`` the non-decreasing tile → expert map
        the kernels prefetch."""
        E, k = self.n_experts, self.k
        sizes = jnp.bincount(eidx.reshape(-1), length=E).astype(jnp.int32)
        padded = jnp.maximum((sizes + tm - 1) // tm, 1) * tm
        cum = jnp.cumsum(padded)
        off = cum - padded
        row = jnp.take(off, eidx, axis=0) + slot  # [N, k]
        # Σ padded ≤ k·N + E·tm; the buffer itself must ALSO be a tile
        # multiple (k·N need not be) or gmap/tile geometry shears.
        m_pad = -(-(n * k + E * tm) // tm) * tm
        sent = n * k
        inv = jnp.full((m_pad,), sent, jnp.int32).at[row.reshape(-1)].set(
            jnp.arange(sent, dtype=jnp.int32))
        tok_of_row = jnp.where(inv == sent, n, inv // k)
        tile_start = jnp.arange(m_pad // tm, dtype=jnp.int32) * tm
        gmap = jnp.clip(
            jnp.searchsorted(cum, tile_start, side="right"), 0, E - 1
        ).astype(jnp.int32)
        return row, inv, tok_of_row, gmap

    @jax.named_scope("moe_experts")
    def _gmm_ffn_fused(self, G, params, xs, gmap, use_kernel: bool,
                       interpret: bool):
        """The swiglu/bias-free expert FFN as ONE recompute-backward op
        (:func:`_moe_ffn_swiglu`): only ``xs`` and the weights are saved
        for the backward — ``u``/``v``/``h`` (the ``[M, F]`` tensors that
        dominate the layer scan's residual stacking) are recomputed from
        ``xs`` by two extra grouped matmuls, and the silu gradient chain
        stays inside one fused elementwise region."""
        cd = xs.dtype
        return _moe_ffn_swiglu(
            xs, params["w1"].astype(cd), params["w2"].astype(cd),
            params["w3"].astype(cd), gmap, use_kernel, interpret)

    @jax.named_scope("moe_experts")
    def _gmm_ffn(self, G, params, xs, gmap, tm: int, use_kernel: bool,
                 interpret: bool):
        """The three grouped projections over the tile-aligned buffer
        (kernel or jnp reference — identical math)."""
        cd = xs.dtype

        def mm(rows, key):
            return _ffn_mm(rows, params[key].astype(cd), gmap, use_kernel,
                           bool(interpret))

        u = mm(xs, "w1")
        if self.bias:
            e_of_row = jnp.repeat(gmap, tm)
            u = u + jnp.take(params["b1"].astype(cd), e_of_row, axis=0)
        if self.activation == "swiglu":
            h = jax.nn.silu(u) * mm(xs, "w3")
        elif self.activation == "gelu":
            h = jax.nn.gelu(u, approximate=True)
        else:
            h = jax.nn.relu(u)
        out = mm(h, "w2")
        if self.bias:
            out = out + jnp.take(params["b2"].astype(cd), e_of_row, axis=0)
        return out

    def _gmm_block(self, params, x, capacity: int, tm: int,
                   interpret):
        """One dispatch group through the Pallas grouped-matmul executor.

        Routing is :func:`_top_k_select` — decisions and combine weights
        bit-identical to every other executor; dropped (over-capacity)
        pairs still own a buffer row but carry zero combine weight, so
        they cost ``tm``-tile FLOPs yet never touch the output. Buffer
        build and read-back ride the gather-only custom VJPs
        (:func:`_rows_to_slots` / :func:`_slots_to_rows`)."""
        from ..ops import grouped_matmul as G

        n = x.shape[0]
        f32 = jnp.float32
        gates = self._gates(params, x, f32=True)
        eidx, slot, combine, (c1, gsum) = _top_k_select(
            gates, capacity, self.k)
        row, inv, tok_of_row, gmap = self._tile_layout(eidx, slot, n, tm)
        m_pad = tok_of_row.shape[0]
        use_kernel = (
            G.tileable(m_pad, self.d_model, self.d_ff, tm)
            and G.tileable(m_pad, self.d_ff, self.d_model, tm)
        )
        if interpret is None:
            interpret = False
            use_kernel = use_kernel and jax.default_backend() == "tpu"
        keep_all = jnp.ones(eidx.shape, bool)  # every pair owns a row
        xs = _rows_to_slots(x, tok_of_row, row, keep_all)
        if self.activation == "swiglu" and not self.bias:
            out = self._gmm_ffn_fused(G, params, xs, gmap, use_kernel,
                                      bool(interpret))
        else:
            out = self._gmm_ffn(G, params, xs, gmap, tm, use_kernel,
                                interpret)
        with jax.named_scope("moe_combine"):
            rows = _slots_to_rows(out, row.reshape(-1), inv).reshape(
                n, self.k, self.d_model).astype(f32)
            y = jnp.sum(rows * combine[..., None].astype(f32), axis=1)
        return y, c1, gsum

    @jax.named_scope("moe")
    def apply_gmm(self, params: Dict[str, Any], x, ep: int = 1,
                  tm: int = 128, interpret=None):
        """Single-device MoE via the Pallas tile-aligned grouped matmul
        (:mod:`..ops.grouped_matmul`): :meth:`apply_reference`'s contract
        (same routing, same per-``ep``-group capacity quotas, same aux
        loss) with each projection one ``gmm`` kernel call — ``k·N``
        active rows plus ≤ ``E·tm`` tile padding on the MXU, a
        scalar-prefetched tile→expert map steering weight DMA, f32
        accumulators, and gather-only AD transposes end to end.
        ``token_choice`` only. ``interpret``: None = kernel on TPU /
        jnp reference elsewhere; True forces the kernel in interpret
        mode (tests)."""
        if self.routing != "token_choice":
            raise ValueError(
                "apply_gmm implements token_choice routing only; "
                "use apply_reference for expert_choice")
        n = x.shape[0]
        if n % ep:
            raise ValueError(f"{n} tokens not divisible by ep={ep}")
        cap = self.capacity(n // ep)
        ys, c1s, gsums = [], [], []
        for blk in jnp.split(x, ep, axis=0):
            y, c1, gsum = self._gmm_block(params, blk, cap, tm, interpret)
            ys.append(y)
            c1s.append(c1)
            gsums.append(gsum)
        c1, gsum = sum(c1s), sum(gsums)
        aux = self.n_experts * jnp.sum((c1 / n) * (gsum / n))
        return jnp.concatenate(ys, axis=0), aux

    # -- the dropless executor (sigmoid / shared expert / held share) -----

    @jax.named_scope("moe_route")
    def route(self, params, x):
        """Routing over ALL ``n_experts`` in float32 → ``(eidx [N, k] int32,
        w [N, k] f32)``: scores ``softmax`` or ``sigmoid`` of ``x @ wg``;
        the top ``k`` of ``scores + wg_b`` (the bias takes part in the
        selection only); weights the chosen experts' own scores, divided by
        their sum under ``norm_topk``, times ``routed_scale``. No capacity:
        every choice is kept."""
        f32 = jnp.float32
        logits = jnp.dot(x.astype(f32), params["wg"].astype(f32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = (jax.nn.sigmoid(logits) if self.scoring == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        chosen = scores + params["wg_b"].astype(f32) if self.select_bias \
            else scores
        _, eidx = jax.lax.top_k(chosen, self.k)
        w = jnp.take_along_axis(scores, eidx, axis=1)
        if self.norm_topk:
            w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-20)
        return eidx.astype(jnp.int32), w * self.routed_scale

    def dropless_plan(self, n: int):
        """``(tile height, buffer rows)`` for ``n`` tokens, from the shapes
        alone. The buffer holds the WORST routing, every token choosing as
        many held experts as it can (``n * min(k, held)`` pairs), so no
        load drops a token; the step's routing fills what it needs of it
        and the rest are dead tiles the kernel skips. The executor is the
        tile-aligned grouped matmul (``ops/grouped_matmul``; its jax.numpy
        reference off the TPU or at widths it cannot tile). The tile
        is the MXU's 128 rows when the expected rows an expert are a tile
        or more, else 32: a decode step's handful of rows an expert then
        pads to 32, not 128 (2.83 against 3.28 ms a layer on the v5e), and
        the step is bound by the weight reads either way."""
        nh = self.held_range[1]
        pairs = n * min(self.k, nh)
        tm = 128 if n * self.k / self.n_experts >= 128 else 32
        return tm, -(-(pairs + nh * tm) // tm) * tm

    @jax.named_scope("moe_dispatch")
    def _held_layout(self, eidx, tm: int, rows: int):
        """Tile-aligned rows for the pairs whose expert is HELD, sorted by
        expert: local expert ``e``'s pairs fill rows ``off[e] ..`` with
        ``off`` the exclusive cumsum of its count rounded up to ``tm``
        (:meth:`_tile_layout`'s layout, without its one-tile minimum and
        with the pairs of absent experts left out). Returns ``(row [N, k]``
        (``rows`` = no row: not held), ``tok_of_row [rows]`` (``N`` = empty
        row), ``gmap [rows/tm]`` (``held`` = dead tile), ``sizes [held]``)."""
        n, k = eidx.shape
        e0, nh = self.held_range
        local = (eidx >= e0) & (eidx < e0 + nh)
        eloc = jnp.where(local, eidx - e0, nh).reshape(-1)
        # sorts, sums and gathers only, no scatter
        order = jnp.argsort(eloc, stable=True)     # sorted rank -> pair
        rank = jnp.argsort(order).astype(jnp.int32)    # pair -> rank
        sizes = jnp.sum(eloc[:, None] == jnp.arange(nh)[None, :],
                        axis=0).astype(jnp.int32)
        padded = (sizes + tm - 1) // tm * tm
        cum = jnp.cumsum(padded)
        off = cum - padded                         # first row of an expert
        start = jnp.cumsum(sizes) - sizes          # first sorted rank
        safe = jnp.minimum(eloc, nh - 1)
        row = jnp.where(eloc < nh, off[safe] + rank - start[safe], rows)
        # buffer row r lies in expert e's block at offset o: the pair of
        # sorted rank start[e] + o, if the expert has that many
        r = jnp.arange(rows, dtype=jnp.int32)
        e_of = jnp.minimum(jnp.sum(r[:, None] >= cum[None, :], axis=1),
                           nh - 1)
        o = r - off[e_of]
        filled = (r < cum[-1]) & (o < sizes[e_of])
        pair = order[jnp.clip(start[e_of] + o, 0, n * k - 1)]
        tok_of_row = jnp.where(filled, pair // k, n)
        gmap = jnp.where(r[::tm] < cum[-1], e_of[::tm], nh).astype(jnp.int32)
        return row.reshape(n, k), tok_of_row, gmap, sizes

    @jax.named_scope("moe_shared")
    def _shared_ffn(self, params, x):
        """The shared expert: one ungated SwiGLU every token passes."""
        cd = x.dtype
        u = jax.nn.silu(x @ params["ws1"].astype(cd)) * (
            x @ params["ws3"].astype(cd))
        return u @ params["ws2"].astype(cd)

    @jax.named_scope("moe_experts")
    def _stacked_ffn(self, G, params, xs, gmap, layer, use_kernel: bool,
                     interpret: bool):
        """The three grouped projections against layer ``layer`` of the
        expert STACKS ``params["w1"|"w3"|"w2"]`` ``[L, n, ...]``, read in
        place by the kernel (``ops.grouped_matmul.gmm_stacked``): a layer
        sliced out of the stack and handed to a custom call would be a
        copy of the layer's experts every step. Forward only."""
        cd = xs.dtype

        def mm(rows, key):
            w = params[key].astype(cd)
            if use_kernel:
                return G.gmm_stacked(rows, w, layer, gmap, interpret)
            return G.gmm_reference(
                rows, jax.lax.dynamic_index_in_dim(w, layer, 0,
                                                   keepdims=False), gmap)

        return mm(jax.nn.silu(mm(xs, "w1")) * mm(xs, "w3"), "w2")

    @jax.named_scope("moe")
    def apply_dropless(self, params: Dict[str, Any], x, interpret=None,
                       stats: Optional[list] = None, layer=None):
        """The layer of :meth:`route` with NO capacity, for the experts
        this layer holds: ``y = sum over the token's chosen experts that
        are held of w_e E_e(x)  +  S(x)``, ``x`` ``[N, D]`` → ``(y [N, D]
        f32, 0.0)``. With ``held`` that is this share's PART of the layer
        (what the absent experts would add is left out: the caller's
        exchange sums the shares, and on one chip there is none); without
        it, the whole layer.

        The held pairs are sorted by expert into a tile-aligned row buffer
        and run through one grouped matmul per projection
        (:meth:`dropless_plan`); rows computed are the pairs held plus
        tile padding, never a capacity. ``stats``, a list, is handed
        ``[pairs held, rows computed, most rows at one expert, experts
        with a row]`` (int32): the cached forwards sum them on the device
        (``interpret``: as :meth:`apply_gmm`; the kernels' hand-written
        backward does not know dead tiles, so a held share trains through
        the jax.numpy reference). With ``layer`` (int, may be traced) the
        expert stacks in ``params`` are a whole model's ``[L, n, ...]``
        and this is layer ``layer`` of them (:meth:`_stacked_ffn`)."""
        from ..ops import grouped_matmul as G

        n, D = x.shape
        f32 = jnp.float32
        eidx, w = self.route(params, x)
        tm, rows = self.dropless_plan(n)
        row, tok_of_row, gmap, sizes = self._held_layout(eidx, tm, rows)
        held = row < rows
        row = jnp.minimum(row, rows - 1)     # a row to read for every pair
        use_kernel = (G.tileable(rows, D, self.d_ff, tm)
                      and G.tileable(rows, self.d_ff, D, tm))
        if interpret is None:
            interpret = False
            use_kernel = use_kernel and jax.default_backend() == "tpu"
        xs = _rows_to_slots(x, tok_of_row, row, held)
        if layer is None:
            out = self._gmm_ffn_fused(G, params, xs, gmap, use_kernel,
                                      bool(interpret))
        else:
            out = self._stacked_ffn(G, params, xs, gmap, layer, use_kernel,
                                    bool(interpret))
        with jax.named_scope("moe_combine"):
            got = jnp.take(out, row.reshape(-1), axis=0).reshape(
                n, self.k, D).astype(f32)
            # a select, not a product: the rows of a dead tile are not
            # written and may hold anything
            y = jnp.sum(jnp.where(held[..., None], got * w[..., None], 0.0),
                        axis=1)
        if self.n_shared:
            y = y + self._shared_ffn(params, x).astype(f32)
        if stats is not None:
            live = jnp.sum((gmap < sizes.shape[0]).astype(jnp.int32)) * tm
            stats.append(jnp.stack([
                jnp.sum(held.astype(jnp.int32)), live, jnp.max(sizes),
                jnp.sum((sizes > 0).astype(jnp.int32))]))
        return y, jnp.asarray(0.0, f32)

    @jax.named_scope("moe")
    def apply_partial(self, params: Dict[str, Any], x, n_local: int,
                      e0):
        """Expert-PARTIAL forward for replicated-routing layouts: routing
        over all ``E`` experts computes locally (``wg`` replicated, ``x``
        replicated across the expert-sharded axis), but only the caller's
        ``n_local`` expert shard (global rows ``e0..e0+n_local``) runs —
        the returned ``y`` is that shard's partial combine, and the CALLER
        psums partials across the axis (experts partition the combine sum,
        so Σ_ranks partial == the full MoE output, bit-equal to
        :meth:`apply_reference` with ``ep=1``).

        The decode-path complement to :meth:`apply` (whose all_to_all +
        per-shard token quotas suit big training batches): no token
        slicing, so any batch size works — the tensor-parallel MoE decode
        uses it per position. ``token_choice`` only. ``e0`` may be traced
        (``axis_index``-derived). Expert stacks in ``params`` are the
        LOCAL ``[n_local, ...]`` shards; capacity uses the single-group
        (``ep=1``) convention.
        """
        if self.routing != "token_choice":
            raise ValueError(
                "apply_partial implements token_choice routing only")
        n = x.shape[0]
        cap = self.capacity(n)
        D = self.d_model
        f32 = jnp.float32
        gates = self._gates(params, x)
        eidx, slot, combine, _ = _top_k_select(gates, cap, self.k)
        # global slot→pair map, then THIS shard's rows only
        sent = n * self.k
        pair = jnp.arange(sent, dtype=jnp.int32).reshape(n, self.k)
        cell = jnp.full((self.n_experts, cap), sent, jnp.int32).at[
            eidx.reshape(-1), slot.reshape(-1)
        ].set(pair.reshape(-1), mode="drop")
        cell_l = jax.lax.dynamic_slice_in_dim(cell, e0, n_local,
                                              axis=0).reshape(-1)
        tok_l = jnp.where(cell_l == sent, n, cell_l // self.k)
        blocks = jnp.take(x, tok_l, axis=0, mode="fill",
                          fill_value=0).reshape(n_local, cap, D)
        args = self._expert_args(params, blocks.dtype)
        out = jax.vmap(self._expert_ffn)(*args, blocks)
        # partial combine: only pairs routed to THIS shard contribute
        local = (eidx >= e0) & (eidx < e0 + n_local)
        flat = (eidx - e0) * cap + jnp.minimum(slot, cap - 1)
        rows = jnp.take(
            out.reshape(n_local * cap, D),
            jnp.clip(flat, 0, n_local * cap - 1).reshape(-1), axis=0,
        ).reshape(n, self.k, D).astype(f32)
        w = jnp.where(local, combine, 0.0)
        return jnp.sum(rows * w[..., None].astype(f32), axis=1)

    @jax.named_scope("moe")
    def apply_reference(self, params: Dict[str, Any], x, ep: int = 1):
        """Single-device oracle: identical routing math, full expert stack.

        ``ep`` emulates the expert-group sharding: tokens split into ``ep``
        contiguous blocks (how ``P(("data", "expert"))`` lays a host array
        out within one data group), each block claiming its OWN ``C``
        capacity slots per expert — exactly the per-source-shard dispatch
        the all_to_all layout gives the sharded path. Since capacity only
        decides which (token, expert) pairs are kept, the oracle applies
        experts per token and weighs by the combine weights — no slot
        bookkeeping — and must equal :meth:`apply` bit-closely."""
        n = x.shape[0]
        if n % ep:
            raise ValueError(f"{n} tokens not divisible by ep={ep}")
        cap = self.capacity(n // ep)
        ys, c1s, gsums = [], [], []
        for blk in jnp.split(x, ep, axis=0):
            gates = self._gates(params, blk)
            if self.routing == "expert_choice":
                _, ec_combine = _expert_choice_dispatch(
                    gates, min(cap, blk.shape[0])
                )
                w = jnp.sum(ec_combine, axis=1).T  # [Nb, E] summed weights
            else:
                dispatch, combine, (c1, gsum, _) = _top_k_dispatch(
                    gates, cap, self.k
                )
                w = jnp.sum(combine, axis=-1)  # [Nb, E] kept combine weights
                c1s.append(c1)
                gsums.append(gsum)
            args = self._expert_args(params)
            out_all = jax.vmap(
                self._expert_ffn, in_axes=(0,) * len(args) + (None,)
            )(*args, blk)
            ys.append(jnp.einsum("ne,end->nd", w, out_all))
        if self.routing == "expert_choice":
            return jnp.concatenate(ys, axis=0), jnp.asarray(0.0, jnp.float32)
        c1 = sum(c1s)
        gsum = sum(gsums)
        aux = self.n_experts * jnp.sum((c1 / n) * (gsum / n))
        return jnp.concatenate(ys, axis=0), aux


def build_ep_train_step(model: MoEFeedForward, mesh: Mesh, optimizer,
                        per_sample_loss, aux_weight: float = 1e-2):
    """Compile one dp×ep gradient-synchronous training step.

    The objective is per-token regression/classification on the residual MoE
    output ``y_pred = x + moe(x)``: global mean of ``per_sample_loss`` plus
    ``aux_weight`` × (mean over data groups of the load-balancing aux).

    Returns ``(step, opt_init)`` with the usual contract; ``x``/``y`` are
    token blocks sharded over BOTH axes (``P(("data", "expert"))``), expert
    stacks sharded over ``"expert"``, the router replicated.

    Gradient collectives: expert stacks psum over ``"data"`` only — the
    expert-axis contributions already arrived home through the
    ``all_to_all`` transpose; the replicated router psums over both axes.
    Both normalizations live INSIDE the differentiated scalar, so the psums
    restore the exact global gradients (verified against the oracle).
    """
    if model.n_experts % mesh.shape[EXPERT_AXIS]:
        raise ValueError(
            f"n_experts {model.n_experts} not divisible by expert axis "
            f"{mesh.shape[EXPERT_AXIS]}"
        )

    pspecs = model.specs()
    sspecs = opt_state_specs(optimizer, model.param_shapes(), pspecs)
    token_spec = P((DATA_AXIS, EXPERT_AXIS))
    expert_keys = model.expert_keys()
    dp = mesh.shape[DATA_AXIS]
    ep = mesh.shape[EXPERT_AXIS]

    def step_impl(params, opt_state, x, y):
        n_total = float(x.shape[0] * dp * ep)

        def loss_fn(p):
            h, aux = model.apply(p, x)
            local = jnp.sum(per_sample_loss(y, x + h))
            # Normalize inside the differentiated scalar: token mean + aux
            # counted once per shard / (dp·ep) ⇒ psum of per-shard grads IS
            # the global gradient (aux is identical across an expert group,
            # so dividing by ep de-duplicates its ep copies).
            return local / n_total + (aux_weight / (dp * ep)) * aux

        objective, grads = jax.value_and_grad(loss_fn)(params)
        grads = {
            k: jax.lax.psum(
                g if k in expert_keys else jax.lax.psum(g, EXPERT_AXIS),
                DATA_AXIS,
            )
            for k, g in grads.items()
        }
        # Report the optimized objective itself (token mean + aux term):
        # per-shard scalars are partials of the global sum by construction.
        loss = jax.lax.psum(
            jax.lax.psum(objective, EXPERT_AXIS), DATA_AXIS
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(jnp.add, params, updates)
        return params, opt_state, loss

    step = jax.jit(
        shard_map(
            step_impl, mesh=mesh,
            in_specs=(pspecs, sspecs, token_spec, token_spec),
            out_specs=(pspecs, sspecs, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )
    return step, make_opt_init(optimizer, mesh, sspecs)
