"""LoRA fine-tuning for the LM family (TPU-native extension).

Low-Rank Adaptation (Hu et al. 2021): freeze the pretrained weights, learn
a rank-``r`` update ``ΔW = (α/r)·A·B`` per adapted matrix. Here the adapted
entries of the params dict become :class:`LoRATensor` — a lazy pytree node
that materializes ``W + (α/r)·A·B`` at each use site, with
``stop_gradient`` on ``W`` so gradients reach ONLY the adapter factors.
Model code is unchanged (same trick as ``quantize.py``); any gradient-based
builder differentiates the right leaves automatically, and plain optimizers
leave the frozen base untouched because its gradient is exactly zero
(decay-style optimizers need :func:`lora_mask` — weight decay is not
gradient-driven).

``B`` initializes to zero, so the adapted model starts EXACTLY at the base
model; :func:`merge_lora` bakes the learned update back into plain arrays
for deployment (and composes with ``quantize_lm_params`` afterwards).

No reference (b13n3rd/elephas) analog: the reference has no fine-tuning
machinery of any kind.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np

from .transformer import (
    DATA_AXIS,
    SEQ_AXIS,
    Mesh,
    P,
    TransformerLM,
)


@jax.tree_util.register_pytree_node_class
class LoRATensor:
    """Frozen base ``w`` ``[*, in, out]`` + trainable ``a`` ``[*, in, r]``,
    ``b`` ``[*, r, out]``; materializes ``w + (α/r)·a@b`` lazily. Leading
    axes broadcast (layer stacks survive ``lax.scan`` slicing)."""

    def __init__(self, w, a, b, alpha: float):
        self.w = w
        self.a = a
        self.b = b
        self.alpha = alpha

    def tree_flatten(self):
        return (self.w, self.a, self.b), self.alpha

    @classmethod
    def tree_unflatten(cls, alpha, children):
        return cls(*children, alpha)

    @property
    def shape(self):
        return self.w.shape

    @property
    def ndim(self):
        return self.w.ndim

    def materialize(self, dtype=jnp.float32):
        rank = self.a.shape[-1]
        delta = jnp.matmul(
            self.a.astype(jnp.float32), self.b.astype(jnp.float32)
        ) * (self.alpha / rank)
        return (jax.lax.stop_gradient(self.w.astype(jnp.float32))
                + delta).astype(dtype)

    # -- the operations the LM applies to its weights --------------------
    def astype(self, dtype):
        return self.materialize(dtype)

    def __jax_array__(self):
        return self.materialize()

    @property
    def T(self):
        return self.materialize().T

    def __getitem__(self, idx):
        return self.materialize()[idx]

    def reshape(self, *shape):
        """Leading-dim reshapes stay LAZY (the mixed-window period scans
        reshape ``[L, ...]`` stacks to ``[L/p, p, ...]``); anything that
        touches the trailing matmul dims materializes."""
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        if (len(shape) >= 2 and tuple(shape[-2:]) == tuple(self.w.shape[-2:])
                and int(np.prod(shape)) == int(np.prod(self.w.shape))):
            lead = tuple(shape[:-2])
            return LoRATensor(
                self.w.reshape(lead + tuple(self.w.shape[-2:])),
                self.a.reshape(lead + tuple(self.a.shape[-2:])),
                self.b.reshape(lead + tuple(self.b.shape[-2:])),
                self.alpha,
            )
        return self.materialize().reshape(shape)


DEFAULT_LORA_KEYS = ("wq", "wv")


def apply_lora(params: Dict[str, Any], keys: Sequence[str] = DEFAULT_LORA_KEYS,
               rank: int = 8, alpha: float = 16.0,
               seed: int = 0) -> Dict[str, Any]:
    """Attach rank-``rank`` adapters to ``keys`` (default: the attention
    q/v projections, the standard LoRA placement). ``A`` ~ N(0, 1/rank),
    ``B`` = 0 — the adapted model starts exactly at the base."""
    rng = np.random.default_rng(seed)
    out: Dict[str, Any] = {}
    for name, value in params.items():
        if name not in keys:
            out[name] = value
            continue
        if isinstance(value, LoRATensor):
            if value.a.shape[-1] != rank or value.alpha != float(alpha):
                raise ValueError(
                    f"{name!r} already adapted with rank "
                    f"{value.a.shape[-1]}/alpha {value.alpha}; re-applying "
                    f"with rank {rank}/alpha {alpha} would silently keep "
                    "the old adapters — merge_lora first to re-adapt"
                )
            out[name] = value  # idempotent for matching config
            continue
        w = jnp.asarray(value)
        if w.ndim < 2:
            raise ValueError(f"cannot adapt non-matrix param {name!r}")
        *lead, d_in, d_out = w.shape
        a = jnp.asarray(
            rng.normal(size=(*lead, d_in, rank)).astype(np.float32)
            / np.sqrt(rank)
        )
        b = jnp.zeros((*lead, rank, d_out), jnp.float32)
        out[name] = LoRATensor(w, a, b, float(alpha))
    missing = [k for k in keys if k not in params]
    if missing:
        raise ValueError(f"keys not in params: {missing}")
    return out


def merge_lora(params: Dict[str, Any]) -> Dict[str, Any]:
    """Bake adapters into plain float arrays (deployment form)."""
    return {
        k: (v.materialize() if isinstance(v, LoRATensor) else v)
        for k, v in params.items()
    }


def lora_mask(params: Dict[str, Any]):
    """Pytree of booleans (same structure as ``params``) — True on
    trainable adapter factors, False on everything else, including each
    adapter's frozen base. For ``optax.masked`` wrappers of decay-style
    optimizers (weight decay is not gradient-driven, so ``stop_gradient``
    alone does not protect the frozen base from it)."""
    return {
        k: (LoRATensor(False, True, True, v.alpha)
            if isinstance(v, LoRATensor) else False)
        for k, v in params.items()
    }


def lora_trainable_count(params: Dict[str, Any]) -> Tuple[int, int]:
    """(trainable adapter element count, total element count)."""
    trainable = total = 0
    for v in params.values():
        if isinstance(v, LoRATensor):
            trainable += v.a.size + v.b.size
            total += v.w.size + v.a.size + v.b.size
        else:
            total += np.size(v)
    return trainable, total


def save_lora(path: str, params: Dict[str, Any]) -> None:
    """Persist ONLY the adapters (a tiny artifact — rank·(in+out) floats
    per adapted matrix) as an npz; reattach to any copy of the base with
    :func:`load_lora`. Full-state checkpointing of the whole adapted dict
    also works through ``utils.save_pytree`` — this is the
    share-the-fine-tune form."""
    arrays: Dict[str, np.ndarray] = {}
    for name, v in params.items():
        if isinstance(v, LoRATensor):
            arrays[f"{name}.a"] = np.asarray(v.a)
            arrays[f"{name}.b"] = np.asarray(v.b)
            arrays[f"{name}.alpha"] = np.float32(v.alpha)
    if not arrays:
        raise ValueError("no LoRA adapters in params")
    np.savez(path, **arrays)


def load_lora(path: str, base_params: Dict[str, Any]) -> Dict[str, Any]:
    """Attach adapters saved by :func:`save_lora` onto ``base_params``
    (plain float weights, e.g. a fresh checkpoint load of the pretrained
    model). Shapes are validated against the base."""
    if not str(path).endswith(".npz"):
        path = str(path) + ".npz"
    with np.load(path) as blob:
        names = sorted({k.rsplit(".", 1)[0] for k in blob.files})
        out = dict(base_params)
        for name in names:
            if name not in base_params:
                raise ValueError(f"adapter {name!r} has no base param")
            w = jnp.asarray(base_params[name])
            a = jnp.asarray(blob[f"{name}.a"])
            b = jnp.asarray(blob[f"{name}.b"])
            if a.shape[:-1] != w.shape[:-1] or b.shape[-1] != w.shape[-1]:
                raise ValueError(
                    f"adapter {name!r} shaped {a.shape}x{b.shape} does not "
                    f"fit base {w.shape}"
                )
            out[name] = LoRATensor(w, a, b, float(blob[f"{name}.alpha"]))
    return out


class MultiTenantLM(TransformerLM):
    """A :class:`TransformerLM` carrying ``n_adapters`` STACKED LoRA
    adapters for multi-tenant serving: one base model, many fine-tuned
    variants, selected PER BATCH ROW inside the decode kernel.

    The adapter factors live in the params dict as layer-stacked
    ``lora_w{t}_a`` ``[L, A, D, r]`` / ``lora_w{t}_b`` ``[L, A, r, out]``
    for each target projection ``t`` (q/k/v/o). :meth:`_attn_proj` adds
    ``(α/r)·(x@A[row])@B[row]`` when an adapter-row vector is active —
    installed via :meth:`adapter_context` INSIDE a traced kernel body, so
    the row ids are an ordinary traced argument of the program (never
    captured constants; the compiled kernel serves any row→adapter
    assignment). ``B`` initializes to zero, so adapter 0 (and every fresh
    adapter) is exactly the base model — the serving engine's token-identity
    guarantee for un-adapted tenants.

    Tenancy is a serving concept: training a single adapter still goes
    through :func:`apply_lora` on a plain model; :meth:`load_adapter`
    installs the trained factors into one stack row here.
    """

    def __init__(self, *args, n_adapters: int = 4, lora_rank: int = 4,
                 lora_alpha: Optional[float] = None,
                 lora_targets: Sequence[str] = ("q", "v"), **kwargs):
        super().__init__(*args, **kwargs)
        if n_adapters < 1:
            raise ValueError(f"n_adapters must be >= 1, got {n_adapters}")
        if lora_rank < 1:
            raise ValueError(f"lora_rank must be >= 1, got {lora_rank}")
        targets = tuple(lora_targets)
        bad = [t for t in targets if t not in ("q", "k", "v", "o")]
        if bad or len(set(targets)) != len(targets):
            raise ValueError(
                f"lora_targets must be distinct members of q/k/v/o, "
                f"got {targets}")
        self.n_adapters = int(n_adapters)
        self.lora_rank = int(lora_rank)
        self.lora_alpha = float(2 * lora_rank if lora_alpha is None
                                else lora_alpha)
        self.lora_targets = targets
        self._adapter_rows = None  # traced [rows] int vector, or None

    # -- params ----------------------------------------------------------
    def _lora_out_dim(self, t: str) -> int:
        Dkv = (self.d_model // self.n_heads) * self.n_kv_heads
        return self.d_model if t in ("q", "o") else Dkv

    def param_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        shapes = super().param_shapes()
        sds = jax.ShapeDtypeStruct
        L, A, D, r = (self.n_layers, self.n_adapters, self.d_model,
                      self.lora_rank)
        for t in self.lora_targets:
            shapes[f"lora_w{t}_a"] = sds((L, A, D, r), jnp.float32)
            shapes[f"lora_w{t}_b"] = sds((L, A, r, self._lora_out_dim(t)),
                                         jnp.float32)
        return shapes

    def init(self, seed: int = 0) -> Dict[str, np.ndarray]:
        out = super().init(seed)
        # LoRA convention (apply_lora above): A ~ N(0, 1/r), B = 0 — every
        # adapter starts EXACTLY at the base model.
        rng = np.random.default_rng(np.random.SeedSequence([seed, 0x10A]))
        for t in self.lora_targets:
            a_key, b_key = f"lora_w{t}_a", f"lora_w{t}_b"
            out[a_key] = (
                rng.normal(size=self.param_shapes()[a_key].shape)
                / np.sqrt(self.lora_rank)
            ).astype(np.float32)
            out[b_key] = np.zeros(self.param_shapes()[b_key].shape,
                                  np.float32)
        return out

    def _block_keys(self):
        keys = super()._block_keys()
        extra = []
        for t in self.lora_targets:
            extra += [f"lora_w{t}_a", f"lora_w{t}_b"]
        return keys + tuple(extra)

    # -- the kernel-side hook -------------------------------------------
    @contextlib.contextmanager
    def adapter_context(self, rows):
        """Activate per-row adapter selection: ``rows`` int ``[B]`` — the
        adapter id of each batch row in every subsequent projection. MUST
        be entered inside the traced kernel body (``rows`` a traced arg),
        never around a jit boundary."""
        prev = self._adapter_rows
        self._adapter_rows = rows
        try:
            yield
        finally:
            self._adapter_rows = prev

    def _attn_proj(self, lp, name: str, x, wide: bool = False):
        y = super()._attn_proj(lp, name, x, wide)
        rows = self._adapter_rows
        if rows is None or name not in self.lora_targets:
            return y
        cd = x.dtype
        # lp slices are per-layer: [A, D, r] / [A, r, out]; gather each
        # row's factors, two thin matmuls, scaled residual delta.
        a = lp[f"lora_w{name}_a"].astype(cd)[rows]
        b = lp[f"lora_w{name}_b"].astype(cd)[rows]
        scale = self.lora_alpha / self.lora_rank
        if x.ndim == 2:        # decode step: x [S, D]
            delta = jnp.einsum("sd,sdr->sr", x, a)
            delta = jnp.einsum("sr,sro->so", delta, b)
        else:                  # prefill/chunk: x [S, T, D]
            delta = jnp.einsum("std,sdr->str", x, a)
            delta = jnp.einsum("str,sro->sto", delta, b)
        return y + scale * delta.astype(y.dtype)

    # -- host helpers ----------------------------------------------------
    def load_adapter(self, params: Dict[str, Any], adapter_id: int,
                     factors: Dict[str, Tuple[Any, Any]]) -> Dict[str, Any]:
        """Install trained factors into stack row ``adapter_id``:
        ``factors`` maps target letter → ``(a [L, D, r], b [L, r, out])``.
        Returns a new params dict (stacks are rebuilt, not mutated)."""
        if not 0 <= adapter_id < self.n_adapters:
            raise ValueError(f"adapter_id {adapter_id} out of range "
                             f"[0, {self.n_adapters})")
        out = dict(params)
        for t, (a, b) in factors.items():
            if t not in self.lora_targets:
                raise ValueError(f"{t!r} is not an adapted target "
                                 f"{self.lora_targets}")
            for key, new in ((f"lora_w{t}_a", a), (f"lora_w{t}_b", b)):
                stack = jnp.asarray(out[key])
                new = jnp.asarray(new, stack.dtype)
                if new.shape != stack.shape[:1] + stack.shape[2:]:
                    raise ValueError(
                        f"{key} row must be {stack.shape[:1] + stack.shape[2:]},"
                        f" got {new.shape}")
                out[key] = stack.at[:, adapter_id].set(new)
        return out

    def randomize_adapter(self, params: Dict[str, Any], adapter_id: int,
                          seed: int = 0, scale: float = 0.02) -> Dict[str, Any]:
        """Give adapter ``adapter_id`` a nonzero delta (small random ``B``)
        — the test/bench shortcut for 'a tenant whose outputs must differ
        from the base'."""
        rng = np.random.default_rng(np.random.SeedSequence([seed, adapter_id]))
        factors = {}
        for t in self.lora_targets:
            a = np.asarray(params[f"lora_w{t}_a"])[:, adapter_id]
            b = (rng.normal(size=np.asarray(
                params[f"lora_w{t}_b"]).shape[0:1] + np.asarray(
                params[f"lora_w{t}_b"]).shape[2:]) * scale).astype(np.float32)
            factors[t] = (a, b)
        return self.load_adapter(params, adapter_id, factors)

    def merged_params(self, params: Dict[str, Any],
                      adapter_id: int) -> Dict[str, Any]:
        """Bake ONE adapter into plain dense weights — the single-tenant
        deployment form, and the equivalence oracle for tests (the merged
        model's ``apply`` must match the batched delta path numerically)."""
        if not 0 <= adapter_id < self.n_adapters:
            raise ValueError(f"adapter_id {adapter_id} out of range "
                             f"[0, {self.n_adapters})")
        scale = self.lora_alpha / self.lora_rank
        out = {}
        for k, v in params.items():
            if k.startswith("lora_"):
                continue
            out[k] = v
        for t in self.lora_targets:
            a = jnp.asarray(params[f"lora_w{t}_a"])[:, adapter_id]
            b = jnp.asarray(params[f"lora_w{t}_b"])[:, adapter_id]
            w = jnp.asarray(params[f"w{t}"])
            out[f"w{t}"] = w + scale * jnp.einsum(
                "ldr,lro->ldo", a.astype(jnp.float32), b.astype(jnp.float32))
        return out

    def base_model(self) -> TransformerLM:
        """The architecture-equal plain :class:`TransformerLM` (for
        ``merged_params`` consumers — its param_shapes match the merged
        dict exactly)."""
        m = TransformerLM(
            self.vocab, self.d_model, self.n_heads, self.n_layers,
            self.d_ff, self.max_len,
            compute_dtype=str(self.compute_dtype),
            pos_encoding=self.pos_encoding,
            tie_embeddings=self.tie_embeddings,
            n_kv_heads=self.n_kv_heads, activation=self.activation,
            norm=self.norm, norm_eps=self.norm_eps,
            attn_bias=self.attn_bias, ffn_bias=self.ffn_bias,
            rope_theta=self.rope_theta,
            attn_window=(self.attn_windows if self.mixed_window
                         else self.attn_window),
        )
        return m


def build_lora_lm_train_step(model: TransformerLM, mesh: Mesh, optimizer,
                             attn: str = "ring",
                             vocab_block: Optional[int] = None):
    """Compile a dp×sp fine-tuning step over a LoRA-adapted params dict.

    Like :func:`~elephas_tpu.models.transformer.build_lm_train_step` but
    the sharding specs are derived from the ACTUAL params pytree (adapter
    nodes change its structure), everything replicated — the dense LM
    family's layout; that structural difference is why this is a separate
    builder (no ``accum_steps`` here — shrink the batch instead; adapter
    grads are tiny). The optimizer is wrapped in ``optax.masked`` over
    :func:`lora_mask`, so optimizer state exists ONLY for the adapter
    factors (no full-model moment buffers for frozen weights) and
    decay-style optimizers cannot touch the base; non-adapter gradients
    are zeroed before the update as well.

    ``vocab_block`` streams the loss head in vocab-column chunks
    (``chunked_summed_xent``) so the ``[B, T, V]`` logits and log-probs
    never materialize — the fine-tuning memory lever for the V = 32k–152k
    imported checkpoints LoRA most often targets.
    """
    import optax
    from .transformer import (
        _check_seq_len,
        _validate_lm_step,
        chunked_summed_xent,
    )

    if getattr(model, "moe", None) is not None:
        # an explicit family check — _supports_speculative became a
        # capacity predicate in round 5 and no longer marks "dense"
        raise NotImplementedError(
            "LoRA fine-tuning targets the dense TransformerLM family"
        )
    sp = _validate_lm_step(model, mesh, attn)
    dp = mesh.shape[DATA_AXIS]
    tok_spec = P(DATA_AXIS, SEQ_AXIS)

    def replicated_like(tree):
        return jax.tree_util.tree_map(lambda _: P(), tree)

    def masked_optimizer(params):
        return optax.masked(optimizer, lora_mask(params))

    def make_step_impl(mask, opt):
        def step_impl(params, opt_state, tokens, positions, targets):
            ntok_total = float(tokens.shape[0] * tokens.shape[1] * dp * sp)

            def loss_fn(p):
                if vocab_block is not None:
                    h, _ = model.apply_hidden(p, tokens, positions,
                                              attn=attn)
                    w = model.head_weight(p)
                    if isinstance(w, LoRATensor):  # untied adapted head
                        w = w.materialize()
                    ce = chunked_summed_xent(h, w, targets, vocab_block)
                    return ce / ntok_total
                logits = model.apply(p, tokens, positions, attn=attn)
                logp = jax.nn.log_softmax(logits, axis=-1)
                ll = jnp.take_along_axis(
                    logp, targets[..., None], axis=-1
                )[..., 0]
                return -jnp.sum(ll) / ntok_total

            objective, grads = jax.value_and_grad(loss_fn)(params)
            # LoRA trains ONLY the adapter factors: zero every other
            # gradient (the adapted bases are already zero via
            # stop_gradient; the non-adapted params are zeroed here).
            grads = jax.tree_util.tree_map(
                lambda g, m: (
                    jax.lax.psum(jax.lax.psum(g, SEQ_AXIS), DATA_AXIS)
                    if m else jnp.zeros_like(g)
                ),
                grads, mask,
            )
            loss = jax.lax.psum(jax.lax.psum(objective, SEQ_AXIS), DATA_AXIS)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(jnp.add, params, updates)
            return params, opt_state, loss

        return step_impl

    def build(params):
        opt = masked_optimizer(params)
        pspecs = replicated_like(params)
        sspecs = replicated_like(jax.eval_shape(opt.init, params))
        return jax.jit(
            shard_map(
                make_step_impl(lora_mask(params), opt), mesh=mesh,
                in_specs=(pspecs, sspecs, tok_spec, tok_spec, tok_spec),
                out_specs=(pspecs, sspecs, P()),
                check_vma=False,
            ),
            donate_argnums=(0, 1),
        )

    cache: Dict[Any, Any] = {}

    def step(params, opt_state, tokens, positions, targets):
        _check_seq_len(model, sp, tokens.shape[1])
        key = jax.tree_util.tree_structure(params)
        if key not in cache:
            cache[key] = build(params)
        return cache[key](params, opt_state, tokens, positions, targets)

    def opt_init(params):
        # masked init: moment buffers exist only for the adapter factors
        return masked_optimizer(params).init(params)

    return step, opt_init
