"""Slot-based batched KV cache: the device state of the serving engine.

One fixed ``{"k"/"v": [L, S, Hkv, T, Dh]}`` buffer pair (the standard
:meth:`TransformerLM.init_cache` layout with batch = ``n_slots``) backs
every in-flight request: the BATCH axis is the SLOT axis. A model of window
and full layers built with ``window_cache="ring"`` has TWO such pairs side
by side, the horizon-long ``"k"/"v"`` of its full layers and a ring
``"kw"/"vw": [L_win, S, Hkv, R, Dh]`` of the window's length for its window
layers; every stack has the same slot axis, and a slot's lifecycle is the
same. A LATENT-attention model (``kv_lora_rank``) has a third kind, one
stack and no values: ``"k": [L, S, 1, T, row]``, a position's row its
normed latent and the one rotary key all heads share (zeros up to whole
lanes; 1,280 bytes a position a layer for 512 + 64 in bfloat16). The
prefill-insert writes a prompt's rows and attends keys and values it
multiplies out of them; the decode step attends the rows themselves
(``ops/flash_decode.mla_decode``). Same slot axis, same lifecycle, same
staleness-repair invariant: a released slot's rows are dead because the
next occupant writes every position before a query of its own reads it.

A model with LINEAR-ATTENTION layers (``layer_types``; Gated DeltaNet,
``ops/gated_delta.py``) has a fourth kind, which is not rows at all: beside
the ``"k"/"v"`` of its full layers, ``"s": [L_lin, S, H/g, dk, g dv]``
float32, a head's recurrent state, and ``"conv": [L_lin, S, (W - 1) C]``,
the last inputs of the layer's short convolution. Same slot axis and the
same lifecycle, but NOT the same invariant: every step folds the state into
itself, so nothing a later write could repair is ever dead. Three rules
take the invariant's place, each in the model's cached forwards and each
with a test that fails without it (``tests/serving/test_state_cache.py``):

- **an insert at position 0 starts from a zero state and a zero tail**, a
  continuation chunk (``pos0 > 0``) from the slot's own
  (``TransformerLM.decode_chunk``): what the slot's last occupant left is
  not the new one's past, and release does no device work to clear it;
- **nothing past ``n_valid`` touches state or tail**: the insert program
  tells the model how many of a bucket's tokens are real, as it tells a
  ring;
- **a row that is not live keeps both**: the decode programs hand their
  ``live`` mask to ``decode_step``, single and fused alike, so a free slot
  (dummy token at position 0) and a parked partial prefill (at its write
  head) ride the batch without folding garbage in.

A LOOPED model (``passes`` > 1: the whole layer stack run several times a
token) keeps every stack ``passes`` times as deep, pass ``u``'s layer ``l``
at cache layer ``u * L + l``: the same slot axis, lifecycle and invariant,
each pass's rows written at their position before any query reads them.

A request's lifecycle against it:

1. **allocate** — pop a slot id off the free list (host bookkeeping only).
2. **prefill-insert** — run the prompt through
   :meth:`TransformerLM.prefill_slot` (a ``decode_chunk`` at position 0
   over just that slot's rows), which writes the prompt's K/V without
   touching any other slot. Prompts are right-padded to a power-of-two
   bucket so the insert program compiles once per bucket, not once per
   prompt length; pad K/V is harmless by the staleness-repair invariant
   (every pad position is overwritten by this request's own decode writes
   before any of its queries attend it) and the first token is read from
   the REAL last row of the logits. (A ring is filled from the real
   tokens only: the insert program tells the model how many there are.)
3. **decode in place** — the engine's batched ``decode_step`` advances all
   active slots with per-row positions; this module only tracks where each
   slot's write head is.
4. **release** — push the slot id back on the free list. No device work:
   the stale K/V left behind is dead by construction (the next occupant's
   prefill starts at position 0 and repairs every position before reading
   it), which is what makes slot reclaim O(1).

Rolling caches of a model whose EVERY layer is windowed are refused up
front — their one stack's ring-write margin bookkeeping is per-rollout,
not per-slot (see :meth:`TransformerLM.prefill_slot`).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def program_name(fn) -> str:
    """The name a compiled program goes by: the called function's own,
    through any ``functools.partial`` around it (``partial(_decode_kernel,
    model)`` is ``_decode_kernel``, which XLA calls ``jit__decode_kernel``).
    What the engine's spans carry as ``program=``."""
    while isinstance(fn, partial):
        fn = fn.func
    return getattr(fn, "__name__", type(fn).__name__)


def bucket_length(n: int, minimum: int = 8) -> int:
    """Smallest power of two >= ``n`` (and >= ``minimum``): the prompt pad
    target, so one compiled insert program serves a 2× range of prompt
    lengths instead of one program per length."""
    b = max(int(minimum), 1)
    while b < n:
        b *= 2
    return b


@partial(jax.jit, static_argnames=("model",), donate_argnums=(2,))
def _insert_kernel(model, params, cache, tokens, t_last, slot, pos0):
    """Compiled prefill-insert: ``tokens`` ``[1, Tb]`` (bucket-padded) into
    slot ``slot`` of ``cache`` starting at position ``pos0``; returns
    (last real logits ``[V]`` f32, cache). Keyed on (model, Tb) —
    ``t_last``/``slot``/``pos0`` stay traced so every request (and every
    prefill CHUNK) in a bucket reuses one program. The cache is DONATED:
    on accelerators the multi-GB buffer updates in place instead of being
    copied (CPU silently ignores the hint)."""
    # a model with a ring beside the horizon fills it from the real
    # tokens, and one with linear-attention layers folds only those into
    # its state; every other model reads nothing of the padding
    kw = ({"n_valid": t_last + 1}
          if model._two_kind or getattr(model, "hybrid", False) else {})
    logits, cache = model.prefill_slot(params, tokens, slot, cache,
                                       pos0=pos0, **kw)
    last = jax.lax.dynamic_index_in_dim(logits[0], t_last, axis=0,
                                        keepdims=False)
    return last, cache


class SlotKVCache:
    """Free-list + per-slot write-head bookkeeping over one batched KV
    buffer. Pure host object apart from the buffers it owns: every device
    mutation goes through the compiled insert kernel or the engine's
    decode step, and ``self.cache`` is always the current functional value.

    ``capacity`` overrides the cache time axis (already-aligned totals
    only — the sharded engine passes ``shards × aligned(ceil(len/shards))``
    so each shard's local slice meets the flash-decode block contract);
    default is ``aligned_cache_length(max_len)`` via ``init_cache``.
    """

    def __init__(self, model, params, n_slots: int,
                 max_len: Optional[int] = None,
                 capacity: Optional[int] = None,
                 cache: Optional[Dict[str, Any]] = None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if model._ring_cache:
            raise NotImplementedError(
                "SlotKVCache needs a linear (horizon) cache; all-windowed "
                "models allocate rolling buffers (see "
                "TransformerLM.prefill_slot)"
            )
        self.model = model
        self.params = params
        self.n_slots = int(n_slots)
        self.max_len = int(model.max_len if max_len is None else max_len)
        if cache is not None:
            self.cache = cache          # sharded engine pre-places its own
        else:
            self.cache = model.init_cache(self.n_slots,
                                          length=capacity or self.max_len)
        self.capacity = int(self.cache["k"].shape[3])
        if self.max_len > self.capacity:
            raise ValueError(
                f"max_len {self.max_len} exceeds cache capacity "
                f"{self.capacity}")
        self._free: List[int] = list(range(self.n_slots - 1, -1, -1))
        # write head per slot: the absolute position the NEXT write lands
        # at (prompt length after insert; +1 per decode step)
        self.pos = np.zeros(self.n_slots, np.int32)
        # requests the engine evicted from a slot to re-prefill later
        # (``ServingEngine._preempt`` counts here, as on the paged cache)
        self.preemptions = 0

    # -- slot accounting -------------------------------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> int:
        return self.n_slots - len(self._free)

    def allocate(self) -> int:
        if not self._free:
            raise RuntimeError("no free slot (caller must check free_slots)")
        return self._free.pop()

    def release(self, slot: int) -> None:
        if slot in self._free or not 0 <= slot < self.n_slots:
            raise ValueError(f"bad release of slot {slot}")
        self.pos[slot] = 0
        self._free.append(slot)

    # -- weight rollover --------------------------------------------------
    def set_params(self, params) -> None:
        """Swap the weights future PREFILL INSERTS run under (decode steps
        take params from the engine per launch). Pure host reassignment:
        the params pytree has the same shapes/dtypes, so the compiled
        insert kernels never retrace, and params are never donated, so no
        kernel can be holding a donated alias of the old tree."""
        self.params = params

    # -- device ops ------------------------------------------------------
    def padded_length(self, n_tokens: int, pos0: int = 0) -> int:
        """The length :meth:`insert` pads ``n_tokens`` to at ``pos0``: the
        bucket, but never past the cache end — a clamped
        dynamic_update_slice would silently SHIFT the write left over live
        positions, which is worse than the extra program the odd trailing
        bucket costs."""
        return min(bucket_length(n_tokens), self.capacity - pos0)

    def insert_program(self, insert_fn=None):
        """The compiled program :meth:`insert` runs: ``insert_fn``, or the
        default for this model (the engine names it on its span)."""
        return insert_fn if insert_fn is not None else partial(
            _insert_kernel, self.model)

    def insert(self, slot: int, prompt: np.ndarray,
               insert_fn=None, pos0: int = 0) -> jnp.ndarray:
        """Prefill ``prompt`` ``[T0]`` int into ``slot`` at positions
        ``pos0..pos0+T0-1``; returns the logits of the last REAL prompt
        position ``[V]`` (what the first generated token is selected
        from). ``pos0 > 0`` is a chunked-prefill continuation: the chunk
        attends everything this slot already holds. ``insert_fn``
        overrides the compiled kernel (the sharded engine passes its
        shard_map'd one with the same ``(params, cache, tokens, t_last,
        slot, pos0) → (last, cache)`` signature)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        T0 = prompt.shape[0]
        pos0 = int(pos0)
        if not 1 <= T0 <= self.max_len:
            raise ValueError(f"prompt length {T0} not in [1, {self.max_len}]")
        if not 0 <= pos0 <= self.max_len - T0:
            raise ValueError(
                f"pos0 {pos0} + chunk {T0} exceeds max_len {self.max_len}")
        Tb = self.padded_length(T0, pos0)
        padded = np.zeros((1, Tb), np.int32)
        padded[0, :T0] = prompt
        last, self.cache = self.insert_program(insert_fn)(
            self.params, self.cache, jnp.asarray(padded), T0 - 1, slot, pos0)
        self.pos[slot] = pos0 + T0
        return last

    def advance(self, slot: int) -> None:
        """Record one decode-step write for ``slot`` (the write itself
        happened inside the engine's batched decode program)."""
        self.pos[slot] += 1

    def remaining(self, slot: int) -> int:
        return self.max_len - int(self.pos[slot])
