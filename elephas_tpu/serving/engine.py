"""The continuous-batching driver loop.

``ServingEngine`` is the host orchestrator over a small set of compiled
programs — prefill-insert (per prompt-length bucket), ONE batched decode
step, and (fast path) a FUSED K-step decode — multiplexing every
in-flight request through them:

    submit() ──▶ scheduler (bounded queue) ──▶ prefill into a free slot
                                                     │ first token
                                                     ▼
                     one decode program over ALL slots per step()
                     (single step, or a fused ``lax.scan`` of K steps
                      when the fast path engages; per-row positions;
                      free slots ride along as no-op rows)
                                                     │ token(s) per slot
                                                     ▼
                     EOS / length? → release slot → next queued request

The decode batch is always the full ``[n_slots]`` geometry, so each
decode program compiles ONCE: admission, completion, and reclaim never
retrace. Free slots decode a dummy token at position 0 — the garbage K/V
that writes is dead by the staleness-repair invariant (the next
occupant's prefill overwrites it before anything attends it), and
position 0 is the cheapest row a masked decode can run.
(A model with linear-attention layers keeps a recurrent state a slot,
which no later write repairs: the decode programs hand their ``live``
mask to ``decode_step``, and a row that is not live, a free slot or a
parked partial prefill, leaves state and convolution tail as they were:
``serving/cache.py``.)

Four fast-path mechanisms (all OFF by default; every default-config
behavior, including greedy/sampled token streams, is unchanged):

- **Chunked prefill** (``prefill_chunk=``): a prompt longer than the
  chunk size is inserted as fixed-size chunks interleaved with decode
  steps, so co-batched requests see a bounded inter-token-latency bump
  per chunk instead of one whole-prompt stall. A partially-prefilled
  slot rides the decode batch as a non-live row parked AT ITS WRITE
  HEAD: the garbage K/V each interleaved step writes there is exactly
  what the next chunk overwrites.
- **Fused multi-token decode** (``fuse_k=``): when no admission is
  pending, no open chunk train, no live deadline, and every active slot
  has ≥K budget left, K decode steps run inside ONE compiled
  ``lax.scan`` program. Rows are independent and selection is keyed by
  ``(seed, position)``, so the emitted streams are token-identical to K
  single steps; the host truncates at EOS/budget afterward (the
  post-EOS device writes are garbage the staleness-repair invariant
  makes dead).
- **Device-resident step state**: the per-slot carry token / position /
  temperature / PRNG key / liveness live as device arrays the decode
  kernels advance in place; the host touches them only through a tiny
  jitted row-scatter at admission and release, instead of re-uploading
  full mirrors every step. The KV cache is donated through every
  kernel, so on accelerators the multi-GB buffer updates in place.
- **Speculative decoding** (``speculate_k=``): a cheap drafter proposes
  ``speculate_k - 1`` tokens per live slot, then ONE fused verify
  program scores the carry + drafts as a ``decode_chunk`` and accepts
  each row's longest prefix that matches what the sequential engine
  would have emitted — the same ``(seed, position)``-keyed selection
  rule at every chunk position — so up to ``speculate_k`` tokens commit
  per launch and the emitted stream is BITWISE the non-speculative one
  (greedy and sampled alike; see
  :func:`~elephas_tpu.models.transformer.spec_verify_select` for why
  this is PR 1's distribution-exact accept/resample rule under a
  deterministic proposer). Speculation stands down to the single-step
  driver on exactly the conditions that collapse ``_fuse_window``.

Selection is per slot inside the compiled step
(:func:`~elephas_tpu.models.transformer.select_slot_tokens`): greedy rows
and sampled rows coexist in one batch, and a request's sample stream is
keyed by ``(seed, position)`` — independent of slot assignment and of
what else is co-batched, so results are reproducible under any
interleaving (and under any chunking or fusion). Greedy outputs are
token-identical to per-request :meth:`TransformerLM.generate` wherever
the two run the same arithmetic — on the CPU, and on the TPU under
``jax.default_matmul_precision("highest")``. At the TPU's default matmul
precision they are numerically different programs (the engine prefills
through ``decode_chunk``, ``generate`` through the flash kernel) and were
seen on the v5e to part where two logits tie within ~0.004; the dense and
the paged engine stayed equal to each other in every run.

With ``mesh=`` the programs come from
:func:`~elephas_tpu.models.sharded_generate.build_serving_ops` instead:
slots shard over ``"data"``, the KV cache time axis over ``"seq"``, and
the driver loop here is UNCHANGED — the ops have the same signatures,
including the chunked insert and the fused decode.

Time is injectable (``clock=``): latency tests pin exact TTFT/queue-wait
numbers with a fake clock instead of sleeping. The fast-path histograms
(inter-token latency, dispatch overhead, chunk stalls) deliberately read
a SEPARATE ``perf_clock`` (``time.perf_counter`` by default) — they
measure wall clock, and reading the lifecycle clock for them would
perturb fake-clock tests. The fleet trace-replay harness injects a
simulated ``perf_clock`` so even the latency histograms replay
deterministically in tier-1; the real-time default is unchanged.

A ``jax.profiler`` trace shows the loop by name: ``submit`` and every
phase of ``step`` run inside ``jax.profiler.TraceAnnotation`` spans
prefixed ``elephas.engine.`` (``step`` › ``reap``, ``decide``,
``prefill`` › ``insert`` / ``select_first`` / ``set_row`` / ``fetch``,
``prefill_chunk``, ``decode`` › ``dispatch`` / ``fetch`` / ``emit``), on the
device trace's clock. They record only while a trace runs and cost well
under a microsecond otherwise; there is nothing to turn on, and no span
sits inside a per-row or per-token loop (docs/SERVING.md, "Reading a
profile"). A span that directly wraps a call of one of the engine's
compiled programs says which execution on the device it caused:
``launch``, the engine's count of such calls (``snapshot()["work"]
["programs_launched"]``), and ``program``, the called function's name;
each ``fetch`` carries the ``launch`` it waits for, and a call with no
span of its own shows as ``launches`` on the spans it happened in.
``benchmark/program_runs.py`` joins them to the device's executions.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from ..models.transformer import (_adapter_ctx, select_slot_tokens,
                                  spec_verify_select)
from ..ops.flash_decode import kv_block_walk, kv_copied_positions
from ..ops.gated_delta import BLOCK as GDN_BLOCK
from .cache import SlotKVCache, bucket_length, program_name
from .memory import PagedKVCache, PagesExhausted
from .metrics import RequestTiming, ServingMetrics
from .scheduler import AdmissionError, Scheduler, ServingRequest


@partial(jax.jit, static_argnames=("model",), donate_argnums=(2,))
def _decode_kernel(model, params, cache, tokens, pos, temps, keys, live):
    """One batched decode step over every slot + per-slot selection, as a
    single program: ``tokens``/``pos``/``temps`` ``[S]``, ``keys``
    ``[S, 2]``, ``live`` ``[S]`` bool → ``(emitted [S] int32, tokens,
    pos, cache)``. ``pos`` is per-row — exactly the batched-speculative
    form of ``decode_step`` — so slots at wildly different depths advance
    together. The carry token/position advance IN the program (live rows
    only), so the host never re-uploads them; the cache is donated. The
    model is handed ``live`` too: a row that is not live (a free slot, a
    parked partial prefill) leaves a linear-attention layer's state and
    convolution tail as they were."""
    logits, cache = model.decode_step(params, tokens, pos, cache, live=live)
    emit = select_slot_tokens(logits, pos + 1, temps, keys)
    tokens = jnp.where(live, emit, tokens)
    pos = jnp.where(live, pos + 1, pos)
    return emit, tokens, pos, cache


@partial(jax.jit, static_argnames=("model", "n_steps"), donate_argnums=(2,))
def _fused_decode_kernel(model, params, cache, tokens, pos, temps, keys,
                         live, n_steps: int):
    """``n_steps`` decode steps fused into ONE program (``lax.scan`` of
    the single-step body): amortizes per-token dispatch overhead. Emits
    every step's tokens ``[S, n_steps]``; non-live rows neither advance
    nor change their carry (their emitted entries are garbage the host
    ignores). Token-identical to ``n_steps`` single-step launches — rows
    are independent and selection is ``(seed, position)``-keyed."""
    def body(carry, _):
        tok, p, cache = carry
        logits, cache = model.decode_step(params, tok, p, cache, live=live)
        emit = select_slot_tokens(logits, p + 1, temps, keys)
        tok = jnp.where(live, emit, tok)
        p = jnp.where(live, p + 1, p)
        return (tok, p, cache), emit

    (tokens, pos, cache), emitted = jax.lax.scan(
        body, (tokens, pos, cache), None, length=n_steps)
    return emitted.T, tokens, pos, cache


@partial(jax.jit, static_argnames=("model",), donate_argnums=(2,))
def _verify_kernel(model, params, cache, drafts, tokens, pos, temps, keys,
                   live):
    """ONE speculative verify program over every slot: score the carry +
    ``W`` drafted tokens as a single ``decode_chunk`` (each row's chunk
    starts at its own ``pos``), select what the sequential engine WOULD
    emit at all ``W+1`` positions (:func:`spec_verify_select`), and
    advance live rows past their accepted run + correction in-program.
    Returns ``(sel [S, W+1], n_accepted [S], tokens, pos, cache)`` —
    compiled once per draft width, like the fused kernel per ``n_steps``.
    The chunk's K/V writes land at ``pos..pos+W``; the rejected tail is
    stale-dead by the staleness-repair invariant (the next round's chunk
    starts at ``pos + n + 1`` and overwrites it before anything attends
    it)."""
    chunk = jnp.concatenate([tokens[:, None], drafts], axis=1)
    logits, cache = model.decode_chunk(params, chunk, pos, cache)
    sel, n = spec_verify_select(logits, drafts, pos, temps, keys)
    corr = jnp.take_along_axis(sel, n[:, None], axis=1)[:, 0]
    tokens = jnp.where(live, corr, tokens)
    pos = jnp.where(live, pos + n + 1, pos)
    return sel, n, tokens, pos, cache


@partial(jax.jit, static_argnames=("model", "n_steps"), donate_argnums=(2,))
def _draft_propose_kernel(model, params, cache, tokens, pos, live, aids,
                          n_steps: int):
    """Greedy draft rollout on the DRAFT model's own dense slot cache:
    ``n_steps`` decode steps from the TARGET's carry/position state (the
    draft write head always equals the target's committed head at round
    start), emitting argmax proposals ``[S, n_steps]`` under each row's
    adapter. The rollout conditions on its own proposals — that is what
    drafting means — and the cache rows it writes past this round's
    accepted prefix are overwritten by the next round's rollout before
    anything attends them (same contiguous-frontier repair as the target
    cache). Greedy argmax keeps the proposer a delta distribution, which
    the exact-match acceptance rule requires."""
    def body(carry, _):
        tok, p, cache = carry
        with _adapter_ctx(model, aids):
            logits, cache = model.decode_step(params, tok, p, cache)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tok = jnp.where(live, nxt, tok)
        p = jnp.where(live, p + 1, p)
        return (tok, p, cache), nxt

    (_, _, cache), drafts = jax.lax.scan(
        body, (tokens, pos, cache), None, length=n_steps)
    return drafts.T, cache


@partial(jax.jit, static_argnames=("model",), donate_argnums=(2,))
def _draft_insert_kernel(model, params, cache, tokens, slot, aid):
    """Prefill the draft cache's ``slot`` row with the (bucket-padded)
    prompt under the row's adapter — a :class:`MultiTenantLM` draft model
    serves per-tenant drafters inside the same compiled program. The
    logits are discarded: the next rollout re-reads the carry the TARGET
    selected."""
    with _adapter_ctx(model, jnp.reshape(aid, (1,))):
        _, cache = model.prefill_slot(params, tokens, slot, cache)
    return cache


class NgramDrafter:
    """Self-drafting prompt-lookup proposer (host-side, deterministic, no
    extra parameters): propose the ``k`` tokens that FOLLOWED the most
    recent earlier occurrence of the context's trailing n-gram (longest
    ``n`` first), falling back to repeating the last token. Free to run
    and strong on structured continuations (code, retrieval-grounded
    text, loops); acceptance on high-entropy text is low, which costs
    wasted chunk width but never changes the emitted stream — the verify
    rule is exact under ANY deterministic proposer."""

    def __init__(self, n_max: int = 3):
        if n_max < 1:
            raise ValueError(f"n_max must be >= 1, got {n_max}")
        self.n_max = int(n_max)

    def propose(self, context, k: int) -> np.ndarray:
        ctx = np.asarray(context, np.int32).reshape(-1)
        T = ctx.shape[0]
        out = np.full(k, int(ctx[-1]) if T else 0, np.int32)
        for n in range(min(self.n_max, T - 1), 0, -1):
            pat = ctx[T - n:]
            wins = np.lib.stride_tricks.sliding_window_view(ctx[:-1], n)
            hits = np.nonzero((wins == pat[None, :]).all(axis=1))[0]
            if hits.size:
                s = int(hits[-1])
                cont = ctx[s + n: s + n + k]
                out[:cont.size] = cont
                out[cont.size:] = int(cont[-1])
                return out
        return out


class ModelDrafter:
    """Draft-transformer proposer: greedy rollouts from a small model on
    its OWN dense slot cache (engine-managed), prefilled at admission and
    advanced in lockstep with the target's committed stream. Pass a
    :class:`~elephas_tpu.models.lora.MultiTenantLM` to draft per-adapter:
    each row rolls out under the row's adapter. A non-multi-tenant draft
    model drafts every tenant with its base weights — acceptance may
    drop for adapted rows, correctness never depends on the proposer.
    Local engines only (dense or paged); meshes use the n-gram drafter."""

    def __init__(self, model, params):
        if model._ring_cache:
            raise NotImplementedError(
                "draft model must use a linear (horizon) cache — windowed "
                "models roll their buffers in prefill_slot")
        if getattr(model, "hybrid", False):
            raise NotImplementedError(
                "a draft model with linear-attention layers folds every "
                "proposal into its recurrent state, and the rollout past the "
                "accepted prefix cannot be written over as cache rows are")
        self.model = model
        self.params = params


@partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _scatter_row(tok, pos, temps, keys, live, slot, t, p, tmp, key, lv):
    """Jitted single-row update of the device-resident step state (one
    program — ``slot`` and the values stay traced). The five state
    arrays are donated: a row scatter must not copy the batch."""
    return (tok.at[slot].set(t), pos.at[slot].set(p),
            temps.at[slot].set(tmp), keys.at[slot].set(key),
            live.at[slot].set(lv))


def _host_key(seed: int) -> np.ndarray:
    """The uint32 ``[2]`` key ``jax.random.PRNGKey(seed)`` returns
    (threefry2x32), made on the host: the seed plus
    ``jax_random_seed_offset``, its two's-complement 64 bits split high and
    low, first cut to 32 bits where x64 is off (the high word then reads
    0)."""
    s = int(np.int64(seed)) + jax.config.jax_random_seed_offset
    hi = (s >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([hi, s & 0xFFFFFFFF], np.uint32)


@jax.jit
def _select_first(last, t0, temp, key):
    """Select the FIRST generated token from the prefill's last-position
    logits ``[V]`` with the same per-slot rule the decode step applies
    (the token occupies position ``t0``)."""
    return select_slot_tokens(
        last[None], jnp.asarray([t0]), jnp.asarray([temp]), key[None])[0]


@dataclass
class FinishedRequest:
    """Terminal record handed back by :meth:`ServingEngine.result` /
    :meth:`ServingEngine.drain`.

    ``token_versions[i]`` is the weights version live at the decode round
    that emitted ``tokens[i]`` — every token is attributable to exactly
    ONE version, and version boundaries fall only between rounds.
    ``version_first``/``version_last`` summarize the stream's span (equal
    unless a hot swap landed mid-request; ``-1`` on a request cancelled
    before its first token)."""

    request_id: str
    prompt: np.ndarray            # [T0] int32
    tokens: List[int]             # generated continuation (EOS included)
    # "eos" | "length" | "deadline" | "cancelled" | "shed" (deadline
    # provably unmeetable at admission time — never cost a slot)
    finish_reason: str
    timing: RequestTiming
    token_versions: List[int] = field(default_factory=list)
    version_first: int = -1
    version_last: int = -1


class ServingEngine:
    """Continuous-batching inference over one model: ``submit() →
    request_id``, ``step()`` (one scheduler action), ``drain()`` (run to
    empty). See the module docstring for the loop shape and the
    ``prefill_chunk`` / ``fuse_k`` fast-path knobs."""

    def __init__(self, model, params, n_slots: int = 8,
                 max_len: Optional[int] = None, max_queue: int = 64,
                 mesh=None, clock: Callable[[], float] = time.monotonic,
                 metrics_window: int = 1024, max_finished: int = 1024,
                 fault_plan=None, prefill_chunk: Optional[int] = None,
                 fuse_k: int = 1, paged: bool = False, page_size: int = 16,
                 pages_per_partition: Optional[int] = None,
                 prefix_cache: bool = True, speculate_k: int = 1,
                 drafter=None,
                 perf_clock: Callable[[], float] = time.perf_counter,
                 itl_estimate_s: Optional[float] = None):
        if max_finished < 1:
            raise ValueError(f"max_finished must be >= 1, got {max_finished}")
        if fuse_k < 1:
            raise ValueError(f"fuse_k must be >= 1, got {fuse_k}")
        if speculate_k < 1:
            raise ValueError(f"speculate_k must be >= 1, got {speculate_k}")
        if speculate_k > 1 and getattr(model, "n_experts", 0):
            raise ValueError(
                "speculate_k > 1 needs a dense-FFN target: the verify chunk "
                "re-groups MoE expert dispatch, which breaks the bitwise pin "
                "against sequential decode")
        if getattr(model, "hybrid", False):
            if speculate_k > 1:
                raise NotImplementedError(
                    "speculate_k > 1: a verify chunk folds its rejected "
                    "drafts into a linear-attention layer's state, and "
                    "without a snapshot of the state it cannot be rolled "
                    "back")
            if paged:
                raise NotImplementedError(
                    "paged=True: the page pool holds pages of K and V rows, "
                    "and a linear-attention layer's per-slot state has no "
                    "pool beside them yet")
            if mesh is not None:
                raise NotImplementedError(
                    "mesh=: the sharded serving ops split K and V stacks "
                    "over the mesh and know no recurrent state; a model with "
                    "linear-attention layers is served by the local "
                    "dense-slot engine")
        if getattr(model, "passes", 1) > 1:
            if speculate_k > 1:
                raise NotImplementedError(
                    "speculate_k > 1: the verify program and its pin "
                    "against sequential decode are written for a stack "
                    "walked once a token, not a looped one (passes > 1)")
            if paged or mesh is not None:
                raise NotImplementedError(
                    "a looped stack (passes > 1) keeps a cache layer a pass "
                    "and layer, served by the local dense-slot engine only: "
                    "the paged pool and the sharded ops hold one layer a "
                    "weight layer")
        if (mesh is not None or paged) and getattr(model, "latent", False):
            raise NotImplementedError(
                "a latent-attention model's cache is one stack of latent "
                "rows, served by the local dense-slot engine only: the paged "
                "pool holds pages of per-head K and V rows, and the sharded "
                "ops split K and V stacks over the mesh")
        if (mesh is not None or paged) and (
                getattr(model, "_two_kind", False)
                or getattr(model, "n_lead", 0)):
            raise NotImplementedError(
                "a ring of the window's length beside the horizon "
                "(window_cache='ring') and leading layers outside the layer "
                "scan are served by the local dense-slot engine only: the "
                "paged pool and the sharded ops walk one stack of every "
                "layer")
        if mesh is not None and isinstance(drafter, ModelDrafter):
            raise NotImplementedError(
                "ModelDrafter is local-engine only (its slot cache is "
                "unsharded); mesh engines speculate with the n-gram drafter")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if itl_estimate_s is not None and itl_estimate_s <= 0:
            raise ValueError(
                f"itl_estimate_s must be > 0, got {itl_estimate_s}")
        self.model = model
        self.params = params
        # the window of the model's window layers (None: it has none): the
        # decode span then also says what THEY need to attend
        self._window = getattr(model, "_max_window", None)
        # layers that cache latent rows (0: none): ``snapshot()`` then says
        # how many rows the decode steps had to read, layer by layer
        self._latent_layers = (model.n_layers
                               if getattr(model, "latent", False) else 0)
        # layers that keep a recurrent state (0: none): the decode span
        # then says how many states its live rows read and wrote
        self._linear_layers = int(getattr(model, "n_linear", 0))
        # a looped stack's passes and cache layers (1, or ``{}``: a stack
        # walked once): its decode and prefill spans say both, and
        # ``snapshot()`` how many rows the decode steps read, cache layer by
        # cache layer
        self._passes = int(getattr(model, "passes", 1))
        self._loop_args = ({} if self._passes == 1 else
                           {"passes": self._passes,
                            "cache_layers": self._passes * model.n_layers})
        # how the decode kernel is called a layer (set below where the
        # decode program is the model's own ``decode_step`` on a slot
        # cache): the span and the counters then say how many cache blocks
        # its live rows attend and how many the kernel visits for them
        self._decode_walks = None
        self.clock = clock
        # latency-histogram clock (ITL / dispatch / chunk stalls): real
        # wall time by default, injectable so fleet trace replay pins the
        # histograms deterministically. Separate from ``clock`` so fake
        # lifecycle clocks never see extra reads.
        self._perf = perf_clock
        # per-token latency floor for deadline-aware admission: a queued
        # request whose remaining budget cannot finish by its deadline
        # even at this rate is SHED at decide time instead of admitted and
        # reaped late. None = only already-expired queued work is shed.
        self.itl_estimate_s = (None if itl_estimate_s is None
                               else float(itl_estimate_s))
        self.max_finished = int(max_finished)
        # chunk size rounds UP to the insert kernel's bucket grid so a
        # full chunk is never padded (one compiled program per chunk)
        self.prefill_chunk = (None if prefill_chunk is None
                              else bucket_length(int(prefill_chunk)))
        self.fuse_k = int(fuse_k)
        # resilience.FaultPlan (duck-typed): serving_stall(step_index)
        # seconds accumulate into _skew, which every engine-side clock read
        # adds on — a deterministic "this step took 30s" without sleeping,
        # which is what pushes a request past its deadline in tests.
        self.fault_plan = fault_plan
        self._skew = 0.0
        self._step_index = 0
        self.scheduler = Scheduler(max_queue=max_queue)
        self.metrics = ServingMetrics(n_slots=n_slots, window=metrics_window,
                                      spec_k=int(speculate_k))
        self._paged = bool(paged)
        if paged:
            # paged engine: the KV pool + block tables live in PagedKVCache,
            # which exposes the same insert/decode surface the driver loop
            # already speaks (local and mesh) — the loop below is unchanged
            self.kv = PagedKVCache(
                model, params, n_slots, max_len=max_len,
                page_size=page_size,
                pages_per_partition=pages_per_partition,
                prefix_cache=prefix_cache, mesh=mesh)
            self._insert_fn = None          # PagedKVCache dispatches inside
            self._decode_fn = self.kv.decode_fn
            self._fused_fn = self.kv.fused_fn
            self._verify_fn = self.kv.verify_fn
            if mesh is None:
                state_shardings = [None] * 5
            else:
                from jax.sharding import NamedSharding, PartitionSpec as P
                from ..parallel.mesh import DATA_AXIS
                row = NamedSharding(mesh, P(DATA_AXIS))
                state_shardings = [row, row, row,
                                   NamedSharding(mesh, P(DATA_AXIS, None)),
                                   row]
        elif mesh is None:
            self.kv = SlotKVCache(model, params, n_slots, max_len=max_len)
            if hasattr(model, "decode_walks"):
                self._decode_walks = model.decode_walks(self.kv.cache)
            self._insert_fn = None          # SlotKVCache's compiled default
            self._decode_fn = partial(_decode_kernel, model)
            self._fused_fn = partial(_fused_decode_kernel, model)
            self._verify_fn = partial(_verify_kernel, model)
            state_shardings = [None] * 5
        else:
            # deferred import: sharded_generate is a heavier module and
            # this is the only place the local path would pull it in
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..models.sharded_generate import build_serving_ops
            from ..parallel.mesh import DATA_AXIS
            ops = build_serving_ops(model, mesh, n_slots,
                                    max_len=max_len)
            self.kv = SlotKVCache(model, params, n_slots,
                                  max_len=ops.max_len, cache=ops.init_cache())
            self._insert_fn = ops.insert
            self._decode_fn = ops.decode
            self._fused_fn = ops.decode_fused
            self._verify_fn = ops.verify
            row = NamedSharding(mesh, P(DATA_AXIS))
            state_shardings = [row, row, row,
                               NamedSharding(mesh, P(DATA_AXIS, None)), row]
        # per-slot step state, DEVICE-resident: the decode kernels advance
        # it in place; the host writes single rows through _scatter_row at
        # admission/release instead of re-uploading [S] mirrors every step
        S = self.kv.n_slots
        init = (jnp.zeros(S, jnp.int32),        # carry token per slot
                jnp.zeros(S, jnp.int32),        # write-head position
                jnp.zeros(S, jnp.float32),      # <=0 ⇒ greedy row
                jnp.zeros((S, 2), jnp.uint32),  # PRNG key per slot
                jnp.zeros(S, bool))             # live (advancing) row?
        (self._tok, self._pos, self._temps, self._keys, self._live) = (
            a if sh is None else jax.device_put(a, sh)
            for a, sh in zip(init, state_shardings))
        # speculative decoding (speculate_k >= 2): drafter + (for a model
        # drafter) its own dense slot cache, advanced in lockstep with the
        # target's committed stream
        self.speculate_k = int(speculate_k)
        self.drafter = None
        self._draft_cache = None
        if self.speculate_k > 1:
            self.drafter = NgramDrafter() if drafter is None else drafter
            if isinstance(self.drafter, ModelDrafter):
                dm = self.drafter.model
                self._draft_cache = dm.init_cache(S, self.kv.max_len)
                self._draft_aids = np.zeros(S, np.int32)
        # weight rollover: the monotonic-ish version stamp of the weights
        # currently serving (0 until the first swap; a rollback republishes
        # an OLDER stamp) and the drafter-staleness flag — a ModelDrafter
        # whose params were NOT swapped with the target's stands down until
        # fresh drafter params arrive (acceptance would crater, and the
        # drafter must never speculate against weights it has not seen).
        self.weights_version = 0
        self._drafter_stale = False
        self._partial: Optional[ServingRequest] = None  # open chunk train
        # calls made to the engine's own compiled programs since it was
        # built (:meth:`_count`), and how many of them no span of their own
        # carries
        self._launched = 0
        self._bare = 0
        self._last_action: Optional[str] = None
        self._slot_req: Dict[int, ServingRequest] = {}
        self._requests: Dict[str, ServingRequest] = {}
        self._finished: Dict[str, FinishedRequest] = {}
        self._next_id = 0
        self._admit_seq = itertools.count()  # preemption recency order

    # -- time ------------------------------------------------------------
    def _now(self) -> float:
        """Engine time: the injected clock plus accumulated injected-stall
        skew (every deadline check and timing stamp reads this, so an
        injected stall ages EVERYTHING consistently)."""
        return self.clock() + self._skew

    # -- submission ------------------------------------------------------
    def submit(self, prompt, max_new: int, temperature: float = 0.0,
               eos_id: Optional[int] = None, priority: int = 0,
               seed: int = 0, on_token: Optional[Callable] = None,
               request_id: Optional[str] = None,
               deadline_s: Optional[float] = None,
               adapter_id: int = 0) -> str:
        """Enqueue one generation request; returns its id. Raises
        :class:`AdmissionError` (with a machine-readable ``.reason``) on
        validation failure or queue backpressure — rejected work never
        holds a queue entry or a slot. ``deadline_s`` bounds the request's
        whole lifetime from submit: once exceeded it is reaped at the next
        ``step()`` with ``finish_reason="deadline"`` and whatever tokens it
        produced, and its slot is reclaimed. ``adapter_id`` selects the
        request's LoRA variant on a paged engine serving a
        :class:`~elephas_tpu.models.lora.MultiTenantLM` (0 = the base
        model everywhere)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        T0 = prompt.shape[0]
        rid = request_id or f"req-{self._next_id}"
        with _span("elephas.engine.submit", request_id=rid):
            try:
                if rid in self._requests or rid in self._finished:
                    raise AdmissionError("bad_request",
                                         f"duplicate request_id {rid!r}")
                if max_new < 1:
                    raise AdmissionError(
                        "bad_request", f"max_new must be >= 1, got {max_new}")
                if deadline_s is not None and deadline_s <= 0:
                    raise AdmissionError(
                        "bad_request",
                        f"deadline_s must be > 0, got {deadline_s}")
                if T0 < 1 or T0 > self.kv.max_len:
                    raise AdmissionError(
                        "prompt_too_long",
                        f"prompt length {T0} not in [1, {self.kv.max_len}]")
                if T0 + int(max_new) > self.kv.max_len:
                    raise AdmissionError(
                        "length_exceeds_cache",
                        f"prompt {T0} + max_new {max_new} exceeds "
                        f"max_len {self.kv.max_len}")
                n_adapters = int(getattr(self.model, "n_adapters", 1))
                if adapter_id != 0 and not self._paged:
                    raise AdmissionError(
                        "bad_request",
                        f"adapter_id {adapter_id}: non-zero adapters need the "
                        f"paged engine (paged=True)")
                if not 0 <= adapter_id < max(n_adapters, 1):
                    raise AdmissionError(
                        "bad_request",
                        f"adapter_id {adapter_id} not in [0, {n_adapters})")
                if self._paged and not self.kv.fits(T0 + int(max_new)):
                    raise AdmissionError(
                        "length_exceeds_cache",
                        f"prompt {T0} + max_new {max_new} cannot fit the page "
                        f"pool even alone "
                        f"({self.kv.pages_per_partition - 1} usable pages per "
                        f"partition of {self.kv.page} tokens)")
                submitted_at = self._now()
                req = ServingRequest(
                    request_id=rid, prompt=prompt, max_new=int(max_new),
                    temperature=float(temperature), eos_id=eos_id,
                    priority=int(priority), seed=int(seed), on_token=on_token,
                    adapter_id=int(adapter_id),
                    deadline_at=(None if deadline_s is None
                                 else submitted_at + float(deadline_s)),
                    timing=RequestTiming(request_id=rid, prompt_tokens=int(T0),
                                         submitted_at=submitted_at))
                self.scheduler.push(req)
            except AdmissionError as e:
                self.metrics.observe_reject(e.reason)
                raise
            self._next_id += 1
            self._requests[rid] = req
            self.metrics.observe_submit(req.adapter_id)
            return rid

    # -- the loop --------------------------------------------------------
    def step(self) -> str:
        """Run ONE scheduler action — ``"prefill"`` (admit the next queued
        request into a free slot), ``"prefill_chunk"`` (advance an open
        chunked-prefill train), ``"decode"`` (one batched decode program
        over all slots — a single step, or a fused K-step block when the
        fast path engages), or ``"idle"`` — and return which one ran.
        Expired deadlines are reaped first, so a timed-out request frees
        its slot before this step's work is chosen."""
        if self.fault_plan is not None:
            self._skew += self.fault_plan.serving_stall(self._step_index)
        self._step_index += 1
        with _span("elephas.engine.step", step=self._step_index) as span:
            bare_at_start = self._bare
            with _span("elephas.engine.reap"):
                self._shed_unmeetable()
                self._reap_expired()
            with _span("elephas.engine.decide"):
                # live decode rows only: a partially-prefilled slot is
                # allocated but must not count as decodable (with no live
                # rows its chunks run back-to-back instead of alternating
                # with no-op decodes)
                free_pages, need_pages = self._admission_budget()
                action = self.scheduler.decide(
                    self.kv.free_slots, len(self._slot_req),
                    has_partial=self._partial is not None,
                    last_action=self._last_action,
                    free_pages=free_pages, need_pages=need_pages,
                    reserve_pages=(self._spec_reserve_pages()
                                   if free_pages is not None else 0))
            span.set_metadata(action=action)
            if action == "prefill":
                req = self.scheduler.pop()
                if req is not None:
                    with _span("elephas.engine.prefill",
                               request_id=req.request_id,
                               prompt_tokens=len(self._req_prompt(req)),
                               **self._loop_args) as prefill:
                        bare = self._bare
                        self._do_prefill(req)
                        self._show_bare(prefill, bare)
            elif action == "prefill_chunk":
                self._do_prefill_chunk()
            elif action == "decode":
                self._do_decode()
            self._show_bare(span, bare_at_start)
        self._last_action = action
        return action

    def _admission_budget(self):
        """``(free_pages, need_pages)`` for the queue HEAD on the paged
        engine — what :meth:`Scheduler.decide` gates admission on —
        ``(None, None)`` whenever pages are not the binding constraint
        (dense engine, empty queue, no free slot, open chunk train).
        ``need`` counts only pages BEYOND the head's cached prefix, and
        the check may evict clean prefix pages to make room, so a cache
        hit admits under pressure a cold prompt would wait out."""
        if (not self._paged or self._partial is not None
                or not self.scheduler.queue_depth
                or self.kv.free_slots == 0):
            return None, None
        head = self.scheduler.peek()
        if head is None:
            return None, None
        # rank of the slot allocate() would hand out next
        rank = self.kv._free[-1] // self.kv.Sl
        return self.kv.admission_check(
            self._req_prompt(head), head.adapter_id, rank)

    def _spec_reserve_pages(self) -> int:
        """Pages the live slots' speculative lookahead may still claim: a
        verify round writes ``pos..pos+speculate_k-1`` per active slot, so
        admission must leave those pages claimable — otherwise an accept
        burst could exhaust the allocator mid-commit, after the verify
        program already ran (``_ensure_decode_guarded``'s evict/preempt
        recovery only helps BEFORE the launch). Counts not-yet-owned
        pages summed across active slots: a cross-partition overestimate
        of any one partition's exposure, which only makes admission
        conservative."""
        if self.speculate_k < 2 or not self._slot_req:
            return 0
        page, need = self.kv.page, 0
        for slot in self._slot_req:
            p = int(self.kv.pos[slot])
            lo = p // page
            hi = min((p + self.speculate_k - 1) // page, self.kv.M - 1)
            owned = self.kv.owned[slot]
            need += sum(1 for m in range(lo, hi + 1) if m not in owned)
        return need

    # -- weight rollover ---------------------------------------------------
    def swap_params(self, params, version: Optional[int] = None,
                    drafter_params=None) -> int:
        """Hot-swap the serving weights WITHOUT draining slots; returns
        the new :attr:`weights_version`.

        Call between ``step()`` calls (the engine is host-driven, so any
        caller on the driver thread already is): every decode round runs
        entirely under one params tree, which is what makes each emitted
        token attributable to exactly one version and keeps version
        boundaries on round boundaries. The swap is donation-safe and
        retrace-free on every fast path — the decode/fused/verify/insert
        kernels donate only the KV cache (params are plain arguments), and
        the new tree has the same shapes/dtypes, so compiled programs are
        reused as-is. In-flight requests keep their slots, carries, and
        K/V; their next round simply runs under the new weights (prompt
        K/V written under older versions stays — attribution is by
        EMISSION round, and a replay applying the same version schedule at
        the same rounds reproduces the stream token-for-token).

        ``version`` stamps the new weights (default: previous + 1). A
        ROLLBACK republishes an older version with its original stamp —
        the stamp records what is serving, not a sequence number.

        Per-knob behavior:

        - paged: the radix prefix cache is flushed (its pages hold K/V
          computed under the old weights); live slots keep their own page
          references, so nothing in flight is disturbed.
        - speculative + :class:`ModelDrafter`: pass ``drafter_params`` to
          swap the drafter ATOMICALLY with the target; without it the
          drafter STANDS DOWN (the engine decodes non-speculatively, still
          token-identical) until a later swap supplies fresh drafter
          params. Host drafters (:class:`NgramDrafter`) are parameterless
          and keep speculating — the verify rule is exact under any
          proposer, so correctness never depends on the drafter's weights.
        """
        if drafter_params is not None and not isinstance(self.drafter,
                                                         ModelDrafter):
            raise ValueError(
                "drafter_params passed but the engine has no ModelDrafter "
                "to swap them into")
        self.params = params
        self.kv.set_params(params)   # prefill inserts; paged: flush prefixes
        if isinstance(self.drafter, ModelDrafter):
            if drafter_params is not None:
                # atomic target+drafter swap: the draft cache's old-version
                # K/V only dents acceptance (verify is exact), and the next
                # rollout overwrites the frontier it actually uses
                self.drafter.params = drafter_params
                self._drafter_stale = False
            else:
                self._drafter_stale = True
        self.weights_version = (self.weights_version + 1 if version is None
                                else int(version))
        self.metrics.observe_swap(self.weights_version)
        return self.weights_version

    # -- early termination ------------------------------------------------
    def cancel(self, request_id: str) -> bool:
        """Terminate a queued or in-flight request NOW: its slot (if any)
        is reclaimed in O(1), a terminal record with
        ``finish_reason="cancelled"`` and the tokens generated so far is
        filed, and the id becomes reusable. Returns False for ids that are
        not live (already finished, or unknown)."""
        req = self._requests.get(request_id)
        if req is None:
            return False
        self._finish_early(req, "cancelled")
        return True

    def _shed_unmeetable(self) -> None:
        """Shed QUEUED requests that provably cannot meet their deadline
        (:meth:`Scheduler.unmeetable`): already expired, or — when the
        engine has an ``itl_estimate_s`` latency floor — the remaining
        budget overruns the deadline even at that floor. Distinct
        ``"shed"`` finish reason: the request was dropped before it cost
        a slot, which is different from a ``"deadline"`` reap of admitted
        work and lets callers retry against another replica."""
        for req in self.scheduler.unmeetable(self._now(),
                                             self.itl_estimate_s):
            self._finish_early(req, "shed")

    def _reap_expired(self) -> None:
        """Reap ADMITTED requests whose deadline passed ("deadline" —
        they cost a slot and may carry partial tokens). Queued requests
        are :meth:`_shed_unmeetable`'s job: an expired deadline is the
        degenerate unmeetable case, and the distinct "shed" reason
        records that the request never cost a slot."""
        now = self._now()
        for req in list(self._requests.values()):
            if (req.slot is not None and req.deadline_at is not None
                    and now >= req.deadline_at):
                self._finish_early(req, "deadline")

    def _finish_early(self, req: ServingRequest, reason: str) -> None:
        """Shared teardown for cancel/deadline: release device + host state
        and file the terminal record. O(1): SlotKVCache.release is a
        free-list push (no cache rewrite — the staleness-repair invariant
        makes the dead rows harmless), and queued entries are tombstoned,
        not re-heapified. A mid-chunk-train request closes its train; its
        partially-written prompt K/V is dead by the same invariant."""
        if req.slot is None:
            self.scheduler.discard(req)
        else:
            slot = req.slot
            if req is self._partial:
                self._partial = None
            self._slot_req.pop(slot, None)
            self.kv.release(slot)
            self._park(slot)
        self._requests.pop(req.request_id, None)
        req.timing.finished_at = self._now()
        req.timing.generated_tokens = len(req.generated)
        req.timing.finish_reason = reason
        self.metrics.observe_cancel(reason, adapter_id=req.adapter_id,
                                    tokens=len(req.generated))
        self._file_finished(self._terminal_record(req, reason))

    def drain(self, max_steps: Optional[int] = None
              ) -> Dict[str, FinishedRequest]:
        """Step until no request is queued or active (or ``max_steps``
        runs out); returns ALL finished requests so far by id."""
        steps = 0
        while self.scheduler.queue_depth or self.kv.active_slots:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return dict(self._finished)

    def result(self, request_id: str,
               pop: bool = True) -> Optional[FinishedRequest]:
        """Fetch (and by default REMOVE) a terminal record. Pop-on-read is
        the retention contract for long-running servers: a result read once
        is not re-buffered. Pass ``pop=False`` to peek."""
        if pop:
            return self._finished.pop(request_id, None)
        return self._finished.get(request_id)

    @staticmethod
    def _terminal_record(req: ServingRequest, reason: str) -> FinishedRequest:
        versions = list(req.token_versions)
        return FinishedRequest(
            request_id=req.request_id, prompt=req.prompt,
            tokens=list(req.generated), finish_reason=reason,
            timing=req.timing, token_versions=versions,
            version_first=versions[0] if versions else -1,
            version_last=versions[-1] if versions else -1)

    def _file_finished(self, fin: FinishedRequest) -> None:
        """Record a terminal request, evicting the OLDEST retained results
        past ``max_finished`` — unread results are dropped rather than
        accumulated forever (the pre-cap behavior leaked one record per
        request for the life of the server)."""
        self._finished[fin.request_id] = fin
        while len(self._finished) > self.max_finished:
            self._finished.pop(next(iter(self._finished)))
            self.metrics.observe_result_evicted()

    def snapshot(self) -> Dict[str, object]:
        """Engine + request metrics as one JSON-able dict; on the paged
        engine a ``"memory"`` section reports page utilization, KV HBM
        bytes, preemptions, and the prefix-cache hit ratio."""
        work = {"programs_launched": self._launched}
        if self._window is not None:
            work["decode_kv_positions_windowed"] = (
                self.metrics.decode_kv_positions_windowed)
        if self._latent_layers:
            work["decode_latent_positions"] = (
                self.metrics.decode_kv_positions * self._latent_layers)
        if self._loop_args:
            work["decode_cache_layer_positions"] = (
                self.metrics.decode_kv_positions
                * self._loop_args["cache_layers"])
        if self._linear_layers:
            work["decode_state_rows"] = self.metrics.decode_state_rows
            work["prefill_state_blocks"] = self.metrics.prefill_state_blocks
        counts = getattr(self.kv, "cache", {}).get("moe_counts")
        if counts is not None:
            # the one place these cross to the host: the cached forwards
            # add to them on the device, in the donated cache
            # (row 0 the decode steps', row 1 the prefill inserts')
            c = np.asarray(counts).astype(np.int64)
            work.update(
                moe_pairs_held=int(c[:, 0].sum()),
                moe_rows_computed=int(c[:, 1].sum()),
                moe_rows_max_expert=int(c[:, 2].max()),
                moe_decode_pairs_held=int(c[0, 0]),
                moe_decode_experts_touched=int(c[0, 3]),
                moe_decode_layer_calls=int(c[0, 4]))
        return self.metrics.snapshot(
            active_slots=self.kv.active_slots,
            queue_depth=self.scheduler.queue_depth,
            memory=self.kv.memory_stats() if self._paged else None,
            work=work)

    # -- device step state -------------------------------------------------
    def _count(self, fn, span=None) -> None:
        """One more call of a compiled program of the engine's own. The
        span that directly wraps the call says which: ``launch``, the count
        after it, and ``program``, the called function's name. A call with
        no span of its own (a park, the draft model's) is counted all the
        same, and :meth:`_show_bare` shows it on the span it happens in."""
        self._launched += 1
        if span is None:
            self._bare += 1
        else:
            span.set_metadata(launch=self._launched,
                              program=program_name(fn))

    def _show_bare(self, span, before: int) -> None:
        """``launches`` on ``span``: the calls since ``self._bare`` read
        ``before`` that no span of their own carries, where there are any."""
        if self._bare != before:
            span.set_metadata(launches=self._bare - before)

    def _set_row(self, slot: int, tok, pos: int, temp: float,
                 key: np.ndarray, live: bool, span=None) -> None:
        """``tok`` is a strong int32 scalar, on the host (a park's) or on
        the device (an admission's selected token), so that both compile
        to one program."""
        (self._tok, self._pos, self._temps, self._keys,
         self._live) = _scatter_row(
            self._tok, self._pos, self._temps, self._keys, self._live,
            slot, tok, pos, temp, key, live)
        self._count(_scatter_row, span)

    def _park(self, slot: int) -> None:
        """Return a slot's row to the free-rider configuration: greedy
        no-op at position 0 whose output is ignored."""
        self._set_row(slot, np.int32(0), 0, 0.0, np.zeros(2, np.uint32),
                      False)

    # -- internals -------------------------------------------------------
    @staticmethod
    def _req_prompt(req: ServingRequest) -> np.ndarray:
        """The tokens this admission must prefill: the original prompt,
        or — after a preemption — prompt ++ already-generated (the resumed
        request re-ingests its own continuation so the token stream picks
        up exactly where it stopped; selection is ``(seed, position)``-
        keyed, so the resumed stream is identical)."""
        return req.prompt if req.resume_prompt is None else req.resume_prompt

    def _do_prefill(self, req: ServingRequest) -> None:
        slot = self.kv.allocate()
        req.timing.admitted_at = self._now()
        req.slot = slot
        req.prefill_version = self.weights_version
        self.metrics.observe_prefill(req.adapter_id)
        prompt = self._req_prompt(req)
        T0 = int(prompt.shape[0])
        if self._paged:
            self.kv.set_adapter(slot, req.adapter_id)
            req.admit_seq = next(self._admit_seq)
            # prefix-cache hit: adopted pages skip their prefill outright
            req.prefill_pos = self.kv.adopt_prefix(slot, prompt)
        C = self.prefill_chunk
        if C is not None and T0 - req.prefill_pos > C:
            # long prompt: open a chunk train — first chunk now, the rest
            # interleaved with decode by the scheduler
            self._partial = req
            self._do_prefill_chunk()
            return
        last = self._insert_guarded(req, prompt[req.prefill_pos:],
                                    pos0=req.prefill_pos)
        self._start_decoding(req, last)

    def _do_prefill_chunk(self) -> None:
        """Advance the open chunk train by one chunk; the FINAL chunk's
        last real logits select the first token and the slot goes live."""
        req = self._partial
        prompt = self._req_prompt(req)
        T0 = int(prompt.shape[0])
        start = req.prefill_pos
        end = min(start + self.prefill_chunk, T0)
        t0 = self._perf()
        with _span("elephas.engine.prefill_chunk",
                   request_id=req.request_id, pos0=start,
                   chunk_tokens=end - start):
            last = self._insert_guarded(req, prompt[start:end], pos0=start)
            last.block_until_ready()
        self.metrics.observe_prefill_chunk(
            end - start, len(self._slot_req), self._perf() - t0)
        req.prefill_pos = end
        if end < T0:
            # park the row non-live AT THE WRITE HEAD: the garbage K/V an
            # interleaved decode step writes there lands exactly where the
            # next chunk's insert overwrites it
            self._set_row(req.slot, np.int32(0), end, 0.0,
                          np.zeros(2, np.uint32), False)
            return
        self._partial = None
        self._start_decoding(req, last)

    def _start_decoding(self, req: ServingRequest, last) -> None:
        """Shared admission tail: select the first token from the prompt's
        last real logits, make the slot a live decode row with it, then
        read it, stamp timing and emit it. The insert, the selection and
        the row write are queued back to back; the host waits for the
        device once, at the read."""
        T0 = int(self._req_prompt(req).shape[0])
        key = _host_key(req.seed)
        with _span("elephas.engine.prefill.select_first") as span:
            first = _select_first(last, T0, req.temperature, key)
            self._count(_select_first, span)
            selected = self._launched
        req.next_pos = T0           # position the first token occupies
        if self._paged and req.prefill_version == self.weights_version:
            # publish the now-complete prompt pages for future prefix hits.
            # A prompt whose (chunked) prefill SPANNED a swap is excluded:
            # its pages hold mixed-version K/V, and the prefix cache's
            # contract — page content is a pure function of the token
            # prefix — only holds within one weight version.
            self.kv.register_prefix(req.slot, self._req_prompt(req))
        if isinstance(self.drafter, ModelDrafter):
            self._draft_prefill(req)
        self._slot_req[req.slot] = req
        with _span("elephas.engine.prefill.set_row") as span:
            self._set_row(req.slot, first, T0, req.temperature, key, True,
                          span=span)
        with _span("elephas.engine.prefill.fetch", launch=selected):
            # the blocking read: the host waits here for the device
            tok = int(first)
        if req.timing.first_token_at is None:   # preserve TTFT on resume
            req.timing.first_token_at = self._now()
        self._emit(req, tok)

    def _draft_prefill(self, req: ServingRequest) -> None:
        """(Re)prefill the draft model's slot row with the request's full
        prompt (resume prompt after a preemption): the draft cache must
        agree with the target's committed stream before its first rollout.
        One bucket-padded whole-prompt insert — a drafter is only worth
        running when it is far cheaper than the target, so its prefill is
        never chunked."""
        prompt = self._req_prompt(req)
        dm = self.drafter
        cap = int(self._draft_cache["k"].shape[3])
        T0 = int(prompt.shape[0])
        Tb = min(bucket_length(T0), cap)
        padded = np.zeros((1, Tb), np.int32)
        padded[0, :T0] = prompt
        aid = (req.adapter_id
               if req.adapter_id < int(getattr(dm.model, "n_adapters", 1))
               else 0)
        self._draft_aids[req.slot] = aid
        self._draft_cache = _draft_insert_kernel(
            dm.model, dm.params, self._draft_cache, jnp.asarray(padded),
            req.slot, jnp.int32(aid))
        self._count(_draft_insert_kernel)

    # -- page pressure (paged engine only) --------------------------------
    def _insert_guarded(self, req: ServingRequest, chunk, pos0: int):
        """``kv.insert`` with page-pressure recovery: on
        :class:`PagesExhausted`, evict clean prefix pages — failing that,
        preempt the newest same-rank request — and retry. A request alone
        always fits (``kv.fits`` is checked at submit), so the loop
        terminates."""
        with _span("elephas.engine.prefill.insert") as span:
            while True:
                try:
                    last = self.kv.insert(req.slot, chunk,
                                          insert_fn=self._insert_fn,
                                          pos0=pos0)
                    break
                except PagesExhausted as e:
                    self._relieve_pressure(e, exclude=req)
            # the cache ran the program, and knows which
            self._count(self.kv.insert_program(self._insert_fn), span)
        padded = self.kv.padded_length(len(chunk), pos0)
        self.metrics.observe_insert(
            len(chunk), padded,
            state_blocks=self._linear_layers * -(-padded // GDN_BLOCK))
        return last

    def _ensure_decode_guarded(self, n_steps: int) -> None:
        """Pre-allocate the pages the next decode block will write, with
        the same evict-then-preempt recovery as inserts."""
        while True:
            try:
                self.kv.ensure_decode(list(self._slot_req), n_steps)
                return
            except PagesExhausted as e:
                self._relieve_pressure(e)

    def _relieve_pressure(self, exc: PagesExhausted,
                          exclude: Optional[ServingRequest] = None) -> None:
        """Free pages in the exhausted partition: clean (cache-only)
        prefix pages first, else preempt the newest request on that
        partition's data rank. Raises ``exc`` when neither is possible —
        unreachable while the submit-time ``fits`` invariant holds."""
        if self.kv.evict_pages(exc.partition, exc.shortfall) >= exc.shortfall:
            return
        victim = self._preempt_victim(exc.partition, exclude)
        if victim is None:
            raise exc
        self._preempt(victim)

    def _preempt_victim(self, partition: int,
                        exclude: Optional[ServingRequest] = None
                        ) -> Optional[ServingRequest]:
        """Newest-admitted live request whose slot draws pages from
        ``partition``'s data rank (LIFO preemption: the oldest admitted
        work is the last to lose its slot)."""
        rank = partition // self.kv.sp
        cands = [r for r in self._slot_req.values()
                 if r is not exclude and r.slot // self.kv.Sl == rank]
        if (self._partial is not None and self._partial is not exclude
                and self._partial.slot // self.kv.Sl == rank):
            cands.append(self._partial)
        return max(cands, key=lambda r: r.admit_seq) if cands else None

    def _preempt(self, victim: ServingRequest) -> None:
        """Evict a live request under page pressure: return every page it
        holds, park its row, and requeue it at the FRONT of its priority
        class. On re-admission it prefills prompt ++ generated-so-far and
        continues its exact token stream (``(seed, position)``-keyed
        selection) — preemption is invisible in the output."""
        slot = victim.slot
        if victim is self._partial:
            self._partial = None
        self._slot_req.pop(slot, None)
        self.kv.release(slot)
        self._park(slot)
        # always original prompt ++ ALL generated (NOT _req_prompt: a
        # second preemption must not re-append tokens already folded in)
        victim.resume_prompt = np.concatenate(
            [np.asarray(victim.prompt, np.int32),
             np.asarray(victim.generated, np.int32)])
        victim.slot = None
        victim.carry = None
        victim.prefill_pos = 0
        victim.next_pos = 0
        victim.preemptions += 1
        self.kv.preemptions += 1
        self.scheduler.requeue(victim)

    def _fuse_window(self) -> int:
        """How many decode steps the next decode program may fuse (1 =
        single-step driver). Fusion is bypassed whenever it could change
        OBSERVABLE behavior beyond latency: an open chunk train (its
        chunks must interleave), any live deadline (reaps are per-step
        exact), a fault plan (injected stalls are per-step), or — when
        work is queued — any active EOS-able request (an early-freed slot
        must admit immediately, not up to K-1 steps late). The window is
        clamped to the smallest remaining token budget, so budget
        finishes land exactly on a block boundary."""
        K = self.fuse_k
        if (K < 2 or self.fault_plan is not None
                or self._partial is not None or not self._slot_req):
            return 1
        if any(r.deadline_at is not None for r in self._requests.values()):
            return 1
        active = self._slot_req.values()
        if self.scheduler.queue_depth and any(
                r.eos_id is not None for r in active):
            return 1
        return max(1, min(K, min(r.max_new - len(r.generated)
                                 for r in active)))

    def _spec_window(self) -> int:
        """How many tokens the next decode action may DRAFT (0 = stand
        down to the non-speculative driver). Bypassed on exactly the
        conditions that collapse :meth:`_fuse_window` — an open chunk
        train, any live deadline, a fault plan, or queued work behind an
        EOS-able active request — plus the budget clamp: a row with ``r``
        tokens of budget left needs at most ``r - 1`` drafts (its verify
        chunk emits up to ``drafts + 1``), so the window shrinks to the
        smallest remaining budget minus one and speculation simply stands
        down at 0. The clamp also keeps every chunk write inside the
        cache (``pos + W <= capacity - 1``), so the row-update clamp in
        ``decode_chunk`` never silently corrupts a tail position."""
        K = self.speculate_k
        if (K < 2 or self.fault_plan is not None or self._drafter_stale
                or self._partial is not None or not self._slot_req):
            return 0
        if any(r.deadline_at is not None for r in self._requests.values()):
            return 0
        active = self._slot_req.values()
        if self.scheduler.queue_depth and any(
                r.eos_id is not None for r in active):
            return 0
        return min(K - 1, min(r.max_new - len(r.generated)
                              for r in active) - 1)

    def _draft_tokens(self, W: int) -> jnp.ndarray:
        """``[S, W]`` int32 proposals for this round's verify chunk (free
        rows get zeros — their chunk rows are dead by the staleness-repair
        invariant). Model drafters roll out on-device from the target's
        carry/position state; host drafters (``propose(context, k)``) see
        each request's prompt ++ generated stream, whose last element IS
        the carry token the chunk starts from."""
        if isinstance(self.drafter, ModelDrafter):
            d = self.drafter
            drafts, self._draft_cache = _draft_propose_kernel(
                d.model, d.params, self._draft_cache, self._tok, self._pos,
                self._live, jnp.asarray(self._draft_aids), n_steps=W)
            self._count(_draft_propose_kernel)
            return drafts
        out = np.zeros((self.kv.n_slots, W), np.int32)
        for slot, req in self._slot_req.items():
            ctx = np.concatenate([np.asarray(req.prompt, np.int32),
                                  np.asarray(req.generated, np.int32)])
            out[slot] = self.drafter.propose(ctx, W)
        return jnp.asarray(out)

    def _do_decode_spec(self, W: int) -> None:
        """One speculative round: draft ``W`` tokens per live slot, score
        carry + drafts in ONE fused verify program, and commit each row's
        accepted run + correction in bulk. The emitted stream is BITWISE
        the sequential one — the verify program applies the same ``(seed,
        position)``-keyed selection at every chunk position and accepts
        drafts only while they match it — so speculation changes how many
        program launches the stream costs, never its tokens. Metrics
        count device-committed tokens (``n_accepted + n_active``); like
        the fused path, the host stops DELIVERING a row's run at its
        EOS/budget finish and the leftover device writes are stale-dead."""
        if self._paged:
            # every position the chunk may write (pos..pos+W) gets its
            # page BEFORE the launch: the bulk commit itself cannot fail
            # (may evict/preempt under pressure — recompute the batch)
            self._ensure_decode_guarded(W + 1)
            if not self._slot_req:
                return
        n_active = len(self._slot_req)
        kv_args = self._kv_span_args(W + 1, chunk=True)
        with _span("elephas.engine.decode", n_active=n_active, k=W + 1,
                   speculative=1, **kv_args):
            t0 = self._perf()
            with _span("elephas.engine.decode.dispatch") as span:
                bare = self._bare
                drafts = self._draft_tokens(W)
                (sel, n_acc, self._tok, self._pos,
                 self.kv.cache) = self._verify_fn(
                    self.params, self.kv.cache, drafts, self._tok,
                    self._pos, self._temps, self._keys, self._live)
                self._count(self._verify_fn, span)
                self._show_bare(span, bare)     # a draft model's rollout
            with _span("elephas.engine.decode.fetch", launch=self._launched):
                toks = np.asarray(sel)
                n_acc = np.asarray(n_acc)
            t1 = self._perf()
            with _span("elephas.engine.decode.emit") as span:
                bare = self._bare
                act = list(self._slot_req.items())
                accepted = sum(int(n_acc[slot]) for slot, _ in act)
                for slot, req in act:
                    for j in range(int(n_acc[slot]) + 1):
                        if req.request_id not in self._requests:
                            break
                        # the verify chunk wrote this token's K/V at its
                        # position
                        self.kv.advance(slot)
                        req.next_pos += 1
                        self._emit(req, int(toks[slot, j]))
                self._show_bare(span, bare)     # a park for each that finished
        self.metrics.observe_spec_round(
            n_active, n_drafted=n_active * W, n_accepted=accepted,
            n_emitted=accepted + n_active, block_s=t1 - t0,
            host_s=self._perf() - t1, **kv_args)

    def _kv_positions(self, k: int) -> int:
        """Key positions the next decode program must attend: each live
        row's ``k`` queries see ``next_pos + 1 .. next_pos + k`` keys."""
        return (k * sum(r.next_pos + 1 for r in self._slot_req.values())
                + len(self._slot_req) * k * (k - 1) // 2)

    def _kv_span_args(self, k: int, chunk: bool = False) -> Dict[str, int]:
        """What the decode span and the ``work`` counters say of the keys
        the next decode program needs: ``kv_positions``, and for a model
        with window layers ``kv_positions_windowed``, the same sum with
        each query's keys limited to the window. Where the program is
        ``k`` steps of the decode kernel (not a verify ``chunk``), also the
        cache blocks the live rows attend, summed over steps and layers,
        the visits the kernel makes for them (``kv_blocks_live``,
        ``kv_blocks_walked``: :func:`kv_block_walk`, which the kernel
        walks by) and the positions its copies bring in
        (``kv_positions_copied``: :func:`kv_copied_positions`, which the
        kernel sizes them by; the latent kernel copies whole blocks)."""
        out = {"kv_positions": self._kv_positions(k)}
        if self._window is not None:
            w = self._window
            out["kv_positions_windowed"] = sum(
                min(w, r.next_pos + 1 + j)
                for r in self._slot_req.values() for j in range(k))
        if self._decode_walks and not chunk:
            pos = (np.fromiter((r.next_pos for r in self._slot_req.values()),
                               np.int64)[:, None] + np.arange(k))
            live = walked = copied = 0
            latent = {"granule": None} if self._latent_layers else {}
            for *walk, layers in self._decode_walks:
                _, n_walked, n_live = kv_block_walk(pos, *walk)
                live += layers * int(np.sum(n_live))
                walked += layers * int(np.sum(n_walked))
                copied += layers * int(np.sum(
                    kv_copied_positions(pos, *walk, **latent)))
            out.update(kv_blocks_live=live, kv_blocks_walked=walked,
                       kv_positions_copied=copied)
        if self._linear_layers and not chunk:
            out["state_rows"] = (len(self._slot_req) * k
                                 * self._linear_layers)
        return out

    def _do_decode(self) -> None:
        W = self._spec_window()
        if W > 0:
            self._do_decode_spec(W)
            return
        K = self._fuse_window()
        if self._paged:
            # decode writes land in allocated pages only: grow each active
            # slot's tail before launching (may evict/preempt under
            # pressure — recompute the batch if rows were preempted away)
            self._ensure_decode_guarded(K)
            if not self._slot_req:
                return
        n_active = len(self._slot_req)
        kv_args = self._kv_span_args(K)
        with _span("elephas.engine.decode", n_active=n_active, k=K,
                   **kv_args, **self._loop_args):
            t0 = self._perf()
            with _span("elephas.engine.decode.dispatch") as span:
                fn = self._decode_fn if K == 1 else partial(
                    self._fused_fn, n_steps=K)
                emit, self._tok, self._pos, self.kv.cache = fn(
                    self.params, self.kv.cache, self._tok, self._pos,
                    self._temps, self._keys, self._live)
                self._count(fn, span)
            # the launch it waits for: its dispatch's
            with _span("elephas.engine.decode.fetch", launch=self._launched):
                # the blocking read: the host waits here for the device
                toks = np.asarray(emit).reshape(-1, K)      # [S, K]
            t1 = self._perf()
            with _span("elephas.engine.decode.emit") as span:
                bare = self._bare
                for slot, req in list(self._slot_req.items()):
                    # consume this row's emitted tokens in order; stop at
                    # its finish (EOS/budget/cancel-from-callback) — the
                    # device kept decoding past it, but those writes are
                    # garbage the staleness-repair invariant already covers
                    for j in range(K):
                        if req.request_id not in self._requests:
                            break
                        # this step WROTE each carry token's K/V at its
                        # position
                        self.kv.advance(slot)
                        req.next_pos += 1
                        self._emit(req, int(toks[slot, j]))
                self._show_bare(span, bare)     # a park for each that finished
        self.metrics.observe_decode_block(
            n_active, K, block_s=t1 - t0,
            host_s=self._perf() - t1, **kv_args)

    def _emit(self, req: ServingRequest, tok: int) -> None:
        """Deliver one generated token: record, stream, finish/continue.
        The token is stamped with the CURRENT weights version — the
        version every program of this decode round ran under (swaps only
        happen between host-driven rounds), so attribution is exact."""
        req.generated.append(tok)
        req.token_versions.append(self.weights_version)
        done_eos = req.eos_id is not None and tok == req.eos_id
        done_len = len(req.generated) >= req.max_new
        done = done_eos or done_len
        if req.on_token is not None:
            req.on_token(req.request_id, tok, done)
        if not done:
            return   # device carry already holds `tok` (kernel write-back)
        req.timing.finished_at = self._now()
        req.timing.generated_tokens = len(req.generated)
        req.timing.finish_reason = "eos" if done_eos else "length"
        self.metrics.observe_finish(req.timing, adapter_id=req.adapter_id)
        self._file_finished(
            self._terminal_record(req, req.timing.finish_reason))
        slot = req.slot
        self._slot_req.pop(slot, None)
        self._requests.pop(req.request_id, None)
        self.kv.release(slot)
        self._park(slot)
