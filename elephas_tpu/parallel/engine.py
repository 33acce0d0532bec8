"""The compiled data-parallel training engine.

This is the TPU-native replacement for the reference's entire L2–L3 stack
(parameter server + workers, ``elephas/parameter/server.py``,
``elephas/worker.py``; SURVEY.md §2.3/§2.4): instead of executors pickling
weight deltas over HTTP/TCP to a driver-hosted server, every elephas training
mode becomes ONE jitted XLA program, ``shard_map``-ed over a 1-D ``"data"``
mesh, in which per-worker model replicas train locally (``lax.scan`` over
shuffled batches) and merge through ``psum`` collectives riding ICI. Weights
never leave the chips; the host only stages input data and reads back final
parameters + metric histories.

Mode → schedule mapping (exact semantics in MERGE SEMANTICS below):

- ``synchronous``  — train ``epochs`` locally, ONE merge at the end.
  This is bit-faithful to the reference sync path: each worker computes
  ``delta = w0 - w_final`` and the driver applies the (averaged) deltas
  (``elephas/spark_model.py:~150``).
- ``asynchronous`` / ``hogwild``, ``frequency='epoch'`` — merge after every
  local epoch (the on-device analog of per-epoch pull/push against the
  parameter server, ``elephas/worker.py:~70``).
- ``asynchronous`` / ``hogwild``, ``frequency='batch'`` — merge after every
  batch (the analog of per-batch pull/push).

MERGE SEMANTICS. The reference's parameter server applies every pushed delta
in full (``weights -= delta``, ``parameter/server.py:~40``), so one "round" of
W workers moves the server by the SUM of deltas; the fork's synchronous path
averages instead (``divide_by(num_workers)``). Both are provided:
``merge='sum'`` (server/upstream-faithful, default for async modes) and
``merge='mean'`` (fork-sync-faithful, default for synchronous). True unordered
asynchrony cannot exist inside a lockstep XLA program; staleness collapses to
"one merge period", which is the documented fidelity envelope (SURVEY.md
§7.3.1) — the wire-level parameter server in ``elephas_tpu/parameter/``
remains available when literal asynchrony is wanted.

Padding. Partitions rarely divide the batch size, and worker count rarely
divides device count; both are padded (samples with zero sample-weight,
workers with a zero valid-flag) and masked out of losses, optimizer updates,
and merge denominators, so results match the unpadded math the reference
computes.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.adapters import KerasModelAdapter
from .mesh import DATA_AXIS, build_mesh

Array = Any


def _pad_block(arr: np.ndarray, target_rows: int) -> np.ndarray:
    """Zero-pad ``arr`` along axis 0 to ``target_rows``."""
    n = arr.shape[0]
    if n == target_rows:
        return arr
    pad = np.zeros((target_rows - n,) + arr.shape[1:], dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


# -- helpers shared by the local-training and gradient-sync builders ---------


def _make_shuffler(S: int, B: int):
    """Per-worker epoch shuffle into ``[S, B, ...]`` batch blocks."""

    def shuffled_batches(x_l, y_l, sw_l, key):
        perm = jax.random.permutation(key, x_l.shape[0])
        xb = x_l[perm].reshape((S, B) + x_l.shape[1:])
        yb = y_l[perm].reshape((S, B) + y_l.shape[1:])
        swb = sw_l[perm].reshape((S, B))
        return xb, yb, swb

    return shuffled_batches


def _make_tile(L: int):
    return lambda t: jnp.broadcast_to(t[None], (L,) + t.shape).astype(t.dtype)


def _seeded_ntv_stack(ntv0, mergeable, L: int):
    """Tile non-trainable state per local worker. Integer non-mergeable
    entries are seed-generator state: offset each replica by its global
    worker id so dropout masks are independent across workers (as the
    reference's independent executors are), not identical copies."""
    tile = _make_tile(L)
    widx = jax.lax.axis_index(DATA_AXIS) * L + jnp.arange(L)
    stack = []
    for t, is_m in zip(ntv0, mergeable):
        tiled = tile(t)
        if not is_m and jnp.issubdtype(jnp.asarray(t).dtype, jnp.integer):
            tiled = tiled + widx.reshape(
                (L,) + (1,) * jnp.asarray(t).ndim
            ).astype(tiled.dtype)
        stack.append(tiled)
    return stack


def _merged_ntv_bases(ntv_stack, base_ntv, wvalid, mergeable, denom, kind):
    """Merge weight-slot ntv entries (BN stats) across workers: per mergeable
    entry the merged base value, ``None`` for non-mergeable (seed) entries."""
    out = []
    for i, is_m in enumerate(mergeable):
        if not is_m:
            out.append(None)
            continue
        s, b = ntv_stack[i], base_ntv[i]
        delta = b[None] - s
        loc = jnp.sum(
            delta
            * wvalid.reshape((-1,) + (1,) * (delta.ndim - 1)).astype(delta.dtype),
            axis=0,
        )
        tot = jax.lax.psum(loc, DATA_AXIS)
        if kind == "mean":
            tot = tot / denom
        out.append(b - tot)
    return out


def _psum_weighted_means(stats):
    """``(loss_wsum, acc_wsum, wsum)`` arrays → global ``{"loss", "accuracy"}``."""
    loss_ws, acc_ws, wsum = jax.tree_util.tree_map(jnp.sum, stats)
    loss_sum = jax.lax.psum(loss_ws, DATA_AXIS)
    acc_sum = jax.lax.psum(acc_ws, DATA_AXIS)
    w_sum = jnp.maximum(jax.lax.psum(wsum, DATA_AXIS), 1e-9)
    return {"loss": loss_sum / w_sum, "accuracy": acc_sum / w_sum}


def _make_local_eval(eval_step, Sv: int, B: int):
    """Scan the eval step over a worker's validation block."""

    def local_eval(tv, ntv, xv_l, yv_l, sv_l):
        xb = xv_l.reshape((Sv, B) + xv_l.shape[1:])
        yb = yv_l.reshape((Sv, B) + yv_l.shape[1:])
        svb = sv_l.reshape((Sv, B))

        def step(_, batch):
            return None, eval_step(tv, ntv, *batch)

        _, stats = jax.lax.scan(step, None, (xb, yb, svb))
        return jax.tree_util.tree_map(jnp.sum, stats)

    return local_eval


def _psum_val_metrics(vstats):
    vloss = jax.lax.psum(jnp.sum(vstats[0]), DATA_AXIS)
    vacc = jax.lax.psum(jnp.sum(vstats[1]), DATA_AXIS)
    vw = jnp.maximum(jax.lax.psum(jnp.sum(vstats[2]), DATA_AXIS), 1e-9)
    return {"val_loss": vloss / vw, "val_accuracy": vacc / vw}


class FitResult:
    """Final weights + Keras-``History``-shaped metrics (+ carryable state).

    ``weights`` materializes lazily: host numpy copies are only pulled when
    the attribute is read (the checkpoint path), so ordinary fits never pay
    the device→host weight transfer.
    """

    def __init__(self, weights, history: Dict[str, List[float]],
                 opt_state: Any = None, timings: Optional[Dict[str, float]] = None,
                 worker_state: Any = None):
        self._weights = weights  # list OR zero-arg thunk
        self.history = history
        self.opt_state = opt_state
        self.timings = timings or {}
        self.worker_state = worker_state

    @property
    def weights(self) -> List[np.ndarray]:
        if callable(self._weights):
            self._weights = self._weights()
        return self._weights


class CompiledTrainer:
    """Compile-and-run elephas training modes on a device mesh.

    One instance per (adapter, mesh); compiled executables are cached by the
    static schedule/shape signature, so repeated ``fit`` calls with the same
    geometry reuse the XLA program.
    """

    def __init__(self, adapter: KerasModelAdapter, mesh: Optional[Mesh] = None,
                 mode: str = "synchronous", frequency: str = "epoch",
                 merge: str = "auto", remat: bool = False):
        if mode not in ("synchronous", "asynchronous", "hogwild"):
            raise ValueError(f"Unknown mode: {mode}")
        if frequency not in ("epoch", "batch"):
            raise ValueError(f"Unknown frequency: {frequency}")
        self.adapter = adapter
        self.mesh = mesh if mesh is not None else build_mesh()
        self.mode = mode
        self.frequency = frequency
        self.remat = remat
        if mode == "synchronous" and frequency == "batch" and merge == "sum":
            raise ValueError(
                "mode='synchronous', frequency='batch' is the gradient-"
                "synchronous schedule: gradients are weight-averaged per "
                "batch and there is no delta merge, so merge='sum' has no "
                "meaning here (use merge='auto')."
            )
        if merge == "auto":
            merge = "mean" if mode == "synchronous" else "sum"
        if merge not in ("mean", "sum"):
            raise ValueError(f"Unknown merge: {merge}")
        self.merge = merge
        self.optimizer = adapter.make_optimizer()
        self._cache: Dict[tuple, Any] = {}

    # ------------------------------------------------------------------
    def fit(self, blocks: Sequence[Tuple[np.ndarray, np.ndarray]], epochs: int,
            batch_size: int, validation_split: float = 0.0,
            seed: int = 0, verbose: int = 0, opt_state: Any = None,
            keep_opt_state: bool = False, worker_state: Any = None,
            keep_worker_state: bool = False, epoch_offset: int = 0,
            worker_valid: Optional[Sequence[float]] = None) -> FitResult:
        """Train over per-worker data ``blocks`` ``[(x_w, y_w), ...]``.

        ``worker_valid`` (one float per block, 1.0 = live, 0.0 = excluded)
        overrides the merge validity mask — DeepSpark-style partial
        aggregation: an excluded worker's shard still occupies its mesh slot
        (geometry, and therefore the compiled executable, is unchanged) but
        contributes nothing to any merge denominator or batch-delta sum. The
        elastic layer (``SparkModel(membership=...)``) uses this to commit
        rounds without expired members instead of blocking on them.

        Returns merged weights in ``get_weights()`` order plus per-epoch
        history (``loss``[, ``accuracy``, ``val_loss``, ``val_accuracy``]).

        Optimizer state is an explicit input/output of the compiled program:
        pass ``opt_state`` from a previous ``FitResult`` to continue training
        (checkpoint/resume, epoch-chunked fits) instead of cold-starting the
        optimizer; ``keep_opt_state=True`` returns it on the result.

        Merge-faithful chunking (synchronous+epoch mode only):
        ``keep_worker_state=True`` makes the compiled program return the
        per-worker weight stacks UN-merged (``result.worker_state``, with the
        installed weights being a merged *preview* against the original
        base); feed that to the next chunk's ``worker_state=`` with
        ``epoch_offset`` set to the global epoch index so the chunked
        sequence takes exactly the uninterrupted fit's trajectory — workers
        train independently across chunk boundaries and the real merge
        happens once, implicitly, in the last chunk's preview.
        """
        W = len(blocks)
        if W == 0:
            raise ValueError("No worker data blocks (all partitions skipped?)")
        D = self.mesh.devices.size
        Wp = int(math.ceil(W / D) * D)
        L = Wp // D
        B = int(batch_size)
        E = int(epochs)

        # -- split train/val per worker (Keras semantics: validation data is
        # the LAST fraction of each worker's block, taken before shuffling —
        # reference workers call model.fit(validation_split=...)).
        xs, ys, sws, xvs, yvs, svs = [], [], [], [], [], []
        n_trains, n_vals = [], []
        for x_w, y_w in blocks:
            x_w = np.asarray(x_w)
            y_w = np.asarray(y_w)
            n = x_w.shape[0]
            n_val = int(n * validation_split) if validation_split else 0
            n_trains.append(n - n_val)
            n_vals.append(n_val)
        S = max(1, max(int(math.ceil(nt / B)) for nt in n_trains))
        N = S * B
        has_val = any(nv > 0 for nv in n_vals)
        Sv = max(1, max(int(math.ceil(nv / B)) for nv in n_vals)) if has_val else 1
        Nv = Sv * B

        for (x_w, y_w), nt, nv in zip(blocks, n_trains, n_vals):
            x_w = np.asarray(x_w)
            y_w = np.asarray(y_w)
            xs.append(_pad_block(x_w[:nt], N))
            ys.append(_pad_block(y_w[:nt], N))
            sws.append(_pad_block(np.ones((nt,), np.float32), N))
            if has_val:
                xvs.append(_pad_block(x_w[nt:], Nv))
                yvs.append(_pad_block(y_w[nt:], Nv))
                svs.append(_pad_block(np.ones((nv,), np.float32), Nv))

        # -- pad to Wp workers (invalid: zero weights everywhere)
        def stack_pad(parts, row_shape_src):
            while len(parts) < Wp:
                parts.append(np.zeros_like(row_shape_src))
            return np.stack(parts, axis=0)

        x = stack_pad(xs, xs[0])
        y = stack_pad(ys, ys[0])
        sw = stack_pad(sws, np.zeros_like(sws[0]))
        if has_val:
            xv = stack_pad(xvs, xvs[0])
            yv = stack_pad(yvs, yvs[0])
            sv = stack_pad(svs, np.zeros_like(svs[0]))
        else:
            xv = yv = sv = np.zeros((Wp, 1), np.float32)
        if worker_valid is None:
            wvalid = np.array([1.0] * W + [0.0] * (Wp - W), np.float32)
        else:
            if len(worker_valid) != W:
                raise ValueError(
                    f"worker_valid has {len(worker_valid)} entries for "
                    f"{W} worker blocks"
                )
            wvalid = np.array(
                [float(v) for v in worker_valid] + [0.0] * (Wp - W),
                np.float32,
            )
            if wvalid.sum() <= 0.0:
                raise ValueError("worker_valid excludes every worker")
        keys = jax.random.split(jax.random.PRNGKey(seed), Wp)

        # Device staging cache: same block arrays + geometry → reuse the
        # already-sharded device buffers instead of re-transferring host→HBM
        # every fit (the host→device copy can dominate a short fit; data
        # is immutable once staged).
        stage_key = (
            tuple((id(bx), id(by)) for bx, by in blocks),
            validation_split, N, Nv, Wp,
            None if worker_valid is None else tuple(float(v) for v in worker_valid),
        )
        staged = getattr(self, "_staged", None)
        if staged is not None and staged[0] == stage_key:
            x, y, sw, xv, yv, sv, wvalid = staged[1]
        else:
            shard = NamedSharding(self.mesh, P(DATA_AXIS))
            x, y, sw, xv, yv, sv, wvalid = (
                jax.device_put(a, shard) for a in (x, y, sw, xv, yv, sv, wvalid)
            )
            self._staged = (stage_key, (x, y, sw, xv, yv, sv, wvalid))

        tv0, ntv0 = self.adapter.state_values()
        mergeable = [slot is not None for slot in self.adapter._ntv_slots]

        sync_carry = None
        if keep_worker_state or worker_state is not None:
            if not (self.mode == "synchronous" and self.frequency == "epoch"):
                raise ValueError(
                    "worker_state carrying applies to synchronous+epoch mode "
                    f"only (got {self.mode}/{self.frequency}); the other "
                    "schedules merge within each chunk and are already "
                    "cadence-faithful under chunking"
                )
            sync_carry = "carry" if worker_state is not None else "fresh"

        sig = (
            Wp, N, S, B, E, Sv, has_val, self.mode, self.frequency, self.merge,
            tuple(x.shape), tuple(y.shape), str(x.dtype), str(y.dtype),
            sync_carry,
        )
        if sig not in self._cache:
            self._cache[sig] = self._build(
                L=L, S=S, B=B, E=E, Sv=Sv, has_val=has_val,
                mergeable=mergeable, sync_carry=sync_carry,
            )
        fit_fn, opt_init_fn = self._cache[sig]

        t_start = time.perf_counter()
        if opt_state is None:
            opt_state = opt_init_fn(tv0)
        ws_out = None
        if sync_carry is None:
            tv_out, ntv_out, opt_state_out, metrics = fit_fn(
                tv0, ntv0, opt_state, x, y, sw, xv, yv, sv, keys, wvalid
            )
        else:
            e0 = jnp.asarray(int(epoch_offset), jnp.int32)
            if sync_carry == "fresh":
                (tv_out, ntv_out, opt_state_out, metrics, tv_stack,
                 ntv_stack) = fit_fn(
                    tv0, ntv0, opt_state, x, y, sw, xv, yv, sv, keys,
                    wvalid, e0,
                )
                base_tv, base_ntv = tv0, list(ntv0)
            else:
                tv_stack_in = worker_state["tv_stack"]
                ntv_stack_in = worker_state["ntv_stack"]
                base_tv = worker_state["base_tv"]
                base_ntv = worker_state["base_ntv"]
                (tv_out, ntv_out, opt_state_out, metrics, tv_stack,
                 ntv_stack) = fit_fn(
                    tv_stack_in, ntv_stack_in, base_tv, base_ntv, opt_state,
                    x, y, sw, xv, yv, sv, keys, wvalid, e0,
                )
            ws_out = {
                "tv_stack": tv_stack, "ntv_stack": ntv_stack,
                "base_tv": base_tv, "base_ntv": base_ntv,
            }
        jax.block_until_ready(tv_out)
        t_run = time.perf_counter() - t_start

        # -- install merged state back into the live model, ON DEVICE: the
        # Keras-JAX variables accept the compiled program's outputs directly,
        # so trained weights never round-trip the host (a device→host→
        # device copy of the whole state per fit; see install_state). Host copies materialize lazily via result.weights.
        ntv_full = []
        ntv_out = list(ntv_out)
        for is_m, cur in zip(mergeable, ntv0):
            ntv_full.append(ntv_out.pop(0) if is_m else cur)
        self.adapter.install_state(list(tv_out), ntv_full)
        # Snapshot THIS fit's outputs (device handles are immutable, unlike
        # the live variables a later fit would overwrite); numpy materializes
        # only if result.weights is actually read.
        flat_dev = self.adapter.state_to_weights(list(tv_out), ntv_full)
        weights_thunk = lambda: [np.asarray(w) for w in flat_dev]  # noqa: E731

        history: Dict[str, List[float]] = {"loss": [float(v) for v in metrics["loss"]]}
        if self.adapter.wants_accuracy:
            history["accuracy"] = [float(v) for v in metrics["accuracy"]]
        if has_val:
            history["val_loss"] = [float(v) for v in metrics["val_loss"]]
            if self.adapter.wants_accuracy:
                history["val_accuracy"] = [float(v) for v in metrics["val_accuracy"]]
        if verbose:
            for e in range(E):
                line = f"epoch {e + 1}/{E} - loss: {history['loss'][e]:.4f}"
                if "val_loss" in history:
                    line += f" - val_loss: {history['val_loss'][e]:.4f}"
                print(line)
        return FitResult(
            weights_thunk, history,
            opt_state=opt_state_out if keep_opt_state else None,
            timings={"run_seconds": t_run,
                     "samples_per_sec": sum(n_trains) * E / max(t_run, 1e-9)},
            worker_state=ws_out if keep_worker_state else None,
        )

    # ------------------------------------------------------------------
    def _stage_rows(self, n: int, batch_size: int) -> Tuple[int, int]:
        """Inference staging geometry: ``(scan_steps, padded_rows)``.

        Steps are bucketed to powers of two so varying input sizes hit a
        bounded set of compiled executables.
        """
        D = self.mesh.devices.size
        B = int(batch_size)
        S = max(1, int(math.ceil(n / (D * B))))
        S = 1 << (S - 1).bit_length()
        return S, S * D * B

    def _shard_rows(self, *arrays):
        shard = NamedSharding(self.mesh, P(DATA_AXIS))
        return tuple(jax.device_put(a, shard) for a in arrays)

    def predict(self, x: np.ndarray, batch_size: int = 32) -> np.ndarray:
        """Mesh-sharded batched inference: ONE compiled program, input rows
        sharded over the ``"data"`` axis, params replicated.

        The TPU-native replacement for the reference's distributed predict
        (fork ``SparkModel.predict`` over ``mapPartitions`` — executors each
        rebuild a Keras replica; here replicas are the mesh shards of a single
        XLA program).
        """
        x = np.asarray(x)
        n = x.shape[0]
        B = int(batch_size)
        S, rows = self._stage_rows(n, B)
        xp = _pad_block(x, rows)
        sig = ("predict", S, B, xp.shape[1:], str(xp.dtype))
        if sig not in self._cache:
            self._cache[sig] = self._build_predict(S, B)
        fn = self._cache[sig]
        (xp,) = self._shard_rows(xp)
        tv, ntv = self.adapter.state_values()
        out = fn(tv, ntv, xp)
        return np.asarray(out)[:n]

    def evaluate(self, x: np.ndarray, y: np.ndarray,
                 batch_size: int = 32) -> Dict[str, float]:
        """Mesh-sharded evaluation → ``{"loss": ..., ["accuracy": ...]}``.

        Padded rows carry zero sample-weight, so results equal the unpadded
        weighted means regardless of padding/sharding geometry.
        """
        x = np.asarray(x)
        y = np.asarray(y)
        n = x.shape[0]
        B = int(batch_size)
        S, rows = self._stage_rows(n, B)
        xp, yp = _pad_block(x, rows), _pad_block(y, rows)
        sw = _pad_block(np.ones((n,), np.float32), rows)
        sig = ("evaluate", S, B, xp.shape[1:], yp.shape[1:], str(xp.dtype))
        if sig not in self._cache:
            self._cache[sig] = self._build_evaluate(S, B)
        fn = self._cache[sig]
        xp, yp, sw = self._shard_rows(xp, yp, sw)
        tv, ntv = self.adapter.state_values()
        loss, acc = fn(tv, ntv, xp, yp, sw)
        out = {"loss": float(loss)}
        if self.adapter.wants_accuracy:
            out["accuracy"] = float(acc)
        return out

    def _build_predict(self, S: int, B: int):
        predict_fn = self.adapter.build_predict_fn()

        def impl(tv, ntv, x):
            xb = x.reshape((S, B) + x.shape[1:])

            def step(_, xs):
                return None, predict_fn(tv, ntv, xs)

            _, out = jax.lax.scan(step, None, xb)
            return out.reshape((S * B,) + out.shape[2:])

        sharded = shard_map(
            impl, mesh=self.mesh, in_specs=(P(), P(), P(DATA_AXIS)),
            out_specs=P(DATA_AXIS), check_vma=False,
        )
        return jax.jit(sharded)

    def _build_evaluate(self, S: int, B: int):
        eval_step = self.adapter.build_eval_step()

        def impl(tv, ntv, x, y, sw):
            xb = x.reshape((S, B) + x.shape[1:])
            yb = y.reshape((S, B) + y.shape[1:])
            swb = sw.reshape((S, B))

            def step(_, batch):
                return None, eval_step(tv, ntv, *batch)

            _, stats = jax.lax.scan(step, None, (xb, yb, swb))
            loss_ws, acc_ws, wsum = jax.tree_util.tree_map(jnp.sum, stats)
            loss_sum = jax.lax.psum(loss_ws, DATA_AXIS)
            acc_sum = jax.lax.psum(acc_ws, DATA_AXIS)
            w_sum = jnp.maximum(jax.lax.psum(wsum, DATA_AXIS), 1e-9)
            return loss_sum / w_sum, acc_sum / w_sum

        sharded = shard_map(
            impl, mesh=self.mesh,
            in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(), P()), check_vma=False,
        )
        return jax.jit(sharded)

    # ------------------------------------------------------------------
    def _build(self, L: int, S: int, B: int, E: int, Sv: int, has_val: bool,
               mergeable: List[bool], sync_carry: Optional[str] = None):
        """Trace+compile the full multi-epoch training program.

        ``sync_carry`` (synchronous+epoch mode only) selects the
        merge-faithful chunked variants used by checkpointed fits:
        ``"fresh"`` starts worker stacks from the replicated base and
        ``"carry"`` takes them as inputs; BOTH return the per-worker stacks
        un-merged (plus a merged *preview* against the original base), so an
        epoch-chunked sequence reproduces the uninterrupted fit's single
        end-of-fit merge exactly instead of merging once per chunk.
        """
        if self.mode == "synchronous" and self.frequency == "batch":
            return self._build_gradsync(
                L=L, S=S, B=B, E=E, Sv=Sv, has_val=has_val, mergeable=mergeable
            )
        adapter = self.adapter
        optimizer = self.optimizer
        train_step = adapter.build_train_step(optimizer, remat=self.remat)
        eval_step = adapter.build_eval_step()
        merge_kind = self.merge
        merge_every_epoch = self.mode in ("asynchronous", "hogwild") and (
            self.frequency == "epoch"
        )
        merge_every_batch = self.mode in ("asynchronous", "hogwild") and (
            self.frequency == "batch"
        )

        def _bsum(tree_stack, wvalid):
            """Σ_l valid_l * leaf_l over the local worker dim."""
            def leaf(a):
                wshape = (-1,) + (1,) * (a.ndim - 1)
                return jnp.sum(a * wvalid.reshape(wshape).astype(a.dtype), axis=0)
            return jax.tree_util.tree_map(leaf, tree_stack)

        def merge_tv(tv_stack, base_tv, wvalid, denom):
            """Apply summed/averaged worker deltas to the base params."""
            local = _bsum(
                jax.tree_util.tree_map(lambda s, b: b[None] - s, tv_stack, base_tv),
                wvalid,
            )
            total = jax.lax.psum(local, DATA_AXIS)
            if merge_kind == "mean":
                total = jax.tree_util.tree_map(lambda t: t / denom, total)
            return jax.tree_util.tree_map(lambda b, t: b - t, base_tv, total)

        def merge_ntv(ntv_stack, base_ntv, wvalid, denom):
            """Merge only weight-slot ntv entries (BN stats); seed/counter
            state stays per-worker."""
            bases = _merged_ntv_bases(
                ntv_stack, base_ntv, wvalid, mergeable, denom, merge_kind
            )
            return [
                s if b is None
                else jnp.broadcast_to(b[None], s.shape).astype(s.dtype)
                for b, s in zip(bases, ntv_stack)
            ]

        shuffled_batches = _make_shuffler(S, B)

        def local_epoch(tv, ntv, opt, x_l, y_l, sw_l, key):
            xb, yb, swb = shuffled_batches(x_l, y_l, sw_l, key)

            def step(carry, batch):
                tv, ntv, opt = carry
                tv, ntv, opt, stats = train_step(tv, ntv, opt, *batch)
                return (tv, ntv, opt), stats

            (tv, ntv, opt), stats = jax.lax.scan(step, (tv, ntv, opt), (xb, yb, swb))
            return tv, ntv, opt, jax.tree_util.tree_map(jnp.sum, stats)

        local_eval = _make_local_eval(eval_step, Sv, B)
        tile = _make_tile(L)

        def opt_init_impl(tv0):
            # Per-worker optimizer state stack, identical at init.
            return jax.vmap(optimizer.init)(jax.tree_util.tree_map(tile, tv0))

        def fit_impl(tv0, ntv0, opt_stack, x, y, sw, xv, yv, sv, keys, wvalid):
            # Local shapes inside the shard: x [L, N, ...], keys [L, 2],
            # wvalid [L]; tv0/ntv0 replicated; opt_stack [L, ...] per shard.
            denom = jnp.maximum(jax.lax.psum(jnp.sum(wvalid), DATA_AXIS), 1.0)
            tv_stack = jax.tree_util.tree_map(tile, tv0)
            ntv_stack = _seeded_ntv_stack(ntv0, mergeable, L)
            base_tv, base_ntv = tv0, list(ntv0)

            def epoch_body(carry, e):
                tv_stack, ntv_stack, opt_stack, base_tv, base_ntv = carry
                ekeys = jax.vmap(lambda k: jax.random.fold_in(k, e))(keys)

                if merge_every_batch:
                    # Pull/train-one-batch/push per step, merged outside vmap.
                    xb, yb, swb = jax.vmap(shuffled_batches)(x, y, sw, ekeys)
                    # [L, S, B, ...] → scan over S
                    xb = jnp.swapaxes(xb, 0, 1)
                    yb = jnp.swapaxes(yb, 0, 1)
                    swb = jnp.swapaxes(swb, 0, 1)

                    def bstep(carry, batch):
                        tv_stack, ntv_stack, opt_stack, base_tv, base_ntv = carry
                        tv_stack, ntv_stack, opt_stack, stats = jax.vmap(
                            train_step
                        )(tv_stack, ntv_stack, opt_stack, *batch)
                        new_base_tv = merge_tv(tv_stack, base_tv, wvalid, denom)
                        new_base_ntv_full = merge_ntv(
                            ntv_stack, base_ntv, wvalid, denom
                        )
                        # v[0]: mergeable entries are replicated stacks (any
                        # row is the merged value); non-mergeable base is
                        # unused by merges — keep worker 0's, dtype intact.
                        new_base_ntv = [v[0] for v in new_base_ntv_full]
                        tv_stack = jax.tree_util.tree_map(tile, new_base_tv)
                        ntv_stack = [
                            jnp.broadcast_to(b[None], s.shape).astype(s.dtype)
                            if m else s
                            for b, s, m in zip(
                                new_base_ntv, ntv_stack, mergeable
                            )
                        ]
                        return (
                            tv_stack, ntv_stack, opt_stack, new_base_tv,
                            new_base_ntv,
                        ), stats

                    (tv_stack, ntv_stack, opt_stack, base_tv, base_ntv), stats = (
                        jax.lax.scan(
                            bstep,
                            (tv_stack, ntv_stack, opt_stack, base_tv, base_ntv),
                            (xb, yb, swb),
                        )
                    )
                    stats = jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), stats)
                else:
                    tv_stack, ntv_stack, opt_stack, stats = jax.vmap(local_epoch)(
                        tv_stack, ntv_stack, opt_stack, x, y, sw, ekeys
                    )
                    if merge_every_epoch:
                        base_tv = merge_tv(tv_stack, base_tv, wvalid, denom)
                        merged_full = merge_ntv(ntv_stack, base_ntv, wvalid, denom)
                        base_ntv = [v[0] for v in merged_full]
                        tv_stack = jax.tree_util.tree_map(tile, base_tv)
                        ntv_stack = [
                            v if m else s
                            for v, s, m in zip(merged_full, ntv_stack, mergeable)
                        ]

                # -- epoch metrics (weighted sums → psum → global means)
                metrics = _psum_weighted_means(stats)
                if has_val:
                    vstats = jax.vmap(
                        lambda tv, ntv, a, b, c: local_eval(tv, ntv, a, b, c)
                    )(tv_stack, ntv_stack, xv, yv, sv)
                    metrics.update(_psum_val_metrics(vstats))

                return (tv_stack, ntv_stack, opt_stack, base_tv, base_ntv), metrics

            (tv_stack, ntv_stack, opt_stack, base_tv, base_ntv), metrics = (
                jax.lax.scan(
                    epoch_body,
                    (tv_stack, ntv_stack, opt_stack, base_tv, base_ntv),
                    jnp.arange(E),
                )
            )

            if not (merge_every_epoch or merge_every_batch):
                # synchronous: the single end-of-fit merge
                base_tv = merge_tv(tv_stack, base_tv, wvalid, denom)
                merged_full = merge_ntv(ntv_stack, base_ntv, wvalid, denom)
                base_ntv = [v[0] for v in merged_full]

            ntv_mergeable_out = [v for v, m in zip(base_ntv, mergeable) if m]
            return base_tv, ntv_mergeable_out, opt_stack, metrics

        mesh = self.mesh
        pspec_rep = P()
        pspec_data = P(DATA_AXIS)

        if sync_carry is not None:
            if merge_every_epoch or merge_every_batch:
                raise ValueError(
                    "sync_carry variants exist only for synchronous+epoch "
                    f"mode, not {self.mode}/{self.frequency}"
                )

            def carry_core(tv_stack, ntv_stack, base_tv, base_ntv, opt_stack,
                           x, y, sw, xv, yv, sv, keys, wvalid, e0):
                denom = jnp.maximum(
                    jax.lax.psum(jnp.sum(wvalid), DATA_AXIS), 1.0
                )

                def epoch_body(carry, e):
                    tv_stack, ntv_stack, opt_stack = carry
                    # fold the GLOBAL epoch index so a chunked sequence
                    # shuffles identically to the uninterrupted fit
                    ekeys = jax.vmap(
                        lambda k: jax.random.fold_in(k, e + e0)
                    )(keys)
                    tv_stack, ntv_stack, opt_stack, stats = jax.vmap(
                        local_epoch
                    )(tv_stack, ntv_stack, opt_stack, x, y, sw, ekeys)
                    metrics = _psum_weighted_means(stats)
                    if has_val:
                        vstats = jax.vmap(
                            lambda tv, ntv, a, b, c: local_eval(tv, ntv, a, b, c)
                        )(tv_stack, ntv_stack, xv, yv, sv)
                        metrics.update(_psum_val_metrics(vstats))
                    return (tv_stack, ntv_stack, opt_stack), metrics

                (tv_stack, ntv_stack, opt_stack), metrics = jax.lax.scan(
                    epoch_body, (tv_stack, ntv_stack, opt_stack),
                    jnp.arange(E),
                )
                # merged PREVIEW against the ORIGINAL base: on the final
                # chunk this IS the uninterrupted fit's single merge
                merged_tv = merge_tv(tv_stack, base_tv, wvalid, denom)
                merged_full = merge_ntv(ntv_stack, base_ntv, wvalid, denom)
                merged_base_ntv = [v[0] for v in merged_full]
                ntv_mergeable_out = [
                    v for v, m in zip(merged_base_ntv, mergeable) if m
                ]
                return (merged_tv, ntv_mergeable_out, opt_stack, metrics,
                        tv_stack, ntv_stack)

            if sync_carry == "fresh":
                def fit_carry(tv0, ntv0, opt_stack, x, y, sw, xv, yv, sv,
                              keys, wvalid, e0):
                    tv_stack = jax.tree_util.tree_map(tile, tv0)
                    ntv_stack = _seeded_ntv_stack(ntv0, mergeable, L)
                    return carry_core(
                        tv_stack, ntv_stack, tv0, list(ntv0), opt_stack,
                        x, y, sw, xv, yv, sv, keys, wvalid, e0,
                    )

                in_specs = (
                    pspec_rep, pspec_rep, pspec_data, pspec_data, pspec_data,
                    pspec_data, pspec_data, pspec_data, pspec_data,
                    pspec_data, pspec_data, pspec_rep,
                )
                donate = (2,)
            else:  # "carry"
                fit_carry = carry_core
                in_specs = (
                    pspec_data, pspec_data, pspec_rep, pspec_rep, pspec_data,
                    pspec_data, pspec_data, pspec_data, pspec_data,
                    pspec_data, pspec_data, pspec_data, pspec_data, pspec_rep,
                )
                # stacks and opt_stack are consumed and re-returned
                donate = (0, 1, 4)

            shard_fit = shard_map(
                fit_carry, mesh=mesh, in_specs=in_specs,
                out_specs=(pspec_rep, pspec_rep, pspec_data, pspec_rep,
                           pspec_data, pspec_data),
                check_vma=False,
            )
            shard_opt_init = shard_map(
                opt_init_impl, mesh=mesh, in_specs=(pspec_rep,),
                out_specs=pspec_data, check_vma=False,
            )
            return (jax.jit(shard_fit, donate_argnums=donate),
                    jax.jit(shard_opt_init))

        shard_fit = shard_map(
            fit_impl,
            mesh=mesh,
            in_specs=(
                pspec_rep, pspec_rep, pspec_data, pspec_data, pspec_data,
                pspec_data, pspec_data, pspec_data, pspec_data, pspec_data,
                pspec_data,
            ),
            out_specs=(pspec_rep, pspec_rep, pspec_data, pspec_rep),
            check_vma=False,
        )
        shard_opt_init = shard_map(
            opt_init_impl, mesh=mesh, in_specs=(pspec_rep,),
            out_specs=pspec_data, check_vma=False,
        )
        # Donate the optimizer-state stack: it is consumed and returned every
        # call, so aliasing its buffers halves its HBM footprint (arg 2 =
        # opt_stack in fit_impl's signature).
        return jax.jit(shard_fit, donate_argnums=(2,)), jax.jit(shard_opt_init)

    # ------------------------------------------------------------------
    def _build_gradsync(self, L: int, S: int, B: int, E: int, Sv: int,
                        has_val: bool, mergeable: List[bool]):
        """Gradient-synchronous DP-SGD: ``mode='synchronous',
        frequency='batch'``.

        The canonical TPU data-parallel schedule (SURVEY.md §7.1.3's "fast
        path"), a deliberate extension beyond the reference's three schedules:
        per batch, every worker computes gradients of its sample-weighted loss
        SUM on the SHARED parameters; the sums ride one ``psum`` over ICI and
        one optimizer step applies their weighted mean. Parameters never
        diverge, so there is no delta merge at all — strictly better
        convergence than local-training schedules at the cost of one
        collective per batch (cheap on ICI, exactly what the hardware is for).
        BatchNorm statistics stay per-worker during the fit and merge once at
        the end; dropout masks stay independent per worker.
        """
        adapter = self.adapter
        optimizer = self.optimizer
        grad_step = adapter.build_grad_step(remat=self.remat)
        eval_step = adapter.build_eval_step()
        shuffled_batches = _make_shuffler(S, B)
        local_eval = _make_local_eval(eval_step, Sv, B)

        def opt_init_impl(tv0):
            return optimizer.init(tv0)  # ONE state, replicated everywhere

        def fit_impl(tv0, ntv0, opt_state, x, y, sw, xv, yv, sv, keys, wvalid):
            denom = jnp.maximum(jax.lax.psum(jnp.sum(wvalid), DATA_AXIS), 1.0)
            ntv_stack = _seeded_ntv_stack(ntv0, mergeable, L)
            tv = tv0

            def epoch_body(carry, e):
                tv, ntv_stack, opt = carry
                ekeys = jax.vmap(lambda k: jax.random.fold_in(k, e))(keys)
                xb, yb, swb = jax.vmap(shuffled_batches)(x, y, sw, ekeys)
                xb = jnp.swapaxes(xb, 0, 1)  # [S, L, B, ...]
                yb = jnp.swapaxes(yb, 0, 1)
                swb = jnp.swapaxes(swb, 0, 1)

                def bstep(carry, batch):
                    tv, ntv_stack, opt = carry
                    grads, ntv_stack, stats = jax.vmap(
                        grad_step, in_axes=(None, 0, 0, 0, 0)
                    )(tv, ntv_stack, *batch)
                    gsum = jax.tree_util.tree_map(
                        lambda g: jnp.sum(g, axis=0), grads
                    )
                    gtot = jax.lax.psum(gsum, DATA_AXIS)
                    wtot = jnp.maximum(
                        jax.lax.psum(jnp.sum(stats[2]), DATA_AXIS), 1e-9
                    )
                    ghat = jax.tree_util.tree_map(lambda g: g / wtot, gtot)
                    updates, opt = optimizer.update(ghat, opt, tv)
                    tv = jax.tree_util.tree_map(jnp.add, tv, updates)
                    return (tv, ntv_stack, opt), jax.tree_util.tree_map(
                        jnp.sum, stats
                    )

                (tv, ntv_stack, opt), stats = jax.lax.scan(
                    bstep, (tv, ntv_stack, opt), (xb, yb, swb)
                )
                metrics = _psum_weighted_means(stats)
                if has_val:
                    vstats = jax.vmap(
                        lambda ntv_l, a, b, c: local_eval(tv, ntv_l, a, b, c)
                    )(ntv_stack, xv, yv, sv)
                    metrics.update(_psum_val_metrics(vstats))
                return (tv, ntv_stack, opt), metrics

            (tv, ntv_stack, opt_state), metrics = jax.lax.scan(
                epoch_body, (tv, ntv_stack, opt_state), jnp.arange(E)
            )

            # end-of-fit BN-stats merge (mean of per-worker deltas)
            bases = _merged_ntv_bases(
                ntv_stack, list(ntv0), wvalid, mergeable, denom, "mean"
            )
            ntv_mergeable_out = [b for b in bases if b is not None]
            return tv, ntv_mergeable_out, opt_state, metrics

        mesh = self.mesh
        pspec_rep = P()
        pspec_data = P(DATA_AXIS)
        # One shared optimizer state: replicated in AND out (unlike the
        # per-worker stacks of the local-training schedules).
        shard_fit = shard_map(
            fit_impl,
            mesh=mesh,
            in_specs=(
                pspec_rep, pspec_rep, pspec_rep, pspec_data, pspec_data,
                pspec_data, pspec_data, pspec_data, pspec_data, pspec_data,
                pspec_data,
            ),
            out_specs=(pspec_rep, pspec_rep, pspec_rep, pspec_rep),
            check_vma=False,
        )
        shard_opt_init = shard_map(
            opt_init_impl, mesh=mesh, in_specs=(pspec_rep,),
            out_specs=pspec_rep, check_vma=False,
        )
        return jax.jit(shard_fit, donate_argnums=(2,)), jax.jit(shard_opt_init)
