"""Core engine / driver orchestration: ``SparkModel`` and ``SparkMLlibModel``.

Rebuild of reference ``elephas/spark_model.py:~1``. The public surface is the
reference's (constructor signature, ``fit(rdd, epochs, batch_size, verbose,
validation_split)``, ``predict``, ``master_network``, ``save`` /
``load_spark_model``), but the execution underneath is TPU-native:

- **Fast path (default)** — all of training compiles into ONE XLA program
  ``shard_map``-ed over a ``jax.sharding.Mesh``: per-worker replicas train in
  ``lax.scan`` loops and merge by ``psum`` over ICI
  (:mod:`elephas_tpu.parallel.engine`). The driver's remaining job is exactly
  what the north star prescribes: shard data onto chips, read back weights.
- **Host path (compatibility)** — the reference's literal architecture:
  worker generators consumed through ``rdd.mapPartitions(...)`` (threads),
  synchronous deltas merged on the driver, async/hogwild workers pushing to a
  live HTTP/Socket parameter server (:mod:`elephas_tpu.parameter`).

Path selection: ``parameter_server_mode='jax'`` (async modes) / default for
synchronous → fast path; ``'http'`` / ``'socket'`` → host path, which is also
the reference's default, so reference user code gets reference behavior
unchanged. Pass ``parameter_server_mode='jax'`` (or ``comm='jax'``) to opt
into on-device merging.

Reference behaviors kept: ``rdd.repartition(num_workers)`` before training
(``spark_model.py:~100``), partitions ``<= batch_size`` skipped
(``worker.py:~45``), sync merge = delta averaging (fork ``divide_by``
semantics; ``merge='sum'`` gives upstream sequential-subtract semantics),
async merge = full-delta application (Downpour).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .data.rdd import RDD
from .mllib.adapter import from_matrix, from_vector, to_matrix, to_vector
from .mllib.linalg import DenseMatrix, DenseVector
from .parallel.engine import CompiledTrainer
from .parallel.mesh import build_mesh
from .parameter.client import BaseParameterClient
from .parameter.server import HttpServer, SocketServer
from .utils.rdd_utils import lp_to_simple_rdd
from .worker import AsynchronousSparkWorker, SparkWorker


def _serialize_optimizer(optimizer) -> Any:
    """Keras optimizer → a config each worker can rebuild a FRESH optimizer
    from (reference ships ``master_optimizer`` the same way)."""
    if optimizer is None:
        return "sgd"
    if isinstance(optimizer, str):
        return optimizer
    import keras

    try:
        return keras.optimizers.serialize(optimizer)
    except Exception:
        return "sgd"


class SparkModel:
    """Distributed data-parallel trainer for a compiled Keras model."""

    def __init__(self, model, mode: str = "asynchronous", frequency: str = "epoch",
                 parameter_server_mode: str = "http",
                 num_workers: Optional[int] = None,
                 custom_objects: Optional[dict] = None, batch_size: int = 32,
                 port: int = 4000, mesh=None, merge: str = "auto",
                 comm: Optional[str] = None, remat: bool = False,
                 compression: Optional[str] = None,
                 master_optimizer=None, master_loss=None, master_metrics=None,
                 fault_plan=None, retry_policy=None,
                 ps_timeout: float = 60.0,
                 membership=None, quorum: Optional[int] = None,
                 round_deadline_s: Optional[float] = None,
                 backup_stragglers: bool = True,
                 hot_standby: bool = False,
                 elastic=None,
                 wire_stall_timeout_s: Optional[float] = None,
                 *args, **kwargs):
        if mode not in ("synchronous", "asynchronous", "hogwild"):
            raise ValueError(f"Unknown mode: {mode}")
        if parameter_server_mode not in ("http", "socket", "native", "jax"):
            raise ValueError(
                f"Unknown parameter_server_mode: {parameter_server_mode}"
            )
        self._master_network = model
        self.mode = mode
        self.frequency = frequency
        self.parameter_server_mode = parameter_server_mode
        self.num_workers = num_workers
        self.custom_objects = custom_objects
        self.batch_size = batch_size
        self.port = port
        self.merge = merge
        self.mesh = mesh
        self.remat = remat
        # comm overrides: 'jax' = on-device engine, 'host' = reference-shaped
        # host path. Default: sync → jax; async → per parameter_server_mode.
        if comm is None:
            if mode == "synchronous":
                comm = "jax"
            else:
                comm = "jax" if parameter_server_mode == "jax" else "host"
        self.comm = comm
        # Delta compression for host PS pushes ('int8' | 'topk:F' | None) —
        # an extension; the reference pushes full f32 lists (SURVEY.md §2.4).
        # Only the host async paths have PS traffic to compress; reject the
        # knob anywhere it would be silently ignored.
        if compression:
            if comm != "host" or mode == "synchronous":
                raise ValueError(
                    "compression applies to the host parameter-server "
                    "paths (asynchronous/hogwild with http/socket/native); "
                    f"mode={mode!r} with comm={comm!r} has no PS "
                    "traffic to compress"
                )
            from .parameter.compression import make_codec

            make_codec(compression)  # validate the spec eagerly
        self.compression = compression
        self.master_optimizer = (
            master_optimizer
            if master_optimizer is not None
            else _serialize_optimizer(getattr(model, "optimizer", None))
        )
        self.master_loss = (
            master_loss if master_loss is not None else getattr(model, "loss", None)
        )
        self.master_metrics = master_metrics
        # Resilience extensions (elephas_tpu.resilience): a seeded FaultPlan
        # injects failures into workers/clients/servers, a RetryPolicy
        # routes host-PS traffic through backoff+breaker, and ps_timeout
        # replaces the reference's five hard-coded 60s wire timeouts.
        self.fault_plan = fault_plan
        self.retry_policy = retry_policy
        self.ps_timeout = float(ps_timeout)
        # Per-recv progress deadline for the socket wire (slow-loris guard):
        # a connection idle BETWEEN frames is fine; one stalled INSIDE a
        # frame past this deadline raises FrameStalledError and reconnects.
        # Required when the fault plan has wire_stall/wire_flip sites (a
        # flipped length field can otherwise hang a receive forever).
        self.wire_stall_timeout_s = (
            None if wire_stall_timeout_s is None else float(wire_stall_timeout_s)
        )
        # Elastic-membership extensions (elephas_tpu.resilience.membership):
        # a HeartbeatRegistry drives K-of-N quorum rounds with straggler
        # backups on the host paths and masks expired workers out of the
        # compiled path's merge; hot_standby adds a replicated standby
        # parameter server that clients fail over to when the primary dies.
        self.membership = membership
        self.quorum = None if quorum is None else int(quorum)
        self.round_deadline_s = round_deadline_s
        self.backup_stragglers = bool(backup_stragglers)
        self.hot_standby = bool(hot_standby)
        if self.quorum is not None and self.quorum < 1:
            raise ValueError("quorum must be >= 1")
        if self.quorum is not None and membership is None:
            raise ValueError(
                "quorum requires a membership registry "
                "(membership=HeartbeatRegistry(...))"
            )
        if self.hot_standby:
            if self.comm != "host" or mode == "synchronous":
                raise ValueError(
                    "hot_standby needs a live parameter server: use an "
                    "asynchronous/hogwild mode with comm='host' "
                    f"(got mode={mode!r}, comm={self.comm!r})"
                )
            if parameter_server_mode not in ("http", "socket"):
                raise ValueError(
                    "hot_standby supports the http/socket parameter servers "
                    f"(got {parameter_server_mode!r})"
                )
        # Elastic HOST training (elephas_tpu.parallel.elastic): an
        # ElasticConfig routes fit through a pool of real worker processes
        # leasing membership from the driver — hosts may join, leave, and
        # die mid-fit; the mesh re-forms per membership epoch. Orthogonal to
        # `membership`, which governs thread-level partitions of one host.
        self.elastic = elastic
        self._elastic_pool = None
        self._standby_server = None
        self._ps_stats: Dict[str, Any] = {}
        self._fit_kwargs: Dict[str, Any] = {}
        self.training_histories: List[Dict[str, Any]] = []
        self.timings: List[Dict[str, float]] = []
        self._server = None
        self.client: Optional[BaseParameterClient] = None
        self._jax_trainer: Optional[CompiledTrainer] = None
        self._jax_trainer_model = None
        self._checkpoint = (None, 1, False)

    # -- properties ------------------------------------------------------
    @property
    def master_network(self):
        return self._master_network

    @master_network.setter
    def master_network(self, network):
        self._master_network = network

    def get_config(self) -> Dict[str, Any]:
        return {
            "mode": self.mode,
            "frequency": self.frequency,
            "parameter_server_mode": self.parameter_server_mode,
            "num_workers": self.num_workers,
            "batch_size": self.batch_size,
            "port": self.port,
            "merge": self.merge,
            "comm": self.comm,
            "remat": self.remat,
            "compression": self.compression,
        }

    # -- training --------------------------------------------------------
    def fit(self, rdd: RDD, epochs: int = 10, batch_size: Optional[int] = None,
            verbose: int = 0, validation_split: float = 0.1,
            checkpoint_dir: Optional[str] = None,
            checkpoint_frequency: int = 1, resume: bool = False,
            profile_dir: Optional[str] = None, **kwargs) -> None:
        """Train on an RDD of ``(x, y)`` sample pairs.

        Mirrors reference ``SparkModel.fit`` (``spark_model.py:~100``):
        repartitions to ``num_workers`` and dispatches per mode.

        TPU-build extensions (beyond the reference — SURVEY.md §5):
        ``checkpoint_dir`` enables mid-training checkpointing every
        ``checkpoint_frequency`` epochs with optimizer state; ``resume=True``
        continues from the latest checkpoint; ``profile_dir`` captures a
        ``jax.profiler`` trace of the training run.
        """
        batch_size = self.batch_size if batch_size is None else batch_size
        num_workers = self._resolve_num_workers()
        if rdd.getNumPartitions() != num_workers:
            rdd = rdd.repartition(num_workers)
        self._checkpoint = (checkpoint_dir, checkpoint_frequency, resume)
        # Extra Keras fit kwargs (e.g. shuffle=False) ride along to the
        # host-path workers' model.fit; the compiled path ignores them.
        self._fit_kwargs = dict(kwargs)
        if profile_dir is not None:
            import jax

            with jax.profiler.trace(profile_dir):
                self._fit(rdd, epochs, batch_size, verbose, validation_split)
        else:
            self._fit(rdd, epochs, batch_size, verbose, validation_split)

    def _resolve_num_workers(self) -> int:
        if self.num_workers is not None:
            return int(self.num_workers)
        if self.mesh is not None:
            return int(self.mesh.devices.size)
        import jax

        return jax.local_device_count()

    def _partition_blocks(self, rdd: RDD, batch_size: int):
        """Partitions → dense per-worker blocks, skipping ``<= batch_size``
        partitions (the reference worker guard).

        Blocks are cached per (rdd identity, batch_size): repeated ``fit``
        calls on the same RDD skip the python-side re-densify AND — because
        the same array objects reach the engine — its device staging cache
        (an unchanged dataset is not copied host→device again).
        """
        key = (id(rdd), batch_size)
        cached = getattr(self, "_block_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        blocks = []
        for part in rdd.partitions():
            if not part:
                continue
            xs = np.stack([np.asarray(x) for x, _ in part])
            ys = np.stack([np.asarray(y) for _, y in part])
            if xs.shape[0] <= batch_size:
                continue
            blocks.append((xs, ys))
        self._block_cache = (key, blocks)
        return blocks

    def _fit(self, rdd: RDD, epochs: int, batch_size: int, verbose: int,
             validation_split: float) -> None:
        if self.elastic is not None:
            self._fit_elastic(rdd, epochs, batch_size, verbose)
        elif self.comm == "jax":
            self._fit_jax(rdd, epochs, batch_size, verbose, validation_split)
        elif self.mode == "synchronous":
            self._fit_host_sync(rdd, epochs, batch_size, verbose, validation_split)
        else:
            self._fit_host_async(rdd, epochs, batch_size, verbose, validation_split)

    def _get_trainer(self) -> CompiledTrainer:
        """Build (or reuse) the compiled trainer — reuse keeps XLA executables
        cached across ``fit`` calls with the same geometry."""
        if (
            self._jax_trainer is None
            or self._jax_trainer_model is not self._master_network
        ):
            from .models.adapters import KerasModelAdapter

            mesh = self.mesh if self.mesh is not None else build_mesh()
            adapter = KerasModelAdapter(
                self._master_network,
                loss=self.master_loss,
                optimizer=self.master_optimizer,
                metrics=self.master_metrics,
                custom_objects=self.custom_objects,
            )
            self._jax_trainer = CompiledTrainer(
                adapter, mesh, mode=self.mode, frequency=self.frequency,
                merge=self.merge, remat=self.remat,
            )
            self._jax_trainer_model = self._master_network
        return self._jax_trainer

    def _membership_mask(self, n: int):
        """K-of-N mask for the fused-program path: ``worker_valid`` floats
        for :meth:`CompiledTrainer.fit`, or ``None`` when every worker is
        live (keeps the common case on the cached no-mask executable).

        The fused program cannot lose a worker mid-flight (all workers are
        one XLA program), so membership here models *external* liveness —
        hosts the registry saw die between rounds. Unknown members default
        to live: the jax path never heartbeats per-batch.
        """
        if self.membership is None:
            return None
        from .resilience.membership import (
            QuorumLostError, member_id_for,
        )

        self.membership.sweep()
        mask = [
            1.0 if self.membership.is_live(member_id_for(i), default=True)
            else 0.0
            for i in range(n)
        ]
        live = int(sum(mask))
        if self.quorum is not None and live < self.quorum:
            raise QuorumLostError(
                f"{live} of {n} workers live, quorum is {self.quorum}"
            )
        if live == n:
            return None
        return mask

    # -- fast path: one XLA program over the mesh ------------------------
    def _fit_jax(self, rdd, epochs, batch_size, verbose, validation_split):
        blocks = self._partition_blocks(rdd, batch_size)
        if not blocks:
            raise ValueError(
                "All partitions were skipped (each needs > batch_size samples)"
            )
        trainer = self._get_trainer()
        checkpoint_dir, checkpoint_frequency, resume = self._checkpoint

        if checkpoint_dir is None:
            if self.fault_plan is not None:
                self.fault_plan.tick("fit_chunk")
            result = trainer.fit(
                blocks, epochs=epochs, batch_size=batch_size,
                validation_split=validation_split, verbose=verbose,
                worker_valid=self._membership_mask(len(blocks)),
            )
            self.training_histories.append(result.history)
            self.timings.append(result.timings)
            return

        # Checkpointed path: epoch-chunked fits carrying optimizer state.
        # Synchronous+epoch mode additionally carries the per-worker weight
        # stacks across chunks (engine worker_state), so the chunked sequence
        # merges ONCE — exactly like the uninterrupted fit — instead of once
        # per chunk; each checkpoint's weights are the merged preview of the
        # stacks at that boundary (what you'd get by merging right then).
        from .utils.checkpoint import (
            has_checkpoint, load_checkpoint, load_pytree, save_checkpoint,
            save_pytree,
        )

        sync_faithful = (
            self.mode == "synchronous" and self.frequency == "epoch"
        )
        ws_path = os.path.join(checkpoint_dir, "worker_state")
        start_epoch, opt_state, worker_state = 0, None, None
        if resume and has_checkpoint(checkpoint_dir):
            weights, meta, opt_state = load_checkpoint(checkpoint_dir)
            self._master_network.set_weights(weights)
            start_epoch = int(meta.get("epoch", 0))
            if sync_faithful and start_epoch > 0:
                # worker_state is written in a separate step from meta.json,
                # so validate its epoch stamp: a crash between the two
                # writes (or an older checkpoint without stacks) must not
                # silently continue from mismatched per-worker state.
                ws_epoch = -1
                if os.path.isdir(ws_path):
                    worker_state = load_pytree(ws_path)
                    ws_epoch = int(worker_state.pop("epoch", -1))
                if ws_epoch != start_epoch:
                    import warnings

                    warnings.warn(
                        f"checkpoint {checkpoint_dir}: worker_state is "
                        f"{'missing' if worker_state is None else f'stamped epoch {ws_epoch}'}"
                        f" but meta says epoch {start_epoch}; resuming from "
                        "the merged checkpoint weights with fresh worker "
                        "stacks (merge-faithfulness to the uninterrupted "
                        "fit is lost for this run)",
                        RuntimeWarning,
                    )
                    worker_state = None
        merged: Dict[str, List[float]] = {}
        epoch = start_epoch
        while epoch < epochs:
            chunk = min(checkpoint_frequency, epochs - epoch)
            if self.fault_plan is not None:
                # One crash opportunity per fit chunk: crash_sites=
                # {"fit_chunk": k} kills the (k+1)th chunk AFTER the
                # previous chunk's checkpoint is durable — the supervisor's
                # auto-resume scenario.
                self.fault_plan.tick("fit_chunk")
            if sync_faithful:
                # seed stays 0 and the GLOBAL epoch index is folded inside
                # the program, matching the uninterrupted fit's shuffles
                result = trainer.fit(
                    blocks, epochs=chunk, batch_size=batch_size,
                    validation_split=validation_split, verbose=verbose,
                    seed=0, epoch_offset=epoch, opt_state=opt_state,
                    keep_opt_state=True, worker_state=worker_state,
                    keep_worker_state=True,
                    worker_valid=self._membership_mask(len(blocks)),
                )
                worker_state = result.worker_state
            else:
                result = trainer.fit(
                    blocks, epochs=chunk, batch_size=batch_size,
                    validation_split=validation_split, verbose=verbose,
                    seed=epoch, opt_state=opt_state, keep_opt_state=True,
                    worker_valid=self._membership_mask(len(blocks)),
                )
            opt_state = result.opt_state
            for k, v in result.history.items():
                merged.setdefault(k, []).extend(v)
            epoch += chunk
            if sync_faithful:
                # stacks first, meta last: meta.json is the commit point,
                # and resume validates the stamp below against meta's epoch
                save_pytree(
                    ws_path, {**worker_state, "epoch": np.int64(epoch)}
                )
            save_checkpoint(
                checkpoint_dir, result.weights,
                {"epoch": epoch, "epochs": epochs, "mode": self.mode},
                opt_state,
            )
            self.timings.append(result.timings)
        self.training_histories.append(merged)

    # -- host path: reference-shaped synchronous -------------------------
    def _fit_host_sync(self, rdd, epochs, batch_size, verbose, validation_split):
        model = self._master_network
        train_config = {
            "epochs": epochs,
            "batch_size": batch_size,
            "verbose": verbose,
            "validation_split": validation_split,
            **self._fit_kwargs,
        }
        parameters = rdd.context.broadcast(model.get_weights())
        worker = SparkWorker(
            model.to_json(), parameters, train_config,
            self.master_optimizer, self.master_loss, self.master_metrics,
            self.custom_objects, fault_plan=self.fault_plan,
        )
        if self.membership is not None:
            # Elastic round: K-of-N commit with straggler backups instead of
            # blocking on every partition (DeepSpark partial aggregation).
            # The mean below is over the RECEIVED deltas only.
            from .resilience.membership import QuorumRunner

            runner = QuorumRunner(
                self.membership, quorum=self.quorum,
                round_deadline_s=self.round_deadline_s,
                backup_stragglers=self.backup_stragglers,
                max_failures=rdd.context.maxTaskFailures,
            )
            committed = runner.run(
                rdd.partitions(), worker.train,
                stage_id=rdd.context._next_stage_id(),
            )
            results = [item for pid in sorted(committed)
                       for item in committed[pid]]
        else:
            results = rdd.mapPartitions(worker.train).collect()
        deltas = [r[0] for r in results]
        self.training_histories.extend(r[1] for r in results if r[1])
        if not deltas:
            raise ValueError(
                "All partitions were skipped (each needs > batch_size samples)"
            )
        new_parameters = [np.array(w) for w in model.get_weights()]
        merge = "mean" if self.merge == "auto" else self.merge
        scale = 1.0 / len(deltas) if merge == "mean" else 1.0
        for delta in deltas:
            new_parameters = [
                p - scale * np.asarray(d) for p, d in zip(new_parameters, delta)
            ]
        model.set_weights(new_parameters)

    # -- elastic host path: driver as control plane over host processes --
    def _fit_elastic(self, rdd, epochs, batch_size, verbose) -> None:
        """Train over an elastic pool of real host processes.

        One elastic round = one global pass over the densified data: the
        driver recuts the batch over the CURRENT host formation each round
        (the mesh re-forms as hosts join/leave/die), every host runs one
        local ``model.fit`` epoch on its shard, and the sample-weighted
        merged delta commits through the versioned, epoch-fenced parameter
        store. ``epochs`` maps to rounds; ``validation_split`` is a
        driver-side concern the elastic path does not consume (workers see
        training shards only).
        """
        from .parallel.elastic import ElasticHostPool

        model = self._master_network
        blocks = self._partition_blocks(rdd, batch_size)
        if not blocks:
            raise ValueError(
                "All partitions were skipped (each needs > batch_size samples)"
            )
        x = np.concatenate([b[0] for b in blocks])
        y = np.concatenate([b[1] for b in blocks])
        task_config = {
            "model_json": model.to_json(),
            "optimizer": self.master_optimizer,
            "loss": self.master_loss,
            "metrics": self.master_metrics or [],
            "local_epochs": 1,
            "batch_size": batch_size,
        }
        pool = ElasticHostPool(
            model.get_weights(), self.elastic,
            task={"builtin": "keras_fit_task"},
            task_config=task_config,
            fault_plan=self.fault_plan,
        )
        self._elastic_pool = pool
        weights = pool.fit(x, y, rounds=epochs)
        model.set_weights(weights)
        self.training_histories.append({
            "mode": "elastic",
            "loss": list(pool.history["loss"]),
            "rounds_committed": int(pool.stats["rounds_committed"]),
            "reformations": int(pool.stats["reformations"]),
        })

    # -- host path: reference-shaped async/hogwild against a live PS -----
    def start_server(self) -> None:
        weights = self._master_network.get_weights()
        if self.parameter_server_mode == "native":
            from .parameter.native import NativeServer

            cls = NativeServer
        elif self.parameter_server_mode == "http":
            cls = HttpServer
        else:
            cls = SocketServer
        server_kwargs = {}
        if cls is SocketServer and self.wire_stall_timeout_s is not None:
            server_kwargs["stall_timeout_s"] = self.wire_stall_timeout_s
        self._server = cls(
            weights, mode=self.mode, port=self.port,
            fault_plan=self.fault_plan, name="primary", **server_kwargs,
        )
        self._server.start()
        self.port = self._server.port  # native server may bind an OS port
        if self.hot_standby:
            # The standby gets NO fault plan: it is the recovery target, and
            # sharing the primary's plan would also re-consult server-side
            # drop decisions on replicated deltas (losing committed updates
            # is exactly what the standby exists to prevent).
            self._standby_server = cls(
                weights, mode=self.mode, port=0, name="standby",
                **server_kwargs,
            )
            self._standby_server.start()
            self._server.attach_standby(self._standby_server)

    def _make_client(self) -> BaseParameterClient:
        if self.parameter_server_mode == "native":
            from .parameter.compression import make_codec
            from .parameter.native import NativeClient

            weights = self._master_network.get_weights()
            client = NativeClient(
                [w.shape for w in weights], [w.dtype for w in weights],
                self.port,
                # fresh codec per client: top-k error-feedback residual is
                # per-worker state (mirrors the http/socket wrapper below)
                codec=make_codec(self.compression),
            )
        else:
            # Wire knobs reach the socket transport only; get_client ignores
            # them for http. The fault plan goes in twice on purpose: here it
            # corrupts the actual bytes on the wire (FaultySocket under the
            # checksummed framing), while FaultyClient below injects at the
            # logical request level — the soak composes both.
            client = BaseParameterClient.get_client(
                self.parameter_server_mode, self.port, host="127.0.0.1",
                timeout=self.ps_timeout,
                fault_plan=self.fault_plan,
                stall_timeout_s=self.wire_stall_timeout_s,
            )
            if self._standby_server is not None:
                from .resilience.policy import FailoverClient

                # Bottom of the wrapper stack: transport selection. Injected
                # wire faults (FaultyClient, above) stay retryable without
                # tripping a failover; only genuine endpoint death does.
                standby = BaseParameterClient.get_client(
                    self.parameter_server_mode, self._standby_server.port,
                    host="127.0.0.1", timeout=self.ps_timeout,
                    fault_plan=self.fault_plan,
                    stall_timeout_s=self.wire_stall_timeout_s,
                )
                client = FailoverClient(
                    [client, standby], registry=self.membership,
                )
            if self.fault_plan is not None:
                from .resilience.faults import FaultyClient

                # Transport layer: everything stacked above (compression,
                # retries) sees injected faults as real network ones.
                client = FaultyClient(client, self.fault_plan)
            if self.compression:
                from .parameter.compression import CompressingClient, make_codec

                # fresh codec per client: top-k error-feedback residual is
                # per-worker state (one client per executor, like the
                # reference)
                client = CompressingClient(client, make_codec(self.compression))
        if self.retry_policy is not None:
            from .resilience.policy import ResilientClient

            client = ResilientClient(client, policy=self.retry_policy)
        return client

    def stop_server(self) -> None:
        if self._server is not None:
            if self._standby_server is not None:
                # let in-flight replication land before reading counters
                self._server.flush_replication()
            self._ps_stats = {
                name: {
                    "version": int(getattr(server, "version", -1)),
                    "rejected_stale": int(
                        getattr(server, "rejected_stale", 0)
                    ),
                    "replication_errors": int(
                        getattr(server, "replication_errors", 0)
                    ),
                    "applied_tagged": {
                        k: int(v)
                        for k, v in getattr(
                            server, "applied_tagged", {}
                        ).items()
                    },
                }
                for name, server in (
                    ("primary", self._server),
                    ("standby", self._standby_server),
                )
                if server is not None
            }
            self._server.stop()
            self._server = None
        if self._standby_server is not None:
            self._standby_server.stop()
            self._standby_server = None

    def membership_snapshot(self) -> Dict[str, Any]:
        """JSON-able elastic-training observability: registry events (joins,
        expiries, epoch bumps, backups, failovers, per-round shortfall) plus
        the last fit's parameter-server version/fencing/replication counters.
        Style matches ``ServingMetrics.snapshot()``."""
        snap: Dict[str, Any] = {
            "membership": None, "counters": {}, "rounds": [], "events": [],
        }
        if self.membership is not None:
            snap = self.membership.snapshot()
        snap["parameter_servers"] = dict(self._ps_stats)
        if self._elastic_pool is not None:
            # Host-level control plane: epochs/commits/mesh formations from
            # the last elastic fit (the thread-level registry above tracks
            # partitions; this tracks whole hosts).
            snap["elastic"] = self._elastic_pool.snapshot()
        return snap

    def _fit_host_async(self, rdd, epochs, batch_size, verbose, validation_split):
        model = self._master_network
        self.start_server()
        try:
            train_config = {
                "epochs": epochs,
                "batch_size": batch_size,
                "verbose": verbose,
                "validation_split": validation_split,
                **self._fit_kwargs,
            }

            def make_train(json_config, make_client, train_config, frequency,
                           opt, loss, metrics, custom_objects, fault_plan,
                           registry):
                # Each partition gets its OWN client (thread) — mirrors one
                # client per executor in the reference.
                def run(iterator):
                    client = make_client()
                    try:
                        worker = AsynchronousSparkWorker(
                            json_config, client, train_config, frequency,
                            opt, loss, metrics, custom_objects,
                            fault_plan=fault_plan, registry=registry,
                        )
                        yield from worker.train(iterator)
                    finally:
                        # task retries re-enter run(): a raising attempt must
                        # not leak its TCP connection until GC
                        client.close()

                return run

            fn = make_train(
                model.to_json(), self._make_client,
                train_config, self.frequency, self.master_optimizer,
                self.master_loss, self.master_metrics, self.custom_objects,
                self.fault_plan, self.membership,
            )
            if self.membership is not None:
                # Elastic async round: same K-of-N/backup machinery as the
                # sync path; "reporting" here means the worker finished its
                # pushes. Partitions abandoned at the deadline get their
                # task fenced at the server — a superseding register rolls
                # back their uncommitted pushes and rejects any still coming
                # (late deltas dead by membership epoch).
                from .resilience.membership import QuorumRunner

                runner = QuorumRunner(
                    self.membership, quorum=self.quorum,
                    round_deadline_s=self.round_deadline_s,
                    backup_stragglers=self.backup_stragglers,
                    max_failures=rdd.context.maxTaskFailures,
                )
                stage_id = rdd.context._next_stage_id()
                runner.run(rdd.partitions(), fn, stage_id=stage_id)
                if runner.abandoned:
                    fencer = self._make_client()
                    try:
                        for pid in runner.abandoned:
                            fencer.register_attempt(
                                f"stage-{stage_id}-partition-{pid}",
                                1 << 20,
                            )
                    finally:
                        fencer.close()
            else:
                rdd.mapPartitions(fn).collect()
            client = self._make_client()
            try:
                new_parameters = client.get_parameters()
            finally:
                client.close()
            model.set_weights(new_parameters)
        finally:
            self.stop_server()

    # -- streaming train-to-serve ----------------------------------------
    def fit_stream(self, batches, train_fn, *, sink=None,
                   publish_every: int = 1,
                   max_interval_s: Optional[float] = None,
                   eval_fn=None, eval_batch=None,
                   regression_margin: float = 0.0, ring_size: int = 4,
                   checkpoint_dir: Optional[str] = None,
                   checkpoint_every: int = 1) -> Dict[str, Any]:
        """Streaming ingest with live weight publication (host PS path).

        Drains ``batches`` (an iterable of micro-batches) through a
        :class:`~elephas_tpu.streaming.trainer.StreamTrainer` against this
        model's own parameter server — started/stopped exactly like
        ``_fit_host_async``, standby replication and wrapper stack
        included. ``train_fn(weights, batch) -> (new_weights, loss)`` runs
        driver-side in PS wire order. With ``sink`` (e.g.
        :func:`~elephas_tpu.streaming.publisher.engine_sink` over a live
        serving engine) a :class:`WeightPublisher` publishes every
        ``publish_every`` commits / ``max_interval_s`` seconds behind the
        optional eval gate. With ``checkpoint_dir`` the stream runs under
        a :class:`~elephas_tpu.resilience.supervisor.TrainingSupervisor`
        (checkpoint every ``checkpoint_every`` commits, crash auto-resume
        with exactly-once batch consumption).

        Returns a JSON-able summary (commit count, publisher history);
        the master network ends holding the final PS weights.
        """
        from .streaming import StreamTrainer, WeightPublisher

        if self.mode not in ("asynchronous", "hogwild"):
            raise ValueError(
                "fit_stream needs a live parameter server "
                f"(mode 'asynchronous' or 'hogwild', got {self.mode!r})")
        if self.parameter_server_mode not in ("http", "socket", "native"):
            raise ValueError(
                "fit_stream runs against the host parameter servers "
                f"(http/socket/native, got {self.parameter_server_mode!r})")
        self.start_server()
        try:
            client = self._make_client()
            try:
                trainer = StreamTrainer(client, train_fn)
                publisher = None
                if sink is not None:
                    publisher = WeightPublisher(
                        client, sink, publish_every=publish_every,
                        max_interval_s=max_interval_s, eval_fn=eval_fn,
                        eval_batch=eval_batch,
                        regression_margin=regression_margin,
                        ring_size=ring_size,
                    )
                if checkpoint_dir is not None:
                    from .resilience.supervisor import TrainingSupervisor

                    supervisor = TrainingSupervisor(
                        self, checkpoint_dir,
                        checkpoint_frequency=checkpoint_every,
                    )
                    supervisor.fit_stream(batches, trainer,
                                          publisher=publisher)
                else:
                    trainer.run(batches, publisher=publisher)
                self._master_network.set_weights(client.get_parameters())
                summary: Dict[str, Any] = {
                    "commits": trainer.commits,
                    "last_loss": trainer.last_loss,
                    "last_version": int(
                        getattr(client, "last_seen_version", -1)),
                }
                if publisher is not None:
                    summary["publisher"] = publisher.state_dict()
                return summary
            finally:
                client.close()
        finally:
            self.stop_server()

    # -- inference -------------------------------------------------------
    def predict(self, data, batch_size: Optional[int] = None):
        """Predict on a numpy array (reference: driver-local evaluation) or an
        RDD of feature rows (maintained-fork distributed predict).

        On the fast path (``comm='jax'``) both forms run mesh-sharded: ONE
        compiled XLA program with rows sharded over the ``"data"`` axis —
        the TPU-native analog of the fork's per-executor replica predict.
        Host path keeps the reference's literal shape (Keras replica per
        partition via ``mapPartitions``).
        """
        model = self._master_network
        batch_size = self.batch_size if batch_size is None else batch_size
        if isinstance(data, RDD):
            if self.comm == "jax":
                # The RDD facade is in-process: stage rows once, predict on
                # the mesh, hand back an RDD with the partitioning preserved.
                parts = data.partitions()
                rows = [np.asarray(r) for part in parts for r in part]
                if not rows:
                    return RDD([[] for _ in parts], data.context)
                preds = self._get_trainer().predict(
                    np.stack(rows), batch_size=batch_size
                )
                out_parts, i = [], 0
                for part in parts:
                    out_parts.append(list(preds[i:i + len(part)]))
                    i += len(part)
                return RDD(out_parts, data.context)
            json_config = model.to_json()
            weights = data.context.broadcast(model.get_weights())
            custom_objects = self.custom_objects

            def predict_partition(iterator):
                rows = [np.asarray(x) for x in iterator]
                if not rows:
                    return
                import keras

                replica = keras.models.model_from_json(
                    json_config, custom_objects=custom_objects
                )
                replica.set_weights(weights.value)
                preds = replica.predict(
                    np.stack(rows), batch_size=batch_size, verbose=0
                )
                yield from preds

            return data.mapPartitions(predict_partition)
        if self.comm == "jax":
            return self._get_trainer().predict(
                np.asarray(data), batch_size=batch_size
            )
        return model.predict(np.asarray(data), batch_size=batch_size, verbose=0)

    def _compiled_eval_representable(self) -> bool:
        """True when the compiled eval path emits exactly the shape Keras
        ``evaluate`` would: loss plus (only) an accuracy metric. Weighted
        metrics, non-accuracy metrics (mae, auc, custom), or a gate/adapter
        disagreement (``master_metrics`` overrides) all fail over to Keras so
        no metric is ever silently dropped."""
        from .models.adapters import _is_accuracy_name, compile_metric_names

        names, weighted = compile_metric_names(self._master_network)
        if weighted or not all(_is_accuracy_name(n) for n in names):
            return False
        wants = self._get_trainer().adapter.wants_accuracy
        return wants == bool(names)

    def evaluate(self, x, y, **kwargs):
        """Loss (and accuracy) on held-out data. Fast path: mesh-sharded
        compiled evaluation; host path: driver-local Keras ``evaluate``
        (reference behavior). Return format matches Keras: scalar loss, or
        ``[loss, accuracy]`` when an accuracy metric is compiled in. Models
        compiled with other metrics always evaluate through Keras so the
        return shape never changes."""
        if self.comm == "jax" and self._compiled_eval_representable():
            trainer = self._get_trainer()
            res = trainer.evaluate(
                np.asarray(x), np.asarray(y),
                batch_size=kwargs.get("batch_size", self.batch_size),
            )
            if "accuracy" in res:
                return [res["loss"], res["accuracy"]]
            return res["loss"]
        return self._master_network.evaluate(
            np.asarray(x), np.asarray(y), verbose=kwargs.get("verbose", 0)
        )

    # -- persistence -----------------------------------------------------
    def save(self, path: str) -> None:
        """Whole-model save (reference ``spark_model.py:~90``): Keras file +
        a sidecar JSON with elephas config."""
        self._master_network.save(path)
        meta = self.get_config()
        with open(path + ".elephas.json", "w") as f:
            json.dump(meta, f)

    @property
    def training_histories_(self):
        return self.training_histories


def load_spark_model(path: str, custom_objects: Optional[dict] = None) -> SparkModel:
    """Reference ``load_spark_model`` (``spark_model.py:~25``)."""
    import keras

    model = keras.models.load_model(path, custom_objects=custom_objects)
    config: Dict[str, Any] = {}
    sidecar = path + ".elephas.json"
    if os.path.exists(sidecar):
        with open(sidecar) as f:
            config = json.load(f)
    return SparkModel(
        model,
        mode=config.get("mode", "asynchronous"),
        frequency=config.get("frequency", "epoch"),
        parameter_server_mode=config.get("parameter_server_mode", "http"),
        num_workers=config.get("num_workers"),
        custom_objects=custom_objects,
        batch_size=config.get("batch_size", 32),
        port=config.get("port", 4000),
        merge=config.get("merge", "auto"),
        comm=config.get("comm"),
        remat=config.get("remat", False),
        compression=config.get("compression"),
    )


class SparkMLlibModel(SparkModel):
    """LabeledPoint-RDD skin (reference ``spark_model.py:~200``)."""

    def fit(self, labeled_points: RDD, epochs: int = 10,
            batch_size: Optional[int] = None, verbose: int = 0,
            validation_split: float = 0.1, categorical: bool = False,
            nb_classes: Optional[int] = None, **kwargs) -> None:
        rdd = lp_to_simple_rdd(labeled_points, categorical, nb_classes)
        batch_size = self.batch_size if batch_size is None else batch_size
        num_workers = self._resolve_num_workers()
        rdd = rdd.repartition(num_workers)
        self._fit(rdd, epochs, batch_size, verbose, validation_split)

    def predict(self, mllib_data):
        """Predict on an MLlib ``Vector``/``Matrix``, returning the same type
        (reference ``spark_model.py:~230``)."""
        if isinstance(mllib_data, DenseMatrix):
            return to_matrix(
                self._master_network.predict(from_matrix(mllib_data), verbose=0)
            )
        if isinstance(mllib_data, DenseVector):
            features = from_vector(mllib_data)[None, :]
            return to_vector(self._master_network.predict(features, verbose=0)[0])
        return super().predict(mllib_data)
