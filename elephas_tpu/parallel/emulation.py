"""CPU-portable host emulation for elastic multi-host training.

CPU JAX cannot run real multiprocess collectives ("Multiprocess computations
aren't implemented on the CPU backend"), so nothing short of a pod could
exercise the elastic control plane — process boundaries, SIGKILL, reconnects
— until this module. It emulates a pod with the pieces that matter for
*robustness* testing being real:

- every "host" is a real OS **process** (spawned here, killed with a real
  ``SIGKILL``), so host death is genuine process death, not a mocked flag;
- hosts talk to the driver over real TCP using the parameter-server framing
  from :mod:`elephas_tpu.utils.sockets` (checksummed v2 frames; the driver
  answers in whatever dialect the worker speaks), so connection loss,
  half-open sockets, corrupt frames, and reconnects behave like the wire;
- the cross-host gradient exchange is a **proxy collective**: each host
  sends its round delta to the driver, which reduces over the membership
  epoch's live set and commits through the versioned parameter-server store
  (:class:`~elephas_tpu.parallel.elastic.ElasticHostPool`). On a real pod
  the same pool drives ``jax.distributed`` instead (``JaxPodBackend``) and
  XLA's DCN collectives replace the proxy — the control plane (membership,
  epochs, fencing, commit log) is identical.

The worker half of this file is deliberately **standalone**: run as a script
(``python .../emulation.py --driver host:port --host-id N``) it loads only
``utils/sockets.py`` by file path — no ``elephas_tpu`` package import, no
JAX/Keras unless the adopted task needs them — so a numpy-task host boots in
well under a second and tier-1 can afford real fleets.

Worker lifecycle (one TCP connection, full duplex):

1. connect (bounded-backoff retry) → send ``hello`` (host id, pid, device
   count);
2. receive ``adopt`` (task spec + config + heartbeat interval) → start the
   beat thread (beats flow even while a round is computing, so a *live*
   slow host never loses its lease — only dead or partitioned ones do);
3. loop: ``round`` → run the task on the shard → send ``contrib`` stamped
   with the round's membership **epoch**; ``sync`` → informational;
   ``stop`` → ``goodbye`` and exit.

A worker never decides liveness or epochs — the driver's registry does.
Stale verdicts (its contrib carried a fenced epoch) reach it only as the
next ``round``/``sync``, exactly like a pod host that missed a mesh
re-formation.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

if __package__:  # imported as elephas_tpu.parallel.emulation
    from ..utils import sockets as _sockets
else:  # run as a standalone worker script: load sockets.py by path
    import importlib.util

    _sockets_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "utils", "sockets.py",
    )
    _spec = importlib.util.spec_from_file_location("_elephas_sockets",
                                                   _sockets_path)
    _sockets = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_sockets)


# --------------------------------------------------------------------------
# Round tasks. Referenced by NAME over the wire ({"builtin": "sgd_task"}) so
# nothing closure-shaped is pickled across the process boundary; a custom
# task ships as {"file": "/abs/path.py", "fn": "name"} and is loaded by path.
# Every task maps (weights, shard, config) -> (delta, metrics) where the
# driver applies ``weights -= delta`` (the parameter-server update rule).
# --------------------------------------------------------------------------

def sgd_task(weights: List[Any], shard: Any, config: Dict[str, Any]):
    """One least-squares SGD round on ``shard = (x, y)``: cheap and exactly
    deterministic — the workhorse of the membership/fencing tests, where
    what is under test is the control plane, not the model."""
    import numpy as np

    (w,) = weights
    x, y = shard
    # Fixed sleep makes a kill land mid-compute deterministically (chaos
    # tests); per-sample sleep emulates compute proportional to the shard,
    # so throughput genuinely scales with host count (elasticity bench).
    pause = float(config.get("sleep_s", 0.0))
    pause += float(config.get("sleep_per_sample_s", 0.0)) * len(x)
    if pause:
        time.sleep(pause)
    x = np.asarray(x, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    resid = x @ w - y
    grad = x.T @ resid / max(1, x.shape[0])
    lr = float(config.get("lr", 0.1))
    loss = float(np.mean(resid ** 2))
    return [lr * grad], {"loss": loss, "samples": int(x.shape[0])}


_KERAS_CACHE: Dict[Any, Any] = {}


def keras_fit_task(weights: List[Any], shard: Any, config: Dict[str, Any]):
    """One local Keras fit round — the ``SparkModel.fit`` elastic worker.

    The replica is rebuilt from the serialized config exactly like
    ``worker.py`` does on the thread paths, cached per config so each host
    process compiles its XLA program once and reuses it across rounds (and
    across mesh re-formations — only the shard changes)."""
    import numpy as np

    os.environ.setdefault("KERAS_BACKEND", "jax")
    import keras

    key = (config["model_json"], repr(config.get("optimizer")),
           repr(config.get("loss")))
    model = _KERAS_CACHE.get(key)
    if model is None:
        model = keras.models.model_from_json(config["model_json"])
        optimizer = config.get("optimizer") or "sgd"
        if isinstance(optimizer, dict):
            optimizer = keras.optimizers.deserialize(dict(optimizer))
        model.compile(optimizer=optimizer, loss=config.get("loss"),
                      metrics=list(config.get("metrics") or []))
        _KERAS_CACHE[key] = model
    x, y = shard
    before = [np.array(w) for w in weights]
    model.set_weights(before)
    history = model.fit(
        np.asarray(x), np.asarray(y),
        epochs=int(config.get("local_epochs", 1)),
        batch_size=int(config.get("batch_size", 32)),
        verbose=0, validation_split=0.0, shuffle=False,
    )
    after = model.get_weights()
    delta = [b - np.asarray(a) for b, a in zip(before, after)]
    losses = history.history.get("loss", [])
    return delta, {
        "loss": float(losses[-1]) if losses else float("nan"),
        "samples": int(np.asarray(x).shape[0]),
    }


def _resolve_task(spec: Dict[str, Any]):
    if "builtin" in spec:
        fn = globals().get(spec["builtin"])
        if fn is None:
            raise ValueError(f"unknown builtin task {spec['builtin']!r}")
        return fn
    import importlib.util

    mod_spec = importlib.util.spec_from_file_location("_elastic_task",
                                                      spec["file"])
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return getattr(module, spec["fn"])


# --------------------------------------------------------------------------
# Worker main
# --------------------------------------------------------------------------

def worker_main(driver: str, host_id: int, devices: int = 1,
                connect_timeout_s: float = 30.0,
                max_frame_bytes: Optional[int] = None) -> int:
    sock = _sockets.connect_with_retry(driver, timeout_s=connect_timeout_s)
    send_lock = threading.Lock()
    rxbuf = _sockets.ReusableBuffer()
    max_frame = (_sockets.DEFAULT_MAX_FRAME_BYTES if max_frame_bytes is None
                 else int(max_frame_bytes))

    def send(msg: Dict[str, Any]) -> None:
        # workers speak checksummed v2 frames (sockets.send default); the
        # driver's bilingual reader answers in kind
        with send_lock:
            _sockets.send(sock, msg)

    send({"op": "hello", "host": host_id, "pid": os.getpid(),
          "devices": int(devices)})
    task_fn = None
    task_config: Dict[str, Any] = {}
    stop_beats = threading.Event()

    def beat_loop(interval_s: float) -> None:
        while not stop_beats.wait(interval_s):
            try:
                send({"op": "beat", "host": host_id})
            except OSError:
                return

    try:
        while True:
            msg = _sockets.receive(sock, rxbuf, max_frame_bytes=max_frame)
            op = msg.get("op")
            if op == "adopt":
                task_fn = _resolve_task(msg["task"])
                task_config = dict(msg.get("config") or {})
                beat = threading.Thread(
                    target=beat_loop,
                    args=(float(msg.get("beat_interval_s", 0.25)),),
                    daemon=True, name=f"beat-host-{host_id}",
                )
                beat.start()
            elif op == "round":
                delta, metrics = task_fn(msg["weights"], msg["shard"],
                                         {**task_config,
                                          **(msg.get("config") or {})})
                send({"op": "contrib", "host": host_id,
                      "epoch": int(msg["epoch"]), "round": int(msg["round"]),
                      "version": int(msg.get("version", -1)),
                      "delta": delta, "metrics": metrics})
            elif op == "sync":
                pass  # informational: carried state arrives with each round
            elif op == "stop":
                send({"op": "goodbye", "host": host_id})
                return 0
            else:
                raise ValueError(f"unknown driver op {op!r}")
    except (ConnectionError, EOFError, OSError) as err:
        # Driver went away: a pod host would be torn down too. Name the
        # cause on stderr so a dead worker is never a silent mystery.
        print(f"[elastic-worker host-{host_id}] connection lost: {err!r}",
              file=sys.stderr)
        traceback.print_exc(file=sys.stderr)
        return 1
    finally:
        stop_beats.set()
        try:
            sock.close()
        except OSError:
            pass


# --------------------------------------------------------------------------
# Emulation backend: spawn/kill real host processes
# --------------------------------------------------------------------------

class EmulationBackend:
    """Launches one worker **process** per emulated host and owns its
    lifecycle: spawn, SIGKILL (chaos), and reaping — no orphan ``Popen``
    survives :meth:`stop_all`, even on the timeout path."""

    name = "emulation"

    def __init__(self, *, devices_per_host: int = 1,
                 python: Optional[str] = None,
                 env: Optional[Dict[str, str]] = None,
                 quiet: bool = True):
        self.devices_per_host = int(devices_per_host)
        self.python = python or sys.executable
        self.extra_env = dict(env or {})
        self.quiet = quiet
        self.procs: Dict[int, subprocess.Popen] = {}

    def _worker_env(self) -> Dict[str, str]:
        env = dict(os.environ)
        # Each emulated host gets its own virtual device count — the point
        # where "device count changes mid-fit" becomes literally true for
        # the fleet — and runs on the CPU: a chip belongs to one process,
        # and the parent that spawns these hosts may be the one holding it.
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{self.devices_per_host}")
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("KERAS_BACKEND", "jax")
        env.update(self.extra_env)
        return env

    def spawn(self, host_id: int, driver_address: str) -> None:
        if host_id in self.procs and self.procs[host_id].poll() is None:
            raise RuntimeError(f"host {host_id} is already running")
        script = os.path.abspath(__file__)
        self.procs[host_id] = subprocess.Popen(
            [self.python, script, "--driver", driver_address,
             "--host-id", str(host_id),
             "--devices", str(self.devices_per_host)],
            env=self._worker_env(),
            stdout=subprocess.DEVNULL if self.quiet else None,
            stderr=subprocess.DEVNULL if self.quiet else None,
        )

    def kill(self, host_id: int) -> None:
        """SIGKILL — real, unhandleable process death (and reap it: a chaos
        test must not leak zombies into the suite)."""
        proc = self.procs.get(host_id)
        if proc is None or proc.poll() is not None:
            return
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)

    def alive(self, host_id: int) -> bool:
        proc = self.procs.get(host_id)
        return proc is not None and proc.poll() is None

    def stop_all(self, grace_s: float = 5.0) -> None:
        """Reap every spawned process: wait out the grace period for workers
        told to stop, then SIGKILL stragglers and ``wait()`` them all."""
        deadline = time.monotonic() + float(grace_s)
        for proc in self.procs.values():
            if proc.poll() is None:
                try:
                    proc.wait(timeout=max(0.05, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    proc.send_signal(signal.SIGKILL)
        for proc in self.procs.values():
            if proc.poll() is None:
                proc.wait(timeout=30)


class JaxPodBackend:
    """The real-pod counterpart: same :class:`ElasticHostPool` API, but
    hosts are ``jax.distributed`` processes instead of emulated ones.

    This backend does not launch machines — pods are provisioned by the
    cluster manager — it owns the *geometry*: the bootstrap each host must
    run, and the re-initialization plan after a membership change
    (``jax.distributed`` has no elastic resize: the coordinator restarts
    with the survivor count and every surviving host re-dials it —
    ``reform()`` returns that dense re-numbering). The control plane above
    (epochs, fencing, the versioned commit log) is shared with emulation,
    which is what lets tier-1 pin its behavior on CPU."""

    name = "jax"

    def __init__(self, coordinator_address: str, *, port: int = 8476,
                 timeout_s: float = 60.0):
        self.coordinator_address = coordinator_address
        self.port = int(port)
        self.timeout_s = float(timeout_s)

    def bootstrap(self, host_id: int, num_processes: int) -> Dict[str, Any]:
        """The ``initialize_cluster`` call host ``host_id`` must make to
        join the current incarnation of the cluster."""
        return {
            "coordinator_address": self.coordinator_address,
            "num_processes": int(num_processes),
            "process_id": int(host_id),
            "timeout_s": self.timeout_s,
        }

    def reform(self, live_hosts: List[int]) -> Dict[str, Any]:
        """Re-formation plan after a membership change: process ids are
        re-numbered densely over the sorted survivors (``jax.distributed``
        requires ids in ``[0, num_processes)``), the lowest survivor hosts
        the restarted coordinator."""
        ordered = sorted(int(h) for h in live_hosts)
        return {
            "coordinator_host": ordered[0] if ordered else None,
            "num_processes": len(ordered),
            "process_ids": {h: i for i, h in enumerate(ordered)},
        }


def _main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description="elastic emulation worker")
    parser.add_argument("--driver", required=True, help="driver host:port")
    parser.add_argument("--host-id", type=int, required=True)
    parser.add_argument("--devices", type=int, default=1)
    args = parser.parse_args(argv)
    return worker_main(args.driver, args.host_id, devices=args.devices)


if __name__ == "__main__":
    sys.exit(_main())
