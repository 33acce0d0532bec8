"""ZeRO-3 / FSDP: fully-sharded data parallelism over the ``"data"`` axis.

EXTENSION BEYOND THE REFERENCE. The reference replicates the complete model
in every executor (SURVEY.md §2.3: "ZeRO/FSDP sharding" explicitly absent),
so per-worker memory holds params + grads + optimizer state in full. This
module shards all three over the SAME data axis that carries the batch
(Rajbhandari et al. 2020, ZeRO stage 3; torch FSDP; flax's
``fully_sharded_data_parallel`` idiom):

- **at rest**: ALL parameters are concatenated into one flat buffer, padded
  to a multiple of P, and stored as ``[P, chunk]`` — each device keeps one
  row. Optimizer state is built over the chunk, so it is sharded the same
  way. Per-device memory for params+grads+opt state drops by ``P×``.
- **in compute**: exactly ONE ``all_gather`` per step (the single flat
  buffer — not one per parameter) reassembles full params from the chunks
  over ICI, the local microbatch computes grads against the FULL params, and
  the AD transpose of that gather is exactly ONE ``psum_scatter`` that both
  sums gradients across devices AND hands each device only its own chunk —
  the classic all_gather/reduce_scatter pair, same bytes on the wire as
  plain DP's one all-reduce.
- **update**: the optimizer steps on the local chunk only (1/P of the work).

The schedule is EXACTLY equivalent to replicated gradient-synchronous
DP-SGD for ELEMENTWISE optimizer transforms (sgd, momentum, adam, rmsprop,
…) — same math, different layout — which ``tests/parallel/test_fsdp.py``
verifies against a dense single-device oracle (losses + trajectories).

LIMITATION — non-elementwise transforms: anything that reduces ACROSS the
parameter vector (e.g. ``optax.clip_by_global_norm``) sees only the local
``1/P`` chunk inside ``shard_map`` and would compute a per-shard "global"
norm; compose such transforms yourself with an explicit ``psum`` or keep
them out of the FSDP optimizer. Padding tail entries are zero-gradient and
never feed compute, which is likewise harmless only for elementwise
transforms.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS
from .param_utils import make_opt_init, opt_state_specs

FLAT_KEY = "flat"


class FSDPParams:
    """Chunked ⇄ dense views of a named param dict over a mesh axis.

    ``shapes`` maps name → full shape. All params flatten into ONE
    concatenated buffer (offset table kept here), zero-padded to a multiple
    of the axis size and split into ``[P, chunk]`` rows; the chunked
    representation is the single-key dict ``{"flat": [P, chunk]}`` (a dict so
    optimizer-state sharding specs can key on the tree path).
    """

    def __init__(self, shapes: Dict[str, Tuple[int, ...]], n_shards: int):
        self.n_shards = int(n_shards)
        self.shapes = {k: tuple(s) for k, s in shapes.items()}
        self.sizes = {k: int(np.prod(s)) if s else 1 for k, s in self.shapes.items()}
        self.offsets: Dict[str, int] = {}
        off = 0
        for k, n in self.sizes.items():
            self.offsets[k] = off
            off += n
        self.total = off
        self.padded = int(math.ceil(self.total / self.n_shards) * self.n_shards)
        self.chunk = self.padded // self.n_shards

    def chunk_host(self, params: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Full host params → ``{"flat": [P, chunk]}`` host array."""
        if set(params) != set(self.shapes):
            raise ValueError(
                f"param keys {sorted(params)} != layout keys "
                f"{sorted(self.shapes)}"
            )
        flat = np.zeros((self.padded,), np.float32)
        for k, v in params.items():
            o = self.offsets[k]
            flat[o:o + self.sizes[k]] = np.asarray(v, np.float32).reshape(-1)
        return {FLAT_KEY: flat.reshape(self.n_shards, self.chunk)}

    def unchunk_host(self, chunks: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """``{"flat": [P, chunk]}`` host array → full host params."""
        flat = np.asarray(chunks[FLAT_KEY]).reshape(-1)
        return {
            k: flat[o:o + self.sizes[k]].reshape(self.shapes[k])
            for k, o in self.offsets.items()
        }

    def shard(self, mesh: Mesh, chunks: Dict[str, Any]) -> Dict[str, Any]:
        """Place the chunked buffer on the mesh, rows sharded over ``"data"``."""
        sharding = NamedSharding(mesh, P(DATA_AXIS))
        return {k: jax.device_put(v, sharding) for k, v in chunks.items()}

    # -- inside shard_map -------------------------------------------------
    def gather(self, local_chunks: Dict[str, Any],
               axis_name: str = DATA_AXIS) -> Dict[str, Any]:
        """Local ``{"flat": [1, chunk]}`` → FULL dense params: ONE
        all_gather, then views into the gathered buffer. Differentiating
        through this is ONE ``psum_scatter`` (shard_map's all_gather
        transpose) delivering summed, chunked gradients."""
        flat = jax.lax.all_gather(local_chunks[FLAT_KEY][0], axis_name,
                                  tiled=True)
        return {
            k: jax.lax.dynamic_slice_in_dim(
                flat, o, self.sizes[k]
            ).reshape(self.shapes[k])
            for k, o in self.offsets.items()
        }


def build_fsdp_train_step(apply_fn: Callable, shapes: Dict[str, Tuple[int, ...]],
                          mesh: Mesh, optimizer, per_sample_loss,
                          remat: bool = False):
    """Compile one ZeRO-3 training step for a functional model.

    ``apply_fn(params, x) -> y_pred`` consumes FULL dense params (any model
    written against plain named params works unchanged — sharding is purely
    a storage-layout concern). ``optimizer`` must be elementwise — see the
    module docstring's LIMITATION note. Returns ``(step, opt_init, fsdp)``:

    - ``fsdp`` — the :class:`FSDPParams` layout (chunk/unchunk/shard).
    - ``opt_init(sharded_chunks) -> opt_state`` — state over the chunk,
      sharded identically.
    - ``step(chunks, opt_state, x, y) -> (chunks, opt_state, loss)`` —
      ``x``/``y`` sharded over ``"data"``; one all_gather + one
      psum_scatter per step, regardless of how many named params exist.
    """
    fsdp = FSDPParams(shapes, mesh.shape[DATA_AXIS])
    chunk_spec = {FLAT_KEY: P(DATA_AXIS)}
    chunk_shaped = {
        FLAT_KEY: jax.ShapeDtypeStruct((fsdp.n_shards, fsdp.chunk),
                                       jnp.float32)
    }
    # Chunk-shaped state leaves shard with the chunk; scalar bookkeeping
    # (step counts) replicates.
    sspecs = opt_state_specs(optimizer, chunk_shaped, chunk_spec)
    data_spec = P(DATA_AXIS)

    def step_impl(chunks, opt_state, x, y):
        def loss_fn(ch):
            full = fsdp.gather(ch)
            y_pred = apply_fn(full, x)
            return jnp.sum(per_sample_loss(y, y_pred))

        if remat:
            loss_fn = jax.checkpoint(loss_fn)
        local_loss, grads = jax.value_and_grad(loss_fn)(chunks)
        # Differentiating through gather() IS the reduce-scatter: shard_map
        # transposes all_gather to psum_scatter, so `grads` arrives chunked
        # and already summed across devices. Normalize to the global mean:
        n = jax.lax.psum(jnp.asarray(x.shape[0], jnp.float32), DATA_AXIS)
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        loss = jax.lax.psum(local_loss, DATA_AXIS) / n
        updates, opt_state = optimizer.update(grads, opt_state, chunks)
        chunks = jax.tree_util.tree_map(jnp.add, chunks, updates)
        return chunks, opt_state, loss

    step = jax.jit(
        shard_map(
            step_impl, mesh=mesh,
            in_specs=(chunk_spec, sspecs, data_spec, data_spec),
            out_specs=(chunk_spec, sspecs, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )
    return step, make_opt_init(optimizer, mesh, sspecs), fsdp
