#!/usr/bin/env python3
"""Compile each cell's programs at real widths for a described v5e, and
print what the compiler says they need. Costs no chip time.

    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py [--workload NAME]
        [--layers N] [--rows N] [--slots N]

For a training cell: the whole ``build_lm_train_step`` program on one chip
(or on a four-device data mesh). For a serving cell: the largest prefill
insert and the batched decode step of ``ServingEngine``. The overrides try
another depth, rows per chip or slot count without editing a file; what is
settled is then written into the configuration file by hand. A compile
that passes is not a chip run: it counts one program, not what else the
process keeps on the device. Run by hand, not a test.
"""

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("KERAS_BACKEND", "jax")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GB = 2.0 ** 30     # the compiler counts in GiB; a v5e offers it 15.75


def report(label, compiled):
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"{label}: COMPILED. arguments {m.argument_size_in_bytes / GB:.2f} "
          f"GiB, outputs {m.output_size_in_bytes / GB:.2f} GiB (aliased to "
          f"arguments {m.alias_size_in_bytes / GB:.2f}), temporaries "
          f"{m.temp_size_in_bytes / GB:.2f} GiB -> at most {total / GB:.2f} "
          "GiB for this one program", flush=True)
    return total


def weight_shapes(model, cfg, sharding):
    import jax
    import jax.numpy as jnp

    f32 = set(cfg["weights"]["float32_leaves"])
    return {k: jax.ShapeDtypeStruct(
        v.shape, jnp.float32 if k in f32 else jnp.dtype(cfg["weights"]["dtype"]),
        sharding=sharding) for k, v in model.param_shapes().items()}


def rehearse_train(model, cfg, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elephas_tpu import models as M

    tr = cfg["train"]
    mesh = M.build_mesh_sp(data=len(devices), seq=1, devices=devices)
    optimizer = getattr(M, tr["optimizer"])(tr["learning_rate"])
    step, opt_init = M.build_lm_train_step(
        model, mesh, optimizer, attn=tr["attn"], **tr["step_kwargs"])
    rep = NamedSharding(mesh, P())
    params = weight_shapes(model, cfg, rep)
    state = jax.eval_shape(opt_init, params)
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), state)
    rows = tr["rows_per_chip"] * len(devices)
    tok = jax.ShapeDtypeStruct((rows, tr["sequence_length"]), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", "seq")))
    compiled = step.lower(params, state, tok, tok, tok).compile()
    total = report(f"train step, {len(devices)} chip(s), depth "
                   f"{model.n_layers}, {tr['rows_per_chip']} rows x "
                   f"{tr['sequence_length']} per chip", compiled)
    text = compiled.as_text()
    print("  all-reduce ops in the program:", text.count(" all-reduce("),
          "+", text.count("all-reduce-start("), "async;",
          "Mosaic custom calls:", text.count("tpu_custom_call"))
    return total


def rehearse_serve(model, cfg, device):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from elephas_tpu.serving.cache import _insert_kernel
    from elephas_tpu.serving.engine import _decode_kernel

    eng = cfg["engine"]
    one = SingleDeviceSharding(device)
    params = weight_shapes(model, cfg, one)
    cache = jax.eval_shape(
        lambda: model.init_cache(eng["n_slots"], length=eng["max_len"]))
    cache = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
             for k, v in cache.items()}
    kv = sum(v.size * v.dtype.itemsize for v in cache.values())
    wt = sum(v.size * v.dtype.itemsize for v in params.values())
    print(f"weights {wt / GB:.2f} GiB, KV cache {kv / GB:.2f} GiB "
          f"({eng['n_slots']} slots x {cache['k'].shape[3]} tokens)")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    bucket = 2048
    scalar = sds((), jnp.int32)
    worst = report(
        f"prefill insert, bucket {bucket}",
        _insert_kernel.lower(model, params, cache, sds((1, bucket), jnp.int32),
                             scalar, scalar, scalar).compile())
    s = eng["n_slots"]
    worst = max(worst, report(
        "decode step",
        _decode_kernel.lower(model, params, cache, sds((s,), jnp.int32),
                             sds((s,), jnp.int32), sds((s,), jnp.float32),
                             sds((s, 2), jnp.uint32), sds((s,), jnp.bool_)
                             ).compile()))
    return worst


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--slots", type=int)
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies

    from benchmark.manifest import Manifest

    # The program's Pallas dispatchers ask jax.default_backend() and would
    # take their jax.numpy branch here, on the CPU. The rehearsal compiles
    # for the described TPU, so it answers for it (steered here, in the
    # script, not through an option of the program).
    jax.default_backend = lambda: "tpu"

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    man = Manifest()
    for cell in man.data["workloads"]:
        if args.workload and cell["name"] != args.workload:
            continue
        cfg = man.config(cell["config"])
        if args.layers:
            cfg["num_hidden_layers"] = args.layers
        if args.rows and "train" in cfg:
            cfg["train"]["rows_per_chip"] = args.rows
        if args.slots and "engine" in cfg:
            cfg["engine"]["n_slots"] = args.slots
        model = man.module("families", cfg["family"]).build_model(cfg)
        print(f"=== {cell['name']} ({cell['chips']} chip(s))", flush=True)
        if "train" in cfg:
            total = rehearse_train(model, cfg,
                                   list(topo.devices[:cell["chips"]]))
        else:
            total = rehearse_serve(model, cfg, topo.devices[0])
        print("  every program compiled, so each fits the chip alone (the "
              "compiler raises RESOURCE_EXHAUSTED otherwise); largest "
              f"{total / GB:.2f} of 15.75 GiB\n", flush=True)


if __name__ == "__main__":
    main()
