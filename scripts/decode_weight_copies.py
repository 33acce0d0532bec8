#!/usr/bin/env python3
"""What a serving cell's decode and insert programs do to a weight before
they multiply it. Compiles for a described v5e; costs no chip time.

    JAX_PLATFORMS=cpu python scripts/decode_weight_copies.py CELL [CELL ...]
        [--bucket N]

For each cell of ``BENCHMARK.json`` that serves: ``_decode_kernel`` and
``_insert_kernel`` are compiled at the configuration's real widths the way
``benchmark/rehearse_compile.py`` does (weights and cache as shapes only),
and every operation of the compiled program that WRITES a result at least
as large as the model's smallest projection matrix is printed with its
scope path. Left out: operations inside a Mosaic call, operations that
write nothing (parameters, tuples, bitcasts, the loops themselves), and
results of the shape of a cache stack, which the donated cache takes in
place. A ``copy`` or ``*slice*fusion`` of a weight's size in that list is
a re-layout the step pays for every time (PERF.md §6, PR 35);
``copy-start`` / ``slice-start`` are the compiler's asynchronous prefetches
of a layer's weights and cost the step nothing it would not read anyway.
"""

import argparse
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("KERAS_BACKEND", "jax")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_ITEM = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
         "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
         "u64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(_ITEM) + r")\[([0-9,]*)\]")
# `%name = <result type> opcode(operands...), attributes`
_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_WRITES_NOTHING = {"parameter", "get-tuple-element", "tuple", "bitcast",
                   "while", "conditional", "call", "constant", "copy-done",
                   "slice-done", "async-done", "optimization-barrier"}


def result_bytes(result_type: str) -> int:
    """The largest array of an HLO result type (a tuple's largest leaf)."""
    best = 0
    for dtype, dims in _SHAPE.findall(result_type):
        n = _ITEM[dtype]
        for d in filter(None, dims.split(",")):
            n *= int(d)
        best = max(best, n)
    return best


def written_results(text: str, at_least: int, in_place_shapes=()):
    """``[(bytes, opcode, name, result type, scope path)]`` of the
    operations in compiled HLO ``text`` that write ``at_least`` bytes,
    outside fused computations (a fusion is one operation: what it writes
    is its own result) and outside Mosaic calls."""
    bodies = set(re.findall(r" fusion\(.*calls=%?([\w.\-]+)", text))
    out, fused = [], False
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            fused = head.group(1) in bodies
            continue
        m = _OP.match(line)
        if not m or fused:
            continue
        name, rtype, opcode, rest = m.groups()
        if opcode in _WRITES_NOTHING or "tpu_custom_call" in rest \
                or 'custom_call_target="ConcatBitcast"' in rest:
            continue
        n = result_bytes(rtype)
        if n < at_least:
            continue
        if any(s in rtype for s in in_place_shapes):
            continue
        scope = re.search(r'op_name="([^"]*)"', rest)
        path = scope.group(1) if scope else ""
        path = re.sub(r"^jit\(\w+\)/", "", path)
        kind = re.search(r"kind=(k\w+)", rest)
        out.append((n, opcode + (f"[{kind.group(1)}]" if kind else ""),
                    name, rtype.strip(), path))
    return out


def is_relayout(opcode: str, name: str) -> bool:
    """A ``copy`` or a slice fusion: the two forms a weight's re-layout
    takes in a compiled program (never the asynchronous ``-start``s)."""
    if opcode.endswith("-start"):
        return False
    return opcode == "copy" or name.startswith("copy") or (
        opcode.startswith("fusion") and "slice" in name
        and "update-slice" not in name)       # that one writes in place


def hlo_shape(shape, dtype) -> str:
    import numpy as np
    short = {"bfloat16": "bf16", "float32": "f32", "float16": "f16",
             "int32": "s32", "int8": "s8"}[np.dtype(dtype).name]
    return f"{short}[{','.join(str(d) for d in shape)}]"


def serve_programs(model, cfg, device, bucket: int):
    """``{"decode": text, "insert": text}`` compiled for ``device``, and
    the shapes of the cache's stacks as HLO writes them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.rehearse_compile import weight_shapes
    from elephas_tpu.serving.cache import _insert_kernel
    from elephas_tpu.serving.engine import _decode_kernel

    eng = cfg["engine"]
    one = SingleDeviceSharding(device)
    params = weight_shapes(model, cfg, one)
    cache = jax.eval_shape(
        lambda: model.init_cache(eng["n_slots"], length=eng["max_len"]))
    cache = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
             for k, v in cache.items()}

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    s, scalar = eng["n_slots"], sds((), jnp.int32)
    bucket = min(bucket, eng["max_len"] // 2)  # a prompt leaves room to decode
    texts = {
        "decode": _decode_kernel.lower(
            model, params, cache, sds((s,), jnp.int32), sds((s,), jnp.int32),
            sds((s,), jnp.float32), sds((s, 2), jnp.uint32),
            sds((s,), jnp.bool_)).compile().as_text(),
        "insert": _insert_kernel.lower(
            model, params, cache, sds((1, bucket), jnp.int32), scalar, scalar,
            scalar).compile().as_text(),
    }
    return texts, [hlo_shape(v.shape, v.dtype) for v in cache.values()]


def smallest_projection(model, cfg) -> int:
    """Bytes of one layer of the smallest stacked matrix whose result a
    cached forward splits into heads, in the type the cell serves in."""
    import numpy as np
    import jax.numpy as jnp
    shapes = model.param_shapes()
    width = jnp.dtype(cfg["weights"]["dtype"]).itemsize
    return min(int(np.prod(shapes[k].shape[1:])) * width
               for k in ("wq", "wk", "wv", "wq_b", "wkv_b", "lin_z")
               if k in shapes)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workloads", nargs="+")
    ap.add_argument("--bucket", type=int, default=2048,
                    help="the insert program's prompt bucket (at most half "
                    "the cell's horizon)")
    args = ap.parse_args()

    import jax
    from jax.experimental import topologies

    from benchmark.manifest import Manifest

    # compiled for the described TPU, so the Pallas dispatchers take their
    # kernels (benchmark/rehearse_compile.py says why it is steered here)
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    man = Manifest()
    for name in args.workloads:
        cfg = man.config(man.cell(name)["config"])
        if "engine" not in cfg:
            print(f"=== {name}: a training cell, no cached forward\n")
            continue
        model = man.module("families", cfg["family"]).build_model(cfg)
        floor = smallest_projection(model, cfg)
        texts, stacks = serve_programs(model, cfg, topo.devices[0],
                                       args.bucket)
        for which, text in texts.items():
            rows = written_results(text, floor, stacks)
            bad = [r for r in rows if is_relayout(r[1], r[2])]
            print(f"=== {name} {which}: {len(rows)} operation(s) write "
                  f">= {floor / 1e6:.1f} MB outside Mosaic calls and the "
                  f"cache; {len(bad)} copy / slice fusion(s), "
                  f"{sum(r[0] for r in bad) / 1e6:.1f} MB written")
            # one line for the operations that differ only in their number
            seen = {}
            for n, opcode, op, rtype, path in rows:
                key = (n, opcode, re.sub(r"[.\d]+$", "", op), rtype, path)
                seen[key] = seen.get(key, 0) + 1
            for (n, opcode, op, rtype, path), count in seen.items():
                mark = "  <-- re-layout" if is_relayout(opcode, op) else ""
                print(f"  {count:3d} x {n / 1e6:7.1f} MB  {opcode:<18} "
                      f"{op:<24} {rtype}  [{path}]{mark}")
        print(flush=True)


if __name__ == "__main__":
    main()
