#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

looks the cell up in ``BENCHMARK.json``, loads its configuration and its
traffic mix, runs the mix's driver in this one process on the TPU it finds,
and prints as its last line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` in a traced run).
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics. Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result. See ``benchmark/README.md``.
"""

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse                    # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import sys                         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("KERAS_BACKEND", "jax")


class Ctx:
    """What a driver is given: the cell's data, the seed, the devices, the
    compile meter, and ``log`` for lines that name the device."""

    def __init__(self, manifest, workload, seed, seconds, trace, devices,
                 t_start, out_dir):
        from benchmark.meter import CompileMeter

        self.manifest = manifest
        self.cell = manifest.cell(workload)
        self.cfg = manifest.config(self.cell["config"])
        self.mix = manifest.traffic(self.cell["traffic"])
        self.seed, self.seconds, self.trace = int(seed), float(seconds), \
            bool(trace)
        self.devices = list(devices)
        self.t_start = t_start
        self.out_dir = out_dir
        self.meter = CompileMeter()
        d = self.devices[0]
        self.tag = f"[{d.platform}/{d.device_kind} x{len(self.devices)}]"

    def log(self, *args):
        print(self.tag, *args, flush=True)


def run_cell(manifest, workload, seed, seconds, trace, devices,
             t_start=None, out_dir=None) -> dict:
    """Run one cell on ``devices`` and return the object of the last line.
    ``main`` calls it on the TPU; tests call it in-process at tiny widths."""
    ctx = Ctx(manifest, workload, seed, seconds, trace, devices,
              time.perf_counter() if t_start is None else t_start,
              out_dir or os.path.join(manifest.root, ".bench_out"))
    ctx.log(f"cell {workload}: configuration {ctx.cfg['name']}, traffic "
            f"{ctx.mix['name']} (driver {ctx.mix['driver']}), seed "
            f"{ctx.seed}, {ctx.seconds:g} s, trace {int(ctx.trace)}")
    result = manifest.module("drivers", ctx.mix["driver"]).run(ctx)

    group = "per_layer" if ctx.trace else "end_to_end"
    metrics = {}
    for m in manifest.metrics_for(workload, group):
        if ctx.trace:
            value = manifest.module("layer_metrics", m["name"]).read(
                result["facts"])
        else:
            value = result["end_to_end"].get(m["name"])
        if value is None:
            ctx.log(f"metric {m['name']}: nothing to read, left out")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    stats_ = [d.memory_stats() or {} for d in ctx.devices]
    d0 = ctx.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": max(
                  int(s.get("peak_bytes_in_use", 0)) for s in stats_)}
    last = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    summary = result["facts"].get("trace")
    if ctx.trace and summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        last["breakdown"] = summary["breakdown"]
    ctx.log(f"set-up {result['end_to_end']['setup_s']:.2f} s, of which "
            f"{ctx.meter.compile_s:.2f} s tracing, lowering and compiling "
            f"(cache: {ctx.meter.hits} hits, {ctx.meter.writes} written)")
    return last


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)

    import jax

    # the program's own placement rule: JAX_COMPILATION_CACHE_DIR if set,
    # else the fixed <checkout>/.jax_cache; small programs are kept too
    from harness_env import place_compile_cache

    place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"benchmark: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              "device(s). No result.", file=sys.stderr)
        return 3
    last = run_cell(manifest, args.workload, args.seed, args.seconds,
                    args.trace, devices[:cell["chips"]], t_start=T_START)
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
