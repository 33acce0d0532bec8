#!/usr/bin/env python3
"""The ``flash_decode`` kernel alone on the chip: the copy of a row's last
cache block whole against the copy of its live rows only, at a serving
cell's shapes and over positions drawn from that cell's traffic.

    python scripts/flash_decode_tail.py [--shape ouro|olmo] [--iters 96]
        [--sets 8] [--seed 0] [--granules 0,16,32]

``--shape ouro``: the Ouro cell's decode call, B 24, Hkv 16, G 1, Dh 128,
T 1,024, a stacked bf16 cache of 48 cache layers (12 layers x 4 passes)
and a layer index, positions from ``eval-closed-30``. ``--shape olmo``:
the Olmo-Hybrid cell's, B 192, Hkv 30, G 1, two full layers, positions
from ``chat-closed-240``. Each row's position is drawn uniformly from
every decode position of the mix's request pool (a request of prompt p
and answer a decodes at p .. p + a - 1), ``--sets`` sets of B rows.

Each granule is a form of the kernel (0: whole blocks, the copy before
the trim; the kernel's own is ``_TAIL_GRANULE``, which the script sets
for each form before it traces it). Every form runs on the same inputs;
its output and lse are compared with the whole-block form's element for
element. Its time is
the device events of ``--iters`` calls under the profiler, each call on
the next layer and the next set of positions: the mean and median us a
call, us a visit (a block of all KV heads), and the live bytes (K and V
of positions 0 .. pos of every row) over the mean time at 819 GB/s.
Prints one JSON line. Needs a TPU.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {
    "ouro": dict(B=24, Hkv=16, G=1, Dh=128, T=1024, L=48,
                 traffic="eval-closed-30"),
    "olmo": dict(B=192, Hkv=30, G=1, Dh=128, T=1024, L=2,
                 traffic="chat-closed-240"),
}
HBM_BYTES_PER_S = 819e9


def traffic_positions(name: str, rows: int, sets: int, seed: int):
    """``[sets, rows]`` decode positions drawn from mix ``name``'s pool."""
    import numpy as np

    from benchmark.traffic import closed_loop_pool

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    pairs = closed_loop_pool(mix, seed)
    every = np.concatenate([np.arange(p, p + a) for p, a in pairs])
    rng = np.random.default_rng([seed, 42])
    return rng.choice(every, size=(sets, rows))


def kernel_durations_us(fn, calls, name: str):
    """Device time of kernel ``name`` in each of ``calls`` (argument
    tuples of ``fn``), in us, in call order."""
    import jax

    from benchmark.trace import find_xplane, load_xplane

    jax.block_until_ready(fn(*calls[0]))          # compile outside the trace
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for args in calls:
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        device_ops, _ = load_xplane(find_xplane(d))
    # an event's name is its HLO text: `%flash_decode.1 = (...) custom-call(`
    events = sorted((s, e) for n, s, e in device_ops[sorted(device_ops)[0]]
                    if n.split(" = ")[0].lstrip("%").split(".")[0] == name)
    if len(events) != len(calls):
        raise RuntimeError(f"{name}: {len(events)} events for {len(calls)} "
                           "calls")
    return [(e - s) * 1e6 for s, e in events]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="ouro")
    ap.add_argument("--granules", default="0,16,32")
    for k, v in (("iters", 96), ("sets", 8), ("seed", 0)):
        ap.add_argument(f"--{k}", type=int, default=v)
    a = ap.parse_args()

    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    # the module (``elephas_tpu.ops.flash_decode`` names the function)
    fd = importlib.import_module("elephas_tpu.ops.flash_decode")

    if jax.default_backend() != "tpu":
        sys.exit("needs a TPU")
    s = SHAPES[a.shape]
    B, Hkv, G, Dh, T, L = (s[k] for k in ("B", "Hkv", "G", "Dh", "T", "L"))
    bt = fd._block_t(T)
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(a.seed), 3)

    @jax.jit
    def stack(key):
        # one random layer, shifted a little a layer: no float32 copy of
        # the whole stack is ever made
        one = jax.random.normal(key, (B, Hkv, T, Dh), bf)
        shift = (jnp.arange(L, dtype=jnp.float32) / L).astype(bf)
        return one[None] + shift.reshape(L, 1, 1, 1, 1)

    q = jax.random.normal(keys[0], (B, Hkv, G, Dh), bf)
    k, v = stack(keys[1]), stack(keys[2])
    pos_sets = traffic_positions(s["traffic"], B, a.sets, a.seed)
    pos_dev = [jnp.asarray(p, jnp.int32) for p in pos_sets]
    # the caches are arguments: a jitted function that closed over them
    # would embed them in the program as constants
    calls = [(q, k, v, pos_dev[i % a.sets], jnp.int32(i % L))
             for i in range(a.iters)]
    per_set = [int(fd.kv_block_walk(p, T)[1].sum()) for p in pos_sets]
    visits = sum(per_set[i % a.sets] for i in range(a.iters))
    live_bytes = sum(int((pos_sets[i % a.sets] + 1).sum())
                     for i in range(a.iters)) * 2 * Hkv * Dh * 2

    forms = [(f"granule{g}" if g else "whole", g or None)
             for g in (int(x) for x in a.granules.split(","))]

    out = {"device": jax.devices()[0].device_kind, "shape": a.shape,
           "dims": dict(B=B, Hkv=Hkv, G=G, Dh=Dh, T=T, L=L, bt=bt,
                        dtype="bfloat16"),
           "traffic": s["traffic"],
           "mean_pos": float(np.mean(pos_sets)),
           "visits_per_call": visits / a.iters, "forms": {}}
    ref = None
    for label, granule in forms:
        # the kernel reads its granule when it is traced
        fd._TAIL_GRANULE = granule
        fn = jax.jit(lambda q, k, v, p, l: fd.flash_decode_lse(
            q, k, v, p, layer=l))
        got = [np.asarray(x) for x in fn(q, k, v, pos_dev[0],
                                          jnp.int32(L - 1))]
        ref = got if ref is None else ref
        durs = kernel_durations_us(fn, calls, "flash_decode")
        mean = statistics.fmean(durs)
        copied = sum(int(fd.kv_copied_positions(
            pos_sets[i % a.sets], T, granule=granule).sum())
            for i in range(a.iters))
        out["forms"][label] = {
            "us_per_call_mean": round(mean, 3),
            "us_per_call_median": round(statistics.median(durs), 3),
            "us_per_visit": round(sum(durs) / visits, 4),
            "live_roofline_pct": round(
                100 * live_bytes / (sum(durs) * 1e-6) / HBM_BYTES_PER_S, 2),
            "copied_pct_of_walked": round(
                100 * copied / (visits * bt), 2),
            "equal_to_whole": all(np.array_equal(x, y)
                                  for x, y in zip(got, ref)),
        }
    base = out["forms"]["whole"]["us_per_call_mean"] if "whole" in \
        out["forms"] else None
    if base:
        for f in out["forms"].values():
            f["saved_pct"] = round(100 * (1 - f["us_per_call_mean"] / base),
                                   2)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
