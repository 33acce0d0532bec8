#!/usr/bin/env python3
"""The flash training kernels alone on the chip: the backward's two forms
timed and compared, and the forward timed with and without what each
visible tile pair adds to it.

    python scripts/flash_bwd_forms.py [--b 2] [--h 32] [--hkv 8] [--t 4096]
        [--dh 128] [--iters 20] [--seed 0]

The defaults are the train cells' attention (bf16, rope, causal, 512-row
tiles). The one-pass backward (``flash_bwd_dkv`` accumulating dq beside
dk and dv) and the separate ``flash_bwd_dq`` and ``flash_bwd_dkv`` kernels
run on the same inputs; their dq, dk and dv are compared element for
element. Each kernel's time is the median over ``--iters`` calls of its
device events in a profiler trace, and is given in microseconds per
visible tile pair. The forward is timed as the cells run it (rope,
causal), without rope (k is not rotated on every visit), and without
rope and causality (no mask on any tile). Prints one JSON line. Needs a
TPU.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def visible_pairs(nq: int, nk: int, bq: int, bk: int, causal: bool) -> int:
    if not causal:
        return nq * nk
    return sum(1 for i in range(nq) for j in range(nk)
               if j * bk <= i * bq + bq - 1)


def kernel_us(fn, args, iters: int, names):
    """Median per-call device time of each kernel in ``names`` (us)."""
    import jax

    from benchmark.trace import find_xplane, load_xplane

    jax.block_until_ready(fn(*args))          # compile outside the trace
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        device_ops, _ = load_xplane(find_xplane(d))
    # an event's name is its HLO text: `%flash_bwd_dkv.1 = (...) custom-call(`
    ops = [(n.split(" = ")[0].lstrip("%").split(".")[0], s, e)
           for n, s, e in device_ops[sorted(device_ops)[0]]]
    times = {}
    for name in names:
        durs = [(e - s) * 1e6 for n, s, e in ops if n == name]
        if len(durs) != iters:
            raise RuntimeError(f"{name}: {len(durs)} events for {iters} "
                               f"calls among {sorted({n for n, _, _ in ops})}")
        times[name] = statistics.median(durs)
    return times


def main():
    ap = argparse.ArgumentParser()
    for k, v in (("b", 2), ("h", 32), ("hkv", 8), ("t", 4096), ("dh", 128),
                 ("iters", 20), ("seed", 0)):
        ap.add_argument(f"--{k}", type=int, default=v)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from elephas_tpu.models.transformer import _rope_angles
    from elephas_tpu.ops import pallas_flash as F

    if jax.default_backend() != "tpu":
        sys.exit("needs a TPU")
    bq = bk = F._BQ
    keys = jax.random.split(jax.random.PRNGKey(a.seed), 4)
    bf = jnp.bfloat16
    q = jax.random.normal(keys[0], (a.b, a.h, a.t, a.dh), bf)
    k = jax.random.normal(keys[1], (a.b, a.hkv, a.t, a.dh), bf)
    v = jax.random.normal(keys[2], (a.b, a.hkv, a.t, a.dh), bf)
    do = jax.random.normal(keys[3], (a.b, a.h, a.t, a.dh), bf)
    pos = jnp.broadcast_to(jnp.arange(a.t), (a.b, a.t))
    tables = F.make_rope_tables(*_rope_angles(pos, a.dh))
    nq, nk = -(-a.t // bq), -(-a.t // bk)
    causal_visits = a.b * a.h * visible_pairs(nq, nk, bq, bk, True)
    full_visits = a.b * a.h * nq * nk

    fwd = jax.jit(lambda q, k, v, c2, s2: F._flash_fwd_tpu(
        q, k, v, True, bq, bk, False, rope=(c2, s2)))
    o, lse = fwd(q, k, v, *tables)

    out = {"device": jax.devices()[0].device_kind,
           "shape": dict(B=a.b, H=a.h, Hkv=a.hkv, T=a.t, Dh=a.dh, bq=bq,
                         bk=bk, dtype="bfloat16"),
           "visits_causal": causal_visits, "visits_full": full_visits,
           "one_pass_chosen": F._one_pass_bwd(nq * bq, a.dh, bf)}

    # the backward, each form traced on its own
    chooser = F._one_pass_bwd
    grads, bwd_us = {}, {}
    for form, one_pass in (("one_pass", True), ("two_kernels", False)):
        F._one_pass_bwd = lambda *x, one_pass=one_pass: one_pass
        fn = jax.jit(lambda q, k, v, o, lse, do, c2, s2: F._flash_bwd_tpu(
            q, k, v, o, lse, do, True, bq, bk, False, rope=(c2, s2)))
        grads[form] = [np.asarray(g, np.float32)
                       for g in fn(q, k, v, o, lse, do, *tables)]
        names = ["flash_bwd_dkv"] + ([] if one_pass else ["flash_bwd_dq"])
        t = kernel_us(fn, (q, k, v, o, lse, do, *tables), a.iters, names)
        bwd_us[form] = {n: round(x, 1) for n, x in t.items()}
        bwd_us[form]["us_per_visit"] = round(sum(t.values()) / causal_visits,
                                             4)
    F._one_pass_bwd = chooser
    out["backward_us"] = bwd_us
    out["one_pass_vs_two_kernels"] = {
        n: {"equal": bool(np.array_equal(x, y)),
            "n_differ": int(np.sum(x != y)),
            "max_abs_diff": float(np.max(np.abs(x - y))),
            "max_abs": float(np.max(np.abs(y)))}
        for n, x, y in zip(("dq", "dk", "dv"), grads["one_pass"],
                           grads["two_kernels"])}

    # the forward: as the cells run it, without rope, without causality
    fwd_us = {}
    for label, causal, rope in (("rope_causal", True, True),
                                ("causal", True, False),
                                ("full", False, False)):
        fn = jax.jit(lambda q, k, v, c2, s2, causal=causal, rope=rope:
                     F._flash_fwd_tpu(q, k, v, causal, bq, bk, False,
                                      rope=(c2, s2) if rope else None))
        t = kernel_us(fn, (q, k, v, *tables), a.iters, ["flash_fwd"])
        visits = causal_visits if causal else full_visits
        fwd_us[label] = {"flash_fwd": round(t["flash_fwd"], 1),
                         "us_per_visit": round(t["flash_fwd"] / visits, 4)}
    out["forward_us"] = fwd_us

    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
