"""Closed loop: ``callers`` callers, each sending its next request when
its last one completes, so the engine stays saturated whatever its speed
and the queue never overflows.

Timeline: set-up | ``warm_in_s``, not counted | the window of ``--seconds``
| (traced run only) ``profile_s`` more with the profiler on. ``attempted``
counts the requests resolved (finished or rejected) inside the window;
those still in flight when it ends are neither attempted nor failed.
"""

from benchmark import serving, stats
from benchmark.traffic import closed_loop_pool


def run(ctx):
    mix = ctx.mix
    _, weights, engine, correct = serving.set_up(ctx)

    pool = closed_loop_pool(mix, ctx.seed)
    load = serving.Load(ctx, engine)
    sent = 0

    def send():
        nonlocal sent
        p, m = pool[sent % len(pool)]
        load.submit(sent, load.clock(), p, m)
        sent += 1

    programs0 = ctx.meter.programs
    t0 = load.clock()
    setup_s = t0 - ctx.t_start
    w0 = t0 + float(mix["warm_in_s"])
    w1 = w0 + ctx.seconds
    for _ in range(int(mix["callers"])):
        send()

    def run_until(t_end):
        while load.clock() < t_end:
            load.step()
            for _ in load.take_done():
                send()

    run_until(w1)
    t_end = load.clock()
    summary = (serving.profile_phase(ctx, load, run_until)
               if ctx.trace else None)
    compiled = ctx.meter.programs - programs0

    def resolved_at(r):
        return r["token_times"][-1] if r["done"] else r["submitted"]

    resolved = [r for r in load.records
                if (r["done"] or r["rejected"]) and w0 <= resolved_at(r) < w1]
    failed = [r for r in resolved if not r["done"]]
    tokens = stats.tokens_in_window(load.records, w0, w1)
    ctx.log(f"window: {len(resolved)} requests resolved, {len(failed)} "
            f"failed; {tokens} tokens in {w1 - w0:.1f} s (loop overran by "
            f"{t_end - w1:.3f} s); {compiled} programs compiled inside")
    correct = (correct and compiled == 0
               and serving.check_streams(ctx, weights, resolved))
    in_window = [r for r in load.records
                 if any(w0 <= t < w1 for t in r["token_times"])]
    facts = serving.serving_facts(engine, in_window, summary)
    return {
        "correct": bool(correct), "attempted": len(resolved),
        "failed": len(failed),
        "end_to_end": {
            "serve_tokens_per_s": tokens / (w1 - w0) if tokens else None,
            "setup_s": setup_s,
        },
        "facts": facts,
    }
