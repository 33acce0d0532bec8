"""Open loop: requests are sent when they are due, whether or not earlier
ones have finished. The mix fixes the rate; nothing is searched for.

Timeline: set-up (weights, engine, warm-up, logit check) | ``warm_in_s`` of
the same arrivals, not counted | the window of ``--seconds`` | arrivals go
on (so that the requests of the window finish under the load they began
under) until every request due in the window is done or ``grace_s`` has
passed. A traced run then serves on for ``profile_s`` with the profiler
on: host-clock numbers come from the undisturbed window, device numbers
from the same load just after it.
"""

import time

from benchmark import serving, stats
from benchmark.traffic import open_loop_schedule


def run(ctx):
    mix = ctx.mix
    _, weights, engine, correct = serving.set_up(ctx)

    warm_in, grace = float(mix["warm_in_s"]), float(mix["grace_s"])
    extra = float(mix.get("profile_s", 3.0)) + 2.0 if ctx.trace else 0.0
    horizon = warm_in + ctx.seconds + grace + extra
    schedule = open_loop_schedule(mix, ctx.seed, horizon)
    load = serving.Load(ctx, engine)
    nxt = 0

    programs0 = ctx.meter.programs
    t0 = load.clock()
    setup_s = t0 - ctx.t_start
    w0, w1 = t0 + warm_in, t0 + warm_in + ctx.seconds

    def offer_and_step():
        nonlocal nxt
        now = load.clock()
        while nxt < len(schedule) and t0 + schedule[nxt]["due_s"] <= now:
            r = schedule[nxt]
            load.submit(r["index"], t0 + r["due_s"], r["prompt_len"],
                        r["max_new"])
            nxt += 1
        if load.step() == "idle":
            due = (t0 + schedule[nxt]["due_s"] if nxt < len(schedule)
                   else now + 0.001)
            time.sleep(min(max(due - load.clock(), 0.0), 0.001))
        load.take_done()

    def run_until(t_end):
        while load.clock() < t_end:
            offer_and_step()

    run_until(w0)
    depth0 = engine.scheduler.queue_depth
    run_until(w1)
    depth1 = engine.scheduler.queue_depth
    in_window = [r for r in load.records if w0 <= r["due"] < w1]
    summary = (serving.profile_phase(ctx, load, run_until)
               if ctx.trace else None)
    t_grace = w1 + grace + extra
    while (load.clock() < t_grace
           and not all(r["done"] or r["rejected"] for r in in_window)):
        offer_and_step()
    compiled = ctx.meter.programs - programs0

    failed = [r for r in in_window if not r["done"]]
    ttft = stats.ttft_ms(in_window)
    gaps = stats.token_gaps_ms(in_window)
    mid = (w0 + w1) / 2
    halves = [[t for r, t in zip(in_window, ttft) if (r["due"] < mid) == h]
              for h in (True, False)]
    ctx.log("backlog: queue depth " f"{depth0} at the window's start, "
            f"{depth1} at its end; median TTFT of its halves "
            + " / ".join(f"{stats.median(h):.0f} ms" if h else "-"
                         for h in halves))
    ctx.log(f"window: {len(in_window)} requests due, {len(failed)} failed "
            f"({sum(1 for r in failed if r['rejected'])} rejected); "
            f"{len(gaps)} token gaps; {compiled} programs compiled inside")
    correct = (correct and compiled == 0
               and serving.check_streams(ctx, weights, in_window))
    facts = serving.serving_facts(engine, in_window, summary)
    return {
        "correct": bool(correct), "attempted": len(in_window),
        "failed": len(failed),
        "end_to_end": {
            "ttft_p95_ms": stats.percentile(ttft, 95) if ttft else None,
            "itl_p95_ms": stats.percentile(gaps, 95) if gaps else None,
            "setup_s": setup_s,
        },
        "facts": facts,
    }
