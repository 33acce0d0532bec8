"""What the serving drivers share: the engine under test with weights made
on the device, warm-up of exactly the shapes the mix uses, the logit check
against the plain reference, and the loop that offers requests, steps the
engine and stamps every token on the host clock.

The engine is the program's ``ServingEngine`` through its public surface
(``submit``, ``step``, ``on_token``, ``snapshot``, ``result``); the check
also uses its cache manager's ``allocate``/``insert``/``release`` and the
model's ``decode_step``, because the engine hands out tokens, not logits.
"""

import contextlib
import time

import numpy as np

from benchmark import checks, stats, trace
from benchmark.traffic import prompt_tokens
from benchmark.weights import make_weights


def build_engine(ctx):
    from elephas_tpu.serving import ServingEngine

    cfg = ctx.cfg
    model = ctx.manifest.module("families", cfg["family"]).build_model(cfg)
    weights = make_weights(model, ctx.seed, cfg["weights"]["dtype"],
                           cfg["weights"]["float32_leaves"])
    engine = ServingEngine(model, weights, **cfg["engine"])
    return model, weights, engine


def set_up(ctx):
    """Everything before the first offered request: ``(model, weights,
    engine, correct)`` with every program of the mix warm and the logit
    check made."""
    model, weights, engine = build_engine(ctx)
    warm_up(ctx, engine, ctx.mix)
    return model, weights, engine, check_logits(ctx, model, weights, engine)


def buckets_for(mix: dict) -> list:
    """The prompt-length buckets (powers of two, as the engine pads) that
    this mix's prompts fall into."""
    from elephas_tpu.serving.cache import bucket_length

    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    out, b = [], bucket_length(lo)
    while b < hi:
        out.append(b)
        b *= 2
    return out + [bucket_length(hi)]


def warm_up(ctx, engine, mix):
    """One request per prompt bucket, two tokens each: compiles (or loads)
    every prefill program, the decode step and the row updates, and no
    other shape."""
    vocab = ctx.cfg["vocab_size"]
    for i, b in enumerate(buckets_for(mix)):
        n = min(b, mix["prompt_tokens"]["max"])
        engine.submit(prompt_tokens(ctx.seed, -1 - i, n, vocab), 2)
    done = engine.drain(max_steps=10_000)
    for rid in list(done):
        engine.result(rid)


def check_logits(ctx, model, weights, engine):
    """Prefill through the engine's own insert program, then a few batched
    ``decode_step``s through the cache, against the reference's full
    forward at the same positions. Outside the window; the slots are
    released afterwards."""
    import jax
    import jax.numpy as jnp

    ref = ctx.manifest.module("reference", ctx.cfg["family"])
    chk = ctx.cfg["check"]
    vocab, steps = ctx.cfg["vocab_size"], chk["decode_steps"]
    kv = engine.kv
    n_slots = kv.n_slots

    decode = jax.jit(
        lambda p, c, t, ps: model.decode_step(p, t, ps, c),
        donate_argnums=(1,))

    prompts = [prompt_tokens(ctx.seed, -100 - i, n, vocab)
               for i, n in enumerate(chk["prompt_lengths"])]
    slots, rows = [], []
    for p in prompts:
        slot = kv.allocate()
        slots.append(slot)
        rows.append([np.asarray(kv.insert(slot, p), np.float32)])
    fed = [[] for _ in prompts]
    for j in range(steps):
        tok = np.zeros(n_slots, np.int32)
        pos = np.zeros(n_slots, np.int32)
        for i, slot in enumerate(slots):
            fed[i].append(int(rows[i][-1].argmax()))
            tok[slot], pos[slot] = fed[i][-1], len(prompts[i]) + j
        logits, kv.cache = decode(weights, kv.cache, jnp.asarray(tok),
                                  jnp.asarray(pos))
        logits = np.asarray(logits, np.float32)
        for i, slot in enumerate(slots):
            rows[i].append(logits[slot])
    for slot in slots:
        kv.release(slot)

    got_all, want_all = [], []
    for p, f, r in zip(prompts, fed, rows):
        seq = np.concatenate([p, np.asarray(f, np.int32)])
        # cut on the device: only these rows cross to the host
        want_all.append(np.asarray(
            ref.forward(ctx.cfg, weights, seq)[len(p) - 1:]))
        got_all.append(np.stack(r))
    ok, worst, share = checks.logits_agree(
        np.concatenate(got_all), np.concatenate(want_all), ref.MIN_SHARE)
    n = sum(len(g) for g in got_all)
    ctx.log(f"check: prefill + {steps} decode steps against the reference "
            f"at {n} positions of {len(prompts)} prompts: worst error "
            f"{worst:.4f} of the largest logit, {share:.3f} of positions "
            f"within {checks.LOGIT_RTOL} (need {ref.MIN_SHARE}) -> "
            f"{'ok' if ok else 'WRONG'}")
    return ok


def check_streams(ctx, weights, records, limit=4, max_tokens=1024):
    """For up to ``limit`` finished requests: every emitted token against
    the reference's logits, teacher-forced on the engine's own stream."""
    ref = ctx.manifest.module("reference", ctx.cfg["family"])
    picked = [r for r in records
              if r["done"] and len(r["prompt"]) + len(r["tokens"]) <= max_tokens
              ][:limit]
    if not picked:
        ctx.log("check: no finished request short enough to check a stream")
        return False
    ok_all = True
    for r in picked:
        seq = np.concatenate([r["prompt"],
                              np.asarray(r["tokens"][:-1], np.int32)])
        logits = np.asarray(
            ref.forward(ctx.cfg, weights, seq)[len(r["prompt"]) - 1:])
        ok, worst, share = checks.stream_agrees(logits, r["tokens"],
                                                ref.MIN_SHARE)
        ctx.log(f"check: request {r['index']} ({len(r['prompt'])} + "
                f"{len(r['tokens'])} tokens): greedy tokens trail the "
                f"reference's best logit by at most {worst:.4f} of the "
                f"largest logit, {share:.3f} within {checks.TIE_RTOL} -> "
                f"{'ok' if ok else 'WRONG'}")
        ok_all = ok_all and ok
    return ok_all


class Load:
    """Requests offered to one engine, every token stamped on the host
    clock. ``records`` holds, per request: ``index``, ``due``,
    ``submitted``, ``prompt``, ``tokens``, ``token_times``, ``done``,
    ``rejected``."""

    def __init__(self, ctx, engine):
        self.ctx, self.engine = ctx, engine
        self.clock = time.perf_counter
        self.records = []
        self.by_id = {}
        self.actions = []          # what each annotated step returned
        self.annotate = False
        self.just_done = []

    def submit(self, index, due, prompt_len, max_new):
        from elephas_tpu.serving.scheduler import AdmissionError

        rec = {"index": index, "due": due, "submitted": None,
               "prompt": prompt_tokens(self.ctx.seed, index, prompt_len,
                                       self.ctx.cfg["vocab_size"]),
               "max_new": max_new, "tokens": [], "token_times": [],
               "done": False, "rejected": False, "queue_wait_s": None}
        self.records.append(rec)
        with self._span("submit"):
            try:
                rid = self.engine.submit(rec["prompt"], max_new,
                                         on_token=self._on_token)
                self.by_id[rid] = rec
            except AdmissionError as e:
                rec["rejected"] = str(e.reason)
        rec["submitted"] = self.clock()
        return rec

    def _on_token(self, rid, tok, done):
        rec = self.by_id[rid]
        rec["token_times"].append(self.clock())
        rec["tokens"].append(int(tok))
        if done:
            rec["done"] = True
            self.just_done.append((rid, rec))

    def _span(self, name):
        import jax

        if not self.annotate:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + name)

    def step(self):
        with self._span(f"engine.step#{len(self.actions)}"):
            action = self.engine.step()
        if self.annotate:
            self.actions.append(action)
        return action

    def take_done(self) -> list:
        """The requests that finished since the last call, their terminal
        records popped from the engine (nothing piles up there)."""
        out = []
        for rid, rec in self.just_done:
            fin = self.engine.result(rid)
            if fin is not None:
                rec["queue_wait_s"] = fin.timing.queue_wait
            out.append(rec)
        self.just_done = []
        return out

    def rename(self, span_name: str) -> str:
        """``engine.step#12`` -> ``engine.step:decode``."""
        if span_name.startswith("engine.step#"):
            i = int(span_name.split("#")[1])
            if i < len(self.actions):
                return "engine.step:" + self.actions[i]
        return span_name


def serving_facts(engine, window_records, summary):
    return {
        "records": window_records,
        "lateness_ms": stats.lateness_ms(window_records),
        "token_gaps_ms": stats.token_gaps_ms(window_records),
        "snapshot": engine.snapshot(),
        "trace": summary,
    }


def profile_phase(ctx, load, run_until):
    """Serve on with the profiler on for the mix's ``profile_s``: the
    caller's ``run_until(t_end)`` keeps offering the same load."""
    prof = trace.Profiler(ctx.out_dir)
    prof.start()
    load.annotate = True
    run_until(load.clock() + float(ctx.mix.get("profile_s", 3.0)))
    load.annotate = False
    return prof.stop(rename=load.rename)
