#!/usr/bin/env python3
"""Which execution on the device each engine span caused.

``program_trace.py`` gives a decode step the device operations that START
inside its ``elephas.engine.decode`` span: containment, which holds only
while the host waits in that span for the one program it enqueued. The
engine also says what it enqueued: every span that directly wraps a call of
one of its compiled programs carries ``launch`` (the engine's count of such
calls, after this one) and ``program`` (the called function's name), and
``elephas.engine.decode.fetch`` carries the ``launch`` it waits for. The
device's ``XLA Modules`` line has one event for every execution, called
``jit_<program>(<fingerprint>)`` on a v5e. This file joins the two and reads
the host's part of a step off the pairs.

The join is by ORDER, checked by time. The spans in launch order, the
executions in start order: the first span's execution (the anchor) is the
last of its program that ends before the ``fetch`` span with its launch
does (the next one ends a whole step later, also where a step is kept
queued and the execution before STARTS just before the span), or for a
program nobody fetches the first that starts at or after the span does;
each later
span's is the next of its program, after at least as many other executions
as launch numbers lie between the two spans (calls that have no span of
their own: a finished request's park, a draft model's rollout). Spans at
the end whose execution the profiler cut are left out, as are executions at
the start whose span began before the profiler did. Then the checks, each
of which must hold or the join gives nothing and says which did not:

- every execution starts at or after its span's start;
- every execution ends at or before the end of the ``fetch`` span that
  carries its launch (true of a loop that keeps one step queued too: the
  fetch of launch ``n`` waits for execution ``n``, whichever span it runs
  under); both to within ``SKEW_S``, because the host's clock and the
  device's are two clocks that one trace lines up to within a millisecond
  or so, not the same in every trace (v5e traces showed executions 0.03
  and 0.11 ms before their spans, and the runs whose per-span kernel sums
  read one kernel low are the ones where it is most: PERF.md section 6,
  PR 36), while a wrong pairing is off by a whole execution, 12 ms or
  more in every cell;
- between a program's first and last joined execution, the executions of
  that program number the joined ones: exactly for a program that a
  ``decode.dispatch`` span names, and for another plus at most the calls
  without a span that the launch numbers show in between.

A v5e trace has no identifier to join by instead: the module event and the
runtime's ``DoEnqueueProgram`` share a ``run_id``, but the latter runs on a
runtime thread, outside the engine's span, and the ``PjitFunction(...)``
event inside the span carries none (PERF.md, section 3).

Pure functions over ``program_trace.load``'s lists, so tests feed made-up
ones. Device idle time here is time with no execution on the modules line.

    python3 benchmark/program_runs.py [<trace dir>]

prints the pairs (launch, program, span, execution start and length, lags),
the host's split of a decode step, how many of each kernel an execution
and its span hold, and how many operations an execution holds.
"""

import functools
import os
import sys
from collections import Counter, namedtuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import program_trace as pt, stats, trace      # noqa: E402

ENGINE = pt.SPAN_PREFIX + "engine."
DISPATCH, FETCH = ENGINE + "decode.dispatch", ENGINE + "decode.fetch"
DECODE, PREFILL = ENGINE + "decode", ENGINE + "prefill"
INSERT, SET_ROW = ENGINE + "prefill.insert", ENGINE + "prefill.set_row"
SKEW_S = 2e-3       # how far the two clocks of a trace may disagree

# one joined launch: ``span`` and ``fetch`` are ``(name, s, e, args)``
# (``fetch`` is ``None`` except for a decode program whose fetch span the
# trace holds), ``run`` indexes ``Joined.runs``
Pair = namedtuple("Pair", "launch program span run fetch")
# ``runs``: every ``(name, s, e)`` of the first device's modules line, by
# start; ``pairs`` in launch order; ``ops``/``starts``: ``pt.by_start`` of
# the device's operations; ``spans``: the program's
Joined = namedtuple("Joined", "runs pairs ops starts spans")


def log(*args):
    print("program_runs:", *args, file=sys.stderr, flush=True)


def program_of(module_name: str) -> str:
    """``jit__decode_kernel(2924632646694356351)`` -> ``_decode_kernel``:
    what a v5e trace calls an execution, back to the function's name."""
    base = module_name.split("(")[0]
    return base[4:] if base.startswith("jit_") else base


# -- the join ------------------------------------------------------------

def join(loaded: dict):
    """``Joined`` for what ``program_trace.load`` gave, or ``None``: for a
    trace with no device, for a program whose spans carry no ``launch``
    (the parent of the PR that brought it), and where a check fails."""
    if not loaded["modules"]:
        return None
    first = sorted(loaded["modules"])[0]
    runs = sorted(loaded["modules"][first], key=lambda r: (r[1], r[2]))
    spans = loaded["spans"]
    launched = sorted(
        (sp for sp in spans if "launch" in sp[3] and "program" in sp[3]),
        key=lambda sp: int(sp[3]["launch"]))
    if not launched or not runs:
        return None
    fetches = {int(sp[3]["launch"]): sp for sp in pt.named(spans, FETCH)
               if "launch" in sp[3]}
    names = [program_of(r[0]) for r in runs]

    pairs, at, before = [], None, None
    for k, sp in enumerate(launched):
        n, program = int(sp[3]["launch"]), str(sp[3]["program"])
        if at is not None:              # by order
            at = next((i for i in range(at + n - before, len(runs))
                       if names[i] == program), None)
        elif n in fetches:              # the anchor, by its fetch's end
            at = next((i for i in reversed(range(len(runs)))
                       if names[i] == program
                       and runs[i][2] <= fetches[n][2] + SKEW_S), None)
        else:                           # the anchor, by its span's start
            at = next((i for i, r in enumerate(runs) if names[i] == program
                       and r[1] >= sp[1] - SKEW_S), None)
        if at is None:
            # the profiler cut this span's execution, and then every later
            # one's: nothing else may have been left out
            late = [s for s in launched[k:] if s[1] < runs[-1][1]]
            if late:
                log(f"launch {int(late[0][3]['launch'])} "
                    f"({late[0][3]['program']}) has no execution on the "
                    "modules line, and later executions are there")
                return None
            break
        pair = Pair(n, program, sp, at, fetches.get(n))
        _, s, e = runs[at]
        if s < sp[1] - SKEW_S:
            log(f"launch {n} ({program}): its execution starts "
                f"{(sp[1] - s) * 1e3:.3f} ms before its span")
            return None
        if pair.fetch is not None and e > pair.fetch[2] + SKEW_S:
            log(f"launch {n} ({program}): its execution ends "
                f"{(e - pair.fetch[2]) * 1e3:.3f} ms after its fetch span")
            return None
        pairs.append(pair)
        before = n
    if not pairs:
        log("no span's execution is on the modules line")
        return None

    # a decode program is only ever called from a dispatch span; a row
    # update is also a park's, which has none
    dispatched = {p.program for p in pairs if p.span[0] == DISPATCH}
    for program in sorted({p.program for p in pairs}):
        mine = [p for p in pairs if p.program == program]
        there = sum(1 for i in range(mine[0].run, mine[-1].run + 1)
                    if names[i] == program)
        in_range = sum(1 for p in pairs
                       if mine[0].launch <= p.launch <= mine[-1].launch)
        bare = (0 if program in dispatched else
                mine[-1].launch - mine[0].launch + 1 - in_range)
        if not 0 <= there - len(mine) <= bare:
            log(f"{program}: {there} executions between launch "
                f"{mine[0].launch} and {mine[-1].launch}, for {len(mine)} "
                f"spans and at most {bare} calls without one")
            return None
    ops, starts = pt.by_start(loaded["device_ops"].get(first, []))
    return Joined(runs, pairs, ops, starts, spans)


@functools.lru_cache(maxsize=2)
def _joined(path: str):
    return join(pt.load(path))


def for_facts(facts: dict):
    """The join of this run's trace; ``None`` for a run that was not
    traced, and wherever ``join`` gives nothing."""
    if pt.for_facts(facts) is None:
        return None
    return _joined(pt.newest())


# -- what the pairs say ----------------------------------------------------

def decode_pairs(j: Joined) -> list:
    """The pairs whose span is a ``decode.dispatch``: single step, fused
    block or verify round, whichever program ran."""
    return [p for p in j.pairs if p.span[0] == DISPATCH]


def program_ms(j: Joined) -> list:
    """Length of each decode program's own execution."""
    return [(j.runs[p.run][2] - j.runs[p.run][1]) * 1e3
            for p in decode_pairs(j)]


def launch_lag_ms(j: Joined) -> list:
    """Per decode pair: from the later of its dispatch span's start and
    the end of the execution before its own to its execution's start. The
    device waiting for the enqueue; a negative one is a skew between the
    host's clock and the device's, and is reported as it is."""
    out = []
    for p in decode_pairs(j):
        since = p.span[1]
        if p.run > 0:
            since = max(since, j.runs[p.run - 1][2])
        out.append((j.runs[p.run][1] - since) * 1e3)
    return out


def fetch_lag_ms(j: Joined) -> list:
    """Per decode pair whose fetch span the trace holds: from its
    execution's end to that span's end."""
    return [(p.fetch[2] - j.runs[p.run][2]) * 1e3
            for p in decode_pairs(j) if p.fetch is not None]


def step_gap_ms(j: Joined) -> list:
    """Idle time of the device between two decode executions with no other
    program between them: everything the host does between two steps."""
    decode = sorted(p.run for p in decode_pairs(j))
    return [(j.runs[b][1] - j.runs[a][2]) * 1e3
            for a, b in zip(decode, decode[1:]) if b == a + 1]


def _ops_in(j: Joined, lo: float, hi: float):
    """The operations that start inside ``[lo, hi)``, as
    ``program_trace._tables`` takes a step's."""
    return pt.ops_between(j.ops, j.starts, lo, hi)


def _own_and_seen(j: Joined):
    """Per decode pair whose ``elephas.engine.decode`` span (the one its
    dispatch span lies in) the trace holds: ``(pair, the execution's own
    interval, that span's)``."""
    steps = pt.named(j.spans, DECODE)
    for p in decode_pairs(j):
        sp = next((sp for sp in steps
                   if sp[1] <= p.span[1] and p.span[2] <= sp[2]), None)
        if sp is not None:
            yield p, j.runs[p.run][1:], (sp[1], sp[2])


def span_short_ms(j: Joined) -> list:
    """Per decode pair: device self time of the operations inside the
    execution's own interval, minus that of the operations that start
    inside its ``elephas.engine.decode`` span (what
    ``program_trace._tables(per="decode")`` gives the step). Signed here;
    the metric is the median of the absolute value."""
    def self_ms(window):
        return sum(pt.device_ms_by_scope(_ops_in(j, *window),
                                         *window).values())

    return [self_ms(own) - self_ms(seen) for _, own, seen in _own_and_seen(j)]


def _admissions(j: Joined):
    """Per ``elephas.engine.prefill`` span that holds a joined ``insert``
    and a joined ``set_row``: ``(span, the pairs launched inside it)``. A
    prefill that only opens a chunk train has no ``set_row`` and is left
    out."""
    for sp in pt.named(j.spans, PREFILL):
        mine = [p for p in j.pairs
                if sp[1] <= p.span[1] and p.span[2] <= sp[2]]
        kinds = {p.span[0] for p in mine}
        if INSERT in kinds and SET_ROW in kinds:
            yield sp, mine


def programs_per_request(j: Joined) -> list:
    """Per admission: the executions on the modules line from its insert's
    to its ``set_row``'s, both counted: the engine's own and the ones that
    eager calls enqueue between them."""
    out = []
    for _, mine in _admissions(j):
        a = min(p.run for p in mine if p.span[0] == INSERT)
        b = max(p.run for p in mine if p.span[0] == SET_ROW)
        out.append(b - a + 1)
    return out


def prefill_stall_ms(j: Joined) -> list:
    """Per admission: idle time of the device from the prefill span's start
    to the end of the last execution it launched."""
    busy = trace.union((s, e) for _, s, e in j.runs)
    out = []
    for sp, mine in _admissions(j):
        lo, hi = sp[1], max(j.runs[p.run][2] for p in mine)
        out.append((hi - lo - trace.total(trace.clip(busy, lo, hi))) * 1e3)
    return out


def kernel_counts(j: Joined):
    """``[(launch, kernels of the execution, kernels of its span)]`` per
    decode pair, each a ``Counter`` of ``pallas_call`` names: whether a
    span that reads low holds a kernel fewer than its execution (the
    kernel began outside the span), or the execution holds one fewer too
    (its event lost its path, or was not recorded)."""
    def kernels(window):
        return Counter(filter(None, (pt.kernel_of(n) for n, _, _ in
                                     _ops_in(j, *window))))

    return [(p.launch, kernels(own), kernels(seen))
            for p, own, seen in _own_and_seen(j)]


def op_counts(j: Joined):
    """``[(launch, operations, of them without a scope path)]`` per decode
    pair, inside the execution's own interval: an execution with an event
    fewer than the others lost one; one with as many, and one more that
    kept only its HLO name (``%fusion.3``), lost a path."""
    out = []
    for p in decode_pairs(j):
        _, s, e = j.runs[p.run]
        inside = _ops_in(j, s, e)
        out.append((p.launch, len(inside),
                    sum(1 for n, _, _ in inside if n.startswith("%"))))
    return out


def median_of(facts: dict, values, absolute: bool = False):
    """What a reader under ``layer_metrics/`` returns: the median of
    ``values(join)`` over the profiled sub-window, ``None`` with no join
    or nothing to take a median of."""
    j = for_facts(facts)
    if j is None:
        return None
    xs = [abs(v) if absolute else v for v in values(j)]
    return stats.median(xs) if xs else None


# -- by hand ------------------------------------------------------------

def _median(xs):
    return stats.median(xs) if xs else float("nan")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = trace.find_xplane(argv[0] if argv else pt.TRACE_DIR)
    loaded = pt.load(path)
    for dev in sorted(loaded["modules"])[:1]:
        print(f"on the modules line of {dev}:",
              dict(Counter(r[0] for r in loaded["modules"][dev])))
    j = join(loaded)
    if j is None:
        print("no join: no device, no span with a launch, or a check "
              "failed (see above)")
        return 1
    t0 = j.runs[0][1]
    print(f"{len(j.pairs)} launches joined to {len(j.runs)} executions on "
          f"the modules line (launch {j.pairs[0].launch} to "
          f"{j.pairs[-1].launch}); ms from the first execution's start")
    lag = dict(zip((p.launch for p in decode_pairs(j)), launch_lag_ms(j)))
    print(f"{'launch':>8}  {'program':<24}{'span':<34}{'span at':>10}"
          f"{'run at':>10}{'run ms':>9}{'launch lag':>11}{'fetch lag':>10}")
    for p in j.pairs[:int(argv[1]) if len(argv) > 1 else 60]:
        _, s, e = j.runs[p.run]
        fetch = ("" if p.fetch is None
                 else f"{(p.fetch[2] - e) * 1e3:10.3f}")
        print(f"{p.launch:>8}  {p.program:<24}"
              f"{p.span[0][len(pt.SPAN_PREFIX):]:<34}"
              f"{(p.span[1] - t0) * 1e3:10.3f}{(s - t0) * 1e3:10.3f}"
              f"{(e - s) * 1e3:9.3f}"
              + (f"{lag[p.launch]:11.3f}" if p.launch in lag else " " * 11)
              + fetch)
    by_program = Counter(p.program for p in j.pairs)
    print("joined by program:", dict(by_program))

    def host_ms(name):
        return _median([(e - s) * 1e3 for _, s, e, _ in
                        pt.named(j.spans, ENGINE + name)])

    gap, ll, fl = (_median(step_gap_ms(j)), _median(launch_lag_ms(j)),
                   _median(fetch_lag_ms(j)))
    emit, decide, reap = (host_ms("decode.emit"), host_ms("decide"),
                          host_ms("reap"))
    print("the host's part of a decode step, median ms "
          f"({len(step_gap_ms(j))} decode-to-decode gaps of "
          f"{len(decode_pairs(j))} decode executions):")
    print(f"  program {_median(program_ms(j)):.3f} | step gap {gap:.3f} = "
          f"launch lag {ll:.3f} + fetch lag {fl:.3f} + emit {emit:.3f} + "
          f"decide {decide:.3f} + reap {reap:.3f} + the rest "
          f"{gap - ll - fl - emit - decide - reap:.3f}")
    print(f"  smallest launch lag {min(launch_lag_ms(j)):.3f}, smallest "
          f"fetch lag {min(fetch_lag_ms(j), default=float('nan')):.3f} "
          "(negative: the two clocks disagree by that much)")
    short = span_short_ms(j)
    print(f"  execution minus span, device self ms: median of |.| "
          f"{_median([abs(x) for x in short]):.4f}, largest "
          f"{max(short, key=abs, default=float('nan')):.4f}, "
          f"{sum(1 for x in short if abs(x) > 1e-3)} of {len(short)} "
          "pairs differ")
    print(f"  an admission: {_median(programs_per_request(j)):.1f} "
          f"executions, {_median(prefill_stall_ms(j)):.3f} ms of device "
          f"idle ({len(programs_per_request(j))} admissions)")
    tally = Counter()
    for _, own, seen in kernel_counts(j):
        for k in set(own) | set(seen):
            tally[(k, own[k], seen[k])] += 1
    print("kernels an execution holds | its span holds: pairs")
    for (k, a, b), n in sorted(tally.items()):
        print(f"  {k:<24}{a:>4} |{b:>4}: {n}")
    per = []
    for p in decode_pairs(j):
        _, s, e = j.runs[p.run]
        per.append(pt.device_ms_by_scope(_ops_in(j, s, e), s, e))
    scopes = sorted({k for by in per for k in by},
                    key=lambda k: -_median([by.get(k, 0.0) for by in per]))
    print("median device self ms a decode execution, by scope "
          f"(all of it {_median([sum(by.values()) for by in per]):.3f}):")
    for scope, backward in scopes:
        xs = [by.get((scope, backward), 0.0) for by in per]
        print(f"  {scope:<16}{_median(xs):10.4f}   "
              f"({min(xs):.4f} to {max(xs):.4f})")
    print("operations an execution holds, of them without a path: pairs")
    for (n, bare), count in sorted(Counter(
            (n, bare) for _, n, bare in op_counts(j)).items()):
        print(f"  {n:>6} {bare:>6}: {count}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
