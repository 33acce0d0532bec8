#!/usr/bin/env python3
"""The program's own names, read back from a profiler trace.

``trace.py`` reduces a trace by what the benchmark wrapped around the
program (``bench:`` spans, XLA's operation numbers). The program also names
itself: every device operation carries the ``jax.named_scope`` path it was
traced under (``jit(step_impl)/transpose(jvp(layers))/while/body/
closed_call/attn/dot_general``: the scan, the layer part, and ``transpose(``
for the backward pass), a Pallas kernel carries the ``name=`` of its
``pallas_call`` (``.../attn_core/flash_fwd/pallas_call``), and the serving
engine wraps the phases of ``step()`` in ``TraceAnnotation``s named
``elephas.engine.*``. Where a v5e trace keeps each (read by hand first,
PERF.md): the path is the ``tf_op`` stat of the event's METADATA, which
``ProfileData`` does not show (``op_paths`` reads it from the file); the
spans' keyword arguments are plain event stats.

Pure functions over event lists, as ``trace.py`` is, so that tests feed
hand-built lists: a device operation is ``(op_name, start_s, end_s)``, a
program span ``(name, start_s, end_s, args)``. ``load`` turns the profiled
sub-window's ``.xplane.pb`` into such lists (once per file).

    python3 benchmark/program_trace.py [<trace dir>]

prints, for the newest trace under the directory (default
``.bench_out/trace`` of this checkout), device ms by scope with forward
and backward apart, ms by kernel, and idle ms by leaf span.
"""

import bisect
import functools
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import stats, trace          # noqa: E402

SPAN_PREFIX = "elephas."
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")
UNSCOPED, NO_SPAN = "(unscoped)", "(none)"

# The vocabulary of elephas_tpu/models/transformer.py, parallel/expert.py
# and serving/engine.py's jitted kernels (docs/TRAINING.md, "Reading a
# profile"). An operation belongs to the INNERMOST of these on its path.
SCOPES = frozenset((
    "embed", "layers", "attn", "kv_write", "attn_core", "ffn", "moe",
    "moe_route", "moe_dispatch", "moe_experts", "moe_combine", "head",
    "loss", "grad_reduce", "optimizer", "sample"))
ATTN = frozenset(("attn", "attn_core"))
FFN = frozenset(("ffn", "moe", "moe_route", "moe_dispatch", "moe_experts",
                 "moe_combine"))
# the scan's own slice, copy and update of its xs / ys carry ``layers`` and
# no inner scope: with ``kv_write`` that is the KV-cache traffic of a step
CACHE_IO = frozenset(("kv_write", "layers"))
HEAD_LOSS = frozenset(("head", "loss"))

_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_KERNEL = re.compile(r"([A-Za-z_][A-Za-z0-9_]*)/pallas_call")


# -- names ------------------------------------------------------------

def scope_of(op_name: str) -> str:
    """The innermost scope of the vocabulary on an operation's path, or
    ``(unscoped)``. Transform wrappers (``jvp(attn)``,
    ``transpose(jvp(attn))``, ``vmap(...)``) do not hide a scope."""
    found = UNSCOPED
    for word in _WORD.findall(op_name):
        if word in SCOPES:
            found = word
    return found


def is_backward(op_name: str) -> bool:
    """Backward operations are the transposes of the forward's: their
    path reads ``transpose(jvp(...))``. (The ``transpose`` primitive of a
    forward operation ends its path and has no parenthesis.)"""
    return "transpose(" in op_name


def kernel_of(op_name: str):
    """The ``name=`` of the ``pallas_call`` an operation is, or ``None``."""
    m = _KERNEL.search(op_name)
    return m.group(1) if m else None


# -- device time ------------------------------------------------------------

def _self_ms(ops, lo: float, hi: float):
    """``(op_name, self ms)`` of one device's events inside ``[lo, hi]``:
    ``trace.self_times``, so a ``while`` does not count its body twice."""
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
              if min(e, hi) > max(s, lo)]
    return [(n, sec * 1e3) for n, sec in trace.self_times(inside)]


def device_ms_by_scope(ops, lo: float, hi: float) -> dict:
    """``{(scope, backward): ms}`` of one device inside ``[lo, hi]``."""
    out = {}
    for name, ms in _self_ms(ops, lo, hi):
        key = (scope_of(name), is_backward(name))
        out[key] = out.get(key, 0.0) + ms
    return out


def kernel_ms(ops, lo: float, hi: float) -> dict:
    """``{kernel name: ms}`` of the named Pallas kernels of one device."""
    out = {}
    for name, ms in _self_ms(ops, lo, hi):
        k = kernel_of(name)
        if k is not None:
            out[k] = out.get(k, 0.0) + ms
    return out


def unscoped_ops(ops, lo: float, hi: float, n: int = 10):
    """The ``n`` operations under no scope with most self time, by their
    HLO names: ``[(name, ms)]``. The compiler made them (a copy of a
    loop's result, a hoisted convert), so they carry no path."""
    acc = {}
    for name, ms in _self_ms(ops, lo, hi):
        if scope_of(name) == UNSCOPED:
            acc[name] = acc.get(name, 0.0) + ms
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


def pick(by_scope: dict, scopes=None, backward=None, exclude=()) -> float:
    """Sum of a ``device_ms_by_scope`` table over a set of scopes (all of
    them when ``None``) and one direction (both when ``None``)."""
    return sum(ms for (scope, bwd), ms in by_scope.items()
               if (scopes is None or scope in scopes)
               and scope not in exclude
               and (backward is None or bwd == backward))


def has_names(by_scope: dict) -> bool:
    """False for a program that names nothing (the parent of the PR that
    brought the names): its readers then find nothing to read."""
    return any(scope != UNSCOPED for scope, _ in by_scope)


# -- program spans ------------------------------------------------------------

def leaf_intervals(spans):
    """``[(name, [(a, b), ...])]``: each span's OWN time, which is its
    interval minus the spans nested inside it. Spans of one thread nest;
    one that only overlaps another is not its child."""
    out = []
    for i, (name, s, e, _) in enumerate(spans):
        children = [(s2, e2) for j, (_, s2, e2, _) in enumerate(spans)
                    if j != i and s <= s2 and e2 <= e
                    and (e2 - s2 < e - s or j > i)]
        out.append((name, trace.subtract([(s, e)], children)))
    return out


def idle_by_leaf_span(ops, spans, lo: float, hi: float) -> dict:
    """Idle seconds of ONE device inside ``[lo, hi]`` by the innermost
    program span open at the time: each gap is split among the spans' own
    intervals (``trace.gaps_by_span`` gives a gap whole to the widest
    span, which with nested spans is always the root). Idle time under no
    span is ``(none)``."""
    busy = trace.clip(trace.union((s, e) for _, s, e in ops), lo, hi)
    idle = trace.gaps(busy, lo, hi)
    out, covered = {}, 0.0
    for name, own in leaf_intervals(spans):
        sec = sum(trace.total(trace.clip(idle, a, b)) for a, b in own)
        if sec > 0:
            out[name] = out.get(name, 0.0) + sec
            covered += sec
    rest = trace.total(idle) - covered
    if rest > 1e-12:
        out[NO_SPAN] = rest
    return out


def children_of(spans, parent, names):
    """Summed duration (s) of the spans called ``names`` inside ``parent``
    ``(name, s, e, args)``."""
    _, s, e, _ = parent
    return sum(e2 - s2 for n, s2, e2, _ in spans
               if n in names and s <= s2 and e2 <= e)


# -- reading the profiler's file ----------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the payload for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        else:                      # fixed 64 / 32: a double or float stat
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        yield key >> 3, value


def op_paths(path: str) -> dict:
    """``{device plane: {event name: scope path}}`` from the file's own
    bytes. The scope path (HLO ``metadata.op_name``) is the ``tf_op`` stat
    of an event's METADATA (``XEventMetadata.stats``), which
    ``ProfileData`` does not show: it gives an XLA Ops event's name (the
    HLO text without its ``metadata={}``) and per-event stats (offset and
    duration only). So this walks the wire format of ``xplane.proto``:
    ``XSpace.planes = 1``; ``XPlane.name = 2, event_metadata = 4,
    stat_metadata = 5`` (maps: key 1, value 2); ``XEventMetadata.name = 2,
    stats = 5``; ``XStat.metadata_id = 1, str_value = 5, ref_value = 7``;
    ``XStatMetadata.name = 2``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = None, [], {}
        for pf, value in _fields(plane):
            if pf == 2:
                name = bytes(value).decode()
            elif pf == 4:
                events.append(dict(_fields(value))[2])
            elif pf == 5:
                entry = dict(_fields(value))
                stat_names[entry[1]] = bytes(
                    dict(_fields(entry[2])).get(2, b"")).decode()
        if not name or not name.startswith("/device:TPU:"):
            continue
        paths = out.setdefault(name, {})
        for meta in events:
            ev_name, tf_op = None, None
            for mf, value in _fields(meta):
                if mf == 2:
                    ev_name = bytes(value).decode()
                elif mf == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        tf_op = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if ev_name and tf_op:
                paths[ev_name] = tf_op.rstrip(":")
    return out


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """``{"device_ops", "modules", "spans"}`` of one ``.xplane.pb``:
    per device plane the ``(scope path, s, e)`` events of the ``XLA Ops``
    line (an operation the compiler made, with no path of its own, keeps
    its HLO name: ``%copy.3``) and the ``(program name, s, e)`` events of
    the ``XLA Modules`` line; and every host event named ``elephas.*`` as
    ``(name, s, e, args)``, sorted by start."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    paths = op_paths(path)
    device_ops, modules, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            known = paths.get(plane.name, {})
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    device_ops[plane.name] = [
                        (known.get(ev.name) or ev.name.split(" = ")[0],
                         ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9,
                                      dict(ev.stats)))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return {"device_ops": device_ops, "modules": modules, "spans": spans}


def newest() -> str:
    return trace.find_xplane(TRACE_DIR)


def for_facts(facts: dict):
    """What ``load`` gives for the run whose ``facts`` these are, with the
    first device's events under ``"ops"`` and the benchmark's window under
    ``"lo"`` / ``"hi"``; ``None`` for a run that was not traced, or whose
    trace shows no device."""
    t = facts.get("trace")
    if not t:
        return None
    try:
        loaded = load(newest())
    except FileNotFoundError:
        return None
    if not loaded["device_ops"]:
        return None
    first = sorted(loaded["device_ops"])[0]
    return {**loaded, "ops": loaded["device_ops"][first],
            "lo": t["lo"], "hi": t["hi"]}


def by_start(ops):
    """``(ops sorted by start, their starts)``, for ``ops_between``."""
    ops = sorted(ops, key=lambda ev: (ev[1], -ev[2]))
    return ops, [s for _, s, _ in ops]


def ops_between(ops, starts, lo: float, hi: float):
    """The events of ``by_start(...)`` that START inside ``[lo, hi)``.
    Programs run one after another on a device, so a window that is one
    program's execution, or a span the host waited in for one, holds whole
    events only."""
    return ops[bisect.bisect_left(starts, lo):bisect.bisect_left(starts, hi)]


def whole_steps(modules):
    """The ``(s, e)`` of each whole execution of the train step: the
    executions of the program that takes most of the device's time (small
    host-side programs run between steps), without one the profiler cut
    short at either end."""
    by_name = {}
    for name, s, e in modules:
        by_name.setdefault(name, []).append((s, e))
    if not by_name:
        return []
    runs = max(by_name.values(), key=trace.total)
    typical = stats.median([e - s for s, e in runs])
    return [(s, e) for s, e in runs if e - s > 0.5 * typical]


def named(spans, name: str):
    return [sp for sp in spans if sp[0] == name]


# -- what the readers under layer_metrics/ share ------------------------------
#
# Device numbers are per whole train step, or per ``elephas.engine.decode``
# span (the host waits in it for the one program it enqueued), of the first
# device, median over the profiled sub-window. A reader that finds nothing
# (no traced run, or a program without the names) returns ``None``.

@functools.lru_cache(maxsize=4)
def _tables(path: str, per: str):
    """``[(window, device_ms_by_scope, kernel_ms, span args)]`` for each
    train step (``per="step"``) or decode span (``per="decode"``)."""
    loaded = load(path)
    if not loaded["device_ops"]:
        return []
    first = sorted(loaded["device_ops"])[0]
    ops, starts = by_start(loaded["device_ops"][first])
    if per == "step":
        windows = [(s, e, {}) for s, e in
                   whole_steps(loaded["modules"].get(first, []))]
    else:
        windows = [(s, e, args) for _, s, e, args in
                   named(loaded["spans"], SPAN_PREFIX + "engine.decode")]
    out = []
    for s, e, args in windows:
        inside = ops_between(ops, starts, s, e)
        out.append(((s, e), device_ms_by_scope(inside, s, e),
                    kernel_ms(inside, s, e), args))
    return out


def tables(facts: dict, per: str):
    """``_tables`` of this run; ``None`` unless it was traced and the
    program names its operations."""
    if for_facts(facts) is None:
        return None
    rows = _tables(newest(), per)
    if not rows or not any(has_names(by) for _, by, _, _ in rows):
        return None
    return rows


def scope_ms(facts: dict, per: str, scopes=None, backward=None,
             exclude=()):
    """Median over steps (or decode spans) of the device ms in these
    scopes and this direction."""
    rows = tables(facts, per)
    if rows is None:
        return None
    return stats.median([pick(by, scopes, backward, exclude)
                         for _, by, _, _ in rows])


def unscoped_pct(facts: dict):
    """Share of the first device's busy time, over the benchmark's window,
    in operations that carry no scope of the vocabulary."""
    t = for_facts(facts)
    if t is None:
        return None
    by = device_ms_by_scope(t["ops"], t["lo"], t["hi"])
    if not has_names(by):
        return None
    return 100.0 * pick(by, (UNSCOPED,)) / sum(by.values())


def cell_config(metric: str, facts: dict):
    """The configuration of the cell being run. A reader is handed only
    ``facts``; the cells that report ``metric`` are in the manifest, and
    where several do (the two train cells) the chips of the run tell them
    apart. ``None`` if that leaves no cell or more than one."""
    from benchmark.manifest import Manifest

    man = Manifest(ROOT)
    entry = [m for m in man.data["per_layer"] if m["name"] == metric]
    cells = [man.cell(c) for m in entry for c in m.get("workloads", [])]
    if facts.get("chips"):
        cells = [c for c in cells if c["chips"] == facts["chips"]]
    return man.config(cells[0]["config"]) if len(cells) == 1 else None


def _peaks():
    """``(flops/s, bytes/s)`` of one chip. The trace does not name the
    device kind; the benchmark runs on the chip JAX finds, and nowhere
    else, so that is the chip that was traced."""
    import jax

    from benchmark.peaks import peaks_for

    return peaks_for(jax.devices()[0].device_kind)[:2]


def train_kernel_roofline_pct(metric: str, facts: dict, kernels,
                              backward: bool):
    """Least time for the attention core's operations and bytes in one
    train step (``kernel_work.train_attention_work``) over the median time
    its named kernels took in a step."""
    from benchmark import kernel_work

    rows = tables(facts, "step")
    cfg = cell_config(metric, facts)
    if rows is None or cfg is None:
        return None
    ms = stats.median([sum(km.get(k, 0.0) for k in kernels)
                       for _, _, km, _ in rows])
    flops, nbytes = kernel_work.train_attention_work(cfg, backward)
    return kernel_work.roofline_pct(flops, nbytes, ms * 1e-3,
                                    *_peaks())


def decode_kernel_roofline_pct(metric: str, facts: dict, kernel: str):
    """Per decode span: least time to read the K and V of the key
    positions the step's live rows attend (the span's ``kv_positions``)
    over the time the kernel took in that span; median over spans."""
    from benchmark import kernel_work

    rows = tables(facts, "decode")
    cfg = cell_config(metric, facts)
    if rows is None or cfg is None:
        return None
    peaks = _peaks()
    shares = []
    for _, _, km, args in rows:
        if km.get(kernel) and args.get("kv_positions"):
            flops, nbytes = kernel_work.decode_attention_work(
                cfg, int(args["kv_positions"]))
            shares.append(kernel_work.roofline_pct(
                flops, nbytes, km[kernel] * 1e-3, *peaks))
    return stats.median(shares) if shares else None


def span_host_ms(facts: dict, parent: str, children=None):
    """Median host ms of the program spans called ``parent`` (prefix
    ``elephas.`` left out), or of the ``children`` inside each."""
    t = for_facts(facts)
    if t is None:
        return None
    parents = named(t["spans"], SPAN_PREFIX + parent)
    if not parents:
        return None
    if children is None:
        return stats.median([(e - s) * 1e3 for _, s, e, _ in parents])
    names = {SPAN_PREFIX + c for c in children}
    return stats.median([children_of(t["spans"], p, names) * 1e3
                         for p in parents])


def idle_unattributed_pct(facts: dict):
    """Idle time of the first device under no program span, over all its
    idle time in the benchmark's window."""
    t = for_facts(facts)
    if t is None or not t["spans"]:
        return None
    idle = idle_by_leaf_span(t["ops"], t["spans"], t["lo"], t["hi"])
    all_idle = sum(idle.values())
    return 100.0 * idle.get(NO_SPAN, 0.0) / all_idle if all_idle else None


# -- by hand ------------------------------------------------------------

def _table(title, rows):
    print(title)
    for row in rows:
        print("  " + "  ".join(f"{c:>12.3f}" if isinstance(c, float)
                               else f"{c:<28}" for c in row))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    loaded = load(trace.find_xplane(argv[0] if argv else TRACE_DIR))
    if not loaded["device_ops"]:
        print("no device plane in this trace")
        return 1
    first = sorted(loaded["device_ops"])[0]
    ops, spans = loaded["device_ops"][first], loaded["spans"]
    _, bench = trace.load_xplane(trace.find_xplane(
        argv[0] if argv else TRACE_DIR))
    if bench:
        lo, hi = bench[0][1], max(e for _, _, e in bench)
    else:
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    by = device_ms_by_scope(ops, lo, hi)
    busy = sum(by.values())
    print(f"{first}: window {hi - lo:.3f} s, busy {busy:.1f} ms "
          f"({len(loaded['device_ops'])} device(s) in the trace)")
    scopes = sorted({s for s, _ in by},
                    key=lambda s: -(by.get((s, False), 0.0)
                                    + by.get((s, True), 0.0)))
    _table("device ms by scope: forward, backward, share of busy %",
           [(s, by.get((s, False), 0.0), by.get((s, True), 0.0),
             100.0 * (by.get((s, False), 0.0) + by.get((s, True), 0.0))
             / busy) for s in scopes])
    _table("device ms by kernel",
           sorted(kernel_ms(ops, lo, hi).items(), key=lambda kv: -kv[1]))
    _table("device ms of the largest operations under no scope",
           unscoped_ops(ops, lo, hi))
    decodes = [(s, e) for _, s, e, _ in
               named(spans, SPAN_PREFIX + "engine.decode")]
    whole = whole_steps(loaded["modules"].get(first, []))
    for what, windows in ((SPAN_PREFIX + "engine.decode span", decodes),
                          ("whole step", [] if decodes else whole)):
        if not windows:
            continue
        ordered, starts = by_start(ops)
        per = [device_ms_by_scope(ops_between(ordered, starts, s, e), s, e)
               for s, e in windows]
        keys = sorted({k for p in per for k in p},
                      key=lambda k: -stats.median(
                          [p.get(k, 0.0) for p in per]))
        _table(f"median device ms per {what} ({len(windows)} of them), "
               "by scope and direction",
               [(f"{s}{' (backward)' if b else ''}",
                 stats.median([p.get((s, b), 0.0) for p in per]))
                for s, b in keys])
    idle = idle_by_leaf_span(ops, spans, lo, hi)
    all_idle = sum(idle.values())
    _table(f"idle ms by leaf span (idle {all_idle * 1e3:.1f} ms of the "
           "window), share of idle %",
           [(n, sec * 1e3, 100.0 * sec / all_idle) for n, sec in
            sorted(idle.items(), key=lambda kv: -kv[1])] if all_idle else [])
    names = sorted({n for n, _, _, _ in spans})
    _table("median host ms per program span, count",
           [(n, stats.median([(e - s) * 1e3 for _, s, e, _ in
                              named(spans, n)]),
             float(len(named(spans, n)))) for n in names])
    for name in ("train_step", "engine.step:decode"):
        starts = [s for n, s, _ in bench if n.startswith(name.split(":")[0])]
        if len(starts) > 2:
            gaps_ms = [(b - a) * 1e3 for a, b in zip(starts, starts[1:])]
            print(f"median ms from one bench:{name.split(':')[0]} span to "
                  f"the next, profiler on: {stats.median(gaps_ms):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
