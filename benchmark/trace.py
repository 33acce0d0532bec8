"""From a profiler trace to numbers: busy union, idle share, idle gaps
named by what the host was doing, device time inside host spans, exposed
collective time. Pure functions over ``(name, start, end)`` events so that
tests can feed a hand-built list; ``load_xplane`` turns an ``.xplane.pb``
into such lists with nothing but ``jax.profiler.ProfileData``.

Times are seconds on the trace's own clock. The benchmark's host spans are
``jax.profiler.TraceAnnotation``s whose names start with ``bench:``.
"""

import glob
import os
import re

SPAN_PREFIX = "bench:"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
OPS_LINE = "XLA Ops"


# -- interval arithmetic ---------------------------------------------------

def union(intervals):
    """Sorted disjoint intervals covering the same points."""
    out = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, holes):
    """The part of ``intervals`` (disjoint, sorted) outside ``holes``."""
    out = []
    holes = union(holes)
    for a, b in intervals:
        cur = a
        for ha, hb in holes:
            if hb <= cur or ha >= b:
                continue
            if ha > cur:
                out.append((cur, ha))
            cur = max(cur, hb)
        if cur < b:
            out.append((cur, b))
    return out


def gaps(busy, lo: float, hi: float):
    """The idle intervals of ``[lo, hi]`` given disjoint sorted ``busy``."""
    return subtract([(lo, hi)], busy)


# -- reductions --------------------------------------------------------------

def busy_and_window(device_ops, lo: float, hi: float):
    """``(busy_s, window_s)``: the union of op intervals inside the window,
    averaged over devices. ``device_ops`` maps device -> events."""
    per_dev = [total(clip(union((s, e) for _, s, e in ops), lo, hi))
               for ops in device_ops.values()]
    if not per_dev:
        return 0.0, hi - lo
    return sum(per_dev) / len(per_dev), hi - lo


def short_name(op_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), kind=kLoop`` -> ``fusion.12
    [kLoop]``; a Mosaic kernel is marked ``[tpu_custom_call]``. The XLA Ops
    line names an event by its whole HLO text, too long to print."""
    head = op_name.split(" = ")[0].lstrip("%")
    for tag in ('custom_call_target="', "kind="):
        if tag in op_name:
            rest = op_name.split(tag, 1)[1]
            return f"{head} [{re.split(r'[\",) ]', rest, maxsplit=1)[0]}]"
    return head


def self_times(ops):
    """``(name, self seconds)`` per event of one device: an event's
    duration minus the events nested inside it (a ``while`` holds its
    body's operations, which are events of their own)."""
    out, stack = [], []          # stack of [name, end, self]
    for name, s, e in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((name, self_s) for name, _, self_s in stack)
    return out


def top_ops(device_ops, lo: float, hi: float, n: int = 10):
    """The ``n`` operations with most self time on the device in the
    window, averaged over devices: ``[[short name, seconds], ...]``."""
    acc = {}
    for ops in device_ops.values():
        inside = [(nm, max(s, lo), min(e, hi)) for nm, s, e in ops
                  if min(e, hi) > max(s, lo)]
        for name, sec in self_times(inside):
            key = short_name(name)
            acc[key] = acc.get(key, 0.0) + sec
    k = max(len(device_ops), 1)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / k] for name, sec in rows]


def gaps_by_span(ops, spans, lo: float, hi: float, n: int = 10):
    """Idle time of ONE device inside ``[lo, hi]``, attributed to the host
    span that covers most of each gap (``(none)`` where no span does):
    ``[[span name, seconds], ...]``, longest first."""
    busy = clip(union((s, e) for _, s, e in ops), lo, hi)
    acc = {}
    for a, b in gaps(busy, lo, hi):
        best, cover = "(none)", 0.0
        for name, s, e in spans:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
        acc[best] = acc.get(best, 0.0) + (b - a)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in rows]


def busy_inside(ops, lo: float, hi: float) -> float:
    """Device-busy seconds of one device inside one host span."""
    return total(clip(union((s, e) for _, s, e in ops), lo, hi))


def exposed_collective_s(ops, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which a collective runs on this device
    and no other operation does."""
    coll = union((s, e) for n, s, e in ops if COLLECTIVE.search(n))
    rest = union((s, e) for n, s, e in ops if not COLLECTIVE.search(n))
    return total(clip(subtract(coll, rest), lo, hi))


def share_matching(device_ops, pattern, lo: float, hi: float):
    """Share (0-1) of device-busy time in ops whose name matches
    ``pattern``; ``None`` with no busy time."""
    rx = re.compile(pattern)
    hit = all_ = 0.0
    for ops in device_ops.values():
        all_ += total(clip(union((s, e) for _, s, e in ops), lo, hi))
        hit += total(clip(union((s, e) for n, s, e in ops if rx.search(n)),
                          lo, hi))
    return hit / all_ if all_ > 0 else None


# -- reading the profiler's file ---------------------------------------------

def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str):
    """``(device_ops, spans)``: ``device_ops`` maps each device plane's
    name to its ``(op name, start_s, end_s)`` events on the ``XLA Ops``
    line; ``spans`` are the host events named ``bench:...`` (prefix
    stripped), from every host thread."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    spans.sort(key=lambda s: s[1])
    return device_ops, spans


def summarize(path: str, rename=None):
    """Everything the per-layer readers need from one trace. ``rename``
    maps a span name to the name it is reported under (the serving drivers
    learn a step's action only after the span has closed). The window is
    the hull of the benchmark's own spans."""
    device_ops, spans = load_xplane(path)
    if rename:
        spans = [(rename(n), s, e) for n, s, e in spans]
    if not spans or not device_ops:
        return None
    lo, hi = spans[0][1], max(e for _, _, e in spans)
    busy_s, window_s = busy_and_window(device_ops, lo, hi)
    first = sorted(device_ops)[0]
    return {
        "device_ops": device_ops, "spans": spans, "lo": lo, "hi": hi,
        "busy_s": busy_s, "window_s": window_s,
        "breakdown": {
            "device_ops": top_ops(device_ops, lo, hi),
            "idle_gaps": gaps_by_span(device_ops[first], spans, lo, hi),
        },
    }


class Profiler:
    """``jax.profiler`` around a sub-window, into a fixed directory inside
    the checkout, reduced as soon as it stops."""

    def __init__(self, out_dir: str):
        import shutil

        self.dir = os.path.join(out_dir, "trace")
        shutil.rmtree(self.dir, ignore_errors=True)

    def start(self):
        import jax

        jax.profiler.start_trace(self.dir)

    def stop(self, rename=None):
        import jax

        jax.profiler.stop_trace()
        return summarize(find_xplane(self.dir), rename=rename)
