"""The arithmetic the per-layer readers share. Each reader under
``layer_metrics/`` is a few lines that pick one of these; a reader that
finds nothing to read returns ``None`` and the metric is left out."""

from benchmark import stats, trace


def _trace(facts):
    return facts.get("trace")


def device_idle_pct(facts):
    t = _trace(facts)
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def _first_device(t):
    return t["device_ops"][sorted(t["device_ops"])[0]]


def span_device_ms(facts, span_name):
    """Median device-busy ms inside the host spans of this name."""
    t = _trace(facts)
    if not t:
        return None
    ops = _first_device(t)
    vals = [trace.busy_inside(ops, s, e) * 1e3
            for n, s, e in t["spans"] if n == span_name]
    return stats.median(vals) if vals else None


def span_device_share_pct(facts, span_name):
    """Device-busy time inside spans of this name over all busy time."""
    t = _trace(facts)
    if not t:
        return None
    ops = _first_device(t)
    inside = sum(trace.busy_inside(ops, s, e)
                 for n, s, e in t["spans"] if n == span_name)
    all_ = trace.busy_inside(ops, t["lo"], t["hi"])
    return 100.0 * inside / all_ if all_ > 0 else None


def exposed_collective_ms_per_step(facts, span_name="train_step"):
    """Per step: collective time with nothing else running on the device;
    median over devices of the median over steps."""
    t = _trace(facts)
    if not t or len(t["device_ops"]) < 2:
        return None
    steps = [(s, e) for n, s, e in t["spans"] if n == span_name]
    if not steps:
        return None
    per_dev = [stats.median([trace.exposed_collective_s(ops, s, e) * 1e3
                             for s, e in steps])
               for ops in t["device_ops"].values()]
    return stats.median(per_dev)


def pallas_share_pct(facts):
    """Share of device-busy time in Mosaic (Pallas) custom calls."""
    t = _trace(facts)
    if not t:
        return None
    share = trace.share_matching(t["device_ops"], PALLAS_OP, t["lo"], t["hi"])
    return None if not share else 100.0 * share


# how a Mosaic (Pallas) kernel reads on the XLA Ops line of a v5e trace:
# the event's name is its HLO text, and the kernel is a custom call with
# this target (seen in PR 23's traces: flash forward and backward, decode)
PALLAS_OP = r'custom_call_target="tpu_custom_call"'
