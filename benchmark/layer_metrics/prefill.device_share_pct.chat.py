"""Device-busy time inside steps that returned "prefill", over all of it."""
from benchmark import readers


def read(facts):
    return readers.span_device_share_pct(facts, "engine.step:prefill")
