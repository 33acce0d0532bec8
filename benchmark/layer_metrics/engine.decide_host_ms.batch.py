"""Median host ms per ``elephas.engine.step`` in its ``reap`` and ``decide``
spans: shedding, expiry and the scheduler's choice of the step's action."""
from benchmark import program_trace as pt


def read(facts):
    return pt.span_host_ms(facts, "engine.step",
                           ("engine.reap", "engine.decide"))
