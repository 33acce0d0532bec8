"""Median host ms of the ``elephas.engine.decode.emit`` span: the per-row
loop after a decode program (``_emit``, callbacks, finish, release, park).
The same quantity as ``fastpath.dispatch_overhead_s`` on the trace's clock."""
from benchmark import program_trace as pt


def read(facts):
    return pt.span_host_ms(facts, "engine.decode.emit")
