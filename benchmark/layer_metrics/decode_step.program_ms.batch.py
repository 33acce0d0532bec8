"""Median length, ms, of a decode program's own execution on the device's
``XLA Modules`` line, joined by ``launch`` to the ``elephas.engine.decode.
dispatch`` span that enqueued it (``program_runs``): what
``decode_step.device_ms.batch`` times from outside, through the benchmark's
``bench:engine.step:decode`` span."""
from benchmark import program_runs as pr


def read(facts):
    return pr.median_of(facts, pr.program_ms)
