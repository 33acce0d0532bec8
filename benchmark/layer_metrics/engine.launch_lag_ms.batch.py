"""Median ms from the later of a ``decode.dispatch`` span's start and the
end of the execution before to the start of the execution that span's
``launch`` joins it to: the device waiting for the enqueue. Negative only
where the host's clock and the device's disagree in the trace; not clipped."""
from benchmark import program_runs as pr


def read(facts):
    return pr.median_of(facts, pr.launch_lag_ms)
