"""Median ms the device idles inside one ``elephas.engine.prefill`` span,
from its start to the end of the last execution it launched (joined by
``launch``): what one admission costs a device that waits for the host."""
from benchmark import program_runs as pr


def read(facts):
    return pr.median_of(facts, pr.prefill_stall_ms)
