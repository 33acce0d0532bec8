"""Median ms the device idles between two decode executions with no other
program between them (``XLA Modules``, the executions joined to
``decode.dispatch`` spans by ``launch``): the host's whole cost of a step,
launch lag + fetch lag + emit + reap + decide + the caller's loop."""
from benchmark import program_runs as pr


def read(facts):
    return pr.median_of(facts, pr.step_gap_ms)
