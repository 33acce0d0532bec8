"""Median number of executions on the ``XLA Modules`` line from the one an
admission's ``prefill.insert`` span launched to the one its ``prefill.
set_row`` span launched: the engine's own calls and the programs that eager
JAX calls enqueue between them."""
from benchmark import program_runs as pr


def read(facts):
    return pr.median_of(facts, pr.programs_per_request)
