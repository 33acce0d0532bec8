"""Median ms from the end of a decode program's execution to the end of the
``elephas.engine.decode.fetch`` span that carries the same ``launch``: how
long after the device is done the host has the tokens."""
from benchmark import program_runs as pr


def read(facts):
    return pr.median_of(facts, pr.fetch_lag_ms)
