"""Idle time of the device under no ``elephas.*`` span (the caller's own
loop between two ``engine.step`` calls), over all its idle time."""
from benchmark import program_trace as pt


def read(facts):
    return pt.idle_unattributed_pct(facts)
