"""Share of the first device's busy time in operations that carry no scope
of the program's vocabulary (benchmark/program_trace.py SCOPES)."""
from benchmark import program_trace as pt


def read(facts):
    return pt.unscoped_pct(facts)
