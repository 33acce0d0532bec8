"""Per decode execution joined to its span by ``launch``: device self ms of
the operations inside the execution's own interval minus that of the
operations that start inside its ``elephas.engine.decode`` span (what the
``decode_step.*`` and decode roofline readers give the step); median of the
absolute value. 0 while containment holds."""
from benchmark import program_runs as pr


def read(facts):
    return pr.median_of(facts, pr.span_short_ms, absolute=True)
