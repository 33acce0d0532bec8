"""Per whole train step on the first device: ms in operations of the
backward pass (``transpose(`` on the path), the gradient reduction left
out: that is the ``grad_reduce`` scope, beside ``collective.exposed_ms``."""
from benchmark import program_trace as pt


def read(facts):
    return pt.scope_ms(facts, "step", backward=True,
                       exclude=("optimizer", "grad_reduce", pt.UNSCOPED))
