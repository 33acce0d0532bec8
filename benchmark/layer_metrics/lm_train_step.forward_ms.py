"""Per whole train step on the first device: ms in operations of the
forward pass (no ``transpose(`` on the path), the optimizer and the gradient
reduction left out. Reads the scopes of elephas_tpu/models/transformer.py."""
from benchmark import program_trace as pt


def read(facts):
    return pt.scope_ms(facts, "step", backward=False,
                       exclude=("optimizer", "grad_reduce", pt.UNSCOPED))
