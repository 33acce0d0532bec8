"""Per whole train step on the first device: ms under the ``head`` (final
norm, logits matmul) and ``loss`` scopes, forward and backward."""
from benchmark import program_trace as pt


def read(facts):
    return pt.scope_ms(facts, "step", pt.HEAD_LOSS)
