"""Per ``elephas.engine.decode`` span: device ms under ``ffn`` (the block's
second half: norm, residual) and the ``moe`` scopes inside it (route,
dispatch, experts, combine), median."""
from benchmark import program_trace as pt


def read(facts):
    return pt.scope_ms(facts, "decode", pt.FFN)
