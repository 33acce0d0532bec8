"""Per ``elephas.engine.decode`` span: device ms under ``attn`` (norm, q/k/v/o
projections, rotary) and ``attn_core`` (the decode kernel), median."""
from benchmark import program_trace as pt


def read(facts):
    return pt.scope_ms(facts, "decode", pt.ATTN)
