"""Per ``elephas.engine.decode`` span: device ms under ``attn_window`` (the
decode kernel over the window layers' rings), median."""
from benchmark import exaone_moe_work


def read(facts):
    return exaone_moe_work.scope_word_ms(facts, "attn_window")
