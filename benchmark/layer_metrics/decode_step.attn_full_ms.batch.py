"""Per ``elephas.engine.decode`` span: device ms under ``attn_full`` (the
decode kernel over the full-attention layers' horizon), median."""
from benchmark import exaone_moe_work


def read(facts):
    return exaone_moe_work.scope_word_ms(facts, "attn_full")
