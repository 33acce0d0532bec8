"""Per ``elephas.engine.decode`` span: device ms under ``attn_latent`` (the
latent decode kernel over every layer's cached rows), median."""
from benchmark import exaone_moe_work


def read(facts):
    return exaone_moe_work.scope_word_ms(facts, "attn_latent")
