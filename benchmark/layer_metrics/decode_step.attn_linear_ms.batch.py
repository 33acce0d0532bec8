"""Per ``elephas.engine.decode`` span: device ms under ``attn_linear`` (the
linear layers' recurrence: the ``gdn_decode`` kernel and what surrounds it
inside the scope), median."""
from benchmark import exaone_moe_work


def read(facts):
    return exaone_moe_work.scope_word_ms(facts, "attn_linear")
