"""Per ``elephas.engine.decode`` span: device ms of KV-cache traffic, median:
``kv_write`` plus the ``layers`` scan's own slice, copy and update of the
stacked cache (operations scoped ``layers`` and nothing inside it)."""
from benchmark import program_trace as pt


def read(facts):
    return pt.scope_ms(facts, "decode", pt.CACHE_IO)
