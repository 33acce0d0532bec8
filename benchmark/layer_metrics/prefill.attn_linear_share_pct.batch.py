"""Device time under ``attn_linear`` (the chunkwise form of the linear
layers' recurrence, ``gdn_chunk``) inside the engine's prefill spans over
all device time inside them, in percent: what a Pallas chunk kernel could
win of an insert."""
from benchmark import gdn_work


def read(facts):
    return gdn_work.prefill_scope_share_pct(facts, "attn_linear")
