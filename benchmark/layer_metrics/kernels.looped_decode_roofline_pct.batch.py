"""Share of its roofline of the ``flash_decode`` Pallas kernel in a LOOPED
stack's decode step, per decode span, median: bandwidth-bound, K and V of
the span's ``kv_positions`` in every pass's layers (``passes`` x layers x 2
Hkv Dh itemsize bytes each: ``loop_work``) / 819 GB/s over the kernel's
ms. Nothing where the span's ``passes`` is not the configuration's
``total_ut_steps``."""
from benchmark import loop_work


def read(facts):
    return loop_work.looped_decode_roofline_pct(facts)
