"""Share of its roofline of the ``gdn_decode`` Pallas kernel, per decode
span, median: bandwidth-bound, the span's ``state_rows`` (live rows x
linear layers) x 2 x heads x 96 x 192 x 4 bytes (a float32 state read once
and written once: ``gdn_work``) / 819 GB/s over the kernel's ms."""
from benchmark import gdn_work


def read(facts):
    return gdn_work.gdn_decode_roofline_pct(facts)
