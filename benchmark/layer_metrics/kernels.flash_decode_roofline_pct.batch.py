"""Share of its roofline of the ``flash_decode`` Pallas kernel, per decode
span, median: bandwidth-bound (K and V of the live key positions, 2 Hkv Dh
itemsize bytes each a layer, from the span's ``kv_positions``; 4 operations a
byte, far under the chip's 240)."""
from benchmark import program_trace as pt

NAME = "kernels.flash_decode_roofline_pct.batch"


def read(facts):
    return pt.decode_kernel_roofline_pct(NAME, facts, "flash_decode")
