"""Share of its roofline of the ``flash_fwd`` Pallas kernel in a train step:
compute-bound (4 B H Dh T (T+1)/2 operations a layer against the bf16 peak;
its q, k, v, o bytes need a seventh of that time)."""
from benchmark import program_trace as pt

NAME = "kernels.flash_fwd_roofline_pct.train"


def read(facts):
    return pt.train_kernel_roofline_pct(NAME, facts, ("flash_fwd",), False)
