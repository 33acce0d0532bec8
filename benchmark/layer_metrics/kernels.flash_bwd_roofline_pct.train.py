"""Share of their roofline of the ``flash_bwd_dq`` and ``flash_bwd_dkv``
Pallas kernels together in a train step: compute-bound, twice the forward's
operations (the scores both kernels recompute are not counted)."""
from benchmark import program_trace as pt

NAME = "kernels.flash_bwd_roofline_pct.train"


def read(facts):
    return pt.train_kernel_roofline_pct(
        NAME, facts, ("flash_bwd_dq", "flash_bwd_dkv"), True)
