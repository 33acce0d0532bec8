"""Per whole train step on the first device: ms under the ``optimizer``
scope (``_lm_step_parts``' ``apply_impl``, fused or not)."""
from benchmark import program_trace as pt


def read(facts):
    return pt.scope_ms(facts, "step", ("optimizer",))
