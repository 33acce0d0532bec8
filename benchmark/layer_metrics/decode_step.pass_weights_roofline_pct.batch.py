"""Share of its roofline of a LOOPED stack's weight products, per decode
span, median: the layer stacks' bytes once a pass plus the head's
(``loop_work``) / 819 GB/s over the span's device ms under the scopes
``attn``, ``ffn`` and ``head``. Nothing where the span's ``passes`` is not
the configuration's ``total_ut_steps``."""
from benchmark import loop_work


def read(facts):
    return loop_work.pass_weights_roofline_pct(facts)
