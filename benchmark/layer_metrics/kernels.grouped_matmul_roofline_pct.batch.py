"""Share of its roofline of the routed experts' grouped matmuls in a decode
step: bandwidth-bound (each touched held expert's three matrices once, the
rows in and out: ``exaone_moe_work``) over the device ms under
``moe_experts`` per decode span, median."""
from benchmark import exaone_moe_work


def read(facts):
    return exaone_moe_work.grouped_matmul_roofline_pct(facts)
