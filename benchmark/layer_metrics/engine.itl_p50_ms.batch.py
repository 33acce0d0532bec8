"""Median gap between a request's consecutive tokens, ms (recorded, not
judged: above capacity the tails swing)."""
from benchmark import stats


def read(facts):
    v = facts.get("token_gaps_ms")
    return stats.median(v) if v else None
