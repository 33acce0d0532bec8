"""How late the load generator ran: submit time minus due time, p95."""
from benchmark import stats


def read(facts):
    v = facts.get("lateness_ms")
    return stats.percentile(v, 95) if v else None
