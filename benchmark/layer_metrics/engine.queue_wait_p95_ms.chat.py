"""p95 of FinishedRequest.timing.queue_wait (submit to admission), ms."""
from benchmark import stats


def read(facts):
    v = [r["queue_wait_s"] * 1e3 for r in facts.get("records", [])
         if r.get("queue_wait_s") is not None]
    return stats.percentile(v, 95) if v else None
