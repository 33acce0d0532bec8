"""Host ms per step in make_lm_batches + shard_lm_batch (median)."""
from benchmark import stats


def read(facts):
    v = facts.get("input_ms")
    return stats.median(v) if v else None
