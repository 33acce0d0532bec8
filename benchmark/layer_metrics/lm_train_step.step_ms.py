"""Median host-clock ms of one step (input + program + loss fetch), over
the window, outside the profiled steps."""
from benchmark import stats


def read(facts):
    v = facts.get("step_ms")
    return stats.median(v) if v else None
