"""Mean share of decode rows that were live, from the engine's own counter
(cumulative over the run, warm-up included)."""


def read(facts):
    v = facts.get("snapshot", {}).get("engine", {}).get("batch_occupancy")
    return None if v is None else 100.0 * v
