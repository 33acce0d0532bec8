"""Prefill's wasted work: 1 - prompt tokens inserted / the bucket sizes they
were padded to, from the engine's ``work`` counters (cumulative, warm-up
included)."""


def read(facts):
    work = facts.get("snapshot", {}).get("work")
    if not work or not work.get("prefill_padded_tokens"):
        return None
    return 100.0 * (1.0 - work["prefill_tokens"]
                    / work["prefill_padded_tokens"])
