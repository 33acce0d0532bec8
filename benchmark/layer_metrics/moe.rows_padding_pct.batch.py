"""Rows the dropless expert executor ran that were padding: 1 - pairs
routed to held experts / rows computed (tile padding included), from the
engine's device-side ``work`` counters (cumulative, warm-up and prefill
included)."""


def read(facts):
    work = facts.get("snapshot", {}).get("work")
    if not work or not work.get("moe_rows_computed"):
        return None
    return 100.0 * (1.0 - work["moe_pairs_held"] / work["moe_rows_computed"])
