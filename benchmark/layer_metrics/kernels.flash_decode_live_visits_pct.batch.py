"""Visits of the decode kernel that found a block with a key to attend:
100 x cache blocks the live rows attended / blocks the kernel visited for
them (all KV heads of a block one visit), summed over decode steps and
layers, from the engine's ``work`` counters (cumulative, warm-up included).
A share of visits, 100 when the kernel walks live blocks only; a program
without the counters (or one whose decode runs another kernel) reports
nothing."""


def read(facts):
    work = facts.get("snapshot", {}).get("work")
    if not work or not work.get("decode_kv_blocks_walked"):
        return None
    return (100.0 * work["decode_kv_blocks_live"]
            / work["decode_kv_blocks_walked"])
