"""Operations and bytes the latent (MLA) decode attention needs, whatever
implements it, and the readers that use them. As ``kernel_work.py``: what
the ALGORITHM needs, never what a kernel pads to or reads twice.

With ``W_UK`` absorbed into the query and ``W_UV`` into the output, one
live position of one layer is ONE cached row of ``kv_lora_rank +
qk_rope_head_dim`` numbers, read once for all heads, and per head a score
against the whole row and a weighted sum of its first ``kv_lora_rank``
columns:

- bytes: ``(kv_lora_rank + qk_rope_head_dim) x itemsize`` (1,152 for 512 +
  64 in bfloat16; a kernel that keeps the row padded to whole lanes reads
  1,280 and shows it as a lower share);
- operations: ``2 x heads x (kv_lora_rank + qk_rope_head_dim +
  kv_lora_rank)`` (139,264 for 64 heads: 121 a byte against the v5e's 240,
  so bandwidth bounds the kernel, by a factor of two only).
"""

from benchmark import program_trace as pt
from benchmark import stats
from benchmark.kernel_work import ITEMSIZE, roofline_pct


def latent_row(cfg: dict) -> int:
    """Numbers a position keeps a layer: the latent and the shared key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_decode_work(cfg: dict, kv_positions: int):
    """``(flops, bytes)`` of latent decode attention in ONE decode step
    over every layer held: ``kv_positions`` is the step's sum over live
    rows of the positions the row attends (the ``kv_positions`` argument
    of its ``elephas.engine.decode`` span)."""
    layers = cfg["num_hidden_layers"]
    row = latent_row(cfg)
    return (2.0 * cfg["num_attention_heads"] * (row + cfg["kv_lora_rank"])
            * layers * kv_positions,
            float(row * ITEMSIZE[cfg["compute_dtype"]]) * layers
            * kv_positions)


def mla_decode_roofline_pct(facts: dict, kernel: str = "mla_decode"):
    """Per decode span: the least time for the span's latent decode work
    (the larger of bytes / peak bandwidth and operations / peak rate) over
    the time the kernel named ``kernel`` took in that span; median over
    spans. ``None`` for a run that was not traced, a program without the
    kernel, or a configuration without latent attention."""
    cfg = facts.get("cfg") or {}
    rows = pt.tables(facts, "decode")
    if rows is None or cfg.get("kv_lora_rank") is None:
        return None
    peaks = pt._peaks()
    shares = [roofline_pct(*latent_decode_work(cfg,
                                               int(args["kv_positions"])),
                           km[kernel] * 1e-3, *peaks)
              for _, _, km, args in rows
              if km.get(kernel) and args.get("kv_positions")]
    return stats.median(shares) if shares else None


def kv_live_positions(facts: dict):
    """Median over ``elephas.engine.decode`` spans of the span's
    ``kv_positions`` over its ``n_active``: how deep a live slot is, which
    is what the traffic offers the cache. ``None`` without a traced run or
    without the span's arguments."""
    t = pt.for_facts(facts)
    if t is None:
        return None
    vals = [int(a["kv_positions"]) / int(a["n_active"])
            for _, _, _, a in pt.named(t["spans"],
                                       pt.SPAN_PREFIX + "engine.decode")
            if a.get("kv_positions") and a.get("n_active")]
    return stats.median(vals) if vals else None
