"""Operations and bytes the Gated DeltaNet decode update needs, whatever
implements it, and the readers of the linear-attention layers. As
``kernel_work.py``: what the ALGORITHM needs, never what a kernel pads to
or reads twice.

One live row of one linear layer, one decode step: the row's recurrent
state, ``linear_num_value_heads x linear_key_head_dim x
linear_value_head_dim`` numbers, is read once and written once, in the
type the CONFIGURATION keeps it in, float32 (``assumed``: a narrower state
is a different result, not a higher share):

- bytes: ``2 x heads x dk x dv x 4`` (4,423,680 for 30 x 96 x 192; q, k,
  v, the gates and the output are a thousandth of that and not counted);
- operations: ``7 x heads x dk x dv`` (the decay, and a multiply and an add
  each for ``S^T k``, ``k u^T`` and ``S^T q``): 0.9 an operation a byte
  against the v5e's 240, so bandwidth bounds the kernel.
"""

from benchmark import program_trace as pt
from benchmark import stats
from benchmark.kernel_work import roofline_pct

STATE_ITEMSIZE = 4          # float32, the configuration's


def state_numbers(cfg: dict) -> int:
    """Numbers in one sequence's state of one linear layer."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])


def linear_layers(cfg: dict) -> int:
    return sum(k == "linear_attention" for k in cfg.get("layer_types") or ())


def gdn_decode_work(cfg: dict, state_rows: int):
    """``(flops, bytes)`` of the decode update of ``state_rows`` states
    (live rows x linear layers x steps: the ``state_rows`` argument of an
    ``elephas.engine.decode`` span)."""
    n = state_numbers(cfg)
    return 7.0 * n * state_rows, 2.0 * n * STATE_ITEMSIZE * state_rows


def gdn_decode_roofline_pct(facts: dict, kernel: str = "gdn_decode"):
    """Per decode span: the least time for the span's state traffic (bytes
    / peak bandwidth; the operations are far below their peak) over the
    time the kernel named ``kernel`` took in that span; median over spans.
    ``None`` for a run that was not traced, a program without the kernel
    or the span argument, or a configuration without linear layers."""
    cfg = facts.get("cfg") or {}
    rows = pt.tables(facts, "decode")
    if rows is None or not linear_layers(cfg):
        return None
    peaks = pt._peaks()
    shares = [roofline_pct(*gdn_decode_work(cfg, int(args["state_rows"])),
                           km[kernel] * 1e-3, *peaks)
              for _, _, km, args in rows
              if km.get(kernel) and args.get("state_rows")]
    return stats.median(shares) if shares else None


def prefill_scope_share_pct(facts: dict, word: str = "attn_linear"):
    """Device self time of operations whose scope path holds ``word``
    inside the ``elephas.engine.prefill`` spans (the host waits in each for
    its insert program), over all device time inside them, in percent.
    ``None`` for a run that was not traced, without such spans, or for a
    program without the scope."""
    t = pt.for_facts(facts)
    if t is None:
        return None
    ops, starts = pt.by_start(t["ops"])
    hit = all_ = 0.0
    for name in ("engine.prefill", "engine.prefill_chunk"):
        for _, s, e, _ in pt.named(t["spans"], pt.SPAN_PREFIX + name):
            for op, ms in pt._self_ms(pt.ops_between(ops, starts, s, e),
                                      s, e):
                all_ += ms
                if word in pt._WORD.findall(op):
                    hit += ms
    return 100.0 * hit / all_ if hit and all_ else None
