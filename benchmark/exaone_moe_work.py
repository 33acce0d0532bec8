"""Operations and bytes of the grouped expert matmuls of a HELD share (the
``exaone_moe`` family's sparse layers), and the time under the two
attention scopes that ``program_trace.SCOPES`` does not know. As
``kernel_work.py``: what the ALGORITHM needs, never what an executor pads
to, so the same count holds whichever executor runs the experts (a Pallas
grouped matmul or ``ragged_dot``).

One call of one sparse layer on ``rows`` (token, expert) pairs whose expert
is held, ``experts`` of the held experts having at least one:

- bytes: every touched expert's three matrices once, ``experts x 3 x
  hidden_size x moe_intermediate_size x itemsize``, plus each row in and
  out, ``rows x 2 x hidden_size x itemsize`` (the ``[rows,
  moe_intermediate_size]`` intermediates can stay on the chip);
- operations: ``rows x 3 x 2 x hidden_size x moe_intermediate_size``.

A decode step at 128 slots has ~8 rows an expert: 0.03 operations a byte,
far under the chip's 240, so its roofline is the weight read.
"""

from benchmark import program_trace as pt
from benchmark import stats
from benchmark.kernel_work import ITEMSIZE, roofline_pct


def grouped_matmul_work(cfg: dict, rows: float, experts: float):
    """``(flops, bytes)`` of one sparse layer's routed experts on ``rows``
    held pairs over ``experts`` touched experts."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    size = ITEMSIZE[cfg["compute_dtype"]]
    return (rows * 3 * 2.0 * d * f,
            experts * 3.0 * d * f * size + rows * 2.0 * d * size)


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)


def decode_step_work(cfg: dict, work: dict):
    """``(flops, bytes)`` of the routed experts in ONE decode step: the
    mean over the run's decode steps, from the engine's device-side
    counters (``snapshot()["work"]``: pairs held, experts touched and layer
    calls of the decode steps alone), times the sparse layers a step
    walks. ``None`` where the program does not count (no such counters)."""
    calls = work.get("moe_decode_layer_calls")
    if not calls:
        return None
    flops, nbytes = grouped_matmul_work(
        cfg, work["moe_decode_pairs_held"] / calls,
        work["moe_decode_experts_touched"] / calls)
    n = sparse_layers(cfg)
    return n * flops, n * nbytes


def grouped_matmul_roofline_pct(facts: dict):
    """Least time for a decode step's routed-expert work over the median
    device ms under ``moe_experts`` in a decode span. The work is the
    run's mean step (warm-up and warm-in included, when fewer slots are
    live), the time the profiled steady state's: the share errs low."""
    work = decode_step_work(facts["cfg"],
                            facts.get("snapshot", {}).get("work", {}))
    ms = pt.scope_ms(facts, "decode", ("moe_experts",))
    if work is None or not ms:
        return None
    return roofline_pct(*work, ms * 1e-3, *pt._peaks())


def scope_word_ms(facts: dict, word: str):
    """Median over ``elephas.engine.decode`` spans of the device self ms
    in operations whose scope path holds ``word`` (a scope the program
    names inside one of ``program_trace.SCOPES``, such as ``attn_window``
    inside ``attn_core``). ``None`` for a run that was not traced or a
    program without the scope."""
    t = pt.for_facts(facts)
    if t is None:
        return None
    ops, starts = pt.by_start(t["ops"])
    spans = pt.named(t["spans"], pt.SPAN_PREFIX + "engine.decode")
    vals = [sum(ms for name, ms in
                pt._self_ms(pt.ops_between(ops, starts, s, e), s, e)
                if word in pt._WORD.findall(name))
            for _, s, e, _ in spans]
    return stats.median(vals) if any(vals) else None
