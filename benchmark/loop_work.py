"""Bytes and operations a LOOPED stack's decode step needs (the ``ouro``
family: the whole layer stack run ``total_ut_steps`` times a token), whatever
implements it, and the readers of its cell. As ``kernel_work.py``: what the
ALGORITHM needs, from the configuration's keys and the span's own
arguments, never what a kernel pads to or reads twice.

One decode step of a stack of ``L = num_hidden_layers`` layers run
``passes`` times (the ``passes`` argument of an ``elephas.engine.decode``
span; the configuration's ``total_ut_steps``):

- the attention kernel reads the K and V of every live key position in
  EVERY pass's cache layers, ``passes x L`` of them, each pass its own:
  ``kernel_work.decode_attention_work`` counts ``L`` and so a quarter of it
  at four passes;
- the layer stacks are read once a pass, ``passes x L x`` (q, k, v, o and
  the three FFN matrices, in the compute dtype), and the head once: the
  four norm scales a layer (float32, 32 KiB of a layer's 103 MB) are left
  out. Every live row multiplies by all of it, 2 operations a parameter
  and a row.
"""

from benchmark import program_trace as pt
from benchmark import stats
from benchmark.kernel_work import (ITEMSIZE, decode_attention_bytes,
                                   decode_attention_flops, head_dim,
                                   roofline_pct)


def cache_layers(cfg: dict, passes: int) -> int:
    """The cache layers a step of ``passes`` passes walks."""
    return int(passes) * cfg["num_hidden_layers"]


def looped_decode_work(cfg: dict, kv_positions: int, passes: int):
    """``(flops, bytes)`` of the decode-attention kernel in ONE decode
    step: the step's ``kv_positions`` (its sum over live rows of the keys
    each attends) in every pass's every layer."""
    layers = cache_layers(cfg, passes)
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    size = ITEMSIZE[cfg["compute_dtype"]]
    return (decode_attention_flops(kv_positions, h, dh, layers),
            decode_attention_bytes(kv_positions, hkv, dh, size, layers))


def layer_parameters(cfg: dict) -> int:
    """The matrices of one layer: q, k, v, o and the SwiGLU FFN's three."""
    d, f, dh = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * dh
    kv = cfg["num_key_value_heads"] * dh
    return d * (2 * q + 2 * kv) + 3 * d * f


def pass_weights_work(cfg: dict, passes: int, rows: int):
    """``(flops, bytes)`` of the weight products in ONE decode step of
    ``rows`` live rows: every layer's matrices once a pass, the head
    once."""
    size = ITEMSIZE[cfg["compute_dtype"]]
    params = (cache_layers(cfg, passes) * layer_parameters(cfg)
              + cfg["hidden_size"] * cfg["vocab_size"])
    return 2.0 * rows * params, float(params * size)


def _decode_rows(facts: dict):
    """``(cfg, rows)``: the decode spans' ``_tables`` whose ``passes`` is
    the configuration's ``total_ut_steps``; ``None`` for a run that was
    not traced, a configuration that does not loop, or a program whose
    spans do not say (the parent of the PR that brought the loop)."""
    cfg = facts.get("cfg") or {}
    want = cfg.get("total_ut_steps")
    rows = pt.tables(facts, "decode") if want else None
    if rows is None:
        return None
    rows = [r for r in rows if r[3].get("passes") == want]
    return (cfg, rows) if rows else None


def looped_decode_roofline_pct(facts: dict, kernel: str = "flash_decode"):
    """Per decode span: the least time to read every pass's K and V of the
    span's ``kv_positions`` over the span's ms in kernel ``kernel``;
    median over spans."""
    found = _decode_rows(facts)
    if found is None:
        return None
    cfg, rows = found
    peaks = pt._peaks()
    shares = [roofline_pct(*looped_decode_work(cfg, int(args["kv_positions"]),
                                               args["passes"]),
                           km[kernel] * 1e-3, *peaks)
              for _, _, km, args in rows
              if km.get(kernel) and args.get("kv_positions")]
    return stats.median(shares) if shares else None


WEIGHT_SCOPES = ("attn", "ffn", "head")


def pass_weights_roofline_pct(facts: dict):
    """Per decode span: the least time to read the layer stacks once a pass
    and the head once, over the span's device ms under the scopes that
    multiply by them (``attn``: the projections and their norms; ``ffn``;
    ``head``: the final norms and the logits); median over spans."""
    found = _decode_rows(facts)
    if found is None:
        return None
    cfg, rows = found
    peaks = pt._peaks()
    shares = []
    for _, by, _, args in rows:
        ms = pt.pick(by, WEIGHT_SCOPES)
        if ms and args.get("n_active"):
            shares.append(roofline_pct(
                *pass_weights_work(cfg, args["passes"],
                                   int(args["n_active"]) * int(
                                       args.get("k", 1))),
                ms * 1e-3, *peaks))
    return stats.median(shares) if shares else None
