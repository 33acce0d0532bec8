"""The operations and bytes a kernel's ALGORITHM needs, from the
configuration's published keys and the cell's shapes; never from a kernel's
block sizes, padding or recomputation. A kernel's roofline share is the
least time the chip could take for that work (``peaks.py``) over the time
the kernel took in the trace, so a kernel that reads more than it needs
(``flash_decode`` reads every slot's whole horizon) or recomputes (the
flash backward) shows it as a low share, not as extra work.

Pure arithmetic: ``tests/benchmark/test_program_trace.py`` counts each
function by hand at tiny shapes.
"""

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def attended_keys(seq_len: int, window=None) -> float:
    """Sum over the ``seq_len`` queries of one causal sequence of the keys
    each attends: ``T (T + 1) / 2``, or ``min(window, t + 1)`` per query
    under a sliding window."""
    if window and window < seq_len:
        return window * (window + 1) / 2 + (seq_len - window) * window
    return seq_len * (seq_len + 1) / 2


def causal_attention_flops(batch: int, heads: int, dh: int, seq_len: int,
                           window=None, backward: bool = False) -> float:
    """One layer's attention core on ``batch`` sequences: scores and
    weighted values, 2 operations a multiply-add each, so ``4 B H Dh``
    per attended (query, key) pair. The backward needs twice the forward
    (dq; dk and dv; the recomputed scores are not counted)."""
    fwd = 4.0 * batch * heads * dh * attended_keys(seq_len, window)
    return 2.0 * fwd if backward else fwd


def causal_attention_bytes(batch: int, heads: int, kv_heads: int, dh: int,
                           seq_len: int, itemsize: int,
                           backward: bool = False) -> float:
    """One layer: q and o (``heads``), k and v (``kv_heads``) once each;
    the backward reads those four and ``do`` and writes dq, dk, dv."""
    qo = batch * seq_len * heads * dh * itemsize
    kv = batch * seq_len * kv_heads * dh * itemsize
    return float(4 * qo + 4 * kv) if backward else float(2 * qo + 2 * kv)


def decode_attention_bytes(kv_positions: int, kv_heads: int, dh: int,
                           itemsize: int, layers: int) -> float:
    """K and V of every live key position, in every layer: what one decode
    step must read, whatever the kernel does read. ``kv_positions`` is the
    step's sum over live rows of the keys the row attends (the
    ``kv_positions`` argument of its ``elephas.engine.decode`` span)."""
    return 2.0 * kv_heads * dh * itemsize * layers * kv_positions


def decode_attention_flops(kv_positions: int, heads: int, dh: int,
                           layers: int) -> float:
    """Scores and weighted values of one query per row against its live
    keys: ``4 H Dh`` per key position and layer."""
    return 4.0 * heads * dh * layers * kv_positions


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bytes: float):
    """``(seconds, bound)``: the least time for this work on a chip with
    these peaks, and which of the two sets it (``"compute"`` or
    ``"bandwidth"``)."""
    t_flops, t_bytes = flops / peak_flops, nbytes / peak_bytes
    return ((t_flops, "compute") if t_flops >= t_bytes
            else (t_bytes, "bandwidth"))


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 peak_flops: float, peak_bytes: float):
    """Least time over measured time, in percent; ``None`` without a
    measured time."""
    if not seconds or seconds <= 0:
        return None
    return 100.0 * least_seconds(flops, nbytes, peak_flops,
                                 peak_bytes)[0] / seconds


# -- the cells' shapes -------------------------------------------------------

def train_attention_work(cfg: dict, backward: bool):
    """``(flops, bytes)`` of the flash kernels in ONE train step on ONE
    chip: ``rows_per_chip`` sequences of ``sequence_length`` in every
    layer, in the compute dtype."""
    tr = cfg["train"]
    b, t = tr["rows_per_chip"], tr["sequence_length"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    layers = cfg["num_hidden_layers"]
    size = ITEMSIZE[cfg["compute_dtype"]]
    return (layers * causal_attention_flops(b, h, dh, t,
                                            cfg.get("sliding_window"),
                                            backward),
            layers * causal_attention_bytes(b, h, hkv, dh, t, size,
                                            backward))


def decode_attention_work(cfg: dict, kv_positions: int):
    """``(flops, bytes)`` of the decode-attention kernel in ONE decode
    step whose live rows attend ``kv_positions`` keys in all."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    layers = cfg["num_hidden_layers"]
    size = ITEMSIZE[cfg["compute_dtype"]]
    return (decode_attention_flops(kv_positions, h, dh, layers),
            decode_attention_bytes(kv_positions, hkv, dh, size, layers))
