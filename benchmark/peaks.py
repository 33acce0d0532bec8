"""The chip's published peaks, and the operations a trained token needs.

Copied from ``bench.py`` (the peak table keyed by ``device_kind``, no
default; ``lm_train_flops_per_token``) so that no later PR can change the
yardstick by editing the program's harness. Source of the v5e row: Google
Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
"""

# device_kind substring -> (bf16 FLOP/s, HBM bytes/s, HBM bytes)
PEAKS = (
    ("v5 lite", (197e12, 819e9, 16e9)),
    ("v5litepod", (197e12, 819e9, 16e9)),
    ("v5e", (197e12, 819e9, 16e9)),
)


def peaks_for(device_kind: str):
    """``(flops_per_s, bytes_per_s, hbm_bytes)`` of one chip. A device that
    is not in the table is an error, never a default."""
    kind = device_kind.lower()
    for tag, row in PEAKS:
        if tag in kind:
            return row
    raise ValueError(
        f"no peaks on record for device kind {device_kind!r}; add a row to "
        "benchmark/peaks.py with its source")


def lm_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Matmul operations per trained token, forward plus backward (3x the
    forward), causal-aware: a token attends (T+1)/2 keys on average, or at
    most the sliding window. Recomputation is not counted. ``cfg`` holds
    the published keys of a dense decoder (the Mistral family)."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    ffn, vocab = cfg["intermediate_size"], cfg["vocab_size"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dkv = (d // heads) * kv_heads
    mats = 3 if cfg.get("hidden_act") == "silu" else 2   # SwiGLU has three
    mm_params = layers * (2 * d * d + 2 * d * dkv + mats * d * ffn)
    fwd = 2 * (mm_params + d * vocab)
    window = cfg.get("sliding_window")
    keys = (seq_len + 1) / 2
    if window and window < seq_len:
        # token t sees min(window, t+1) keys
        keys = (window * (window + 1) / 2
                + (seq_len - window) * window) / seq_len
    fwd += layers * 4 * d * keys          # scores and weighted values
    return 3.0 * fwd
