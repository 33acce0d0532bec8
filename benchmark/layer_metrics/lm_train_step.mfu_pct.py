"""Model FLOP/s utilisation: matmul operations a trained token needs
(benchmark/peaks.py, no recomputation) x tokens/s over chips x the published
bf16 peak. End-to-end utilisation, not a kernel's roofline share."""


def read(facts):
    if not facts.get("peak_flops"):
        return None
    return (100.0 * facts["flops_per_token"] * facts["tokens_per_s"]
            / (facts["chips"] * facts["peak_flops"]))
