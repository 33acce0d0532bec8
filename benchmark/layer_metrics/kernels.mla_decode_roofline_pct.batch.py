"""Share of its roofline of the ``mla_decode`` Pallas kernel, per decode
span, median: the larger of bytes / 819 GB/s and operations / 197 TFLOP/s
(one latent row of ``kv_lora_rank + qk_rope_head_dim`` numbers a live
position a layer, read once; ``2 x heads x (row + kv_lora_rank)``
operations: ``mla_work``) over the kernel's ms. Bandwidth bounds it, by a
factor of two only (121 operations a byte against the chip's 240), so a
kernel that spends as long on the MXU as on its copies still reads near
half."""
from benchmark import mla_work


def read(facts):
    return mla_work.mla_decode_roofline_pct(facts)
