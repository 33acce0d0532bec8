"""Custom TPU ops.

``pallas_ops`` holds the fused classification-loss kernel (used automatically
on TPU via ``models.losses``); ``layer_norm`` the fused LayerNorm (custom
VJP) behind the LM family's norms; ``flash_decode`` the GQA-native KV-cache
decode-attention kernel behind ``TransformerLM.decode_step`` (and
``mla_decode``, its latent-attention sibling over a cache of latent rows);
``gated_delta`` the Gated DeltaNet linear-attention recurrence (its
chunkwise form, the ``gdn_decode`` kernel that updates a slot's recurrent
state in place, the short convolution);
``flash_attention`` the blockwise training-time attention; ``ring_attention``
and ``ulysses`` the two canonical sequence-parallel exact-attention schedules
over the mesh (explicitly-labeled extensions — the reference has no
long-context support, SURVEY.md §5.7). jnp reference implementations double
as CPU fallbacks and test oracles.
"""

from .pallas_ops import (
    categorical_crossentropy_from_logits,
    fused_xent_from_logits,
    xent_from_logits_reference,
)
from .layer_norm import fused_layer_norm, layer_norm, layer_norm_reference
from .flash_decode import (
    decode_attention,
    decode_attention_reference,
    flash_decode,
    latent_decode_attention,
    mla_decode,
    mla_decode_reference,
)
from .gated_delta import (
    gdn_chunk,
    gdn_decode,
    gdn_decode_reference,
    gdn_recurrence,
)
from .flash_attention import flash_attention
from .ring_attention import attention_reference, ring_attention
from .ulysses import ulysses_attention

__all__ = [
    "categorical_crossentropy_from_logits",
    "fused_xent_from_logits",
    "xent_from_logits_reference",
    "fused_layer_norm",
    "layer_norm",
    "layer_norm_reference",
    "decode_attention",
    "decode_attention_reference",
    "flash_decode",
    "latent_decode_attention",
    "mla_decode",
    "mla_decode_reference",
    "gdn_chunk",
    "gdn_decode",
    "gdn_decode_reference",
    "gdn_recurrence",
    "ring_attention",
    "attention_reference",
    "ulysses_attention",
    "flash_attention",
]
