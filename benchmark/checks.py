"""The comparisons that decide ``correct``.

Both rules compare the program with the benchmark's own plain reference
(``reference/<family>.py``: float32, highest matmul precision, no kernel,
no cache, nothing imported from the program).

``logits_agree``: the program computes in bfloat16, whose products carry 8
bits; through tens of layers the logits of a random-weight model end up a
few percent of the largest logit away from float32. The tolerance is a
share of the reference's largest absolute logit at that position. It is
wide enough for bfloat16 and far too tight for 8-bit arithmetic or a
dropped term (a missing residual, rotation or expert moves logits by their
own size). A sparse-expert model adds one thing: where two router logits
tie within bfloat16 rounding, the program and the reference send the token
to different experts and that position's logits differ by an expert's
whole output. So a family's reference states the share of positions that
must agree (1.0 for a dense model).

``stream_agrees``: the rule of ``harness_env.greedy_streams_agree`` (equal,
or parted at a tie) pointed at the reference: every token a greedy request
emitted must lie within the tie tolerance of the reference's largest logit
at its position, teacher-forced on the emitted stream itself.
"""

import numpy as np

LOGIT_RTOL = 0.06      # share of the largest |reference logit| at a position
TIE_RTOL = 0.06        # a greedy token may trail the best logit by this share


def logits_agree(got, want, min_share: float = 1.0, rtol: float = LOGIT_RTOL):
    """``got``/``want``: ``[N, V]``. Returns ``(ok, worst, share)``: the
    worst position's error as a share of its largest reference logit, and
    the share of positions inside ``rtol``."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        return False, float("inf"), 0.0
    scale = np.abs(want).max(axis=-1)
    err = np.abs(got - want).max(axis=-1) / np.maximum(scale, 1e-9)
    share = float((err <= rtol).mean())
    return share >= min_share, float(err.max()), share


def stream_agrees(ref_logits, tokens, min_share: float = 1.0,
                  rtol: float = TIE_RTOL):
    """``ref_logits`` ``[N, V]``: the reference's logits at the positions
    that chose ``tokens`` ``[N]``. Returns ``(ok, worst, share)``."""
    ref = np.asarray(ref_logits, np.float32)
    tokens = np.asarray(tokens)
    top = ref.max(axis=-1)
    chosen = ref[np.arange(len(tokens)), tokens]
    short = (top - chosen) / np.maximum(np.abs(ref).max(axis=-1), 1e-9)
    share = float((short <= rtol).mean())
    return share >= min_share, float(short.max()), share
