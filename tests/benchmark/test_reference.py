"""The two plain references against the program's dense path at tiny
widths in float32, and the comparison rules."""

import numpy as np
import pytest

from benchmark import checks
from benchmark.manifest import REPO_ROOT, Manifest
from benchmark.weights import make_weights
from tests.benchmark._tiny import CONFIGS


@pytest.mark.parametrize("name", ["tiny-dense-serve", "tiny-moe-serve"])
def test_reference_matches_the_dense_oracle(name):
    import jax.numpy as jnp

    man = Manifest(REPO_ROOT)
    cfg = dict(CONFIGS[name])
    model = man.module("families", cfg["family"]).build_model(cfg)
    weights = make_weights(model, 2**31 + 3, "float32")
    ref = man.module("reference", cfg["family"])
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], size=37)
    want = np.asarray(model.apply(
        weights, jnp.asarray(tokens[None], jnp.int32),
        jnp.arange(37, dtype=jnp.int32)[None], "dense"))[0]
    got = np.asarray(ref.forward(cfg, weights, tokens))
    assert got.shape == want.shape == (37, cfg["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_references_import_nothing_from_the_program():
    import ast
    import os

    for family in ("mistral", "mixtral"):
        path = os.path.join(REPO_ROOT, "benchmark", "reference",
                            family + ".py")
        for node in ast.walk(ast.parse(open(path).read())):
            mods = ([a.name for a in node.names]
                    if isinstance(node, ast.Import) else
                    [node.module or ""] if isinstance(node, ast.ImportFrom)
                    else [])
            assert not any(m.startswith("elephas_tpu") for m in mods), path


def test_weights_are_seeded_typed_and_initialised_like_the_program():
    man = Manifest(REPO_ROOT)
    cfg = dict(CONFIGS["tiny-moe-serve"])
    model = man.module("families", "mixtral").build_model(cfg)
    a = make_weights(model, 5, "bfloat16", ["wg", "lnf_s"])
    b = make_weights(model, 5, "bfloat16", ["wg", "lnf_s"])
    c = make_weights(model, 6, "bfloat16", ["wg", "lnf_s"])
    assert set(a) == set(model.param_shapes())
    for k, v in a.items():
        assert v.shape == model.param_shapes()[k].shape
        assert str(v.dtype) == ("float32" if k in ("wg", "lnf_s")
                                else "bfloat16")
        assert (np.asarray(v, np.float32) == np.asarray(b[k], np.float32)).all()
    assert (np.asarray(a["w1"], np.float32)
            != np.asarray(c["w1"], np.float32)).any()
    assert (np.asarray(a["ln1_s"], np.float32) == 1).all()
    w1 = np.asarray(a["w1"], np.float32)
    limit = np.sqrt(6.0 / (w1.shape[-2] + w1.shape[-1]))
    assert np.abs(w1).max() <= limit * 1.01 and w1.std() > limit / 3


def test_logit_and_stream_rules():
    want = np.array([[4.0, 0.0, -2.0], [1.0, 2.0, 0.5]])
    ok, worst, share = checks.logits_agree(want + 0.01, want)
    assert ok and worst < 0.01 and share == 1.0
    off = want.copy()
    off[1, 0] += 1.0                         # half of the largest logit
    assert not checks.logits_agree(off, want)[0]
    assert checks.logits_agree(off, want, min_share=0.5)[0]
    assert not checks.logits_agree(want * np.nan, want)[0]
    # a greedy token that is the best, or ties with it, agrees
    assert checks.stream_agrees(want, [0, 1])[0]
    assert checks.stream_agrees(np.array([[2.0, 1.95, 0.0]]), [1])[0]
    assert not checks.stream_agrees(want, [0, 2])[0]
