"""The plain reference against the program's ``api.forward``, on the
registered configs reduced to a CPU size, in float32."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchlib import reference, weights
from benchlib.families import dense

ARCHS = ["qwen3-4b", "stablelm-1.6b", "h2o-danube-3-4b"]


def _model_of(cfg):
    return {k: getattr(cfg, k) for k in (
        "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size", "qk_norm", "rope_theta", "rotary_pct",
        "norm_eps", "sliding_window", "tie_embeddings", "dtype")}


@pytest.mark.parametrize("arch,tie", [(a, False) for a in ARCHS]
                         + [("qwen3-4b", True)])
def test_reference_matches_program_forward_in_float32(arch, tie):
    from repro.config import get_config
    from repro.models import api

    cfg = get_config(arch).reduced().replace(tie_embeddings=tie)
    assert cfg.dtype == "float32"
    m = _model_of(cfg)
    w = weights.make_weights(dense, m, seed=2 ** 31 + 17)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 96))
    logits, _ = api.forward(dense.program_tree(m, w),
                            jnp.asarray(tokens, jnp.int32), cfg)
    want = np.asarray(logits, np.float32)[..., :reference.SERVED_VOCAB]
    got = dense.served_logits(m, w, tokens)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    assert (reference.token_gaps(got, want.argmax(-1)) == 0).all()


def test_program_tree_has_the_programs_shapes():
    from repro.config import get_config
    from repro.models import api

    for arch, tie in [(a, False) for a in ARCHS] + [("qwen3-4b", True)]:
        cfg = get_config(arch).reduced().replace(tie_embeddings=tie)
        m = _model_of(cfg)
        tree = dense.program_tree(m, weights.make_weights(dense, m, 3))
        want = api.build_params(cfg, None)
        shapes = jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
        assert shapes == jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                                      want)


def test_weights_depend_on_the_whole_seed():
    m = _model_of(__import__("repro.config", fromlist=["get_config"])
                  .get_config("stablelm-1.6b").reduced())
    a = weights.make_weights(dense, m, 5)["wq"]
    b = weights.make_weights(dense, m, 5 + 2 ** 32)["wq"]
    c = weights.make_weights(dense, m, 5)["wq"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(c))


def test_gap_reads_how_far_a_token_lies_below_the_best():
    ref = np.array([[[0.0, 2.0, 1.5]]])
    assert reference.token_gaps(ref, np.array([[1]])).tolist() == [[0.0]]
    assert reference.token_gaps(ref, np.array([[2]])).tolist() == [[0.5]]
