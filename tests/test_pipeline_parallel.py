"""Pipeline-parallel transformer forward (parallel/pipeline.py).

The GPipe schedule must be numerically transparent: staged blocks +
microbatching + ppermute handoffs produce exactly the dense forward."""
import numpy as np
import jax
import pytest
from jax.sharding import Mesh

from fedtorch_tpu.models.transformer import TransformerLM
from fedtorch_tpu.parallel.pipeline import pipeline_apply


def _model_and_toks(layers=4, d_model=32, heads=4, seq=24, vocab=48,
                    batch=8):
    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          num_heads=heads, num_layers=layers, max_len=seq)
    toks = jax.random.randint(jax.random.key(1), (batch, seq), 0, vocab)
    params = model.init(jax.random.key(0), toks)["params"]
    return model, params, toks


@pytest.mark.parametrize("n_pp,microbatches", [(1, 1), (2, 2), (4, 4),
                                               (4, 8), (2, 1)])
def test_pipeline_matches_dense(n_pp, microbatches):
    model, params, toks = _model_and_toks()
    mesh = Mesh(np.asarray(jax.devices()[:n_pp]), ("pp",))
    dense = model.apply({"params": params}, toks)
    out = pipeline_apply(model, params, toks, mesh,
                         num_microbatches=microbatches)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_eight_stage_single_block_each():
    model, params, toks = _model_and_toks(layers=8)
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("pp",))
    dense = model.apply({"params": params}, toks)
    out = pipeline_apply(model, params, toks, mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_rejects_indivisible_layers():
    model, params, toks = _model_and_toks(layers=3)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
    with pytest.raises(ValueError, match="divisible"):
        pipeline_apply(model, params, toks, mesh)


def test_rejects_indivisible_batch():
    model, params, toks = _model_and_toks(batch=6)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
    with pytest.raises(ValueError, match="microbatch"):
        pipeline_apply(model, params, toks, mesh, num_microbatches=4)


def test_pipeline_moe_model():
    """pipeline_apply must thread num_experts into the rebuilt blocks:
    a MoE transformer pipelined over 4 stages equals its dense oracle."""
    model = TransformerLM(vocab_size=32, d_model=16, num_heads=2,
                          num_layers=4, max_len=16, num_experts=4)
    toks = jax.random.randint(jax.random.key(1), (4, 16), 0, 32)
    params = model.init(jax.random.key(0), toks)["params"]
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("pp",))
    dense = model.apply({"params": params}, toks)
    out = pipeline_apply(model, params, toks, mesh, num_microbatches=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)
