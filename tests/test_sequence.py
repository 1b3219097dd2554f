"""Sequence parallelism (ring + ulysses attention) correctness tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from fedtorch_tpu.parallel.sequence import (
    reference_attention, ring_attention, ulysses_attention,
)


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("sp",))


def _qkv(b=2, s=32, h=4, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape) for k in ks)


@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_matches_dense_attention(n_shards):
    q, k, v = _qkv()
    out = ring_attention(q, k, v, _mesh(n_shards))
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n_shards", [2, 8])
def test_causal_matches_dense(n_shards):
    q, k, v = _qkv(seed=3)
    out = ring_attention(q, k, v, _mesh(n_shards), causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_long_sequence_sharded():
    """A sequence too big to be comfortable dense still runs sharded."""
    q, k, v = _qkv(b=1, s=1024, h=2, d=8, seed=5)
    out = ring_attention(q, k, v, _mesh(8), causal=True)
    assert out.shape == (1, 1024, 2, 8)
    assert bool(jnp.all(jnp.isfinite(out)))
    # spot-check the first 64 positions against dense
    ref = reference_attention(q[:, :64], k[:, :64], v[:, :64], causal=True)
    np.testing.assert_allclose(np.asarray(out[:, :64]), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_jit_compatible():
    mesh = _mesh(2)
    q, k, v = _qkv(s=16)
    f = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(reference_attention(q, k, v)),
                               atol=2e-5, rtol=2e-5)


class TestRingFlashBlocks:
    """block_impl='flash': each ring step through the flash kernel,
    pieces merged by logsumexp weighting (parallel/sequence.py
    _ring_flash_local)."""

    @pytest.mark.parametrize("n_shards", [1, 2, 8])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_oracle(self, n_shards, causal):
        q, k, v = _qkv(s=64, seed=7)
        out = ring_attention(q, k, v, _mesh(n_shards), causal=causal,
                             block_impl="flash")
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_matches_dense_block_impl(self):
        q, k, v = _qkv(s=64, seed=9)
        a = ring_attention(q, k, v, _mesh(4), causal=True,
                           block_impl="flash")
        b = ring_attention(q, k, v, _mesh(4), causal=True,
                           block_impl="dense")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_oracle(self):
        """The lse joint VJP composes with the sharded merge: grads
        through the flash ring == grads through dense attention."""
        q, k, v = _qkv(s=64, seed=11)
        mesh = _mesh(8)
        gf = jax.grad(lambda q: jnp.sum(ring_attention(
            q, k, v, mesh, causal=True, block_impl="flash") ** 2))(q)
        gr = jax.grad(lambda q: jnp.sum(reference_attention(
            q, k, v, causal=True) ** 2))(q)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-5, rtol=5e-4)

    def test_rejects_unknown_impl(self):
        q, k, v = _qkv()
        with pytest.raises(ValueError, match="block_impl"):
            ring_attention(q, k, v, _mesh(2), block_impl="sparse")

    @pytest.mark.parametrize("strategy", ["ring", "ulysses"])
    def test_real_kernel_traces_under_shard_map_vma(self, strategy,
                                                    monkeypatch):
        """shard_map's check_vma requires pallas_call outputs to
        declare their varying mesh axes; the kernel propagates the
        inputs' vma onto out_shape. Off-TPU the flash call falls back
        to the XLA oracle, so this combination first fired on the real
        chip — TRACING the real
        pallas path here (no execution) pins the check on CPU."""
        import fedtorch_tpu.ops.pallas.flash_attention as fa
        from fedtorch_tpu.parallel.sequence import ulysses_attention

        monkeypatch.setattr(fa, "on_tpu", lambda: True)
        q, k, v = _qkv(s=64, seed=13)
        mesh = _mesh(4)
        fn = (ring_attention if strategy == "ring"
              else ulysses_attention)
        jax.jit(lambda q, k, v: fn(
            q, k, v, mesh, causal=True,
            block_impl="flash")).trace(q, k, v)

    @pytest.mark.parametrize("strategy", ["ring", "ulysses"])
    def test_backward_kernel_traces_under_shard_map_vma(self, strategy,
                                                        monkeypatch):
        """The same check on the backward rule's ``pallas_call`` (its
        outputs declare the inputs' vma as the forward's do): the
        gradient traced on the real pallas path."""
        import fedtorch_tpu.ops.pallas.flash_attention as fa

        monkeypatch.setattr(fa, "on_tpu", lambda: True)
        q, k, v = _qkv(s=64, seed=13)
        mesh = _mesh(4)
        fn = (ring_attention if strategy == "ring"
              else ulysses_attention)
        text = str(jax.jit(jax.grad(lambda q, k, v: jnp.sum(fn(
            q, k, v, mesh, causal=True, block_impl="flash") ** 2),
            argnums=(0, 1, 2))).trace(q, k, v).jaxpr)
        assert "flash_attention_bwd" in text

    def test_interpreted_kernels_gradients_match_oracle(self,
                                                        monkeypatch):
        """Both kernels in the interpreter under ``shard_map``
        (ulysses' local attention between its all-to-alls) against
        dense attention's gradients. The ring's blocks are not run this
        way: the interpreter's own slicing fails shard_map's vma check;
        their log-sum-exp cotangent is in test_flash_attention.py."""
        import fedtorch_tpu.ops.pallas.flash_attention as fa

        monkeypatch.setattr(fa, "_backend", lambda bq, bk, force: None)
        q, k, v = _qkv(s=64, seed=17)
        mesh = _mesh(2)
        gf = jax.grad(lambda q, k, v: jnp.sum(ulysses_attention(
            q, k, v, mesh, causal=True, block_impl="flash") ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(reference_attention(
            q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-4)


class TestUlysses:
    """All-to-all (head-parallel) strategy: must agree with dense AND
    with the ring strategy on identical inputs."""

    @pytest.mark.parametrize("n_shards", [1, 2, 4])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, n_shards, causal):
        q, k, v = _qkv(seed=7)
        out = ulysses_attention(q, k, v, _mesh(n_shards), causal=causal)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_matches_ring(self):
        q, k, v = _qkv(b=1, s=64, h=8, d=8, seed=9)
        ring = ring_attention(q, k, v, _mesh(8), causal=True)
        uly = ulysses_attention(q, k, v, _mesh(8), causal=True)
        np.testing.assert_allclose(np.asarray(uly), np.asarray(ring),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_local_matches_dense(self, causal):
        """block_impl='flash': the local full-sequence attention runs
        the flash kernel between the two all-to-alls — exact."""
        q, k, v = _qkv(s=64, seed=13)
        out = ulysses_attention(q, k, v, _mesh(4), causal=causal,
                                block_impl="flash")
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_flash_local_gradients_match_oracle(self):
        """The flash custom VJP composed with the two all-to-alls under
        shard_map: gradients == dense attention's."""
        q, k, v = _qkv(s=64, seed=15)
        mesh = _mesh(4)
        gf = jax.grad(lambda q: jnp.sum(ulysses_attention(
            q, k, v, mesh, causal=True, block_impl="flash") ** 2))(q)
        gr = jax.grad(lambda q: jnp.sum(reference_attention(
            q, k, v, causal=True) ** 2))(q)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-5, rtol=5e-4)

    def test_rejects_unknown_block_impl(self):
        q, k, v = _qkv()
        with pytest.raises(ValueError, match="block_impl"):
            ulysses_attention(q, k, v, _mesh(2), block_impl="sparse")

    def test_rejects_indivisible_heads(self):
        q, k, v = _qkv(h=4)
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, k, v, _mesh(8))

    def test_jit_compatible(self):
        mesh = _mesh(4)
        q, k, v = _qkv(s=16, h=4)
        f = jax.jit(lambda a, b, c: ulysses_attention(
            a, b, c, mesh, causal=True))
        np.testing.assert_allclose(
            np.asarray(f(q, k, v)),
            np.asarray(reference_attention(q, k, v, causal=True)),
            atol=2e-5, rtol=2e-5)


def test_sequence_parallel_training_step():
    """Long-context TRAINING, not just forward: optimizer steps through
    long_context_apply (ring + flash blocks) on the 8-shard mesh track
    dense-attention training exactly — same losses, decreasing."""
    import optax
    from fedtorch_tpu.models.transformer import TransformerLM, \
        long_context_apply

    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh(8)
    model = TransformerLM(vocab_size=32, d_model=16, num_heads=2,
                          num_layers=1, max_len=64)
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, 32)
    tgts = jnp.roll(toks, -1, axis=1)
    params = model.init(jax.random.key(0), toks)["params"]
    # training placement: params/tokens replicated over the SP mesh so
    # residual adds mix mesh-resident activations consistently
    rep = NamedSharding(mesh, P())
    params = jax.device_put(params, rep)
    toks, tgts = jax.device_put(toks, rep), jax.device_put(tgts, rep)

    def nll(logits):
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, tgts[..., None],
                                             axis=-1))

    def train(loss_fn, params, steps=3):
        opt = optax.sgd(0.5)
        state = opt.init(params)
        losses = []
        for _ in range(steps):
            loss, g = jax.value_and_grad(loss_fn)(params)
            upd, state = opt.update(g, state)
            params = optax.apply_updates(params, upd)
            losses.append(float(loss))
        return losses

    sp_losses = train(lambda p: nll(long_context_apply(
        model, p, toks, mesh, strategy="ring", block_impl="flash")),
        params)
    dense_losses = train(lambda p: nll(model.apply({"params": p}, toks)),
                         params)
    np.testing.assert_allclose(sp_losses, dense_losses, rtol=1e-4)
    assert sp_losses[-1] < sp_losses[0]


def test_long_context_apply_ulysses_flash_matches_dense():
    """block_impl='flash' under ulysses runs the LOCAL head-slice
    attention through the flash kernel — same logits."""
    from fedtorch_tpu.models.transformer import TransformerLM, \
        long_context_apply
    model = TransformerLM(vocab_size=32, d_model=16, num_heads=2,
                          num_layers=1, max_len=64)
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, 32)
    params = model.init(jax.random.key(0), toks)["params"]
    ref = model.apply({"params": params}, toks)
    out = long_context_apply(model, params, toks, _mesh(2),
                             strategy="ulysses", block_impl="flash")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_long_context_apply_strategies_agree():
    """The transformer forward must be identical under both
    sequence-parallel strategies and the dense baseline."""
    from fedtorch_tpu.models.transformer import TransformerLM, \
        long_context_apply

    model = TransformerLM(vocab_size=64, d_model=32, num_heads=8,
                          num_layers=2, max_len=128)
    toks = jax.random.randint(jax.random.key(2), (2, 128), 0, 64)
    params = model.init(jax.random.key(0), toks)["params"]
    dense = model.apply({"params": params}, toks)
    mesh = _mesh(8)
    for strategy in ("ring", "ulysses"):
        out = long_context_apply(model, params, toks, mesh,
                                 strategy=strategy)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=3e-4, rtol=3e-4, err_msg=strategy)
