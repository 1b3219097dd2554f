"""Flash-attention kernel (ops/pallas/flash_attention.py): interpret-mode
kernel semantics, custom-VJP gradients, and transformer integration.

The real-TPU lowering of the same kernel is exercised by
chip_smoke.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtorch_tpu.models.transformer import TransformerLM
from fedtorch_tpu.ops.pallas.flash_attention import flash_attention
from fedtorch_tpu.parallel.sequence import reference_attention


def _qkv(B=2, T=256, H=4, D=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (B, T, H, D), dtype) for k in ks)


class TestForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_interpret_kernel_matches_oracle(self, causal):
        """The pallas kernel (interpreter) == dense attention; T=256
        with 128-blocks exercises the multi-block online-softmax path
        and, for causal, the block-skipping loop bound."""
        q, k, v = _qkv()
        ref = reference_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, force="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_single_block_small_seq(self):
        """T smaller than the block size clamps to one block."""
        q, k, v = _qkv(T=32, D=16)
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, force="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_uneven_block_ratio(self):
        """block_q != block_k exercises the inner K loop bound."""
        q, k, v = _qkv(T=256)
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=128,
                              block_k=64, force="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("T", [100, 200, 257])
    def test_indivisible_seq(self, T):
        """T > block with T % block != 0 re-derives a divisor block
        (gcd, or one block for degenerate divisors) — forward AND
        gradient must both work on such shapes (T=200 -> blocks of 8;
        T=257 prime -> a single block)."""
        q, k, v = _qkv(T=T, D=32)
        ref = reference_attention(q, k, v, causal=True)
        for force in ("xla", "interpret"):
            out = flash_attention(q, k, v, causal=True, force=force)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5, rtol=2e-5,
                                       err_msg=f"force={force}")
        gf = jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, causal=True, force="xla") ** 2))(q)
        gr = jax.grad(lambda q: jnp.sum(reference_attention(
            q, k, v, causal=True) ** 2))(q)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-5, rtol=5e-4)

    def test_bfloat16_inputs(self):
        q, k, v = _qkv(dtype=jnp.bfloat16)
        ref = reference_attention(q.astype(jnp.float32),
                                  k.astype(jnp.float32),
                                  v.astype(jnp.float32), causal=True)
        out = flash_attention(q, k, v, causal=True, force="interpret")
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=3e-2, rtol=3e-2)


class TestBackendSelection:
    def test_unknown_force_raises(self):
        q, k, v = _qkv(T=32, D=16)
        with pytest.raises(ValueError, match="force"):
            flash_attention(q, k, v, force="interp")  # typo'd string

    def test_mismatched_kv_length_raises_clearly(self):
        """kv_len != q_len is unsupported (shared-T tiling); it must
        fail with the shapes spelled out, not an opaque reshape error
        (ADVICE r3). Same check on the lse variant."""
        from fedtorch_tpu.ops.pallas.flash_attention import \
            flash_attention_with_lse
        q, _, _ = _qkv(T=64, D=16)
        k, _, _ = _qkv(T=32, D=16, seed=1)
        with pytest.raises(ValueError, match="identical shape"):
            flash_attention(q, k, k, force="xla")
        with pytest.raises(ValueError, match="identical shape"):
            flash_attention_with_lse(q, k, k, force="xla")

    @pytest.mark.parametrize("T,block", [(256, 128), (64, 128),
                                         (192, 128)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_mosaic_lowering_accepts_blocks(self, T, block, causal):
        """AOT-lower the REAL pallas path for platform 'tpu' from this
        CPU process: jax runs Mosaic's block-mapping validation
        (_check_block_mappings) at lowering time, no device needed.
        Round 5 on-chip found that interpret mode accepts block shapes
        Mosaic rejects (the [1, block_q] lse block); this pins the
        whole failure class without a chip. Covers the clean 128-tile,
        the one-block (block == T) path, and a gcd divisor (T=192 ->
        block 64)."""
        import fedtorch_tpu.ops.pallas.flash_attention as fa
        q, k, v = _qkv(T=T, D=64)

        def fwd(q, k, v):
            (q3, k3, v3), _, scale, bq, bk, _ = fa._prep(
                q, k, v, None, block, block, None)
            o3 = fa._flash3(q3, k3, v3, scale, causal, bq, bk, True)
            _, lse3 = fa._flash3_lse(q3, k3, v3, scale, causal, bq, bk,
                                     True)
            return o3, lse3

        jax.jit(fwd).trace(q, k, v).lower(lowering_platforms=("tpu",))

    def test_default_blocks_follow_measured_winners(self):
        """Block defaults: (128, 128) up to T=2048, (512, 512) from
        T=4096 (ops/pallas/flash_attention.py:_default_blocks has the
        ground). Explicit args override; divisor adjustment still
        applies."""
        import fedtorch_tpu.ops.pallas.flash_attention as fa

        assert fa._default_blocks(1024) == (128, 128)
        assert fa._default_blocks(2048) == (128, 128)  # 0.68x window
        assert fa._default_blocks(4096) == (512, 512)
        assert fa._default_blocks(8192) == (512, 512)

        q, k, v = _qkv(T=256, D=16)
        *_, bq, bk, _ = fa._prep(q, k, v, None, None, None, None)
        assert (bq, bk) == (128, 128)  # the validated sub-2048 shape
        *_, bq, bk, _ = fa._prep(q, k, v, None, 64, 64, None)
        assert (bq, bk) == (64, 64)    # explicit args respected
        q, k, v = _qkv(T=96, D=16)     # T below the default block
        *_, bq, bk, _ = fa._prep(q, k, v, None, None, None, None)
        assert (bq, bk) == (96, 96)    # clamped to one block

    def test_lse_output_is_lane_narrow(self):
        """ADVICE r5 satellite: the lse HBM output is [BH, T, 8]
        (_LSE_LANES), not the 128-lane broadcast — 16x less lse HBM
        traffic. The narrowed write must still carry the exact lse:
        interpret-mode kernel lse == dense-oracle lse, and the full
        forward stays exact. (The Mosaic acceptance of the
        (1, block_q, 8) block is pinned by
        test_mosaic_lowering_accepts_blocks, which AOT-lowers the lse
        variant for platform 'tpu'.)"""
        import fedtorch_tpu.ops.pallas.flash_attention as fa
        from fedtorch_tpu.ops.pallas.flash_attention import \
            flash_attention_with_lse

        assert fa._LSE_LANES == 8
        # the narrow block satisfies the stated Mosaic rule by
        # construction: last block dim == array dim
        q, k, v = _qkv(T=256, D=32)
        o_i, lse_i = flash_attention_with_lse(q, k, v, causal=True,
                                              force="interpret")
        o_x, lse_x = flash_attention_with_lse(q, k, v, causal=True,
                                              force="xla")
        np.testing.assert_allclose(np.asarray(lse_i),
                                   np.asarray(lse_x),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(o_i), np.asarray(o_x),
                                   atol=2e-5, rtol=2e-5)

    def test_lse_kernel_shape_is_narrow(self):
        """The pallas forward's raw lse buffer really is 8 lanes (the
        HBM allocation the advisor sized), independent of the wrapper
        slicing."""
        import fedtorch_tpu.ops.pallas.flash_attention as fa

        def fwd(q3, k3, v3):
            return fa._fwd_pallas(q3, k3, v3, 0.125, False, 64, 64,
                                  interpret=True)

        shapes = jax.eval_shape(
            fwd, *(jax.ShapeDtypeStruct((4, 128, 32), jnp.float32)
                   for _ in range(3)))
        o_shape, lse_shape = shapes
        assert o_shape.shape == (4, 128, 32)
        assert lse_shape.shape == (4, 128)  # sliced from [*, *, 8]

    def test_degenerate_block_falls_back_to_xla(self, monkeypatch):
        """A prime-ish T collapses the divisor blocks to ~T; on TPU the
        [T, T] score tile would blow VMEM, so _prep must route the call
        to the XLA oracle even when the platform offers pallas."""
        import fedtorch_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "on_tpu", lambda: True)
        q, k, v = _qkv(T=1000, D=16)  # gcd(1000,128)=8<16 -> block=1000
        *_, use_pallas = fa._prep(q, k, v, None, 128, 128, None)
        assert use_pallas is False
        q, k, v = _qkv(T=256, D=16)   # clean tiling stays on the kernel
        *_, use_pallas = fa._prep(q, k, v, None, 128, 128, None)
        assert use_pallas is True


class TestGradients:
    @pytest.mark.parametrize("causal", [False, True])
    def test_custom_vjp_matches_dense_grads(self, causal):
        """The chunked flash backward (recompute-from-logsumexp scan)
        must reproduce the dense oracle's q/k/v gradients."""
        q, k, v = _qkv(T=128, D=32)

        def loss_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=causal, force="xla",
                                block_q=64) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(
                reference_attention(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4,
                err_msg=f"d{name} mismatch")

    def test_interpret_forward_backward(self):
        """Gradients flow through the interpreter-run kernel too (the
        VJP is backend-independent)."""
        q, k, v = _qkv(T=128, D=32)
        g = jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, causal=True,
                            force="interpret") ** 2))(q)
        assert bool(jnp.all(jnp.isfinite(g)))


class TestTransformerIntegration:
    def test_flash_model_matches_dense_model(self):
        """attention='flash' is a pure backend swap: same params, same
        logits as attention='dense'."""
        toks = jax.random.randint(jax.random.key(1), (2, 64), 0, 32)
        dense_m = TransformerLM(vocab_size=32, d_model=32, num_heads=2,
                                num_layers=2, max_len=64)
        flash_m = TransformerLM(vocab_size=32, d_model=32, num_heads=2,
                                num_layers=2, max_len=64,
                                attention="flash")
        params = dense_m.init(jax.random.key(0), toks)["params"]
        ref = dense_m.apply({"params": params}, toks)
        out = flash_m.apply({"params": params}, toks)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_flash_training_step(self):
        """End-to-end grad through the flash transformer is finite and
        matches the dense transformer's grad."""
        toks = jax.random.randint(jax.random.key(1), (2, 64), 0, 32)
        tgts = jnp.roll(toks, -1, axis=1)

        def make_loss(attention):
            m = TransformerLM(vocab_size=32, d_model=32, num_heads=2,
                              num_layers=1, max_len=64,
                              attention=attention)

            def loss(p):
                logits = m.apply({"params": p}, toks)
                logp = jax.nn.log_softmax(logits)
                return -jnp.mean(jnp.take_along_axis(
                    logp, tgts[..., None], axis=-1))

            return m, loss

        dense_m, dense_loss = make_loss("dense")
        _, flash_loss = make_loss("flash")
        params = dense_m.init(jax.random.key(0), toks)["params"]
        gd = jax.grad(dense_loss)(params)
        gf = jax.grad(flash_loss)(params)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree.leaves(gd), jax.tree.leaves(gf)))
        assert err < 5e-5

    def test_config_surface(self):
        from fedtorch_tpu.config import ExperimentConfig, ModelConfig
        from fedtorch_tpu.models import define_model
        cfg = ExperimentConfig(
            model=ModelConfig(arch="transformer", attention="flash",
                              mlp_num_layers=1, rnn_seq_len=16,
                              rnn_hidden_size=8)).finalize()
        model = define_model(cfg, batch_size=2)
        assert model.module.attention == "flash"


class TestAutoDispatch:
    """Sequence-length dispatch guard: 'auto' must keep sequences
    shorter than FLASH_MIN_SEQ_LEN off the flash kernel and flip to
    flash exactly there."""

    def test_boundary(self):
        from fedtorch_tpu.ops.attention_dispatch import (
            FLASH_MIN_SEQ_LEN, resolve_attention,
        )
        assert resolve_attention("auto", 1024) == "dense"
        assert resolve_attention("auto", 2048) == "dense"  # 0.68x case
        assert resolve_attention("auto", FLASH_MIN_SEQ_LEN - 1) \
            == "dense"
        assert resolve_attention("auto", FLASH_MIN_SEQ_LEN) == "flash"
        assert resolve_attention("auto", 8192) == "flash"

    def test_explicit_modes_pass_through(self):
        from fedtorch_tpu.ops.attention_dispatch import (
            resolve_attention,
        )
        assert resolve_attention("dense", 8192) == "dense"
        assert resolve_attention("flash", 128) == "flash"
        with pytest.raises(ValueError, match="attention"):
            resolve_attention("fast", 128)

    def test_auto_is_the_config_default(self):
        from fedtorch_tpu.config import ExperimentConfig, ModelConfig
        assert ExperimentConfig().finalize().model.attention == "auto"
        with pytest.raises(ValueError, match="attention"):
            ExperimentConfig(
                model=ModelConfig(attention="fast")).finalize()

    def test_auto_equals_dense_below_threshold(self):
        """At short T the 'auto' model must be the dense model
        bit-for-bit (same params, same logits)."""
        toks = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % 7
        outs = {}
        for mode in ("auto", "dense"):
            m = TransformerLM(vocab_size=7, d_model=16, num_heads=2,
                              num_layers=1, attention=mode)
            params = m.init(jax.random.key(0), toks)["params"]
            outs[mode] = m.apply({"params": params}, toks)
        np.testing.assert_array_equal(np.asarray(outs["auto"]),
                                      np.asarray(outs["dense"]))
