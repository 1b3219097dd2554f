"""Flash-attention kernels (ops/pallas/flash_attention.py): interpret-mode
kernel semantics forward and backward, custom-VJP gradients, and
transformer integration.

The real-TPU lowering of the same kernel is exercised by
chip_smoke.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtorch_tpu.models.transformer import TransformerLM
import fedtorch_tpu.ops.pallas.flash_attention as fa
from fedtorch_tpu.ops.pallas.flash_attention import (
    flash_attention, flash_attention_with_lse,
)
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
        q, k, v = _qkv(T=T, D=64)

        def fwd(q, k, v):
            (q3, k3, v3), _, scale, bq, bk, _ = fa._prep(
                q, k, v, None, block, block, None)
            o3 = fa._flash3(q3, k3, v3, scale, causal, bq, bk, True)
            _, lse3 = fa._flash3_lse(q3, k3, v3, scale, causal, bq, bk,
                                     True)
            return o3, lse3

        jax.jit(fwd).trace(q, k, v).lower(lowering_platforms=("tpu",))

        # the backward kernel under both rules (the second with the
        # log-sum-exp's cotangent): the same check on its blocks, the
        # whole-sequence dq block and the 8-lane statistics among them
        def loss(q, k, v):
            o3, lse3 = fwd(q, k, v)
            return jnp.sum(o3) + jnp.sum(lse3)

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            q, k, v).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("flash_attention_bwd") >= 2

    def test_default_blocks_follow_measured_winners(self):
        """Block defaults: (128, 128) up to T=2048, (1024, 1024) from
        T=4096 (ops/pallas/flash_attention.py:_default_blocks has the
        chip's readings, PR 42). Explicit args override; divisor
        adjustment still applies."""

        assert fa._default_blocks(1024) == (128, 128)
        assert fa._default_blocks(2048) == (128, 128)  # 0.68x window
        assert fa._default_blocks(4096) == (1024, 1024)
        assert fa._default_blocks(8192) == (1024, 1024)

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
        """A prime-ish T collapses the divisor blocks to ~T; on TPU a
        [T, T] score tile beyond the largest the kernels were built at
        (1024 x 1024) would blow VMEM, so _prep must route the call to
        the XLA oracle even when the platform offers pallas."""
        monkeypatch.setattr(fa, "on_tpu", lambda: True)
        q, k, v = _qkv(T=1032, D=16)  # gcd(1032,128)=8<16 -> block=1032
        *_, use_pallas = fa._prep(q, k, v, None, 128, 128, None)
        assert use_pallas is False
        q, k, v = _qkv(T=1000, D=16)  # one block of 1000: it fits
        *_, bq, bk, use_pallas = fa._prep(q, k, v, None, 128, 128, None)
        assert (bq, bk, use_pallas) == (1000, 1000, True)
        q, k, v = _qkv(T=256, D=16)   # clean tiling stays on the kernel
        *_, use_pallas = fa._prep(q, k, v, None, 128, 128, None)
        assert use_pallas is True


def _dense(q, k, v, causal):
    """The dense form as the models write it: scores, softmax and sums
    float32, the probabilities cast to the values' type for ``p v``;
    (o float32, lse [B, T, H])."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) \
        / np.sqrt(q.shape[-1])
    if causal:
        T = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s,
                      -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd",
                   jax.nn.softmax(s, axis=-1).astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, jax.nn.logsumexp(s, axis=-1).transpose(0, 2, 1)


def _grads(f, q, k, v, w, w_lse=None):
    """d/d(q, k, v) of ``sum(o * w) [+ sum(lse * w_lse)]``, float32."""
    def loss(q, k, v):
        o, lse = f(q, k, v)
        out = jnp.sum(o.astype(jnp.float32) * w)
        return out if w_lse is None else out + jnp.sum(lse * w_lse)

    return [np.asarray(g, np.float32) for g in
            jax.grad(loss, argnums=(0, 1, 2))(q, k, v)]


def _case(T, heads, dtype, seed=0):
    """q, k [1, T, 2, D], v [1, T, 2, Dv] and the cotangents of o and
    of the log-sum-exp."""
    D, Dv = heads
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k = (jax.random.normal(a, (1, T, 2, D), dtype) for a in ks[:2])
    v = jax.random.normal(ks[2], (1, T, 2, Dv), dtype)
    w = jax.random.normal(ks[3], (1, T, 2, Dv), jnp.float32)
    return q, k, v, w, jax.random.normal(ks[4], (1, T, 2), jnp.float32)


# float32: the tolerances that stood for the chunked scan (the kernel
# read 3.1e-6 at most on gradients of size 5). bfloat16: the gradients
# come back on bfloat16's grid, whose step at their largest (4 to 8) is
# 0.031: read 0.031 at most for dv (size 5.6), 0.016 for dk, 0.009 for
# dq, against the dense form on the same bfloat16 inputs.
_TOL = {jnp.float32: dict(atol=5e-5, rtol=5e-4),
        jnp.bfloat16: dict(atol=2e-2, rtol=2e-2)}


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
        """Gradients flow through the interpreter-run kernels (forward
        and backward both)."""
        q, k, v = _qkv(T=128, D=32)
        g = jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, causal=True,
                            force="interpret") ** 2))(q)
        assert bool(jnp.all(jnp.isfinite(g)))


class TestBackwardKernel:
    """The backward kernel in the interpreter against the dense form's
    gradients, and who takes it."""

    @pytest.mark.parametrize("heads", [(64, 64), (192, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_grads(self, causal, dtype, heads):
        """Two query and two key tiles (the causal case: one skipped,
        two masked, one unmasked), operands of the inputs' own type;
        value heads of their own width."""
        q, k, v, w, _ = _case(256, heads, dtype)
        got = _grads(lambda *a: (flash_attention(
            *a, causal=causal, block_q=128, block_k=128,
            force="interpret"), None), q, k, v, w)
        want = _grads(lambda *a: _dense(*a, causal), q, k, v, w)
        for a, b, x, name in zip(got, want, (q, k, v), "qkv"):
            assert a.shape == x.shape
            np.testing.assert_allclose(a, b, err_msg=f"d{name}",
                                       **_TOL[dtype])

    @pytest.mark.parametrize("T,block_q,block_k", [
        (256, 128, 64),    # two key tiles a query tile
        (256, 64, 128),    # two query tiles a key tile
        (64, 128, 128),    # one block: T <= block
        (192, 128, 128),   # a gcd divisor: blocks of 64
    ])
    def test_tilings(self, T, block_q, block_k):
        q, k, v, w, _ = _case(T, (64, 64), jnp.float32, seed=T)
        got = _grads(lambda *a: (flash_attention(
            *a, causal=True, block_q=block_q, block_k=block_k,
            force="interpret"), None), q, k, v, w)
        want = _grads(lambda *a: _dense(*a, True), q, k, v, w)
        for a, b, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(a, b, err_msg=f"d{name}",
                                       **_TOL[jnp.float32])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [False, True])
    def test_lse_cotangent(self, causal, dtype):
        """``flash_attention_with_lse`` with both results used (ring
        attention's merge): the log-sum-exp's cotangent enters the
        kernel through ``delta``."""
        q, k, v, w, w_lse = _case(256, (64, 64), dtype, seed=3)
        got = _grads(lambda *a: flash_attention_with_lse(
            *a, causal=causal, block_q=128, block_k=128,
            force="interpret"), q, k, v, w, w_lse)
        want = _grads(lambda *a: _dense(*a, causal), q, k, v, w, w_lse)
        for a, b, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(a, b, err_msg=f"d{name}",
                                       **_TOL[dtype])

    @pytest.mark.parametrize("case", ["xla", "cpu_default", "degenerate"])
    def test_the_scan_stays_where_the_forward_is_the_oracle(
            self, case, monkeypatch):
        """``force='xla'``, the CPU's default and (on a TPU) the
        degenerate divisor take ``_bwd_chunked``, and its gradients bit
        for bit: the backward kernel is never built there."""
        def never(*a, **kw):
            raise AssertionError("the backward kernel was built")

        monkeypatch.setattr(fa, "_bwd_pallas", never)
        if case == "degenerate":
            monkeypatch.setattr(fa, "on_tpu", lambda: True)
        T = 1032 if case == "degenerate" else 128
        q, k, v, w, _ = _case(T, (16, 16), jnp.float32)
        force = "xla" if case == "xla" else None
        got = _grads(lambda *a: (flash_attention(
            *a, causal=True, block_q=128, block_k=128, force=force),
            None), q, k, v, w)
        (q3, k3, v3), _, scale, bq, _, use_pallas = fa._prep(
            q, k, v, None, 128, 128, force)
        assert use_pallas is False
        o3, lse = fa._fwd_xla(q3, k3, v3, scale, True)
        want = fa._bwd_chunked(
            (q3, k3, v3, o3, lse), w.transpose(0, 2, 1, 3).reshape(
                v3.shape), scale=scale, causal=True, block_q=bq)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(
                a, np.asarray(b).reshape(1, 2, T, -1).transpose(
                    0, 2, 1, 3))

    def test_who_takes_the_kernel(self, monkeypatch):
        """The backward rule's decision, which the round's counter
        reads: the kernel wherever the forward ran one and the row's
        ``dq`` fits its VMEM."""
        assert not fa.backward_kernel_taken(4096, 192)    # the CPU
        monkeypatch.setattr(fa, "on_tpu", lambda: True)
        assert fa.backward_kernel_taken(4096, 192)
        assert fa.backward_kernel_taken(16384, 128)
        assert not fa.backward_kernel_taken(32768, 128)   # dq too long
        assert not fa.backward_kernel_taken(1032, 64)     # one block


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
