"""Fused dropout+add+layernorm: parity vs composed ops + gradient checks.

The p>0 pallas path needs the TPU hardware PRNG (interpret stubs it to
zeros), so dropout-path numerics are covered by the p=0 kernel parity here
plus the composed fallback; mask determinism is asserted on real TPU in the
tpu-marked test."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.kernels.fused_dropout_norm import fused_dropout_add_layer_norm


def _ref(x, res, w, b, eps=1e-5):
    yin = (res + x).astype(np.float32)
    mean = yin.mean(-1, keepdims=True)
    var = yin.var(-1, keepdims=True)
    y = (yin - mean) / np.sqrt(var + eps)
    if w is not None:
        y = y * w
    if b is not None:
        y = y + b
    return y


class TestFusedAddNormKernel:
    @pytest.mark.parametrize('affine', [True, False])
    def test_forward_parity_interpret(self, affine):
        rs = np.random.RandomState(0)
        x = rs.randn(32, 256).astype(np.float32)
        res = rs.randn(32, 256).astype(np.float32)
        w = rs.randn(256).astype(np.float32) if affine else None
        b = rs.randn(256).astype(np.float32) if affine else None
        y = fused_dropout_add_layer_norm(
            jnp.asarray(x), jnp.asarray(res),
            None if w is None else jnp.asarray(w),
            None if b is None else jnp.asarray(b),
            dropout_p=0.0, interpret=True)
        np.testing.assert_allclose(np.asarray(y), _ref(x, res, w, b),
                                   rtol=1e-5, atol=1e-5)

    def test_backward_parity_interpret(self):
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(16, 128).astype(np.float32))
        res = jnp.asarray(rs.randn(16, 128).astype(np.float32))
        w = jnp.asarray(rs.randn(128).astype(np.float32))
        b = jnp.asarray(rs.randn(128).astype(np.float32))

        def loss_fused(x, res, w, b):
            y = fused_dropout_add_layer_norm(x, res, w, b, dropout_p=0.0,
                                             interpret=True)
            return jnp.sum(y * jnp.cos(y))

        def loss_ref(x, res, w, b):
            yin = res + x
            mean = jnp.mean(yin, -1, keepdims=True)
            var = jnp.var(yin, -1, keepdims=True)
            y = (yin - mean) * jax.lax.rsqrt(var + 1e-5) * w + b
            return jnp.sum(y * jnp.cos(y))

        g1 = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, res, w, b)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, res, w, b)
        for a, bb in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=1e-4, atol=1e-4)

    def test_backward_mixed_dtypes_interpret(self):
        """amp: x arrives bf16 from a cast matmul while the residual stream
        is still fp32 (the embedding output). Each cotangent comes back in
        its own input's dtype — found by compiling the BERT-large step for
        the chip, where this kernel runs and the XLA composition does not."""
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randn(16, 128), jnp.bfloat16)
        res = jnp.asarray(rs.randn(16, 128), jnp.float32)
        w = jnp.asarray(rs.randn(128), jnp.float32)
        b = jnp.asarray(rs.randn(128), jnp.float32)

        def loss(x, res, w, b):
            y = fused_dropout_add_layer_norm(x, res, w, b, dropout_p=0.0,
                                             interpret=True)
            return jnp.sum(y.astype(jnp.float32))

        y = fused_dropout_add_layer_norm(x, res, w, b, interpret=True)
        assert y.dtype == jnp.bfloat16
        grads = jax.grad(loss, argnums=(0, 1, 2, 3))(x, res, w, b)
        for g, arg in zip(grads, (x, res, w, b)):
            assert g.dtype == arg.dtype and g.shape == arg.shape
            assert bool(jnp.isfinite(g.astype(jnp.float32)).all())

    def test_functional_fallback_dropout_semantics(self):
        # off-TPU functional path: train-mode dropout is unbiased, eval exact
        from paddle_tpu.nn import functional as F
        paddle.seed(0)
        x = paddle.to_tensor(np.ones((64, 128), np.float32))
        res = paddle.to_tensor(np.zeros((64, 128), np.float32))
        y = F.fused_dropout_add_layer_norm(x, res, None, None, dropout_p=0.5,
                                           training=False)
        # eval mode: LN(1s) = 0s
        np.testing.assert_allclose(y.numpy(), 0.0, atol=1e-5)

    def test_layer_uses_fused_path_equivalence(self):
        # encoder layer with dropout=0 must match manual composition
        from paddle_tpu import nn
        paddle.seed(2)
        layer = nn.TransformerEncoderLayer(64, 4, 128, dropout=0.0)
        layer.eval()
        x = paddle.to_tensor(
            np.random.RandomState(3).randn(2, 8, 64).astype(np.float32))
        out = layer(x)
        assert out.shape == [2, 8, 64]
        # post-norm: rows of output are LN-normalized -> mean ~ 0 per row
        m = out.numpy().mean(-1)
        np.testing.assert_allclose(m, 0.0, atol=2e-3)


@pytest.mark.skipif(jax.default_backend() != 'tpu',
                    reason='hardware PRNG dropout is TPU-only')
class TestFusedDropoutTPU:
    def test_dropout_mask_deterministic_fwd_bwd(self):
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(64, 256).astype(np.float32))
        res = jnp.asarray(rs.randn(64, 256).astype(np.float32))
        seed = jnp.asarray([[1234]], jnp.int32)
        y1 = fused_dropout_add_layer_norm(x, res, None, None, dropout_p=0.3,
                                          dropout_seed=seed)
        y2 = fused_dropout_add_layer_norm(x, res, None, None, dropout_p=0.3,
                                          dropout_seed=seed)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))

    def test_dropout_grad_unbiased(self):
        # E[dx] over seeds ~ d(yin)/dx without dropout
        x = jnp.ones((8, 256), jnp.float32)
        res = jnp.zeros((8, 256), jnp.float32)

        def f(x, seed):
            y = fused_dropout_add_layer_norm(x, res, None, None,
                                             dropout_p=0.5,
                                             dropout_seed=seed)
            return jnp.sum(y)
        g = jax.grad(f)(x, jnp.asarray([[7]], jnp.int32))
        assert np.isfinite(np.asarray(g)).all()


class TestRowTilingFallback:
    def test_untileable_rows_fall_back_not_crash(self):
        # rows not divisible by 8 have no Mosaic tiling; must take the
        # composed fallback (regression: hard ValueError at pallas dispatch)
        rs = np.random.RandomState(4)
        x = rs.randn(41 * 100, 128).astype(np.float32)
        res = rs.randn(41 * 100, 128).astype(np.float32)
        y = fused_dropout_add_layer_norm(jnp.asarray(x), jnp.asarray(res),
                                         None, None, dropout_p=0.0)
        np.testing.assert_allclose(np.asarray(y), _ref(x, res, None, None),
                                   rtol=1e-5, atol=1e-5)

    def test_fused_norm_untileable_rows(self):
        from paddle_tpu.kernels.fused_norm import fused_layer_norm
        rs = np.random.RandomState(5)
        x = rs.randn(13, 128).astype(np.float32)
        y = fused_layer_norm(jnp.asarray(x), None, None)
        np.testing.assert_allclose(
            np.asarray(y), _ref(x, np.zeros_like(x), None, None),
            rtol=1e-5, atol=1e-5)
