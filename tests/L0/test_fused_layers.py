"""Fused layer kernels vs composed-jnp references.

Mirrors the reference-equivalence idiom (SURVEY.md §4): every fused
kernel is tested against the stock composition it replaces —
  - layer_norm fwd/bwd vs jax-native LN  (reference: tests/L0/run_fused_layer_norm)
  - scaled masked/causal softmax vs jax.nn.softmax
    (reference: tests/L0/run_transformer/test_fused_softmax.py)
  - label-smoothing softmax CE vs a composed log-softmax formula
    (reference: apex/contrib/test/xentropy)
Kernels run in Pallas interpret mode on CPU (ops/_pallas.py).
"""

import jax
import jax.numpy as jnp
import numpy as np

from _helpers import assert_close
import pytest

from rocm_apex_tpu.normalization import (
    FusedLayerNorm,
    MixedFusedLayerNorm,
    fused_layer_norm,
    fused_layer_norm_affine,
)
from rocm_apex_tpu.ops import layer_norm as ln_ops
from rocm_apex_tpu.ops.softmax import (
    scaled_masked_softmax,
    scaled_upper_triang_masked_softmax,
)
from rocm_apex_tpu.ops.xentropy import softmax_cross_entropy_loss


def ref_ln(x, w=None, b=None, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    if w is not None:
        y = y * w + b
    return y


class TestLayerNorm:
    def test_fwd_affine(self):
        k = jax.random.PRNGKey(0)
        x = jax.random.normal(k, (24, 128))
        w = jax.random.normal(jax.random.PRNGKey(1), (128,)) + 1.0
        b = jax.random.normal(jax.random.PRNGKey(2), (128,))
        y, mu, rs = ln_ops.layer_norm_fwd(x, w, b, 1e-5)
        assert_close(
            np.asarray(y), np.asarray(ref_ln(x, w, b)), rtol=1e-5, atol=1e-5
        )
        assert_close(
            np.asarray(mu).squeeze(), np.asarray(jnp.mean(x, axis=-1)), rtol=1e-5, atol=1e-6
        )

    def test_grad_affine_matches_jax(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (64,)) + 1.0
        b = jax.random.normal(jax.random.PRNGKey(2), (64,))

        def fused(x, w, b):
            return jnp.sum(jnp.sin(ln_ops.layer_norm_affine(x, w, b, 1e-5)))

        def ref(x, w, b):
            return jnp.sum(jnp.sin(ref_ln(x, w, b)))

        gf = jax.grad(fused, argnums=(0, 1, 2))(x, w, b)
        gr = jax.grad(ref, argnums=(0, 1, 2))(x, w, b)
        for a, e in zip(gf, gr):
            assert_close(np.asarray(a), np.asarray(e), rtol=1e-4, atol=1e-4)

    def test_grad_no_affine(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 32))
        gf = jax.grad(lambda x: jnp.sum(ln_ops.layer_norm(x, 1e-5) ** 2))(x)
        gr = jax.grad(lambda x: jnp.sum(ref_ln(x) ** 2))(x)
        assert_close(np.asarray(gf), np.asarray(gr), rtol=1e-4, atol=1e-4)

    def test_module_nd_shape(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4, 32))
        mod = FusedLayerNorm(normalized_shape=32)
        params = mod.init(jax.random.PRNGKey(1), x)
        y = mod.apply(params, x)
        assert_close(
            np.asarray(y),
            np.asarray(ref_ln(x, jnp.ones((32,)), jnp.zeros((32,)))),
            rtol=1e-5,
            atol=1e-5,
        )

    def test_mixed_dtype_output_follows_params(self):
        """Out dtype = param dtype (reference fused_layer_norm.py:198-201)."""
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.bfloat16)
        mod = MixedFusedLayerNorm(normalized_shape=32, param_dtype=jnp.bfloat16)
        params = mod.init(jax.random.PRNGKey(1), x)
        y = mod.apply(params, x)
        assert y.dtype == jnp.bfloat16

    def test_residual_fused_matches_unfused(self):
        """(LN(x+d), x+d) from the fused kernel == add-then-LN, values
        AND gradients through both outputs (incl. the stream cotangent
        folded into the backward pass)."""
        x = jax.random.normal(jax.random.PRNGKey(3), (24, 64))
        d = jax.random.normal(jax.random.PRNGKey(4), (24, 64))
        w = jax.random.normal(jax.random.PRNGKey(5), (64,)) + 1.0
        b = jax.random.normal(jax.random.PRNGKey(6), (64,))

        y, s = ln_ops.layer_norm_residual_affine(x, d, w, b, 1e-5)
        assert_close(
            np.asarray(s), np.asarray(x + d), rtol=1e-6, atol=1e-6
        )
        assert_close(
            np.asarray(y), np.asarray(ref_ln(x + d, w, b)),
            rtol=1e-5, atol=1e-5,
        )

        def fused(x, d, w, b):
            y, s = ln_ops.layer_norm_residual_affine(x, d, w, b, 1e-5)
            # both outputs contribute distinct cotangents
            return jnp.sum(jnp.sin(y)) + jnp.sum(jnp.cos(s) * 0.5)

        def ref(x, d, w, b):
            s = x + d
            return jnp.sum(jnp.sin(ref_ln(s, w, b))) + jnp.sum(
                jnp.cos(s) * 0.5
            )

        gf = jax.grad(fused, argnums=(0, 1, 2, 3))(x, d, w, b)
        gr = jax.grad(ref, argnums=(0, 1, 2, 3))(x, d, w, b)
        for a, e in zip(gf, gr):
            assert_close(
                np.asarray(a), np.asarray(e), rtol=1e-4, atol=1e-4
            )

    def test_residual_mixed_input_dtypes_grad(self):
        """x and delta may differ in dtype (fp32 stream + bf16 delta):
        each cotangent must come back in its own input's dtype
        (round-2 review: a shared dx array broke jax.grad here)."""
        x = jax.random.normal(jax.random.PRNGKey(10), (8, 32), jnp.float32)
        d = jax.random.normal(jax.random.PRNGKey(11), (8, 32), jnp.bfloat16)
        w = jnp.ones((32,))
        b = jnp.zeros((32,))

        def f(x, d):
            y, s = ln_ops.layer_norm_residual_affine(x, d, w, b, 1e-5)
            return jnp.sum(y.astype(jnp.float32) ** 2) + jnp.sum(
                s.astype(jnp.float32)
            )

        gx, gd = jax.grad(f, (0, 1))(x, d)
        assert gx.dtype == jnp.float32
        assert gd.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(gx)).all()

    def test_residual_shape_validation(self):
        from rocm_apex_tpu.normalization.fused_layer_norm import (
            mixed_dtype_fused_layer_norm_residual_affine as lnr,
        )

        x = jnp.zeros((2, 4, 32))
        with pytest.raises(ValueError, match="shapes differ"):
            lnr(x, jnp.zeros((2, 5, 32)), jnp.ones(32), jnp.zeros(32), 32)
        with pytest.raises(ValueError, match="normalized_shape"):
            lnr(x, x, jnp.ones(16), jnp.zeros(16), 16)

    def test_residual_module_form(self):
        x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 32), jnp.bfloat16)
        d = jax.random.normal(jax.random.PRNGKey(8), (2, 8, 32), jnp.bfloat16)
        mod = MixedFusedLayerNorm(normalized_shape=32)
        params = mod.init(jax.random.PRNGKey(9), x)
        y, s = mod.apply(params, d, residual=x)
        assert y.dtype == jnp.float32  # follows fp32 params
        assert s.dtype == jnp.bfloat16  # stream follows the input
        assert_close(
            np.asarray(s, np.float32),
            np.asarray((x + d).astype(jnp.bfloat16), np.float32),
        )


class TestScaledSoftmax:
    def test_causal_matches_masked_jax(self):
        b, sq, sk = 2, 16, 16
        x = jax.random.normal(jax.random.PRNGKey(0), (b, sq, sk)) * 3
        scale = 0.7
        y = scaled_upper_triang_masked_softmax(x, scale)
        mask = np.triu(np.ones((sq, sk), bool), k=1)
        ref = jax.nn.softmax(
            jnp.where(jnp.asarray(mask), -jnp.inf, x * scale), axis=-1
        )
        assert_close(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-6)

    def test_causal_exact_zero_above_diagonal(self):
        """-inf fill ⇒ strictly zero attention to the future, even with
        extreme logit magnitudes (reference upper-triang kernel uses -inf)."""
        x = jnp.full((1, 8, 8), -20000.0)
        y = np.asarray(scaled_upper_triang_masked_softmax(x, 1.0))
        assert np.all(y[0][np.triu_indices(8, k=1)] == 0.0)
        # valid positions still form a normalized distribution
        assert_close(y[0].sum(axis=-1), np.ones(8), rtol=1e-6)

    def test_masked_matches_jax(self):
        b, h, sq, sk = 2, 3, 8, 16
        x = jax.random.normal(jax.random.PRNGKey(0), (b, h, sq, sk))
        mask = jax.random.bernoulli(jax.random.PRNGKey(1), 0.3, (b, 1, sq, sk))
        # keep at least one unmasked key per row
        mask = mask.at[..., 0].set(False)
        scale = 1.3
        y = scaled_masked_softmax(x, mask, scale)
        ref = jax.nn.softmax(jnp.where(mask, -10000.0, x * scale), axis=-1)
        assert_close(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-6)

    def test_causal_grad_matches_jax(self):
        b, s = 1, 8
        x = jax.random.normal(jax.random.PRNGKey(0), (b, s, s))

        def fused(x):
            return jnp.sum(scaled_upper_triang_masked_softmax(x, 0.5) ** 2)

        def ref(x):
            mask = jnp.triu(jnp.ones((s, s), bool), k=1)
            return jnp.sum(jax.nn.softmax(jnp.where(mask, -jnp.inf, x * 0.5)) ** 2)

        assert_close(
            np.asarray(jax.grad(fused)(x)),
            np.asarray(jax.grad(ref)(x)),
            rtol=1e-4,
            atol=1e-5,
        )

    def test_masked_grad_matches_jax(self):
        b, h, sq, sk = 1, 2, 8, 8
        x = jax.random.normal(jax.random.PRNGKey(0), (b, h, sq, sk))
        mask = jnp.zeros((b, 1, sq, sk), bool).at[..., -2:].set(True)

        def fused(x):
            return jnp.sum(jnp.cos(scaled_masked_softmax(x, mask, 2.0)))

        def ref(x):
            return jnp.sum(jnp.cos(jax.nn.softmax(jnp.where(mask, -10000.0, x * 2.0))))

        assert_close(
            np.asarray(jax.grad(fused)(x)),
            np.asarray(jax.grad(ref)(x)),
            rtol=1e-4,
            atol=1e-5,
        )


def ref_smoothed_ce(logits, labels, smoothing):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    if smoothing == 0.0:
        return nll
    smooth_loss = -jnp.mean(logp, axis=-1)
    return (1.0 - smoothing) * nll + smoothing * smooth_loss


class TestXentropy:
    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    def test_fwd_matches_reference(self, smoothing):
        rows, vocab = 16, 96
        logits = jax.random.normal(jax.random.PRNGKey(0), (rows, vocab)) * 2
        labels = jax.random.randint(jax.random.PRNGKey(1), (rows,), 1, vocab)
        loss = softmax_cross_entropy_loss(logits, labels, smoothing)
        ref = ref_smoothed_ce(logits, labels, smoothing)
        assert_close(np.asarray(loss), np.asarray(ref), rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    @pytest.mark.parametrize("padding_idx", [None, 0])
    def test_fused_variant_matches(self, smoothing, padding_idx):
        """softmax_cross_entropy_loss_fused (dlogits emitted during the
        forward read) must match the two-pass op in values AND grads."""
        from rocm_apex_tpu.ops.xentropy import (
            softmax_cross_entropy_loss_fused,
        )

        rows, vocab = 16, 96
        logits = jax.random.normal(jax.random.PRNGKey(2), (rows, vocab)) * 2
        labels = jax.random.randint(jax.random.PRNGKey(3), (rows,), 0, vocab)
        l_f = softmax_cross_entropy_loss_fused(
            logits, labels, smoothing, padding_idx
        )
        l_r = softmax_cross_entropy_loss(logits, labels, smoothing, padding_idx)
        assert_close(
            np.asarray(l_f), np.asarray(l_r), rtol=1e-5, atol=1e-6
        )
        w = jax.random.normal(jax.random.PRNGKey(4), (rows,))
        g_f = jax.grad(
            lambda l: jnp.sum(
                w * softmax_cross_entropy_loss_fused(
                    l, labels, smoothing, padding_idx
                )
            )
        )(logits)
        g_r = jax.grad(
            lambda l: jnp.sum(
                w * softmax_cross_entropy_loss(
                    l, labels, smoothing, padding_idx
                )
            )
        )(logits)
        assert_close(
            np.asarray(g_f), np.asarray(g_r), rtol=1e-5, atol=1e-6
        )

    def test_padding_idx_zeroes_loss_and_grad(self):
        rows, vocab = 8, 32
        logits = jax.random.normal(jax.random.PRNGKey(0), (rows, vocab))
        labels = jnp.array([0, 3, 0, 5, 7, 0, 2, 9])
        loss = softmax_cross_entropy_loss(logits, labels, 0.0, padding_idx=0)
        assert np.all(np.asarray(loss)[np.asarray(labels) == 0] == 0.0)
        g = jax.grad(
            lambda l: jnp.sum(softmax_cross_entropy_loss(l, labels, 0.0, 0))
        )(logits)
        g = np.asarray(g)
        assert np.all(g[np.asarray(labels) == 0] == 0.0)
        assert np.any(g[np.asarray(labels) != 0] != 0.0)

    @pytest.mark.parametrize("smoothing", [0.0, 0.2])
    def test_grad_matches_reference(self, smoothing):
        rows, vocab = 8, 64
        logits = jax.random.normal(jax.random.PRNGKey(0), (rows, vocab))
        labels = jax.random.randint(jax.random.PRNGKey(1), (rows,), 1, vocab)
        gf = jax.grad(
            lambda l: jnp.sum(softmax_cross_entropy_loss(l, labels, smoothing, -1))
        )(logits)
        gr = jax.grad(lambda l: jnp.sum(ref_smoothed_ce(l, labels, smoothing)))(logits)
        assert_close(np.asarray(gf), np.asarray(gr), rtol=1e-4, atol=1e-5)


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="in-kernel dropout uses the TPU PRNG (no interpret lowering)",
)
class TestLayerNormResidualDropoutTPU:
    """Runs only on real TPU (APEX_TPU_TEST_PLATFORM=tpu).

    The fused residual-LN-dropout kernel (ops/layer_norm.py
    `layer_norm_residual_dropout_affine`) regenerates its keep mask
    from the seed in backward; the mask is recovered from the forward's
    stream output and the whole VJP is checked against the explicitly
    composed chain using that same mask."""

    def _setup(self):
        rows, hidden = 1000, 512  # deliberately not a block multiple
        x = jax.random.normal(jax.random.PRNGKey(0), (rows, hidden))
        # delta magnitudes bounded away from 0: an element with
        # |delta|/(1-rate) under ulp(|x|) would be absorbed by the
        # in-kernel fp32 add, making the s - x mask recovery ambiguous
        d = jax.random.normal(jax.random.PRNGKey(1), (rows, hidden))
        delta = jnp.sign(d) * (0.1 + jnp.abs(d))
        w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (hidden,))
        b = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (hidden,))
        return x, delta, w, b

    def test_mask_statistics_and_determinism(self):
        x, delta, w, b = self._setup()
        rate, seed = 0.25, jnp.int32(77)
        _, s = ln_ops.layer_norm_residual_dropout_affine(
            x, delta, w, b, seed, rate, 1e-5
        )
        d_applied = np.asarray(s - x)
        keep = np.abs(d_applied) > 0
        assert abs(keep.mean() - (1 - rate)) < 0.02
        # atol: the recovery s - x re-rounds near-zero delta elements
        np.testing.assert_allclose(
            d_applied[keep],
            (np.asarray(delta) / (1 - rate))[keep],
            rtol=1e-5,
            atol=1e-6,
        )
        _, s2 = ln_ops.layer_norm_residual_dropout_affine(
            x, delta, w, b, seed, rate, 1e-5
        )
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))

    def test_vjp_matches_explicit_composition(self):
        x, delta, w, b = self._setup()
        rate, seed, eps = 0.1, jnp.int32(12345), 1e-5

        def fused(x, delta, w, b):
            return ln_ops.layer_norm_residual_dropout_affine(
                x, delta, w, b, seed, rate, eps
            )

        _, s = fused(x, delta, w, b)
        keep = jnp.abs(s - x) > 0  # backward must regenerate THESE bits

        def explicit(x, delta, w, b):
            d = jnp.where(keep, delta / (1 - rate), 0.0)
            return ln_ops.layer_norm_residual_affine(x, d, w, b, eps)

        cy = jax.random.normal(jax.random.PRNGKey(4), s.shape)
        cs = jax.random.normal(jax.random.PRNGKey(5), s.shape)

        def grads(f):
            def g(x, delta, w, b):
                y, s2 = f(x, delta, w, b)
                return jnp.sum(y * cy) + jnp.sum(s2 * cs)

            return jax.grad(g, (0, 1, 2, 3))(x, delta, w, b)

        for name, a, c in zip(
            ("dx", "ddelta", "dw", "db"), grads(fused), grads(explicit)
        ):
            rel = float(
                jnp.max(jnp.abs(a - c)) / (jnp.max(jnp.abs(c)) + 1e-12)
            )
            assert rel < 2e-5, (name, rel)
