"""Packed-pytree multi-tensor ops vs stock jnp reference.

Mirrors the reference's amp_C kernel tests (reference:
tests/L0/run_amp/test_multi_tensor_scale.py, test_multi_tensor_axpby.py,
test_multi_tensor_l2norm.py): fused results must match composed
implementations, and the overflow flag must trip on injected inf/nan.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocm_apex_tpu.ops import multi_tensor
from rocm_apex_tpu.ops.packing import (
    WIDTH,
    build_pack_spec,
    pack_like,
    pack_tree,
    unpack_tree,
)


def make_tree(key, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(k1, (37, 19), dtype),
        "b": jax.random.normal(k2, (513,), dtype),
        "nested": {"v": jax.random.normal(k3, (4, 5, 6), dtype)},
    }


class TestPacking:
    def test_roundtrip(self):
        tree = make_tree(jax.random.PRNGKey(0))
        packed = pack_tree(tree)
        out = unpack_tree(packed)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, b), tree, out
        )

    def test_roundtrip_mixed_dtype(self):
        tree = {
            "a": jnp.ones((100, 3), jnp.bfloat16),
            "b": jnp.full((7,), 2.0, jnp.float32),
            "c": jnp.full((2, 2), 3.0, jnp.bfloat16),
        }
        packed = pack_tree(tree)
        assert len(packed.buffers) == 2  # bf16 + f32 groups
        out = unpack_tree(packed)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, b), tree, out
        )
        for buf in packed.buffers:
            assert buf.shape[1] == WIDTH
            assert buf.shape[0] % 64 == 0

    def test_pack_like_casts(self):
        params = {"a": jnp.ones((10,), jnp.bfloat16)}
        spec = build_pack_spec(params)
        grads = {"a": jnp.full((10,), 0.5, jnp.float32)}
        packed = pack_like(spec, grads)
        assert packed.buffers[0].dtype == jnp.bfloat16

    def test_jit_transparent(self):
        tree = make_tree(jax.random.PRNGKey(1))

        @jax.jit
        def f(t):
            return unpack_tree(pack_tree(t, spec))

        spec = build_pack_spec(tree)
        out = f(tree)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, b), tree, out
        )


class TestScale:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float16, jnp.bfloat16])
    def test_matches_reference(self, dtype):
        tree = make_tree(jax.random.PRNGKey(2), dtype)
        scaled, found_inf = multi_tensor.scale(tree, 4.0)
        ref = jax.tree_util.tree_map(
            lambda x: (x.astype(jnp.float32) * 4.0).astype(dtype), tree
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32)
            ),
            scaled,
            ref,
        )
        assert not bool(found_inf)

    @pytest.mark.parametrize("bad", [np.inf, -np.inf, np.nan])
    def test_overflow_flag(self, bad):
        tree = make_tree(jax.random.PRNGKey(3))
        tree["b"] = tree["b"].at[101].set(bad)
        _, found_inf = multi_tensor.scale(tree, 1.0)
        assert bool(found_inf)

    def test_out_dtype(self):
        tree = {"a": jnp.ones((5,), jnp.float16)}
        scaled, _ = multi_tensor.scale(tree, 2.0, out_dtype=jnp.float32)
        assert scaled["a"].dtype == jnp.float32
        np.testing.assert_allclose(scaled["a"], 2.0)


class TestManyGridBlocks:
    """A buffer that spans several 64-row grid blocks: every tree above
    packs into one. On a chip this is where a per-block output whose
    block shape Mosaic refuses shows up (a (1, 1) flag block over a
    (grid, 1) array compiled at grid == 1 and was refused at the 134M
    model's grid of 2066)."""

    def _big(self, key):
        return {"big": jax.random.normal(key, (70 * WIDTH + 5,))}

    @pytest.mark.parametrize("bad_at", [None, 3, 69 * WIDTH])
    def test_scale_axpby_and_sumsq(self, bad_at):
        x = self._big(jax.random.PRNGKey(8))
        y = self._big(jax.random.PRNGKey(9))
        if bad_at is not None:
            x["big"] = x["big"].at[bad_at].set(np.inf)
        packed = pack_tree(x)
        assert packed.buffers[0].shape[0] // multi_tensor.BLOCK_ROWS > 1

        scaled, inf_s = multi_tensor.scale(x, 0.5)
        summed, inf_a = multi_tensor.axpby(x, y, 2.0, -0.5)
        out, inf_q, (rsq,) = multi_tensor.scale_sumsq_packed(packed, 0.5)
        for flag in (inf_s, inf_a, inf_q):
            assert bool(flag) == (bad_at is not None)
        if bad_at is None:
            np.testing.assert_allclose(scaled["big"], 0.5 * x["big"])
            np.testing.assert_allclose(
                summed["big"], 2.0 * x["big"] - 0.5 * y["big"], rtol=1e-6
            )
            np.testing.assert_allclose(
                float(rsq.sum()),
                float(jnp.sum((0.5 * x["big"]) ** 2)),
                rtol=1e-5,
            )


class TestAxpby:
    def test_matches_reference(self):
        x = make_tree(jax.random.PRNGKey(4))
        y = make_tree(jax.random.PRNGKey(5))
        out, found_inf = multi_tensor.axpby(x, y, 2.0, -0.5)
        ref = jax.tree_util.tree_map(lambda a, b: 2.0 * a - 0.5 * b, x, y)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), out, ref
        )
        assert not bool(found_inf)

    def test_overflow_flag(self):
        x = {"a": jnp.array([1.0, jnp.inf])}
        y = {"a": jnp.zeros((2,))}
        _, found_inf = multi_tensor.axpby(x, y, 1.0, 1.0)
        assert bool(found_inf)


class TestL2Norm:
    def test_global(self):
        tree = make_tree(jax.random.PRNGKey(6))
        norm, _ = multi_tensor.l2norm(tree)
        flat = jnp.concatenate(
            [jnp.ravel(x) for x in jax.tree_util.tree_leaves(tree)]
        )
        np.testing.assert_allclose(norm, jnp.linalg.norm(flat), rtol=1e-5)

    def test_per_tensor(self):
        tree = make_tree(jax.random.PRNGKey(7))
        norm, per = multi_tensor.l2norm(tree, per_tensor=True)
        ref = jax.tree_util.tree_map(lambda x: jnp.linalg.norm(jnp.ravel(x)), tree)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5), per, ref
        )

    def test_bf16(self):
        tree = {"a": jnp.full((2048,), 2.0, jnp.bfloat16)}
        norm, _ = multi_tensor.l2norm(tree)
        np.testing.assert_allclose(float(norm), 2.0 * np.sqrt(2048), rtol=1e-2)
