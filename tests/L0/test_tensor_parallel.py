"""Tensor-parallel layers/mappings/CE vs single-device references.

Mirrors the reference's multi-GPU TP tests on the 8-device CPU mesh:
  - run_layers_test.py (column/row linear, vocab embedding vs serial)
  - run_cross_entropy_test.py (parallel CE vs plain log-softmax CE)
  - run_mappings_test.py (the four collective primitives)
  - run_data_test.py (broadcast_data)
(reference: tests/L0/run_transformer/*)
"""



import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from _helpers import jit_shmap

from rocm_apex_tpu.transformer import parallel_state, tensor_parallel
from rocm_apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    mappings,
    vocab_parallel_cross_entropy,
    broadcast_data,
)

TP = 4


def tp_mesh():
    devs = jax.devices()
    if len(devs) < TP:
        pytest.skip(f"needs {TP} simulated devices")
    return parallel_state.initialize_model_parallel(TP, 1, devices=devs[:TP])


def shmap(mesh, fn, in_specs, out_specs):
    return jit_shmap(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


class TestMappings:
    def test_copy_fwd_identity_bwd_psum(self):
        mesh = tp_mesh()
        x = jnp.arange(8.0).reshape(2, 4)

        def loss(x):
            y = mappings.copy_to_tensor_model_parallel_region(x)
            # per-rank distinct scaling so the backward psum is visible
            r = jax.lax.axis_index("tensor").astype(jnp.float32)
            return jnp.sum(y * (r + 1.0))

        f = shmap(mesh, jax.grad(loss), (P(),), P())
        g = f(x)
        # grads: sum over ranks of (r+1) = 1+2+3+4 = 10
        np.testing.assert_allclose(np.asarray(g), 10.0 * np.ones((2, 4)))

    def test_reduce_fwd_psum(self):
        mesh = tp_mesh()
        x = jnp.ones((2, 4))
        f = shmap(
            mesh,
            lambda x: mappings.reduce_from_tensor_model_parallel_region(x),
            (P(),),
            P(),
        )
        np.testing.assert_allclose(np.asarray(f(x)), TP * np.ones((2, 4)))

    def test_scatter_gather_roundtrip(self):
        mesh = tp_mesh()
        x = jnp.arange(16.0).reshape(2, 8)

        def roundtrip(x):
            local = mappings.scatter_to_tensor_model_parallel_region(x)
            assert local.shape == (2, 8 // TP)
            return mappings.gather_from_tensor_model_parallel_region(local)

        f = shmap(mesh, roundtrip, (P(),), P())
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x))

    def test_gather_bwd_is_split(self):
        mesh = tp_mesh()
        x = jnp.ones((2, 2))

        def loss(x):
            y = mappings.gather_from_tensor_model_parallel_region(x)
            return jnp.sum(y * jnp.arange(y.shape[-1], dtype=jnp.float32))

        f = shmap(mesh, jax.grad(loss), (P(None, "tensor"),), P(None, "tensor"))
        g = np.asarray(f(jnp.ones((2, 8))))
        np.testing.assert_allclose(g, np.tile(np.arange(8.0), (2, 1)))


class TestColumnParallelLinear:
    def test_matches_serial(self):
        mesh = tp_mesh()
        in_f, out_f = 16, 24
        x = jax.random.normal(jax.random.PRNGKey(0), (4, in_f))
        layer = ColumnParallelLinear(
            input_size=in_f, output_size=out_f, gather_output=True
        )

        def init_and_apply(x):
            params = layer.init(jax.random.PRNGKey(1), x)
            y, _ = layer.apply(params, x)
            # serial reference: gather the sharded kernel and matmul
            k = params["params"]["kernel"]
            k_full = jax.lax.all_gather(k, "tensor", axis=1, tiled=True)
            b = params["params"]["bias"]
            b_full = jax.lax.all_gather(b, "tensor", axis=0, tiled=True)
            y_ref = x @ k_full + b_full
            return y, y_ref

        f = shmap(mesh, init_and_apply, (P(),), (P(), P()))
        y, y_ref = f(x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)

    def test_shard_shapes_and_distinct_init(self):
        mesh = tp_mesh()
        layer = ColumnParallelLinear(input_size=8, output_size=16, gather_output=False)
        x = jnp.ones((2, 8))

        def f(x):
            params = layer.init(jax.random.PRNGKey(1), x)
            k = params["params"]["kernel"]
            assert k.shape == (8, 16 // TP)
            y, _ = layer.apply(params, x)
            assert y.shape == (2, 16 // TP)
            return jax.lax.all_gather(k, "tensor")

        ks = np.asarray(shmap(mesh, f, (P(),), P(None, None, "tensor"))(x))
        # per-rank shards must differ (rank-folded init)
        assert not np.allclose(ks[0], ks[1])


class TestRowParallelLinear:
    def test_matches_serial(self):
        mesh = tp_mesh()
        in_f, out_f = 16, 12
        x = jax.random.normal(jax.random.PRNGKey(0), (4, in_f))
        layer = RowParallelLinear(
            input_size=in_f, output_size=out_f, input_is_parallel=False
        )

        def init_and_apply(x):
            params = layer.init(jax.random.PRNGKey(1), x)
            y, _ = layer.apply(params, x)
            k = params["params"]["kernel"]
            k_full = jax.lax.all_gather(k, "tensor", axis=0, tiled=True)
            y_ref = x @ k_full + params["params"]["bias"]
            return y, y_ref

        f = shmap(mesh, init_and_apply, (P(),), (P(), P()))
        y, y_ref = f(x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)

    def test_column_into_row_pipeline(self):
        """ColumnParallel(gather_output=False) → RowParallel(input_is_parallel)
        equals a serial 2-layer MLP (reference run_layers_test.py pattern)."""
        mesh = tp_mesh()
        d, h = 8, 32
        x = jax.random.normal(jax.random.PRNGKey(0), (4, d))
        col = ColumnParallelLinear(input_size=d, output_size=h, gather_output=False)
        row = RowParallelLinear(input_size=h, output_size=d, input_is_parallel=True)

        def f(x):
            cp = col.init(jax.random.PRNGKey(1), x)
            h_local, _ = col.apply(cp, x)
            h_act = jax.nn.gelu(h_local)
            rp = row.init(jax.random.PRNGKey(2), h_act)
            y, _ = row.apply(rp, h_act)

            ck = jax.lax.all_gather(cp["params"]["kernel"], "tensor", axis=1, tiled=True)
            cb = jax.lax.all_gather(cp["params"]["bias"], "tensor", axis=0, tiled=True)
            rk = jax.lax.all_gather(rp["params"]["kernel"], "tensor", axis=0, tiled=True)
            y_ref = jax.nn.gelu(x @ ck + cb) @ rk + rp["params"]["bias"]
            return y, y_ref

        y, y_ref = shmap(mesh, f, (P(),), (P(), P()))(x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)


class TestVocabParallelEmbedding:
    def test_matches_serial(self):
        mesh = tp_mesh()
        vocab, dim = 32, 8
        ids = jnp.array([[0, 5, 31, 7], [8, 16, 24, 1]], dtype=jnp.int32)
        layer = VocabParallelEmbedding(num_embeddings=vocab, embedding_dim=dim)

        def f(ids):
            params = layer.init(jax.random.PRNGKey(3), ids)
            out = layer.apply(params, ids)
            w_full = jax.lax.all_gather(
                params["params"]["weight"], "tensor", axis=0, tiled=True
            )
            ref = jnp.take(w_full, ids, axis=0)
            return out, ref

        out, ref = shmap(mesh, f, (P(),), (P(), P()))(ids)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


class TestVocabParallelCrossEntropy:
    def test_matches_serial_ce(self):
        mesh = tp_mesh()
        b, s, vocab = 2, 4, 32
        logits = jax.random.normal(jax.random.PRNGKey(0), (b, s, vocab))
        target = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, vocab)

        def f(logits, target):
            local = mappings.scatter_to_tensor_model_parallel_region(logits)
            return vocab_parallel_cross_entropy(local, target)

        loss = shmap(mesh, f, (P(), P()), P())(logits, target)
        ref = -jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), target[..., None], axis=-1
        )[..., 0]
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref), rtol=1e-5, atol=1e-6)

    def test_gradient_matches_serial(self):
        mesh = tp_mesh()
        b, vocab = 4, 16
        logits = jax.random.normal(jax.random.PRNGKey(0), (b, vocab))
        target = jax.random.randint(jax.random.PRNGKey(1), (b,), 0, vocab)

        def par_loss(logits, target):
            def inner(logits, target):
                local = mappings.scatter_to_tensor_model_parallel_region(logits)
                return vocab_parallel_cross_entropy(local, target)

            return jnp.mean(shmap(mesh, inner, (P(), P()), P())(logits, target))

        def ref_loss(logits, target):
            lsm = jax.nn.log_softmax(logits, axis=-1)
            return jnp.mean(
                -jnp.take_along_axis(lsm, target[..., None], axis=-1)[..., 0]
            )

        g_par = jax.grad(par_loss)(logits, target)
        g_ref = jax.grad(ref_loss)(logits, target)
        np.testing.assert_allclose(np.asarray(g_par), np.asarray(g_ref), rtol=1e-5, atol=1e-6)

    def test_bf16_confident_gradient_not_flushed(self):
        """bf16 logits with a confidently-predicted target (p > 0.998)
        must keep a non-zero target-entry gradient: probabilities are
        recomputed in fp32 from saved row stats, never stored as an
        O(b·s·v) bf16 softmax (round-2 review finding)."""
        mesh = tp_mesh()
        b, vocab = 4, 16
        base = jax.random.normal(jax.random.PRNGKey(0), (b, vocab))
        target = jnp.zeros((b,), jnp.int32)
        # push the target logit high: softmax(target) ~ 0.9995+
        logits = base.at[:, 0].set(12.0).astype(jnp.bfloat16)

        def par_loss(logits, target):
            def inner(logits, target):
                local = mappings.scatter_to_tensor_model_parallel_region(
                    logits
                )
                return vocab_parallel_cross_entropy(local, target)

            return jnp.mean(
                shmap(mesh, inner, (P(), P()), P())(logits, target)
            )

        def ref_loss(logits, target):
            lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return jnp.mean(
                -jnp.take_along_axis(lsm, target[..., None], axis=-1)[..., 0]
            )

        g_par = jax.grad(par_loss)(logits, target)
        g_ref = jax.grad(ref_loss)(logits.astype(jnp.float32), target)
        # target-entry gradient is ~ (p-1)/b ~ -1e-4: must not be 0
        assert float(jnp.abs(g_par[:, 0].astype(jnp.float32)).max()) > 0.0
        np.testing.assert_allclose(
            np.asarray(g_par, np.float32),
            np.asarray(g_ref),
            rtol=0.05,
            atol=1e-6,
        )


class TestBroadcastData:
    def test_broadcast_from_rank0(self):
        mesh = tp_mesh()
        # per-rank different data along the tensor axis; rank 0's slice wins
        data = jnp.arange(TP * 4, dtype=jnp.float32).reshape(TP, 4)

        def f(x):
            out = broadcast_data(["x"], {"x": x}, jnp.float32)
            return out["x"]

        got = shmap(mesh, f, (P("tensor"),), P("tensor"))(data)
        expect = np.tile(np.asarray(data[0]), (TP, 1)).reshape(TP, 4)
        np.testing.assert_allclose(np.asarray(got), expect)


class TestRandom:
    def test_seed_offsets(self):
        keys0 = tensor_parallel.model_parallel_prng_keys(1234, 0)
        keys1 = tensor_parallel.model_parallel_prng_keys(1234, 1)
        # data-parallel stream identical across tp ranks, model-parallel differs
        assert np.array_equal(np.asarray(keys0["default"]), np.asarray(keys1["default"]))
        assert not np.array_equal(
            np.asarray(keys0["model-parallel-rng"]),
            np.asarray(keys1["model-parallel-rng"]),
        )

    def test_tracker_fork_advances(self):
        tr = tensor_parallel.RngStateTracker()
        tr.add("model-parallel-rng", 7)
        with tr.fork() as k1:
            pass
        with tr.fork() as k2:
            pass
        assert not np.array_equal(np.asarray(k1), np.asarray(k2))

    def test_checkpoint_recompute_matches(self):
        def fn(x, key):
            y = x * jax.random.normal(key, x.shape)
            return jnp.sum(jnp.tanh(y) ** 2)

        x = jnp.arange(4.0)
        key = jax.random.PRNGKey(0)
        direct = jax.grad(fn)(x, key)
        remat = jax.grad(
            lambda x, k: tensor_parallel.checkpoint(fn, x, k)
        )(x, key)
        np.testing.assert_allclose(np.asarray(direct), np.asarray(remat), rtol=1e-6)
