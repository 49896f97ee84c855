"""Contrib tier tests: xentropy, fast LN, groupbn, transducer, ASP,
bottleneck (incl. spatial halo-exchange parity).

Mirrors apex/contrib/test/* — every contrib feature is validated
against the composed stock implementation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map

from _helpers import jit_shmap as _jit_shmap
from jax.sharding import Mesh, PartitionSpec as P

from rocm_apex_tpu.contrib.bottleneck import Bottleneck, SpatialBottleneck
from rocm_apex_tpu.contrib.groupbn import BatchNorm2d_NHWC
from rocm_apex_tpu.contrib.layer_norm import FastLayerNorm
from rocm_apex_tpu.contrib.sparsity import (
    ASP,
    apply_masks,
    compute_sparse_masks,
    create_mask,
    maintain_sparsity,
)
from rocm_apex_tpu.contrib.transducer import (
    TransducerLoss,
    transducer_joint,
    transducer_loss,
)
from rocm_apex_tpu.contrib.xentropy import SoftmaxCrossEntropyLoss


class TestXentropy:
    def test_matches_logsoftmax(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (6, 50))
        labels = jnp.asarray([3, 0, 7, 49, 0, 11])
        loss = SoftmaxCrossEntropyLoss.apply(logits, labels, 0.0, -1)
        ref = -jnp.take_along_axis(
            jax.nn.log_softmax(logits, -1), labels[:, None], 1
        )[:, 0]
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref), rtol=1e-5)

    def test_padding_idx_zeroes(self):
        logits = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
        labels = jnp.asarray([0, 2, 0, 5])
        loss = SoftmaxCrossEntropyLoss.apply(logits, labels, 0.0, 0)
        assert float(loss[0]) == 0.0 and float(loss[2]) == 0.0
        assert float(loss[1]) > 0.0


class TestFastLayerNorm:
    def test_matches_stock(self):
        m = FastLayerNorm(64)
        x = jax.random.normal(jax.random.PRNGKey(2), (10, 64))
        params = m.init(jax.random.PRNGKey(3), x)
        got = m.apply(params, x)
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        want = (x - mu) / jnp.sqrt(var + 1e-5)
        want = want * params["params"]["weight"] + params["params"]["bias"]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
        )

    def test_rejects_3d(self):
        from rocm_apex_tpu.contrib.layer_norm import fast_layer_norm

        with pytest.raises(ValueError, match="2D"):
            fast_layer_norm(
                jnp.ones((2, 3, 4)), jnp.ones((4,)), jnp.zeros((4,))
            )


class TestGroupBN:
    def test_subgroup_stats(self, eight_devices):
        """bn_group=2 partitions 4 ranks into two stat groups
        (reference: groupbn IPC peer groups)."""
        mesh = Mesh(np.array(eight_devices[:4]), ("data",))
        m = BatchNorm2d_NHWC(num_features=8, bn_group=2)
        # two groups get different data -> different normalized outputs
        x = jnp.concatenate(
            [
                jax.random.normal(jax.random.PRNGKey(4), (4, 4, 4, 8)),
                jax.random.normal(jax.random.PRNGKey(5), (4, 4, 4, 8)) * 3.0,
            ]
        )

        def local(x):
            variables = m.init(jax.random.PRNGKey(6), x)
            y, _ = m.apply(variables, x, mutable=["batch_stats"])
            return y

        f = _jit_shmap(
            local, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
            check_vma=False,
        )
        y = np.asarray(f(x))
        # normalized within groups: each group's output is ~zero-mean
        assert abs(y[:4].mean()) < 0.1 and abs(y[4:].mean()) < 0.1

    def test_fuse_relu(self):
        m = BatchNorm2d_NHWC(num_features=4, bn_group=1, fuse_relu=True)
        x = jax.random.normal(jax.random.PRNGKey(7), (2, 4, 4, 4))
        variables = m.init(jax.random.PRNGKey(8), x)
        y, _ = m.apply(variables, x, mutable=["batch_stats"])
        assert float(np.asarray(y).min()) >= 0.0


def loop_transducer_loss(x, label, f_len, y_len, blank):
    """Literal per-cell alpha recursion (the reference kernel's math,
    transducer_loss_kernel.cu alpha DP) as a python loop."""
    B, T, U, V = x.shape
    lp = np.asarray(jax.nn.log_softmax(x.astype(jnp.float32), -1))
    out = []
    for b in range(B):
        Tn, Un = int(f_len[b]), int(y_len[b]) + 1
        alpha = np.full((Tn, Un), -np.inf)
        alpha[0, 0] = 0.0
        for t in range(Tn):
            for u in range(Un):
                cands = []
                if t > 0:
                    cands.append(alpha[t - 1, u] + lp[b, t - 1, u, blank])
                if u > 0:
                    cands.append(
                        alpha[t, u - 1] + lp[b, t, u - 1, label[b, u - 1]]
                    )
                if cands:
                    alpha[t, u] = np.logaddexp.reduce(cands)
        out.append(
            -(alpha[Tn - 1, Un - 1] + lp[b, Tn - 1, Un - 1, blank])
        )
    return np.asarray(out)


class TestTransducer:
    def test_joint_broadcast(self):
        f = jax.random.normal(jax.random.PRNGKey(9), (2, 5, 8))
        g = jax.random.normal(jax.random.PRNGKey(10), (2, 3, 8))
        h = transducer_joint(
            f, g, jnp.asarray([5, 4]), jnp.asarray([3, 2])
        )
        assert h.shape == (2, 5, 3, 8)
        np.testing.assert_allclose(
            np.asarray(h[0, 1, 2]), np.asarray(f[0, 1] + g[0, 2]), rtol=1e-6
        )

    def test_joint_packed(self):
        f = jax.random.normal(jax.random.PRNGKey(11), (2, 4, 6))
        g = jax.random.normal(jax.random.PRNGKey(12), (2, 3, 6))
        f_len = jnp.asarray([4, 2])
        g_len = jnp.asarray([3, 2])
        offs = jnp.cumsum(f_len * g_len)
        packed = transducer_joint(
            f, g, f_len, g_len,
            pack_output=True, batch_offset=offs, packed_batch=16,
        )
        assert packed.shape == (16, 6)
        # row 12 = batch 1, t=0, u=0
        np.testing.assert_allclose(
            np.asarray(packed[12]), np.asarray(f[1, 0] + g[1, 0]), rtol=1e-6
        )

    def test_loss_matches_loop(self):
        B, T, U, V = 3, 6, 4, 10
        x = jax.random.normal(jax.random.PRNGKey(13), (B, T, U, V))
        label = jax.random.randint(jax.random.PRNGKey(14), (B, U - 1), 1, V)
        f_len = jnp.asarray([6, 4, 5])
        y_len = jnp.asarray([3, 2, 1])
        got = transducer_loss(x, label, f_len, y_len, 0)
        want = loop_transducer_loss(
            np.asarray(x), np.asarray(label), np.asarray(f_len),
            np.asarray(y_len), 0,
        )
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)

    def test_loss_grad_finite(self):
        B, T, U, V = 2, 4, 3, 6
        x = jax.random.normal(jax.random.PRNGKey(15), (B, T, U, V))
        label = jnp.ones((B, U - 1), jnp.int32)
        g = jax.grad(
            lambda x: transducer_loss(
                x, label, jnp.asarray([4, 3]), jnp.asarray([2, 1]), 0
            ).sum()
        )(x)
        assert np.isfinite(np.asarray(g)).all()

    def test_facade(self):
        loss_mod = TransducerLoss()
        x = jax.random.normal(jax.random.PRNGKey(16), (1, 3, 2, 5))
        out = loss_mod(x, jnp.ones((1, 1), jnp.int32), jnp.asarray([3]),
                       jnp.asarray([1]), 0)
        assert out.shape == (1,)

    def test_loss_packed_matches_padded(self):
        """packed_input mode (reference transducer.py:89-117):
        batch_offset = cumsum(f_len*(y_len+1)), max_f_len = T. The
        loss and the per-row gradients must match the padded path,
        with zero grads on don't-care rows never packed."""
        B, T, U, V = 3, 5, 4, 7
        x = jax.random.normal(jax.random.PRNGKey(17), (B, T, U, V))
        label = jax.random.randint(jax.random.PRNGKey(18), (B, U - 1), 1, V)
        f_len = jnp.asarray([5, 3, 4])
        y_len = jnp.asarray([3, 1, 2])
        g_len = y_len + 1
        batch_offset = jnp.cumsum(f_len * g_len)
        total = int(batch_offset[-1])

        # pack the VALID region of x row-major (t-major, u-minor)
        def pack(x):
            rows = []
            for b in range(B):
                for t in range(int(f_len[b])):
                    for u in range(int(g_len[b])):
                        rows.append(x[b, t, u])
            return jnp.stack(rows)

        xp = pack(x)
        assert xp.shape == (total, V)

        loss_mod = TransducerLoss(packed_input=True)
        got = loss_mod(
            xp, label, f_len, y_len, 0,
            batch_offset=batch_offset, max_f_len=T,
        )
        want = transducer_loss(x, label, f_len, y_len, 0)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

        g_packed = jax.grad(
            lambda xp: loss_mod(
                xp, label, f_len, y_len, 0,
                batch_offset=batch_offset, max_f_len=T,
            ).sum()
        )(xp)
        g_padded = jax.grad(
            lambda x: transducer_loss(x, label, f_len, y_len, 0).sum()
        )(x)
        np.testing.assert_allclose(
            np.asarray(g_packed),
            np.asarray(pack(g_padded)),
            rtol=1e-4,
            atol=1e-5,
        )

    def test_loss_packed_requires_offsets(self):
        loss_mod = TransducerLoss(packed_input=True)
        with pytest.raises(ValueError, match="batch_offset"):
            loss_mod(
                jnp.zeros((4, 5)), jnp.ones((1, 1), jnp.int32),
                jnp.asarray([2]), jnp.asarray([1]), 0,
            )


class TestASP:
    def test_mask_keeps_top2_of_4(self):
        w = jnp.asarray([[0.1, -0.9, 0.5, 0.05, 2.0, 0.01, -3.0, 0.2]])
        m = create_mask(w)
        np.testing.assert_array_equal(
            np.asarray(m),
            [[False, True, True, False, True, False, True, False]],
        )

    def test_fifty_percent_sparsity(self):
        w = jax.random.normal(jax.random.PRNGKey(17), (32, 64))
        m = create_mask(w)
        assert float(jnp.mean(m.astype(jnp.float32))) == 0.5

    def test_end_to_end_training_stays_sparse(self):
        """Masked weights stay zero through optimizer steps
        (reference: ASP re-applies masks after optimizer.step)."""
        params = {
            "dense": jax.random.normal(jax.random.PRNGKey(18), (32, 32)),
            "bias": jnp.zeros((32,)),
        }
        asp = ASP()
        params = asp.init_model_for_pruning(params)
        tx = asp.init_optimizer_for_pruning(optax.adam(1e-2))
        state = tx.init(params)
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        for _ in range(3):
            updates, state = tx.update(grads, state, params)
            params = optax.apply_updates(params, updates)
        w = np.asarray(params["dense"])
        mask = np.asarray(asp.masks["dense"])
        assert (w[~mask] == 0).all()
        assert (w[mask] != 0).any()
        assert asp.masks["bias"] is None  # 1-D not prunable


class TestBottleneck:
    def test_shapes_and_residual(self):
        m = Bottleneck(64, 32, 128, stride=2)
        x = jax.random.normal(jax.random.PRNGKey(19), (2, 16, 16, 64))
        variables = m.init(jax.random.PRNGKey(20), x)
        y, _ = m.apply(variables, x, mutable=["batch_stats"])
        assert y.shape == (2, 8, 8, 128)

    def test_spatial_matches_dense(self, eight_devices):
        """H-sharded bottleneck with halo exchange == unsharded
        (reference: SpatialBottleneck correctness bar)."""
        mesh = Mesh(np.array(eight_devices[:4]), ("spatial",))
        dense = Bottleneck(16, 8, 16)
        spatial = SpatialBottleneck(16, 8, 16, spatial_axis="spatial")
        x = jax.random.normal(jax.random.PRNGKey(21), (2, 16, 8, 16))
        variables = dense.init(jax.random.PRNGKey(22), x, train=False)

        y_dense = dense.apply(variables, x, train=False)

        def local(x_shard):
            return spatial.apply(variables, x_shard, train=False)

        # shard H (axis 1) over the spatial axis
        f = _jit_shmap(
            local, mesh=mesh,
            in_specs=(P(None, "spatial"),),
            out_specs=P(None, "spatial"),
            check_vma=False,
        )
        y_spatial = f(x)
        np.testing.assert_allclose(
            np.asarray(y_spatial), np.asarray(y_dense), rtol=1e-4, atol=1e-4
        )
