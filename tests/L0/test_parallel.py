"""Tests for rocm_apex_tpu.parallel: grad sync, SyncBatchNorm, LARC.

Mirrors the reference's distributed test intent
(reference: tests/distributed/DDP/, tests/distributed/synced_batchnorm/,
including the process-group-subset case test_groups.py) on the
CPU-simulated 8-device mesh instead of a 2-GPU host.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from _helpers import jit_shmap as _jit_shmap

from rocm_apex_tpu.parallel import (
    LARC,
    DistributedDataParallel,
    Reducer,
    SyncBatchNorm,
    broadcast_params,
    convert_syncbn_model,
    larc,
    sync_gradients,
)


def data_mesh(devs, n=8):
    return Mesh(np.array(devs[:n]), ("data",))


class TestSyncGradients:
    def test_mean_matches_manual(self, eight_devices):
        mesh = data_mesh(eight_devices)
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 3))

        f = _jit_shmap(
            lambda t: sync_gradients({"w": t}, "data")["w"],
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
        out = f(g)
        expected = jnp.broadcast_to(g.mean(axis=0, keepdims=True), g.shape)
        np.testing.assert_allclose(out, expected, rtol=1e-6)

    def test_sum_when_not_averaging(self, eight_devices):
        mesh = data_mesh(eight_devices)
        g = jax.random.normal(jax.random.PRNGKey(1), (8, 5))
        f = _jit_shmap(
            lambda t: sync_gradients(t, "data", gradient_average=False),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
        np.testing.assert_allclose(
            f(g)[0], g.sum(axis=0), rtol=1e-6
        )

    def test_predivide_factor_preserves_mean(self, eight_devices):
        """predivide changes staging, not the result
        (reference: distributed.py:443-455)."""
        mesh = data_mesh(eight_devices)
        g = jax.random.normal(jax.random.PRNGKey(2), (8, 16))
        f = _jit_shmap(
            lambda t: sync_gradients(t, "data", gradient_predivide_factor=4.0),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
        np.testing.assert_allclose(f(g)[0], g.mean(axis=0), rtol=1e-5)

    def test_allreduce_always_fp32_returns_original_dtype(self, eight_devices):
        mesh = data_mesh(eight_devices)
        g = jax.random.normal(jax.random.PRNGKey(3), (8, 8)).astype(jnp.bfloat16)
        f = _jit_shmap(
            lambda t: sync_gradients(t, "data", allreduce_always_fp32=True),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
        out = f(g)
        assert out.dtype == jnp.bfloat16
        # fp32 accumulation then one rounding — compare against fp32 mean.
        np.testing.assert_allclose(
            np.asarray(out[0], np.float32),
            np.asarray(g.astype(jnp.float32).mean(axis=0)),
            rtol=1e-2,
        )

    def test_group_subsets(self, eight_devices):
        """Reduction restricted to replica subgroups."""
        mesh = data_mesh(eight_devices)
        groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
        g = jax.random.normal(jax.random.PRNGKey(4), (8, 6))
        f = _jit_shmap(
            lambda t: sync_gradients(t, "data", axis_index_groups=groups),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
        out = f(g)
        np.testing.assert_allclose(out[0], g[:4].mean(axis=0), rtol=1e-6)
        np.testing.assert_allclose(out[7], g[4:].mean(axis=0), rtol=1e-6)

    def test_ddp_wrapper_and_reducer(self, eight_devices):
        mesh = data_mesh(eight_devices)
        ddp = DistributedDataParallel(allreduce_always_fp32=True)
        red = Reducer()
        g = jax.random.normal(jax.random.PRNGKey(5), (8, 4))
        f = _jit_shmap(
            lambda t: (ddp(t), red(t)),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
        a, b = f(g)
        np.testing.assert_allclose(a[0], g.mean(axis=0), rtol=1e-6)
        np.testing.assert_allclose(b[0], g.mean(axis=0), rtol=1e-6)

    def test_broadcast_params_restores_agreement(self, eight_devices):
        mesh = data_mesh(eight_devices)
        p = jax.random.normal(jax.random.PRNGKey(6), (8, 3))
        f = _jit_shmap(
            lambda t: broadcast_params({"w": t})["w"],
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
        out = f(p)
        for i in range(8):
            np.testing.assert_allclose(out[i], out[0], rtol=0)

    def test_int_leaves_pass_through(self, eight_devices):
        mesh = data_mesh(eight_devices)
        step = jnp.arange(8, dtype=jnp.int32)
        f = _jit_shmap(
            lambda t: sync_gradients(t, "data"),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
        np.testing.assert_array_equal(f(step), step)


def _reference_bn(x, scale, bias, eps=1e-5):
    """Full-batch BN computed the plain way, channel-last."""
    mean = x.mean(axis=tuple(range(x.ndim - 1)))
    var = x.var(axis=tuple(range(x.ndim - 1)))
    y = (x - mean) / np.sqrt(var + eps)
    return y * scale + bias


class TestSyncBatchNorm:
    def test_matches_full_batch_bn(self, eight_devices):
        """8-way sharded SyncBN == BN over the concatenated batch
        (the core property; reference: tests/distributed/synced_batchnorm/
        two_gpu_unit_test.py)."""
        mesh = data_mesh(eight_devices)
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 6, 5, 4))  # NHWC
        bn = SyncBatchNorm(channel_last=True, axis_name="data")
        vars_ = bn.init(jax.random.PRNGKey(1), x[:2], use_running_average=False)

        def step(xs):
            y, upd = bn.apply(
                vars_, xs, use_running_average=False, mutable=["batch_stats"]
            )
            return y, upd["batch_stats"]

        f = _jit_shmap(
            step, mesh=mesh, in_specs=P("data"), out_specs=(P("data"), P())
        )
        y, stats = f(x)
        expected = _reference_bn(np.asarray(x), 1.0, 0.0)
        np.testing.assert_allclose(np.asarray(y), expected, atol=1e-5)

        # Running stats: torch convention new = 0.9*old + 0.1*batch,
        # with unbiased batch var.
        n = x.size / x.shape[-1]
        exp_mean = 0.1 * np.asarray(x).mean(axis=(0, 1, 2))
        exp_var = 0.9 * 1.0 + 0.1 * np.asarray(x).var(axis=(0, 1, 2)) * n / (n - 1)
        np.testing.assert_allclose(stats["mean"], exp_mean, atol=1e-5)
        np.testing.assert_allclose(stats["var"], exp_var, atol=1e-5)

    def test_nchw_layout(self, eight_devices):
        mesh = data_mesh(eight_devices)
        x = jax.random.normal(jax.random.PRNGKey(2), (16, 4, 3, 5))  # NCHW
        bn = SyncBatchNorm(channel_last=False, axis_name="data")
        vars_ = bn.init(jax.random.PRNGKey(1), x[:2], use_running_average=False)
        f = _jit_shmap(
            lambda xs: bn.apply(vars_, xs, use_running_average=False),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
        y = f(x)
        xl = np.moveaxis(np.asarray(x), 1, -1)
        expected = np.moveaxis(_reference_bn(xl, 1.0, 0.0), -1, 1)
        np.testing.assert_allclose(np.asarray(y), expected, atol=1e-5)

    def test_group_subsets(self, eight_devices):
        """Two groups of 4 normalize independently
        (reference: tests/distributed/synced_batchnorm/test_groups.py)."""
        mesh = data_mesh(eight_devices)
        groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
        x = jax.random.normal(jax.random.PRNGKey(3), (16, 4))
        bn = SyncBatchNorm(
            channel_last=True, axis_name="data", axis_index_groups=groups
        )
        vars_ = bn.init(jax.random.PRNGKey(1), x[:2], use_running_average=False)
        f = _jit_shmap(
            lambda xs: bn.apply(vars_, xs, use_running_average=False),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
        y = np.asarray(f(x))
        np.testing.assert_allclose(
            y[:8], _reference_bn(np.asarray(x[:8]), 1.0, 0.0), atol=1e-5
        )
        np.testing.assert_allclose(
            y[8:], _reference_bn(np.asarray(x[8:]), 1.0, 0.0), atol=1e-5
        )

    def test_eval_uses_running_stats(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (4, 3))
        bn = SyncBatchNorm(axis_name=None, channel_last=True)
        vars_ = bn.init(jax.random.PRNGKey(1), x, use_running_average=False)
        y = bn.apply(vars_, x, use_running_average=True)
        # fresh stats are mean=0 var=1 -> identity up to the epsilon in
        # the denominator: y = x/sqrt(1+eps) scales x by ~eps/2 = 5e-6,
        # which puts |y-x| at 1.2e-5 for the |x|~2.5 draws in this key
        # (ISSUE 2 triage: the old atol=1e-5 sat under the eps term)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=3e-5)

    def test_fuse_relu(self):
        x = jax.random.normal(jax.random.PRNGKey(5), (32, 3))
        bn = SyncBatchNorm(axis_name=None, channel_last=True, fuse_relu=True)
        vars_ = bn.init(jax.random.PRNGKey(1), x, use_running_average=False)
        y = np.asarray(bn.apply(vars_, x, use_running_average=False))
        assert (y >= 0).all()

    def test_gradients_match_full_batch(self, eight_devices):
        """Backward through the psums == backward of full-batch BN
        (the reference needs a hand-written dgrad kernel + allreduce;
        here it is autodiff, but the numbers must agree)."""
        mesh = data_mesh(eight_devices)
        x = jax.random.normal(jax.random.PRNGKey(6), (16, 4))
        bn = SyncBatchNorm(channel_last=True, axis_name="data")
        vars_ = bn.init(jax.random.PRNGKey(1), x[:2], use_running_average=False)

        def sharded_loss(xs):
            def local(xl):
                y = bn.apply(vars_, xl, use_running_average=False)
                return jax.lax.psum(jnp.sum(y**2), "data")

            f = _jit_shmap(local, mesh=mesh, in_specs=P("data"), out_specs=P())
            return f(xs)

        def full_loss(xs):
            mean = xs.mean(axis=0)
            var = xs.var(axis=0)
            y = (xs - mean) * jax.lax.rsqrt(var + 1e-5)
            return jnp.sum(y**2)

        gs = jax.grad(sharded_loss)(x)
        gf = jax.grad(full_loss)(x)
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gf), atol=1e-4)

    def test_convert_syncbn_model(self):
        class Net(nn.Module):
            bn: nn.Module = nn.BatchNorm(use_running_average=False)

            @nn.compact
            def __call__(self, x):
                return self.bn(x)

        net = Net()
        conv = convert_syncbn_model(net, axis_name=None)
        assert isinstance(conv.bn, SyncBatchNorm)
        assert conv.bn.channel_last  # flax axis=-1 -> NHWC
        assert abs(conv.bn.momentum - 0.01) < 1e-9  # 1 - flax 0.99 decay
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 3))
        v = conv.init(jax.random.PRNGKey(1), x)
        y = conv.apply(v, x)
        np.testing.assert_allclose(
            np.asarray(y), _reference_bn(np.asarray(x), 1.0, 0.0), atol=1e-5
        )


class TestLARC:
    def test_clip_mode_matches_manual(self):
        """Rewrite matches the reference formula (LARC.py:69-107)."""
        p = jnp.array([3.0, 4.0])  # ||p|| = 5
        g = jnp.array([0.6, 0.8])  # ||g|| = 1
        lr, trust, eps = 0.1, 0.02, 1e-8
        tx = larc(lr=lr, trust_coefficient=trust, eps=eps)
        out, _ = tx.update({"w": g}, tx.init({"w": p}), {"w": p})
        adaptive = trust * 5.0 / (1.0 + eps)  # = 0.1
        expected = g * min(adaptive / lr, 1.0)
        np.testing.assert_allclose(out["w"], expected, rtol=1e-6)

    def test_scale_mode_and_weight_decay(self):
        p = jnp.array([3.0, 4.0])
        g = jnp.array([0.6, 0.8])
        wd, trust, eps = 0.01, 0.02, 1e-8
        tx = larc(trust_coefficient=trust, clip=False, eps=eps, weight_decay=wd)
        out, _ = tx.update({"w": g}, tx.init({"w": p}), {"w": p})
        adaptive = trust * 5.0 / (1.0 + 5.0 * wd + eps)
        expected = (g + wd * p) * adaptive
        np.testing.assert_allclose(out["w"], expected, rtol=1e-6)

    def test_zero_grad_passthrough(self):
        p = jnp.array([1.0, 2.0])
        g = jnp.zeros(2)
        tx = larc()
        out, _ = tx.update({"w": g}, tx.init({"w": p}), {"w": p})
        np.testing.assert_allclose(out["w"], g)

    def test_class_wrapper_with_optax(self):
        params = {"w": jnp.array([3.0, 4.0])}
        grads = {"w": jnp.array([0.6, 0.8])}
        inner = optax.sgd(0.1)
        opt = LARC(inner, trust_coefficient=0.02, lr=0.1)
        state = opt.init(params)
        updates, _ = opt.update(grads, state, params)
        tx = larc(lr=0.1, trust_coefficient=0.02)
        scaled, _ = tx.update(grads, tx.init(params), params)
        expected, _ = inner.update(scaled, inner.init(params), params)
        np.testing.assert_allclose(updates["w"], expected["w"], rtol=1e-6)


class TestReplicaConsistency:
    """The TPU analogue of the reference's DDP race-condition test
    (reference: tests/distributed/DDP/ddp_race_condition_test.py, which
    hunts for gradient-allreduce/compute overlap races by checking
    p.grad agreement across ranks). Here the hazard class is a missed
    psum or a per-rank RNG leak: after N data-parallel steps on
    per-rank-DIFFERENT batches with dropout active, every rank's
    parameters must be BITWISE identical."""

    def test_params_bitwise_identical_across_ranks(self, eight_devices):
        mesh = data_mesh(eight_devices)

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, *, rng):
                x = nn.Dense(32)(x)
                # dropout with an explicitly folded per-step rng: the
                # MASK may differ per rank (it acts like per-rank data);
                # only the gradient psum keeps params in agreement
                keep = jax.random.bernoulli(rng, 0.9, x.shape)
                x = jnp.where(keep, x / 0.9, 0.0)
                return nn.Dense(4)(x)

        model = Net()
        xs = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
        ys = jax.random.normal(jax.random.PRNGKey(1), (64, 4))
        params0 = model.init(
            jax.random.PRNGKey(2), xs[:1], rng=jax.random.PRNGKey(0)
        )
        tx = optax.sgd(0.05, momentum=0.9)

        def local_steps(params, x, y):
            # per-rank rng stream — folded from the data rank like the
            # reference's per-process seeds
            r = jax.lax.axis_index("data")
            opt_state = tx.init(params)

            def step(carry, i):
                params, opt_state = carry
                rng = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(7), r), i
                )

                def loss_fn(p):
                    pred = model.apply(p, x, rng=rng)
                    return jnp.mean((pred - y) ** 2)

                grads = jax.grad(loss_fn)(params)
                grads = sync_gradients(grads, "data")
                updates, opt_state = tx.update(grads, opt_state, params)
                return (optax.apply_updates(params, updates), opt_state), ()

            (params, _), _ = jax.lax.scan(
                step, (params, opt_state), jnp.arange(5)
            )
            # emit THIS RANK's replica for cross-rank comparison
            return jax.tree_util.tree_map(lambda v: v[None], params)

        f = _jit_shmap(
            local_steps,
            mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=P("data"),
            check_vma=False,
        )
        stacked = jax.jit(f)(params0, xs, ys)
        for path, leaf in jax.tree_util.tree_leaves_with_path(stacked):
            arr = np.asarray(leaf)
            for rnk in range(1, arr.shape[0]):
                np.testing.assert_array_equal(
                    arr[0], arr[rnk],
                    err_msg=f"rank {rnk} diverged at {path}",
                )
