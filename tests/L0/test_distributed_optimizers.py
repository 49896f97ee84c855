"""ZeRO-sharded optimizers vs their unsharded fused counterparts.

Mirrors the reference's distributed-optimizer tests
(reference: apex/contrib/test/optimizers/test_dist_adam.py — sharded
DistributedFusedAdam must match single-GPU FusedAdam) on the 8-device
CPU mesh: the reduce-scatter/shard-update/all-gather pipeline must give
the same params as the unsharded kernel fed the pre-averaged grads.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from _helpers import jit_shmap as _jit_shmap

from rocm_apex_tpu.contrib.optimizers import (
    distributed_fused_adam,
    distributed_fused_lamb,
)
from rocm_apex_tpu.optimizers import fused_adam, fused_lamb

DP = 4


def make_params(key, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(k1, (24, 33), dtype) * 0.1,
        "b": jax.random.normal(k2, (33,), dtype) * 0.01,
        "emb": jax.random.normal(k3, (50, 16), dtype) * 0.1,
    }


def per_rank_grads(key, params, n=DP):
    """n distinct per-rank grad trees (fp32), stacked on axis 0."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key, n * len(leaves))
    out = []
    for r in range(n):
        gs = [
            jax.random.normal(
                keys[r * len(leaves) + i], leaf.shape, jnp.float32
            )
            for i, leaf in enumerate(leaves)
        ]
        out.append(jax.tree_util.tree_unflatten(treedef, gs))
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *out)


def data_mesh():
    devs = jax.devices()
    if len(devs) < DP:
        pytest.skip(f"needs {DP} devices")
    return Mesh(np.array(devs[:DP]), ("data",))


def run_sharded(tx, params, stacked_grads, mesh, steps=3):
    """Run `steps` updates of the distributed transform inside shard_map."""

    def local(params, grads):
        # grads arrive (1, ...) per rank — drop the stacking axis
        grads = jax.tree_util.tree_map(lambda g: g[0], grads)
        state = tx.init(params)
        for _ in range(steps):
            updates, state = tx.update(grads, state, params)
            params = optax.apply_updates(params, updates)
        return params

    f = _jit_shmap(
        local,
        mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(f)(params, stacked_grads)


def run_reference(tx, params, mean_grads, steps=3):
    state = tx.init(params)
    for _ in range(steps):
        updates, state = tx.update(mean_grads, state, params)
        params = optax.apply_updates(params, updates)
    return params


def assert_trees_close(a, b, rtol=2e-6, atol=2e-6):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=rtol, atol=atol,
        )


class TestDistributedFusedAdam:
    @pytest.mark.parametrize("predivide", [True, False])
    def test_matches_unsharded(self, predivide):
        mesh = data_mesh()
        params = make_params(jax.random.PRNGKey(0))
        stacked = per_rank_grads(jax.random.PRNGKey(1), params)
        mean = jax.tree_util.tree_map(lambda g: g.mean(0), stacked)

        dist = distributed_fused_adam(
            1e-2, weight_decay=0.01, predivide=predivide,
            allgather_dtype="fp32", axis_name="data"
        )
        ref = fused_adam(1e-2, weight_decay=0.01)
        got = run_sharded(dist, params, stacked, mesh)
        want = run_reference(ref, params, mean)
        assert_trees_close(got, want)

    def test_bf16_params_master_driven(self):
        """bf16 model params track the fp32 master shards exactly
        (reference e5m2/fp16 allgather-from-masters semantics)."""
        mesh = data_mesh()
        params = make_params(jax.random.PRNGKey(2), jnp.bfloat16)
        stacked = per_rank_grads(jax.random.PRNGKey(3), params)
        mean = jax.tree_util.tree_map(lambda g: g.mean(0), stacked)

        dist = distributed_fused_adam(1e-2, axis_name="data")
        ref = fused_adam(1e-2)
        got = run_sharded(dist, params, stacked, mesh)
        want = run_reference(ref, params, mean)
        # bf16 storage: identical bits expected (same fp32 masters)
        for x, y in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
        ):
            assert x.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(x, np.float32), np.asarray(y, np.float32),
                rtol=2e-2, atol=1e-3,
            )

    def test_grad_norm_clip(self):
        mesh = data_mesh()
        params = make_params(jax.random.PRNGKey(4))
        stacked = per_rank_grads(jax.random.PRNGKey(5), params)
        stacked = jax.tree_util.tree_map(lambda g: g * 50.0, stacked)
        mean = jax.tree_util.tree_map(lambda g: g.mean(0), stacked)

        dist = distributed_fused_adam(
            1e-2, max_grad_norm=1.0, allgather_dtype="fp32",
            axis_name="data"
        )
        # unsharded reference: clip the mean grads by global norm first
        gsq = sum(
            float(jnp.sum(g.astype(jnp.float32) ** 2))
            for g in jax.tree_util.tree_leaves(mean)
        )
        gnorm = np.sqrt(gsq)
        clipped = jax.tree_util.tree_map(
            lambda g: g * min(1.0, 1.0 / gnorm), mean
        )
        ref = fused_adam(1e-2)
        got = run_sharded(dist, params, stacked, mesh)
        want = run_reference(ref, params, clipped)
        assert_trees_close(got, want, rtol=1e-5, atol=1e-6)


class TestDistributedFusedLAMB:
    @pytest.mark.parametrize("use_nvlamb", [False, True])
    def test_matches_unsharded(self, use_nvlamb):
        mesh = data_mesh()
        params = make_params(jax.random.PRNGKey(6))
        stacked = per_rank_grads(jax.random.PRNGKey(7), params)
        mean = jax.tree_util.tree_map(lambda g: g.mean(0), stacked)

        dist = distributed_fused_lamb(
            1e-2, weight_decay=0.01, use_nvlamb=use_nvlamb,
            allgather_dtype="fp32", axis_name="data"
        )
        ref = fused_lamb(1e-2, weight_decay=0.01, use_nvlamb=use_nvlamb)
        got = run_sharded(dist, params, stacked, mesh)
        want = run_reference(ref, params, mean)
        assert_trees_close(got, want, rtol=1e-5, atol=1e-6)

    def test_weight_decay_mask(self):
        mesh = data_mesh()
        params = make_params(jax.random.PRNGKey(8))
        mask = {"w": True, "b": False, "emb": True}
        stacked = per_rank_grads(jax.random.PRNGKey(9), params)
        mean = jax.tree_util.tree_map(lambda g: g.mean(0), stacked)

        dist = distributed_fused_lamb(
            1e-2, weight_decay=0.1, weight_decay_mask=mask,
            allgather_dtype="fp32", axis_name="data"
        )
        ref = fused_lamb(1e-2, weight_decay=0.1, weight_decay_mask=mask)
        got = run_sharded(dist, params, stacked, mesh)
        want = run_reference(ref, params, mean)
        assert_trees_close(got, want, rtol=1e-5, atol=1e-6)


class TestAllgatherDtype:
    """The low-precision post-step all-gather (reference
    e5m2_allgather, distributed_fused_adam.py:64,97,198-206): wire
    bytes halve (bf16) or quarter (e5m2) and the gathered params are
    the wire-rounded masters. Tolerances pin the wire dtype's rounding
    bound: the fp32-wire result is the exact master, so
    |p_wire − p_fp32| ≤ ulp(wire) · |master| — 2^-8 relative for bf16
    (8-bit mantissa step), 2^-2 for e5m2 (2-bit mantissa)."""

    _cache: dict = {}

    def _run(self, wire):
        # identical inputs across tests: cache per wire dtype (3 jit
        # compiles + sharded runs otherwise repeat)
        if wire not in self._cache:
            mesh = data_mesh()
            params = make_params(jax.random.PRNGKey(10))
            stacked = per_rank_grads(jax.random.PRNGKey(11), params)
            dist = distributed_fused_adam(
                1e-2, weight_decay=0.01, allgather_dtype=wire,
                axis_name="data",
            )
            self._cache[wire] = run_sharded(dist, params, stacked, mesh)
        return self._cache[wire]

    def test_bf16_wire_within_rounding_of_fp32(self):
        got = self._run("bf16")
        want = self._run("fp32")
        for x, y in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
        ):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2 ** -8, atol=2e-6
            )

    def test_bf16_wire_is_bf16_of_master_to_one_ulp(self):
        """Not merely close: the gathered value is bf16(master) up to
        ONE fp32 ulp (updates apply as p + fl(bf16(m) − p), one fp32
        re-round) — the same step with fp32 wire, rounded, must match
        to that bound."""
        got = self._run("bf16")
        want = self._run("fp32")
        for x, y in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
        ):
            np.testing.assert_allclose(
                np.asarray(x),
                np.asarray(
                    jnp.asarray(y).astype(jnp.bfloat16).astype(jnp.float32)
                ),
                rtol=3e-7, atol=1e-9,
            )

    def test_e5m2_wire_within_rounding_of_fp32(self):
        got = self._run("e5m2")
        want = self._run("fp32")
        for x, y in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
        ):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2 ** -2, atol=1e-4
            )

    def test_unknown_dtype_rejected(self):
        with pytest.raises(ValueError, match="allgather_dtype"):
            distributed_fused_adam(1e-2, allgather_dtype="fp8")

    def test_default_wire_is_fp32_master_parity(self):
        """The DEFAULT wire must be the bitwise-exact fp32 gather
        (round-5 advice: bf16-by-default silently rounded every param
        every step; the cheap wire is opt-in)."""
        mesh = data_mesh()
        params = make_params(jax.random.PRNGKey(14))
        stacked = per_rank_grads(jax.random.PRNGKey(15), params)
        dflt = distributed_fused_adam(1e-2, axis_name="data")
        fp32 = distributed_fused_adam(
            1e-2, allgather_dtype="fp32", axis_name="data"
        )
        for x, y in zip(
            jax.tree_util.tree_leaves(run_sharded(dflt, params, stacked, mesh)),
            jax.tree_util.tree_leaves(run_sharded(fp32, params, stacked, mesh)),
        ):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_e5m2_wire_saturates_out_of_range_masters(self):
        """Masters beyond e5m2's finite range (57344) must saturate on
        the wire, not overflow to inf and poison the params."""
        mesh = data_mesh()
        params = {"w": jnp.full((8, 8), 1e6, jnp.float32)}
        stacked = {"w": jnp.zeros((DP, 8, 8), jnp.float32)}
        dist = distributed_fused_adam(
            1e-2, allgather_dtype="e5m2", axis_name="data"
        )
        got = run_sharded(dist, params, stacked, mesh, steps=1)
        arr = np.asarray(got["w"])
        assert np.all(np.isfinite(arr))
        fin = float(jnp.finfo(jnp.float8_e5m2).max)
        np.testing.assert_allclose(arr, fin, rtol=1e-6)

    def test_lamb_bf16_wire(self):
        mesh = data_mesh()
        params = make_params(jax.random.PRNGKey(12))
        stacked = per_rank_grads(jax.random.PRNGKey(13), params)

        def run(wire):
            dist = distributed_fused_lamb(
                1e-2, weight_decay=0.01, allgather_dtype=wire,
                axis_name="data",
            )
            return run_sharded(dist, params, stacked, mesh)

        for x, y in zip(
            jax.tree_util.tree_leaves(run("bf16")),
            jax.tree_util.tree_leaves(run("fp32")),
        ):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2 ** -8, atol=2e-6
            )
