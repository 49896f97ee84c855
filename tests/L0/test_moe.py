"""Expert-parallel SwitchMLP vs dense per-token expert computation.

Capability beyond the reference (no MoE there). Bar: with capacity high
enough to drop nothing, the expert-parallel layer on an ``expert`` mesh
must equal the dense computation (each token through its argmax expert,
scaled by the gate probability) — and equal the single-device layer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from _helpers import jit_shmap as _jit_shmap
from jax.sharding import Mesh, PartitionSpec as P

from rocm_apex_tpu.transformer.moe import SwitchMLP, switch_route

EP = 4


def dense_reference(params, x, num_experts):
    """Each token through its argmax expert, times the gate prob."""
    T, h = x.shape
    logits = x @ params["params"]["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    w1 = params["params"]["wi"]  # (E, h, f)
    w2 = params["params"]["wo"]
    out = []
    for t in range(T):
        e = int(expert[t])
        hmid = jax.nn.gelu(x[t] @ w1[e])
        out.append((hmid @ w2[e]) * gate[t])
    return jnp.stack(out)


class TestSwitchRoute:
    def test_capacity_drops(self):
        # all tokens to expert 0, capacity 2 -> only 2 kept
        logits = jnp.tile(jnp.asarray([[10.0, -10.0]]), (5, 1))
        dispatch, combine, _, _ = switch_route(logits, 2)
        assert int(dispatch[:, 0].sum()) == 2
        assert float(combine[2:, 0].sum()) == 0.0

    def test_positions_unique(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
        dispatch, _, _, _ = switch_route(logits, 8)
        # no two tokens share an (expert, slot)
        assert int(dispatch.sum(axis=0).max()) <= 1


class TestSwitchMLP:
    def test_single_device_matches_dense(self):
        T, h, f, E = 24, 16, 32, 4
        m = SwitchMLP(h, f, E, capacity_factor=4.0)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, h))
        params = m.init(jax.random.PRNGKey(2), x)
        y, aux = m.apply(params, x)
        want = dense_reference(params, x, E)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-5
        )
        assert float(aux) > 0.0

    def test_expert_parallel_matches_single_device(self, eight_devices):
        T, h, f, E = 32, 16, 32, 8
        mesh = Mesh(np.array(eight_devices[:EP]), ("expert",))
        m = SwitchMLP(h, f, E, capacity_factor=4.0)
        x = jax.random.normal(jax.random.PRNGKey(3), (T, h))
        params = m.init(jax.random.PRNGKey(4), x)  # all experts local
        y_single, _ = m.apply(params, x)

        # params replicated except wi/wo: each rank hosts E/EP experts,
        # so the expert leaves get a leading (EP,) axis to shard
        def shard_experts(p):
            e_local = E // EP

            def maybe_slice(path, leaf):
                name = "/".join(
                    str(k.key) for k in path if hasattr(k, "key")
                )
                if name.endswith("wi") or name.endswith("wo"):
                    return leaf.reshape(
                        (EP, e_local) + leaf.shape[1:]
                    )
                return leaf

            return jax.tree_util.tree_map_with_path(maybe_slice, p)

        sharded = shard_experts(params)

        # in_specs shard the leading (EP,) axis; inside shard_map the
        # local leaf is (1, e_local, ...) -> squeeze to (e_local, ...)
        def local2(params, x):
            params = jax.tree_util.tree_map_with_path(
                lambda path, leaf: (
                    leaf[0]
                    if "/".join(
                        str(k.key) for k in path if hasattr(k, "key")
                    ).split("/")[-1] in ("wi", "wo")
                    else leaf
                ),
                params,
            )
            return m.apply(params, x)

        f_ep = _jit_shmap(
            local2, mesh=mesh,
            in_specs=(
                {"params": {
                    "router": {"kernel": P()},
                    "wi": P("expert"),
                    "wo": P("expert"),
                }},
                P(),
            ),
            out_specs=(P(), P()),
            check_vma=False,
        )
        y_ep, aux_ep = f_ep(sharded, x)
        np.testing.assert_allclose(
            np.asarray(y_ep), np.asarray(y_single), rtol=1e-4, atol=1e-5
        )

    def test_grads_flow(self):
        T, h, f, E = 16, 8, 16, 4
        m = SwitchMLP(h, f, E, capacity_factor=4.0)
        x = jax.random.normal(jax.random.PRNGKey(5), (T, h))
        params = m.init(jax.random.PRNGKey(6), x)

        def loss(p):
            y, aux = m.apply(p, x)
            return jnp.sum(y**2) + 0.01 * aux

        g = jax.grad(loss)(params)
        for leaf in jax.tree_util.tree_leaves(g):
            assert np.isfinite(np.asarray(leaf)).all()
        # router gets gradient through the gate probability
        assert float(jnp.abs(g["params"]["router"]["kernel"]).sum()) > 0
