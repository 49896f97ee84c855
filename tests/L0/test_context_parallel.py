"""Ring + Ulysses context parallelism vs single-device flash attention.

Capability the reference lacks (SURVEY.md §5 long-context: limited);
the correctness bar is exact agreement (within bf16/fp32 tolerance)
with unsharded flash attention on the gathered sequence — forward and
gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from _helpers import jit_shmap as _jit_shmap
from jax.sharding import Mesh, PartitionSpec as P

from rocm_apex_tpu.ops.flash_attention import flash_attention
from rocm_apex_tpu.transformer.context_parallel import (
    ring_flash_attention,
    ulysses_attention,
)

CP = 4


def cp_mesh(devs):
    return Mesh(np.array(devs[:CP]), ("context",))


def make_qkv(key, bh, s, d):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (bh, s, d)),
        jax.random.normal(kk, (bh, s, d)),
        jax.random.normal(kv, (bh, s, d)),
    )


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_unsharded(self, eight_devices, causal):
        mesh = cp_mesh(eight_devices)
        bh, s, d = 2, 512, 64
        q, k, v = make_qkv(jax.random.PRNGKey(0), bh, s, d)

        ring = _jit_shmap(
            lambda q, k, v: ring_flash_attention(
                q, k, v, "context", causal
            ),
            mesh=mesh,
            in_specs=(P(None, "context"),) * 3,
            out_specs=P(None, "context"),
            check_vma=False,
        )
        got = ring(q, k, v)
        want = flash_attention(q, k, v, None, causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_grads_match(self, eight_devices):
        mesh = cp_mesh(eight_devices)
        bh, s, d = 1, 512, 64
        q, k, v = make_qkv(jax.random.PRNGKey(1), bh, s, d)

        def ring_loss(q, k, v):
            f = _jit_shmap(
                lambda q, k, v: ring_flash_attention(q, k, v, "context", True),
                mesh=mesh,
                in_specs=(P(None, "context"),) * 3,
                out_specs=P(None, "context"),
                check_vma=False,
            )
            return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)

        def flash_loss(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, None, True).astype(jnp.float32) ** 2
            )

        g_ring = jax.grad(ring_loss, (0, 1, 2))(q, k, v)
        g_ref = jax.grad(flash_loss, (0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3
            )


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_unsharded(self, eight_devices, causal):
        mesh = cp_mesh(eight_devices)
        b, s, h, d = 2, 512, 4, 64
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(kq, (b, s, h, d))
        k = jax.random.normal(kk, (b, s, h, d))
        v = jax.random.normal(kv, (b, s, h, d))

        uly = _jit_shmap(
            lambda q, k, v: ulysses_attention(q, k, v, "context", causal),
            mesh=mesh,
            in_specs=(P(None, "context"),) * 3,
            out_specs=P(None, "context"),
            check_vma=False,
        )
        got = uly(q, k, v)

        # reference: plain flash per head on the full sequence
        def ref(q, k, v):
            qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
            kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
            vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
            o = flash_attention(qf, kf, vf, None, causal)
            return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)

        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref(q, k, v)), rtol=2e-4, atol=2e-4
        )

    def test_head_divisibility_error(self, eight_devices):
        mesh = cp_mesh(eight_devices)
        q = jnp.ones((1, 32, 3, 8))  # 3 heads, 4 ranks
        with pytest.raises(ValueError, match="divisible"):
            shard_map(
                lambda q: ulysses_attention(q, q, q, "context"),
                mesh=mesh,
                in_specs=(P(None, "context"),),
                out_specs=P(None, "context"),
                check_vma=False,
            )(q)


class TestGPTContextParallel:
    def test_gpt_on_context_mesh_matches_unsharded(self, eight_devices):
        """Full GPT forward with the sequence sharded over a context
        axis (ring attention + offset positions) equals the unsharded
        model on the gathered sequence."""
        from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel

        CPN = 4
        mesh = Mesh(np.array(eight_devices[:CPN]), ("context",))
        base = dict(
            vocab_size=128,
            hidden_size=64,
            num_layers=2,
            num_attention_heads=4,
            max_position_embeddings=512,
            hidden_dropout=0.0,
            attention_dropout=0.0,
            tensor_parallel_size=1,
            params_dtype=jnp.float32,
            dtype=jnp.float32,
        )
        cfg_cp = GPTConfig(**base, context_parallel_axis="context")
        cfg_ref = GPTConfig(**base)
        model_cp, model_ref = GPTModel(cfg_cp), GPTModel(cfg_ref)

        s = 512
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, s), 0, 128)
        params = model_ref.init(jax.random.PRNGKey(1), tokens)

        want = model_ref.apply(params, tokens)

        f = _jit_shmap(
            lambda p, t: model_cp.apply(p, t),
            mesh=mesh,
            in_specs=(P(), P(None, "context")),
            out_specs=P(None, "context"),
            check_vma=False,
        )
        got = f(params, tokens)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-3, atol=2e-3,
        )
