"""Latency-hiding collective matmuls vs the plain lax collectives.

`ops/collective_matmul.py` decomposes the TP-boundary collectives into
ppermute rings overlapping partial matmuls (arXiv 2305.06942). The
contract tested here:

  - numeric parity of the ring forward AND backward (dx, dW — grads
    taken INSIDE shard_map, the training idiom) with the plain
    `lax.all_gather`/`psum_scatter` composition, at tp 2 and 4, with
    and without sub-shard chunking;
  - a chunk that does not tile the shard falls back to the plain
    collective, still correct;
  - bf16 inputs accumulate in fp32 (the ring's hop sums must not
    round through bf16);
  - the jaxpr proof for the acceptance bar: the sequence-parallel GPT
    stack with collective_matmul=True contains NO full-sequence
    (b, s, hidden) gathered activation between the regions — while the
    blocking-collective variant (the probe's sanity check) does. The
    probe is the shared static auditor (rocm_apex_tpu.monitor.audit),
    which replaced this file's original string-greps over
    str(make_jaxpr(...)); test_monitor.py additionally pins the ring's
    exact ppermute counts on the same config.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from _helpers import jit_shmap

from rocm_apex_tpu.models.gpt import (
    GPTConfig,
    ParallelTransformer,
    gpt_pipeline_functions,
)
from rocm_apex_tpu import monitor
from rocm_apex_tpu.monitor import audit
from rocm_apex_tpu.ops.collective_matmul import (
    all_gather_matmul,
    matmul_reduce_scatter,
)
from rocm_apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
)

ROWS, K, N = 24, 16, 12  # per-rank rows / contraction / output columns


def _mesh(tp):
    devs = jax.devices()
    if len(devs) < tp:
        pytest.skip(f"needs {tp} simulated devices")
    return Mesh(np.array(devs[:tp]), ("tensor",))


def _data(tp, dtype=jnp.float32, k=K):
    x = jax.random.normal(jax.random.PRNGKey(0), (tp * ROWS, k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (tp, k, N), dtype)
    return x, w


class TestAllGatherMatmul:
    @pytest.mark.parametrize("tp", [2, 4])
    @pytest.mark.parametrize("chunk", [None, 8])
    def test_fwd_dx_dw_match_lax(self, tp, chunk):
        """Ring == all_gather-then-dot, for the output and both grads,
        with per-rank distinct weights (each rank is a distinct
        column-parallel shard)."""
        mesh = _mesh(tp)
        x, w = _data(tp)
        # per-rank distinct cotangent weights make a missing psum or a
        # double-counted hop visible in dx/dW
        dl = jnp.asarray(
            np.random.RandomState(2).randn(tp * ROWS, N), jnp.float32
        )

        def both(xs, ws):
            wr = ws[0]

            def ring_loss(xs, wr):
                y = all_gather_matmul(xs, wr, "tensor", chunk)
                return jnp.sum(y * dl)

            def lax_loss(xs, wr):
                xg = jax.lax.all_gather(xs, "tensor", axis=0, tiled=True)
                y = jnp.matmul(
                    xg, wr, preferred_element_type=jnp.float32
                )
                return jnp.sum(y * dl)

            (l1, (dx1, dw1)) = jax.value_and_grad(ring_loss, (0, 1))(
                xs, wr
            )
            (l2, (dx2, dw2)) = jax.value_and_grad(lax_loss, (0, 1))(
                xs, wr
            )
            # the lax reference's dx arrives via all_gather's transpose
            # (psum_scatter) — the same convention the ring must match
            return l1, dx1, dw1, l2, dx2, dw2

        f = jit_shmap(
            both, mesh=mesh,
            in_specs=(P("tensor"), P("tensor")),
            out_specs=(P(), P("tensor"), P("tensor")) * 2,
            check_vma=False,
        )
        l1, dx1, dw1, l2, dx2, dw2 = f(x, w)
        np.testing.assert_allclose(
            float(l1), float(l2), rtol=1e-6, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(dx1), np.asarray(dx2), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(dw1), np.asarray(dw2), rtol=1e-5, atol=1e-5
        )

    def test_non_divisible_chunk_falls_back_correct(self):
        """chunk=7 does not tile the 24-row shard: the op must take the
        plain-collective path and still be exact."""
        tp = 2
        mesh = _mesh(tp)
        x, w = _data(tp)

        def f(xs, ws):
            return all_gather_matmul(xs, ws[0], "tensor", 7)

        y = jit_shmap(
            f, mesh=mesh, in_specs=(P("tensor"), P("tensor")),
            out_specs=P("tensor"), check_vma=False,
        )(x, w).reshape(tp, tp * ROWS, N)
        ref = jnp.stack([x @ w[r] for r in range(tp)])
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_bf16_inputs_fp32_accum(self):
        """bf16 operands: output dtype bf16, but the ring's partial
        sums stay fp32 — the result must match the fp32 reference on
        the same bf16-rounded inputs to bf16 resolution, and the ring
        must agree with the plain bf16 path bitwise-tight."""
        tp = 2
        mesh = _mesh(tp)
        x, w = _data(tp, jnp.bfloat16)

        def f(xs, ws):
            ring = all_gather_matmul(xs, ws[0], "tensor", 8)
            xg = jax.lax.all_gather(xs, "tensor", axis=0, tiled=True)
            plain = jnp.matmul(
                xg, ws[0], preferred_element_type=jnp.float32
            )
            return ring, plain

        ring, plain = jit_shmap(
            f, mesh=mesh, in_specs=(P("tensor"), P("tensor")),
            out_specs=(P("tensor"), P("tensor")), check_vma=False,
        )(x, w)
        assert ring.dtype == jnp.bfloat16
        ref = jnp.matmul(
            x.astype(jnp.float32),
            w[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        # one bf16 rounding step away from the fp32-accumulated plain
        # product (a bf16-accumulating ring would be ~100x worse at
        # K=16 and diverge further with K)
        np.testing.assert_allclose(
            np.asarray(ring, np.float32).reshape(tp, tp * ROWS, N)[0],
            np.asarray(plain, np.float32).reshape(tp, tp * ROWS, N)[0],
            rtol=1e-2, atol=1e-2,
        )
        np.testing.assert_allclose(
            np.asarray(ring, np.float32).reshape(tp, tp * ROWS, N)[0],
            np.asarray(ref),
            rtol=3e-2, atol=3e-2,
        )


class TestMatmulReduceScatter:
    @pytest.mark.parametrize("tp", [2, 4])
    @pytest.mark.parametrize("chunk", [None, 4])
    def test_fwd_dx_dw_match_lax(self, tp, chunk):
        mesh = _mesh(tp)
        k_full = tp * K
        x = jax.random.normal(
            jax.random.PRNGKey(3), (tp * ROWS, k_full), jnp.float32
        )
        w = jax.random.normal(
            jax.random.PRNGKey(4), (k_full, N), jnp.float32
        )
        dl = jnp.asarray(
            np.random.RandomState(5).randn(tp * ROWS, N), jnp.float32
        )

        def both(xc, wc, dl_full):
            def ring_loss(xc, wc):
                y = matmul_reduce_scatter(xc, wc, "tensor", chunk)
                return jnp.sum(y * dl_full)

            def lax_loss(xc, wc):
                y = jnp.matmul(
                    xc, wc, preferred_element_type=jnp.float32
                )
                y = jax.lax.psum_scatter(
                    y, "tensor", scatter_dimension=0, tiled=True
                )
                return jnp.sum(y * dl_full)

            l1, (dx1, dw1) = jax.value_and_grad(ring_loss, (0, 1))(xc, wc)
            l2, (dx2, dw2) = jax.value_and_grad(lax_loss, (0, 1))(xc, wc)
            l1 = jax.lax.psum(l1, "tensor")
            l2 = jax.lax.psum(l2, "tensor")
            return l1, dx1, dw1, l2, dx2, dw2

        f = jit_shmap(
            both, mesh=mesh,
            in_specs=(P(None, "tensor"), P("tensor"), P("tensor")),
            out_specs=(P(), P(None, "tensor"), P("tensor")) * 2,
            check_vma=False,
        )
        l1, dx1, dw1, l2, dx2, dw2 = f(x, w, dl)
        np.testing.assert_allclose(
            float(l1), float(l2), rtol=1e-6, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(dx1), np.asarray(dx2), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(dw1), np.asarray(dw2), rtol=1e-5, atol=1e-5
        )

    def test_forward_matches_serial_product(self):
        """The scattered blocks reassemble to the full serial x @ w —
        and a chunk that does not tile the block stays exact through
        the fallback."""
        tp = 4
        mesh = _mesh(tp)
        k_full = tp * K
        x = jax.random.normal(
            jax.random.PRNGKey(6), (tp * ROWS, k_full), jnp.float32
        )
        w = jax.random.normal(
            jax.random.PRNGKey(7), (k_full, N), jnp.float32
        )
        for chunk in (None, 8, 5):
            f = jit_shmap(
                lambda xc, wc, c=chunk: matmul_reduce_scatter(
                    xc, wc, "tensor", c
                ),
                mesh=mesh,
                in_specs=(P(None, "tensor"), P("tensor")),
                out_specs=P("tensor"),
                check_vma=False,
            )
            y = f(x, w)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(x @ w), rtol=1e-5, atol=1e-5
            )

    def test_bf16_inputs_fp32_accum(self):
        """The hop-accumulator must be fp32: psum_scatter of a bf16
        product and the ring must agree to bf16 resolution against the
        fp32 serial product."""
        tp = 4
        mesh = _mesh(tp)
        k_full = tp * K
        x = jax.random.normal(
            jax.random.PRNGKey(8), (tp * ROWS, k_full), jnp.bfloat16
        )
        w = jax.random.normal(
            jax.random.PRNGKey(9), (k_full, N), jnp.bfloat16
        )
        y = jit_shmap(
            lambda xc, wc: matmul_reduce_scatter(xc, wc, "tensor", 8),
            mesh=mesh,
            in_specs=(P(None, "tensor"), P("tensor")),
            out_specs=P("tensor"),
            check_vma=False,
        )(x, w)
        assert y.dtype == jnp.bfloat16
        ref = x.astype(jnp.float32) @ w.astype(jnp.float32)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(ref),
            rtol=3e-2, atol=0.5,
        )

    def test_rows_not_divisible_by_axis_raises(self):
        tp = 2
        mesh = _mesh(tp)
        x = jnp.ones((tp * ROWS + 1, K))
        w = jnp.ones((K, N))
        with pytest.raises(ValueError, match="not divisible"):
            jit_shmap(
                lambda xc, wc: matmul_reduce_scatter(xc, wc, "tensor"),
                mesh=mesh, in_specs=(P(), P()), out_specs=P("tensor"),
                check_vma=False,
            )(x, w)


class TestUnboundAxisDegradation:
    def test_plain_matmul_outside_shard_map(self):
        """tp=1 / GSPMD usage: both ops are the plain dot, and their
        grads are the plain dot grads."""
        x = jax.random.normal(jax.random.PRNGKey(0), (ROWS, K))
        w = jax.random.normal(jax.random.PRNGKey(1), (K, N))
        np.testing.assert_allclose(
            np.asarray(all_gather_matmul(x, w, "tensor")),
            np.asarray(x @ w), rtol=1e-6,
        )
        g = jax.grad(
            lambda w: jnp.sum(matmul_reduce_scatter(x, w, "tensor") ** 2)
        )(w)
        g_ref = jax.grad(lambda w: jnp.sum((x @ w) ** 2))(w)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(g_ref), rtol=1e-5, atol=1e-5
        )


def _sp_cfg(collective_matmul, **kw):
    return GPTConfig(
        vocab_size=128,
        hidden_size=64,
        num_layers=1,
        num_attention_heads=4,
        max_position_embeddings=32,
        # ffn/tp = 128 != hidden: no shape collision with the probe
        ffn_hidden_size=256,
        hidden_dropout=0.0,
        attention_dropout=0.0,
        tensor_parallel_size=2,
        dtype=jnp.float32,
        sequence_parallel=True,
        collective_matmul=collective_matmul,
        **kw,
    )


class TestPipelineExitStage:
    """The pipeline loss_fn is the sequence-parallel region exit when
    pp>1: it must gather the shard before the head, and reject hidden/
    label row mismatches with a diagnosable error. (Full pp2xtp2
    pipeline-vs-serial parity with sequence_parallel+collective_matmul
    runs in the multichip dryrun, __graft_entry__ part B pattern.)"""

    def test_loss_fn_gathers_the_sequence_shard(self):
        mesh = _mesh(2)
        kw = dict(
            vocab_size=64, hidden_size=32, num_layers=1,
            num_attention_heads=2, max_position_embeddings=16,
            hidden_dropout=0.0, attention_dropout=0.0,
            tensor_parallel_size=2, dtype=jnp.float32,
        )
        cfg_sp = GPTConfig(sequence_parallel=True, **kw)
        cfg_plain = GPTConfig(**kw)
        _, _, _, _, loss_sp = gpt_pipeline_functions(cfg_sp)
        embedding, _, _, _, loss_plain = gpt_pipeline_functions(cfg_plain)
        b, s = 2, 16
        hidden = jax.random.normal(
            jax.random.PRNGKey(0), (b, s, 32), jnp.float32
        )
        labels = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, 64)

        def both(hidden, labels):
            e = embedding.init(jax.random.PRNGKey(2), labels)
            rank = jax.lax.axis_index("tensor")
            shard = jax.lax.dynamic_slice_in_dim(
                hidden, rank * (s // 2), s // 2, axis=1
            )
            return loss_sp(e, shard, labels), loss_plain(e, hidden, labels)

        l_sp, l_plain = jit_shmap(
            both, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )(hidden, labels)
        np.testing.assert_allclose(
            float(l_sp), float(l_plain), rtol=1e-6
        )

    def test_loss_fn_rejects_mismatched_rows(self):
        cfg = GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=1,
            num_attention_heads=2, max_position_embeddings=16,
            tensor_parallel_size=1, dtype=jnp.float32,
        )
        embedding, _, _, _, loss_fn = gpt_pipeline_functions(cfg)
        labels = jnp.zeros((2, 16), jnp.int32)
        e = embedding.init(jax.random.PRNGKey(0), labels)
        bad_hidden = jnp.zeros((2, 8, 32), jnp.float32)  # a stray shard
        with pytest.raises(ValueError, match="pipeline exit stage"):
            loss_fn(e, bad_hidden, labels)


class TestNoGatheredActivationInJaxpr:
    B, S, H = 2, 32, 64

    def _stack_report(self, collective_matmul, chunk=None):
        """`monitor.audit` report of init + fwd + bwd of the sequence-
        parallel stack on a local sequence shard — the activations
        BETWEEN the regions, embedding and head excluded (those are the
        region boundaries, where one full-sequence tensor is
        definitional). Abstract tracing only: nothing compiles."""
        mesh = _mesh(2)
        cfg = _sp_cfg(collective_matmul, collective_matmul_chunk=chunk)
        stack = ParallelTransformer(cfg)
        x_loc = jnp.ones((self.B, self.S // 2, self.H), jnp.float32)

        def step(x):
            params = stack.init(jax.random.PRNGKey(0), x)

            def loss(p, x):
                y = stack.apply(p, x, deterministic=True)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            return jax.grad(loss, (0, 1))(params, x)

        f = shard_map(
            step, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
            check_vma=False,
        )
        return monitor.LintSubject.from_fn(
            f"cm_stack_cm{int(collective_matmul)}_chunk{chunk}", f, x_loc
        )

    def test_collective_matmul_stack_has_no_full_activation(self):
        """The acceptance bar made executable: with the ring boundary
        matmuls, no (b, s, hidden) full-sequence activation exists
        anywhere in the traced train step of the stack — only
        (b, s/tp, hidden) shards and full-sequence tensors of OTHER
        widths (the qkv/ffn shards attention consumes) — and the edge
        collectives really are rings (ppermute, no all_gather/
        reduce_scatter). The blocking-collective variant, audited
        identically, does contain the gather (so the probe itself is
        sound)."""
        full = (self.B, self.S, self.H)
        blocking = self._stack_report(collective_matmul=False).report
        # probe sanity: the gather exists and uses plain collectives
        assert blocking.has_intermediate(full)
        assert blocking.count("all_gather") > 0
        assert blocking.count("ppermute") == 0
        subject = self._stack_report(collective_matmul=True)
        monitor.run_lint(subject, self._ring_rules()).raise_if_failed()
        ring = subject.report
        assert ring.has_intermediate((self.B, self.S // 2, self.H))
        assert ring.count("ppermute") > 0

    def _ring_rules(self):
        """The ring contract as declarative lint rules — the form
        `tools/graphlint.py` pins in CI (spcm_tp2 config)."""
        return [
            monitor.NoMaterialization(
                forbidden_shapes=((self.B, self.S, self.H),)
            ),
            monitor.CollectiveContract(
                forbid=("all_gather", "reduce_scatter")
            ),
        ]

    def test_chunked_ring_also_clean(self):
        monitor.run_lint(
            self._stack_report(collective_matmul=True, chunk=8),
            self._ring_rules(),
        ).raise_if_failed()

    def test_no_async_flag_disables_the_ring(self):
        """`no_async_tensor_model_parallel_allreduce=True` is the
        reference's opt-out of comm/compute overlap: with it, the
        column entry goes back to the blocking gather — the full
        gathered input reappears, and no ring permutes remain."""
        mesh = _mesh(2)
        layer = ColumnParallelLinear(
            input_size=self.H,
            output_size=96,
            gather_output=False,
            sequence_parallel=True,
            collective_matmul=True,
            no_async_tensor_model_parallel_allreduce=True,
            world_size=2,
        )
        x_loc = jnp.ones((self.B, self.S // 2, self.H), jnp.float32)

        def step(x):
            params = layer.init(jax.random.PRNGKey(0), x)
            y, _ = layer.apply(params, x)
            return y

        f = shard_map(
            step, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        )
        report = audit(f, x_loc)
        assert report.has_intermediate((self.B, self.S, self.H))
        assert report.count("ppermute") == 0
