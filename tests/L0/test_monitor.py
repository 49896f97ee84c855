"""The observability layer (rocm_apex_tpu.monitor): in-graph Metrics,
host-side MetricsLogger pipeline, shared FLOPs accounting, and the
static comms/FLOPs auditor.

Wall-time note (ROADMAP): every model-bearing test here reuses the
EXACT shapes of an existing suite config — the SP/CM stack of
test_collective_matmul, the vocab-parallel head of test_linear_xentropy,
the fp32 engine of test_inference — so the compiled programs either hit
the persistent compile cache or never compile at all (`audit` is
make_jaxpr-only: abstract tracing, zero compiles).
"""

import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from _helpers import jit_shmap

from rocm_apex_tpu.amp import LossScaler
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel, ParallelTransformer
from rocm_apex_tpu import monitor
from rocm_apex_tpu.monitor import (
    JsonlWriter,
    Metrics,
    MetricsLogger,
    TensorBoardWriter,
    activation_stats,
    assert_no_intermediate,
    audit,
    mfu,
    model_flops,
    peak_flops_per_chip,
    chip_peaks,
    UnknownDeviceError,
    tree_norm,
)
from rocm_apex_tpu.optimizers.mixed import MixedPrecisionAdam


def _mesh(n):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} simulated devices")
    return Mesh(np.array(devs[:n]), ("tensor",))


# ---------------------------------------------------------------------------
# Metrics pytree
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_record_merge_asdict(self):
        m = Metrics.empty().record("a", 1.0).record("b", jnp.float32(2.0))
        m2 = m.merge(Metrics.empty().record("b", 3.0).record("c", 4.0))
        got = {k: float(v) for k, v in m2.as_dict().items()}
        assert got == {"a": 1.0, "b": 3.0, "c": 4.0}
        assert "a" in m2 and len(m2) == 3
        assert float(m2["c"]) == 4.0

    def test_scalars_only(self):
        with pytest.raises(ValueError, match="scalar"):
            Metrics.empty().record("v", jnp.ones((3,)))

    def test_pytree_round_trip(self):
        m = Metrics.empty().record("x", 1.0).record("y", 2.0)
        leaves, treedef = jax.tree_util.tree_flatten(m)
        assert [float(v) for v in leaves] == [1.0, 2.0]  # sorted names
        m2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert m2.names() == ["x", "y"]

    def test_tree_norm_and_ratio_groups(self):
        tree = {"params": {"g1": jnp.full((4,), 3.0), "g2": jnp.ones((2, 2))}}
        expect = float(np.sqrt(4 * 9.0 + 4 * 1.0))
        assert float(tree_norm(tree)) == pytest.approx(expect)
        m = Metrics.empty().record_ratio_norms(
            tree, jax.tree_util.tree_map(lambda x: 2.0 * x, tree)
        )
        assert float(m["ratio/g1"]) == pytest.approx(0.5)
        assert float(m["ratio/g2"]) == pytest.approx(0.5)

    def test_shard_map_partial_metrics_psum(self):
        """The PR-3 grad convention applied to metrics: shard-partial
        sums and sums-of-squares psum over the axis, so every rank
        reports the GLOBAL scalar."""
        mesh = _mesh(4)
        x = jnp.arange(8.0, dtype=jnp.float32) + 1.0

        def f(xs):
            return (
                Metrics.empty()
                .record("total", jnp.sum(xs), axis_name="tensor")
                .record_norm("norm", {"w": xs}, axis_name="tensor")
                .record("replicated", 7.0)
            )

        m = jit_shmap(
            f, mesh=mesh, in_specs=(P("tensor"),), out_specs=P(),
            check_vma=False,
        )(x)
        assert float(m["total"]) == pytest.approx(float(jnp.sum(x)))
        assert float(m["norm"]) == pytest.approx(
            float(jnp.sqrt(jnp.sum(x * x)))
        )
        assert float(m["replicated"]) == 7.0


# ---------------------------------------------------------------------------
# the jitted GPT train step: one trace, metrics through the jsonl sink
# ---------------------------------------------------------------------------


class TestTrainStepRoundTrip:
    def test_traces_once_and_jsonl_has_the_scalars(self):
        """The acceptance bar: a GPT train step threading a Metrics
        pytree traces EXACTLY once over 3 steps, and the MetricsLogger
        jsonl output carries grad-norm / loss-scale / MFU scalars."""
        b, s = 2, 16
        cfg = GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=2,
            num_attention_heads=2, max_position_embeddings=16,
            hidden_dropout=0.0, attention_dropout=0.0,
            tensor_parallel_size=1, params_dtype=jnp.float32,
            dtype=jnp.float32, attention_impl="jnp",
            use_pallas_softmax=False, lm_head_chunk_size=8,
            activation_stats=True,
        )
        model = GPTModel(cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (b, s), 0, 64)
        labels = jnp.roll(tokens, -1, axis=1)
        params = model.init(jax.random.PRNGKey(1), tokens)
        opt = MixedPrecisionAdam(1e-3)
        scaler = LossScaler(loss_scale="dynamic")
        state = opt.init(params)
        sstate = scaler.init()
        traces = []

        @jax.jit
        def step(state, sstate):
            traces.append(1)  # trace-time side effect: counts COMPILES

            def loss_fn(p):
                mean, inters = model.apply(
                    p, tokens, labels=labels, loss_reduction="mean",
                    mutable=["intermediates"],
                )
                return mean * scaler.loss_scale(sstate), inters

            (scaled, inters), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.model)
            inv = 1.0 / scaler.loss_scale(sstate)
            state2, found_inf = opt.step_and_probe(
                state, grads, grad_scale=inv
            )
            sstate2, _ = scaler.update(sstate, found_inf)
            metrics = (
                Metrics.empty()
                .record("loss", scaled * inv)
                .record_norm("grad_norm", grads)
                .record("loss_scale", sstate2.loss_scale)
                .record("overflows", sstate2.overflows)
                .merge(Metrics(activation_stats(inters)))
            )
            return state2, sstate2, metrics

        raw_count = sum(
            int(x.size) for x in jax.tree_util.tree_leaves(params)
        )
        buf = io.StringIO()
        logger = MetricsLogger(
            writers=[JsonlWriter(stream=buf)],
            window=3,
            tokens_per_step=b * s,
            flops_per_step=model_flops(cfg, b, s, raw_param_count=raw_count),
            peak_flops=1e12,
            memory_stats=False,
        )
        for it in range(3):
            logger.start_step()
            state, sstate, metrics = step(state, sstate)
            logger.end_step(sync_on=metrics["loss"])
            record = logger.log_step(it, metrics)
        assert sum(traces) == 1, "metrics must add ZERO trace count"

        lines = buf.getvalue().strip().splitlines()
        assert len(lines) == 1  # window=3: one flush for three steps
        row = json.loads(lines[0])
        assert record is not None and row["step"] == 2
        for key in ("loss", "grad_norm", "loss_scale", "overflows",
                    "mfu", "tokens_per_sec", "step_time_ms"):
            assert key in row, key
        assert row["loss_scale"] == 65536.0
        assert row["overflows"] == 0.0
        assert row["grad_norm"] > 0.0 and np.isfinite(row["grad_norm"])
        assert row["mfu"] > 0.0
        # the activation taps rode along: one RMS per tap, all finite
        act_keys = [k for k in row if k.startswith("act_rms/")]
        assert any("layer_0" in k and "attn_out" in k for k in act_keys)
        assert any("layer_1" in k and "mlp_out" in k for k in act_keys)
        assert any(k.endswith("hidden_out") for k in act_keys)
        assert all(np.isfinite(row[k]) and row[k] > 0 for k in act_keys)


# ---------------------------------------------------------------------------
# MetricsLogger / writers (host-side, no jax programs)
# ---------------------------------------------------------------------------


class TestMetricsLogger:
    def test_window_mean_and_last_value_counters(self):
        buf = io.StringIO()
        lg = MetricsLogger(
            writers=[JsonlWriter(stream=buf)], window=2,
            last_value=("overflows",), memory_stats=False,
        )
        assert lg.log_step(0, {"loss": 1.0, "overflows": 1}) is None
        rec = lg.log_step(1, {"loss": 3.0, "overflows": 2})
        assert rec["loss"] == pytest.approx(2.0)  # window mean
        assert rec["overflows"] == 2.0  # counter: last value, not mean
        assert json.loads(buf.getvalue())["step"] == 1

    def test_flush_resets_the_window(self):
        lg = MetricsLogger(
            writers=[JsonlWriter(stream=io.StringIO())], window=10,
            memory_stats=False,
        )
        lg.log_step(0, {"x": 1.0})
        assert lg.flush(0)["x"] == 1.0
        assert lg.flush(1) is None  # empty window

    def test_tensorboard_writer_adapts_add_scalar(self):
        rows = []

        class Sink:
            def add_scalar(self, tag, value, step):
                rows.append((tag, value, step))

        lg = MetricsLogger(
            writers=[TensorBoardWriter(Sink())], window=1,
            memory_stats=False,
        )
        lg.log_step(5, {"loss": 2.5})
        assert ("loss", 2.5, 5) in rows

    def test_close_flushes_trailing_partial_window(self, tmp_path):
        """A run whose length is not a multiple of `window` used to
        lose its last < window steps; `close()` (and the context-
        manager form) flushes them and closes owned writers."""
        path = tmp_path / "metrics.jsonl"
        w = JsonlWriter(path=str(path))
        with MetricsLogger(
            writers=[w], window=5, memory_stats=False
        ) as lg:
            for it in range(7):
                lg.log_step(it, {"x": float(it)})
        rows = [json.loads(l) for l in path.read_text().splitlines()]
        assert [r["step"] for r in rows] == [4, 6]
        assert rows[0]["x"] == pytest.approx(2.0)  # mean of 0..4
        assert rows[1]["x"] == pytest.approx(5.5)  # trailing 5, 6
        assert w._stream.closed  # JsonlWriter.close called through
        # idempotent: a second close flushes nothing and re-closes
        assert lg.close() is None

    def test_close_on_empty_window_writes_nothing(self):
        buf = io.StringIO()
        lg = MetricsLogger(
            writers=[JsonlWriter(stream=buf)], window=3,
            memory_stats=False,
        )
        lg.log_step(0, {"x": 1.0})
        lg.log_step(1, {"x": 2.0})
        lg.log_step(2, {"x": 3.0})  # window flushed exactly here
        assert lg.close() is None  # nothing trailing
        assert len(buf.getvalue().strip().splitlines()) == 1

    def test_device_memory_stats_zeroed_with_platform_on_cpu(self):
        """Backends without allocator stats (the CPU tier-1 box) get a
        STABLE schema — zeroed fields plus the platform name — instead
        of missing keys; writers that only take numbers skip the
        string cleanly."""
        from rocm_apex_tpu.monitor import device_memory_stats

        s = device_memory_stats()
        assert s["platform"] == "cpu"  # conftest pins the platform
        assert s["mem_bytes_in_use"] == 0.0
        assert s["mem_peak_bytes_in_use"] == 0.0
        rows = []

        class Sink:
            def add_scalar(self, tag, value, step):
                rows.append(tag)

        TensorBoardWriter(Sink()).write(0, s)
        assert "mem_bytes_in_use" in rows and "platform" not in rows
        # the default logger pipeline carries it end to end as jsonl
        buf = io.StringIO()
        lg = MetricsLogger(writers=[JsonlWriter(stream=buf)], window=1)
        lg.log_step(0, {"loss": 1.0})
        row = json.loads(buf.getvalue())
        assert row["platform"] == "cpu" and row["mem_bytes_in_use"] == 0.0

    def test_jsonl_add_scalar_is_timers_write_compatible(self):
        """`Timers.write(names, writer, it)` lands timer rows in the
        same jsonl stream the metrics use."""
        from rocm_apex_tpu.transformer._timers import Timers

        buf = io.StringIO()
        w = JsonlWriter(stream=buf)
        t = Timers()
        t("fwd").start()
        t("fwd").stop()
        t.write(["fwd"], w, iteration=3)
        row = json.loads(buf.getvalue())
        assert row["step"] == 3 and "fwd-time" in row
        # write's default now RESETS (the log/write unification)
        assert t("fwd").elapsed(reset=False) == 0.0


# ---------------------------------------------------------------------------
# shared FLOPs accounting
# ---------------------------------------------------------------------------


class TestModelFlops:
    def test_matches_the_bench_formula(self):
        """The helper reproduces bench.py's retired hand-computed
        expression exactly (the dedup must not drift the BENCH series)."""
        cfg = GPTConfig(
            vocab_size=1024, hidden_size=128, num_layers=2,
            num_attention_heads=4, max_position_embeddings=128,
        )
        b, s, raw = 16, 128, 1_000_000
        n = raw - cfg.vocab_size * cfg.hidden_size
        expect = (
            6.0 * n * b * s
            + 12.0 * cfg.num_layers * b * s * s * cfg.hidden_size
            + 6.0 * b * s * cfg.hidden_size * cfg.vocab_size
        )
        assert model_flops(cfg, b, s, raw_param_count=raw) == expect
        assert model_flops(cfg, b, s, n_params=n) == expect
        assert model_flops(
            cfg, b, s, n_params=n, include_head=False
        ) == expect - 6.0 * b * s * cfg.hidden_size * cfg.vocab_size
        with pytest.raises(ValueError, match="exactly one"):
            model_flops(cfg, b, s)
        with pytest.raises(ValueError, match="exactly one"):
            model_flops(cfg, b, s, n_params=1, raw_param_count=2)

    def test_mfu_and_peaks(self):
        assert mfu(5e11, 1.0, peak=1e12) == pytest.approx(0.5)
        assert mfu(5e11, 1.0, peak=1e12, n_chips=2) == pytest.approx(0.25)
        assert mfu(1.0, 0.0, peak=1e12) == 0.0
        assert peak_flops_per_chip("TPU v5 litepod") == 197e12
        # a device outside the one table is an error, never a default
        with pytest.raises(UnknownDeviceError):
            peak_flops_per_chip("weird-chip")
        assert chip_peaks("tpu v5e") == (197e12, 819e9)

    def test_logger_omits_mfu_on_unknown_device(self):
        """The CPU the suite runs on is not in the peaks table: the
        logger still reports step time and tokens/s but no MFU."""
        buf = io.StringIO()
        logger = MetricsLogger(
            writers=[JsonlWriter(stream=buf)],
            tokens_per_step=8, flops_per_step=1e9, memory_stats=False,
        )
        logger.start_step()
        logger.end_step()
        logger.log_step(0, {"loss": 1.0})
        row = json.loads(buf.getvalue())
        assert "tokens_per_sec" in row and "step_time_ms" in row
        assert "mfu" not in row


# ---------------------------------------------------------------------------
# static auditor
# ---------------------------------------------------------------------------


class TestAuditBasics:
    def test_scan_multiplies_and_aliases_resolve(self):
        mesh = _mesh(2)

        def f(x):
            def body(c, _):
                c = jax.lax.psum(c, "tensor")
                c = jax.lax.ppermute(
                    c, "tensor", [(0, 1), (1, 0)]
                )
                return c, None
            c, _ = jax.lax.scan(body, x, None, length=5)
            return jax.lax.psum_scatter(
                c, "tensor", scatter_dimension=0, tiled=True
            )

        g = shard_map(
            f, mesh=mesh, in_specs=(P(),), out_specs=P("tensor"),
            check_vma=False,
        )
        r = audit(g, jnp.ones((4, 4), jnp.float32))
        assert r.count("psum") == 5 and r.count("ppermute") == 5
        assert r.count("psum_scatter") == 1  # alias for reduce_scatter
        assert r.count("reduce_scatter") == 1
        # scan-scaled payload: 5 psums + 5 ppermutes of (4,4) fp32,
        # one reduce_scatter of the (2,4) shard
        assert r.bytes("psum") == pytest.approx(5 * 4 * 4 * 4)
        assert r.bytes("reduce_scatter") == pytest.approx(2 * 4 * 4)
        assert "reduce_scatter" in r.summary()

    def test_dot_flops_and_intermediates(self):
        def f(x, w):
            h = x @ w  # (3,4)@(4,5): 2*3*5*4 = 120 FLOPs
            return jnp.sum(h * h)

        r = audit(f, jnp.ones((3, 4)), jnp.ones((4, 5)))
        assert r.dot_count == 1 and r.dot_flops == pytest.approx(120.0)
        assert r.has_intermediate((3, 5))
        # INPUTS are not intermediates: the probe cannot be fooled by
        # the operand that legitimately enters at a region boundary
        assert not r.has_intermediate((4, 5))
        with pytest.raises(AssertionError, match="forbidden"):
            assert_no_intermediate(r, (3, 5))
        assert_no_intermediate(r, (7, 7))

    def test_cond_merges_by_max(self):
        def f(x):
            return jax.lax.cond(
                x.sum() > 0,
                lambda: (x @ x) @ x,  # 2 dots
                lambda: x @ x,        # 1 dot
            )

        r = audit(f, jnp.ones((4, 4)))
        assert r.dot_count == 2  # max over branches, not the sum of 3

    def test_while_loop_body_counts_once_as_lower_bound(self):
        """`lax.while_loop` has a DYNAMIC trip count: the auditor
        counts the body exactly once and flags the totals as lower
        bounds (the documented convention, until now untested)."""
        mesh = _mesh(2)

        def f(x):
            def cond(c):
                i, _ = c
                return i < 5

            def body(c):
                i, v = c
                v = jax.lax.psum(v, "tensor")
                v = jax.lax.ppermute(v, "tensor", [(0, 1), (1, 0)])
                return i + 1, v @ v

            _, v = jax.lax.while_loop(
                cond, body, (jnp.asarray(0), x)
            )
            return v

        g = shard_map(
            f, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        )
        r = audit(g, jnp.ones((4, 4), jnp.float32))
        # 5 runtime trips, ONE counted: exact per-body, a lower bound
        # in total — and the report says so
        assert r.count("psum") == 1
        assert r.count("ppermute") == 1
        assert r.dot_count == 1
        assert r.bytes("psum") == pytest.approx(4 * 4 * 4)
        assert r.while_lower_bound
        assert "lower bounds" in r.summary()

    def test_while_inside_scan_scales_and_stays_flagged(self):
        """A while body under a scan still multiplies by the SCAN trip
        count (the static part of the product is exact; the flag marks
        the dynamic part)."""
        def f(x):
            def outer(c, _):
                def cond(s):
                    i, _ = s
                    return i < 3

                def body(s):
                    i, v = s
                    return i + 1, v @ v

                _, v = jax.lax.while_loop(
                    cond, body, (jnp.asarray(0), c)
                )
                return v, None

            return jax.lax.scan(f=outer, init=x, xs=None, length=4)[0]

        r = audit(f, jnp.ones((4, 4), jnp.float32))
        assert r.dot_count == 4  # 4 scan trips x 1 counted body dot
        assert r.while_lower_bound


class TestAuditWalkerCoverage:
    """One regression pin per call-like primitive the walker must
    recurse into (`audit._inner_jaxprs`'s documented coverage
    contract): a dot seeded INSIDE each region must reach dot_count.
    A walker that silently skips a primitive zeroes the count — these
    were exactly the blind spots of the pre-lint ad-hoc walks."""

    X = jnp.ones((4, 4), jnp.float32)

    def test_pjit(self):
        r = audit(lambda x: jax.jit(lambda y: y @ y)(x), self.X)
        assert r.dot_count == 1

    def test_remat(self):
        def f(x):
            y = jax.checkpoint(lambda x: x @ x)(x)
            return jnp.sum(y * y)

        # the primal dot (replayed inside the remat region) + 2 bwd
        # dots — all of them inside remat2 eqns the walker must enter
        r = audit(jax.grad(f), self.X)
        assert r.dot_count == 3

    def test_custom_jvp_call(self):
        @jax.custom_jvp
        def f(x):
            return x @ x

        @f.defjvp
        def f_jvp(primals, tangents):
            (x,), (t,) = primals, tangents
            return f(x), t @ x + x @ t

        assert audit(f, self.X).dot_count == 1
        # the jvp rule's dots live under the same primitive when traced
        r = audit(lambda x, t: jax.jvp(f, (x,), (t,)), self.X, self.X)
        assert r.dot_count == 3

    def test_custom_vjp_call(self):
        @jax.custom_vjp
        def f(x):
            return x @ x

        def fwd(x):
            return f(x), x

        def bwd(x, g):
            return (g @ x.T + x.T @ g,)

        f.defvjp(fwd, bwd)
        r = audit(
            jax.grad(lambda x: jnp.sum(f(x))), self.X
        )
        assert r.dot_count == 3  # fwd dot + the 2 bwd rule dots

    def test_closed_call(self):
        """`closed_call` carries its body as a ClosedJaxpr param value
        (not the Jaxpr the other call primitives use) — the walker must
        unwrap it. No user-facing API emits one, so bind the primitive
        directly."""
        from jax.extend import linear_util as lu
        from jax.extend.core import jaxpr_as_fun, primitives

        closed = jax.make_jaxpr(lambda y: y @ y)(self.X)

        def g(x):
            (out,) = primitives.closed_call_p.bind(
                lu.wrap_init(
                    jaxpr_as_fun(closed),
                    debug_info=closed.jaxpr.debug_info,
                ),
                x,
                call_jaxpr=closed,
            )
            return out

        assert audit(g, self.X).dot_count == 1

    def test_params_dict_and_nested_tuples(self):
        """`_inner_jaxprs` finds jaxprs held in dict params and in
        arbitrarily nested tuples — the representation future call
        primitives are free to pick."""
        from rocm_apex_tpu.monitor.audit import _inner_jaxprs

        closed = jax.make_jaxpr(lambda y: y @ y)(self.X)
        found = list(
            _inner_jaxprs(
                {
                    "mapping": {"body": closed},
                    "nested": ((closed.jaxpr,), [closed]),
                    "scalar": 3,
                    "name": "not-a-jaxpr",
                }
            )
        )
        assert len(found) == 3


def _sp_cfg(collective_matmul, **kw):
    """EXACTLY test_collective_matmul._sp_cfg — same shapes, and the
    auditor never compiles anyway (make_jaxpr only)."""
    return GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=1,
        num_attention_heads=4, max_position_embeddings=32,
        ffn_hidden_size=256, hidden_dropout=0.0, attention_dropout=0.0,
        tensor_parallel_size=2, dtype=jnp.float32,
        sequence_parallel=True, collective_matmul=collective_matmul,
        **kw,
    )


class TestAuditCollectiveMatmulStack:
    """The PR-3 invariant as auditor assertions, on the exact SP/CM
    config of test_collective_matmul."""

    B, S, H = 2, 32, 64

    def _stack_subject(self, collective_matmul):
        mesh = _mesh(2)
        cfg = _sp_cfg(collective_matmul)
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
            f"spcm_stack_cm{int(collective_matmul)}", f, x_loc
        )

    def test_ring_counts_and_no_full_activation(self):
        """With collective_matmul=True the 4 TP-edge collectives of the
        layer (qkv + dense_h_to_4h columns, dense + dense_4h_to_h rows)
        are ppermute rings: at tp=2 with one piece per shard each op
        permutes once per forward and twice per backward (dx ring +
        rotating dW). The traced step holds THREE forwards' worth of
        edges (flax init traces a forward, then value_and_grad's fwd +
        bwd): 4 + 4 + 4·2 = 16 ppermutes — and NO plain all_gather /
        reduce_scatter edge collectives remain. The full (b, s, h)
        gathered activation does not exist anywhere in init+fwd+bwd.
        Declared as lint rules — the same contract `tools/graphlint.py`
        pins in CI under the `spcm_tp2` config."""
        subject = self._stack_subject(True)
        r = subject.report
        monitor.run_lint(subject, [
            monitor.CollectiveContract(
                expect={"ppermute": 16},
                forbid=("all_gather", "reduce_scatter"),
            ),
            monitor.NoMaterialization(
                forbidden_shapes=((self.B, self.S, self.H),)
            ),
        ]).raise_if_failed()
        # the sequence-local activation DOES exist (probe sanity), and
        # LN affine grads still psum over the axis (grad_sync_axis)
        assert r.has_intermediate((self.B, self.S // 2, self.H))
        assert r.count("psum") > 0

    def test_blocking_counts_and_probe_sanity(self):
        """The blocking-collective variant, audited identically, DOES
        gather the full activation (the probe is sound) and uses the
        plain edge collectives instead of rings."""
        r = self._stack_subject(False).report
        assert r.has_intermediate((self.B, self.S, self.H))
        assert r.count("ppermute") == 0
        assert r.count("all_gather") > 0
        assert r.count("reduce_scatter") > 0
        with pytest.raises(AssertionError):
            assert_no_intermediate(r, (self.B, self.S, self.H))


class TestAuditVocabParallelHead:
    def test_chunked_head_collectives_and_no_logits(self):
        """The vocab-parallel fused head on test_linear_xentropy's
        exact tp=2 config: per-chunk pmax/psum reductions over the
        tensor axis, scan-multiplied by the chunk count, and no
        (rows, vocab) logits intermediate."""
        from rocm_apex_tpu.ops.linear_xentropy import (
            vocab_parallel_linear_cross_entropy,
        )

        mesh = _mesh(2)
        n, h, v, chunk = 37, 16, 48, 8
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(n, h).astype(np.float32))
        w = jnp.asarray((rng.randn(v, h) * 0.1).astype(np.float32))
        y = jnp.asarray(rng.randint(0, v, (n,)).astype(np.int32))

        def f(x, w_loc):
            def loss(x, w_loc):
                return jnp.sum(
                    vocab_parallel_linear_cross_entropy(
                        x, w_loc, y, "tensor", 0.0, None, chunk
                    )
                )

            return jax.grad(loss, (0, 1))(x, w_loc)

        g = shard_map(
            f, mesh=mesh, in_specs=(P(), P("tensor")),
            out_specs=(P(), P("tensor")), check_vma=False,
        )
        r = assert_no_intermediate(audit(g, x, w), (n, v))
        assert r.count("pmax") > 0  # chunk-wise running max
        assert r.count("psum") > 0  # sum-exp / target / dx reductions
        # the reductions are per-chunk: at least one pmax per full
        # chunk of the 37-row input (ceil(37/8) chunks)
        assert r.count("pmax") >= -(-n // chunk)
        assert r.collective_bytes > 0


# ---------------------------------------------------------------------------
# engine stats
# ---------------------------------------------------------------------------


class TestEngineStats:
    def test_stats_counters_and_throughput(self):
        """test_inference's exact fp32 engine config (compile-cache
        hit): counters reconcile with the completed work and the
        latency/throughput fields are sane."""
        from rocm_apex_tpu.inference import InferenceEngine, SamplingParams

        cfg = GPTConfig(
            vocab_size=96, hidden_size=32, num_layers=2,
            num_attention_heads=4, max_position_embeddings=32,
            hidden_dropout=0.0, attention_dropout=0.0,
            tensor_parallel_size=1, params_dtype=jnp.float32,
            dtype=jnp.float32,
        )
        model = GPTModel(cfg)
        toks = jnp.zeros((1, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(1), toks)
        eng = InferenceEngine(
            model, params, num_slots=2, max_prompt_len=8, capacity=24,
            sampling=SamplingParams(temperature=0.0),
        )
        s0 = eng.stats()
        assert s0["admitted"] == 0 and s0["decode_steps"] == 0
        assert s0["prefill_ms_avg"] == 0.0 and s0["decode_ms_avg"] == 0.0

        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
        results = eng.generate(prompts, max_new_tokens=4)
        s = eng.stats()
        assert s["admitted"] == 3.0 and s["evicted"] == 3.0
        assert s["queue_depth"] == 0.0 and s["slots_active"] == 0.0
        assert s["slot_occupancy"] == 0.0
        assert s["prompt_tokens"] == float(sum(len(p) for p in prompts))
        assert s["generated_tokens"] == float(
            sum(len(r.tokens) for r in results)
        )
        assert s["decode_steps"] >= 3  # 4 tokens each, 2 slots for 3 reqs
        assert s["prefill_ms_avg"] > 0.0 and s["decode_ms_avg"] > 0.0
        assert s["decode_tokens_per_sec"] > 0.0
        assert s["prefill_tokens_per_sec"] > 0.0
        # the dict feeds MetricsLogger directly
        lg = MetricsLogger(
            writers=[JsonlWriter(stream=io.StringIO())], window=1,
            memory_stats=False,
        )
        assert lg.log_step(0, s)["admitted"] == 3.0
