"""Quantized ring collectives (ops/quantized_collectives.py).

Pins the module's quantization contract — fp32 rings bitwise-
reproduce an order-matched reference, int8 rings land within the
per-hop quantization noise model and agree bitwise across replicas,
degradation paths equal the plain lax collective — plus the audit-
measured byte story: ppermute hop counts per named_scope, the
per-dtype payload split, and the >= 3.5x wire-byte drop of the dp4
ZeRO grad/param rings at comm_dtype="int8" (ISSUE 11 acceptance).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from _helpers import jit_shmap

from rocm_apex_tpu import monitor
from rocm_apex_tpu.contrib.optimizers import distributed_fused_adam
from rocm_apex_tpu.monitor import audit
from rocm_apex_tpu.ops.quantized_collectives import (
    check_comm_dtype,
    dequantize_int8,
    quantize_int8,
    ring_all_gather,
    ring_all_reduce,
    ring_reduce_scatter,
)

DP = 4
ROWS, COLS = 24, 32  # 6-row blocks at dp4


def data_mesh(n=DP):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices")
    return Mesh(np.array(devs[:n]), ("data",))


def stacked_inputs(key, shape=(DP, ROWS, COLS)):
    return jax.random.normal(key, shape, jnp.float32)


def _run_ring(fn, x, mesh, out_specs=P("data")):
    return jit_shmap(
        fn, mesh=mesh, in_specs=(P("data"),), out_specs=out_specs,
        check_vma=False,
    )(x)


class TestRingParity:
    def test_rs_fp32_bitwise_order_matched(self):
        """The fp32 ring reduce-scatter is DETERMINISTIC: rank b's
        block sums contributions in the fixed ring order b+1, b+2,
        ..., b — bitwise equal to the order-matched numpy reference."""
        mesh = data_mesh()
        x = stacked_inputs(jax.random.PRNGKey(0))

        def local(xs):
            return ring_reduce_scatter(xs[0], "data", comm_dtype="fp32")

        got = np.asarray(_run_ring(local, x, mesh))  # (ROWS,) gathered
        xs = np.asarray(x)
        rows = ROWS // DP
        for b in range(DP):
            acc = xs[(b + 1) % DP, b * rows:(b + 1) * rows].copy()
            for i in range(2, DP + 1):
                acc = acc + xs[(b + i) % DP, b * rows:(b + 1) * rows]
            assert np.array_equal(got[b * rows:(b + 1) * rows], acc), b

    def test_ag_fp32_bitwise_vs_lax(self):
        mesh = data_mesh()
        x = stacked_inputs(jax.random.PRNGKey(1), (DP, ROWS // DP, COLS))

        def ring(xs):
            return ring_all_gather(xs[0], "data", comm_dtype="fp32")

        def plain(xs):
            return jax.lax.all_gather(xs[0], "data", axis=0, tiled=True)

        got = _run_ring(ring, x, mesh)
        want = _run_ring(plain, x, mesh)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_ag_int8_exact_noise_model_and_replica_agreement(self):
        """int8 gather output == dequant(quant(shard)) per shard —
        quantize-once means ONE rounding per element, exactly — and
        every replica reconstructs the identical array bitwise."""
        mesh = data_mesh()
        x = stacked_inputs(jax.random.PRNGKey(2), (DP, ROWS // DP, COLS))

        def ring(xs):
            return ring_all_gather(xs[0], "data", comm_dtype="int8")

        # out_specs P("data") keeps every rank's copy for comparison
        got = np.asarray(_run_ring(ring, x, mesh)).reshape(
            DP, ROWS, COLS
        )
        # jitted reference: the in-ring quantization is compiled, and
        # XLA rewrites x/scale as x*(1/scale) — an eager reference
        # differs by float division rounding, a jitted one is bitwise
        deq = jax.jit(lambda s: dequantize_int8(*quantize_int8(s)))
        want = np.concatenate([np.asarray(deq(s)) for s in x])
        for r in range(DP):
            assert np.array_equal(got[r], want), r

    def test_rs_int8_error_bound(self):
        """int8 reduce-scatter error <= the per-hop noise model: each
        of the n-1 hops re-quantizes the rotating accumulator at
        rowmax/254 granularity; the bound sums the hop-time rowmaxes
        from an fp32 replay of the same ring order."""
        mesh = data_mesh()
        x = stacked_inputs(jax.random.PRNGKey(3))

        def ring(xs):
            return ring_reduce_scatter(xs[0], "data", comm_dtype="int8")

        def plain(xs):
            return jax.lax.psum_scatter(
                xs[0], "data", scatter_dimension=0, tiled=True
            )

        got = np.asarray(_run_ring(ring, x, mesh))
        want = np.asarray(_run_ring(plain, x, mesh))
        xs = np.asarray(x)
        rows = ROWS // DP
        for b in range(DP):
            blk = slice(b * rows, (b + 1) * rows)
            acc = xs[(b + 1) % DP, blk].copy()
            bound = np.zeros((rows, 1), np.float32)
            for i in range(2, DP + 1):
                # the accumulator that crosses the wire before add i
                bound += np.abs(acc).max(-1, keepdims=True) / 254.0
                acc = acc + xs[(b + i) % DP, blk]
            err = np.abs(got[blk] - want[blk])
            assert (err <= 1.05 * bound + 1e-6).all(), (
                b, err.max(), bound.max(),
            )

    def test_all_reduce_roundtrip(self):
        """ring_all_reduce = RS + AG: fp32 matches lax.psum to
        reduction-order noise; int8 stays within the combined bound."""
        mesh = data_mesh()
        x = stacked_inputs(jax.random.PRNGKey(4))

        def ring32(xs):
            return ring_all_reduce(xs[0], "data", comm_dtype="fp32")

        def ring8(xs):
            return ring_all_reduce(xs[0], "data", comm_dtype="int8")

        def plain(xs):
            return jax.lax.psum(xs[0], "data")

        want = np.asarray(_run_ring(plain, x, mesh))[:ROWS]
        got32 = np.asarray(_run_ring(ring32, x, mesh))[:ROWS]
        got8 = np.asarray(_run_ring(ring8, x, mesh))[:ROWS]
        np.testing.assert_allclose(got32, want, rtol=1e-6, atol=1e-6)
        amax = np.abs(want).max()
        assert np.abs(got8 - want).max() <= DP * amax / 254.0 + 1e-6


class TestDegradation:
    def test_unbound_axis_identity(self):
        x = jnp.arange(12.0).reshape(4, 3)
        for fn in (ring_reduce_scatter, ring_all_gather, ring_all_reduce):
            out = fn(x, "no_such_axis", comm_dtype="int8")
            assert np.array_equal(np.asarray(out), np.asarray(x)), fn

    def test_size_one_axis_identity(self):
        mesh = data_mesh(1)
        x = stacked_inputs(jax.random.PRNGKey(5), (1, 8, 4))
        mesh1 = Mesh(np.array(jax.devices()[:1]), ("data",))

        def local(xs):
            return ring_reduce_scatter(xs[0], "data", comm_dtype="int8")

        got = _run_ring(local, x, mesh1)
        assert np.array_equal(np.asarray(got)[:8], np.asarray(x[0]))

    def test_bad_chunk_falls_back_to_lax(self):
        """A chunk that does not tile the shard degrades to the plain
        lax collective — bitwise identical output."""
        mesh = data_mesh()
        x = stacked_inputs(jax.random.PRNGKey(6))

        def ring(xs):
            # shard rows = 6; chunk 5 does not tile -> lax fallback
            return ring_reduce_scatter(
                xs[0], "data", comm_dtype="int8", chunk=5
            )

        def plain(xs):
            return jax.lax.psum_scatter(
                xs[0], "data", scatter_dimension=0, tiled=True
            )

        got = _run_ring(ring, x, mesh)
        want = _run_ring(plain, x, mesh)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        # and the degraded program contains NO ppermute
        rep = audit(
            jax.shard_map(
                ring, mesh=mesh, in_specs=(P("data"),),
                out_specs=P("data"), check_vma=False,
            ),
            x,
        )
        assert rep.count("ppermute") == 0
        assert rep.count("reduce_scatter") == 1

    def test_nontiling_rows_all_reduce_falls_back_to_psum(self):
        mesh = data_mesh()
        x = stacked_inputs(jax.random.PRNGKey(7), (DP, 25, 8))

        def ring(xs):
            return ring_all_reduce(xs[0], "data", comm_dtype="int8")

        def plain(xs):
            return jax.lax.psum(xs[0], "data")

        got = _run_ring(ring, x, mesh)
        want = _run_ring(plain, x, mesh)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_bad_comm_dtype_raises(self):
        with pytest.raises(ValueError, match="comm_dtype"):
            check_comm_dtype("bf16")
        with pytest.raises(ValueError, match="comm_dtype"):
            ring_all_gather(jnp.zeros((4, 4)), "data", comm_dtype="e5m2")

    def test_int8_excludes_wire_cast(self):
        with pytest.raises(ValueError, match="allgather_dtype"):
            distributed_fused_adam(
                1e-3, comm_dtype="int8", allgather_dtype="bf16"
            )


class TestPackedBufferAlignment:
    def test_shard_rows_tile_the_ring(self):
        """PR-9 packed buffers pad rows to BLOCK_ROWS*world multiples,
        so the dp4 grad ring NEVER takes the lax fallback: the padded
        buffer tiles both the axis and the kernel block rows."""
        from rocm_apex_tpu.contrib.optimizers.distributed import (
            _shard_meta,
        )
        from rocm_apex_tpu.ops.optim_kernels import BLOCK_ROWS
        from rocm_apex_tpu.ops.packing import build_pack_spec

        params = {
            "w": jnp.zeros((24, 33)),
            "b": jnp.zeros((33,)),
            "emb": jnp.zeros((50, 16)),
        }
        spec = build_pack_spec(params)
        mesh = data_mesh()

        def local(_):
            world, rank, dims = _shard_meta(spec, "data")
            return jnp.asarray(
                [rows_pad for rows_pad, _ in dims], jnp.int32
            )

        dims = np.asarray(
            jit_shmap(
                local, mesh=mesh, in_specs=(P(),), out_specs=P(),
                check_vma=False,
            )(jnp.zeros(1))
        )
        for rows_pad in dims:
            assert rows_pad % (BLOCK_ROWS * DP) == 0, rows_pad

    def test_packed_rs_parity(self):
        """int8 ring RS over a packed-width buffer lands within the
        noise model of the plain psum_scatter on the same buffer."""
        from rocm_apex_tpu.ops.optim_kernels import WIDTH

        mesh = data_mesh()
        rows = 4 * DP
        x = jax.random.normal(
            jax.random.PRNGKey(8), (DP, rows, WIDTH), jnp.float32
        )

        def ring(xs):
            return ring_reduce_scatter(xs[0], "data", comm_dtype="int8")

        def plain(xs):
            return jax.lax.psum_scatter(
                xs[0], "data", scatter_dimension=0, tiled=True
            )

        got = np.asarray(_run_ring(ring, x, mesh))
        want = np.asarray(_run_ring(plain, x, mesh))
        amax = np.abs(np.asarray(x)).sum(0).max()
        assert np.abs(got - want).max() <= DP * amax / 254.0


class TestAuditPins:
    def test_hop_counts_scopes_and_dtype_bytes(self):
        """A dp4 int8 RS+AG round trip costs exactly 2*(n-1) ppermute
        eqns per ring (payload + fp32 sidecar per hop), attributed to
        the qring_rs / qring_ag named_scopes, and the per-dtype byte
        split shows the int8 payloads next to the fp32 sidecars."""
        mesh = data_mesh()
        x = stacked_inputs(jax.random.PRNGKey(9))

        def local(xs):
            shard = ring_reduce_scatter(xs[0], "data", comm_dtype="int8")
            return ring_all_gather(shard, "data", comm_dtype="int8")

        rep = audit(
            jax.shard_map(
                local, mesh=mesh, in_specs=(P("data"),),
                out_specs=P(), check_vma=False,
            ),
            x,
        )
        hops = 2 * (DP - 1)  # payload + sidecar per hop, m=1 chunks
        assert rep.count_in_scope("qring_rs", "ppermute") == hops
        assert rep.count_in_scope("qring_ag", "ppermute") == hops
        assert rep.count("ppermute") == 2 * hops
        by_dtype = rep.bytes_by_dtype("ppermute")
        rows = ROWS // DP
        # int8 payload: (rows, COLS) x1 byte x (n-1) hops x two rings
        assert by_dtype["int8"] == 2 * (DP - 1) * rows * COLS
        # fp32 sidecar: (rows, 1) x4 bytes x (n-1) hops x two rings
        assert by_dtype["float32"] == 2 * (DP - 1) * rows * 4

    def test_zero_wire_bytes_drop_at_dp4(self):
        """ISSUE 11 acceptance: the audit-measured DP grad reduce-
        scatter + ZeRO param all-gather wire bytes drop >= 3.5x at dp4
        with comm_dtype="int8" (fp32 scale sidecars counted)."""
        mesh = data_mesh()
        params = {
            "w": 0.1 * jax.random.normal(jax.random.PRNGKey(0), (24, 33)),
            "b": jnp.zeros((33,)),
            "emb": 0.1 * jax.random.normal(jax.random.PRNGKey(1), (50, 16)),
        }
        grads = jax.tree_util.tree_map(
            lambda p: jnp.ones_like(p), params
        )

        def one_update(comm_dtype):
            dist = distributed_fused_adam(
                1e-3, axis_name="data", comm_dtype=comm_dtype
            )

            def local(params, grads):
                state = dist.init(params)
                updates, _ = dist.update(grads, state, params)
                return updates

            return audit(
                jax.shard_map(
                    local, mesh=mesh, in_specs=(P(), P()),
                    out_specs=P(), check_vma=False,
                ),
                params, grads,
            )

        rep32 = one_update("fp32")
        rep8 = one_update("int8")
        # fp32 path: one-shot lax reduce_scatter + all_gather
        wire32 = rep32.wire_bytes("reduce_scatter") + rep32.wire_bytes(
            "all_gather"
        )
        assert rep32.count("ppermute") == 0
        # int8 path: everything rides ppermute rings (incl. sidecars)
        wire8 = rep8.wire_bytes("ppermute")
        assert rep8.count("reduce_scatter") == 0
        assert rep8.count("all_gather") == 0
        assert wire32 > 0 and wire8 > 0
        ratio = wire32 / wire8
        assert ratio >= 3.5, (wire32, wire8, ratio)


class TestFoundInfGatherSkip:
    def _trace_update(self, comm_dtype="int8"):
        mesh = data_mesh()
        params = {"w": jnp.zeros((24, 33)), "b": jnp.zeros((33,))}
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        dist = distributed_fused_adam(
            1e-3, axis_name="data", comm_dtype=comm_dtype
        )

        def local(params, grads):
            state = dist.init(params)
            updates, _, info = dist.update(
                grads, state, params, inv_scale=0.5, with_info=True
            )
            return updates

        from jax import shard_map

        return jax.make_jaxpr(
            shard_map(
                local, mesh=mesh, in_specs=(P(), P()),
                out_specs=P(), check_vma=False,
            )
        )(params, grads)

    def test_skip_branch_has_no_collectives(self):
        """The found_inf cond has one branch with ZERO collectives (the
        frozen path: no param gather runs on a skipped step) and one
        with the ppermute gather ring — pinned via the declarative
        CollectiveContract lint rule because the audit merges cond
        branches by max and cannot show the skip."""
        subject = monitor.LintSubject.from_jaxpr(
            "zero_int8_update", self._trace_update("int8")
        )
        report = monitor.run_lint(
            subject,
            [monitor.CollectiveContract(require_skip_cond=True)],
        )
        report.raise_if_failed()

    def test_skip_step_freezes_bitwise(self):
        """Behavioral pin: an overflowed step emits exact-zero updates
        and bitwise-frozen master shards in BOTH comm modes (PR-9
        freeze contract extended to the quantized gather)."""
        mesh = data_mesh()
        params = {
            "w": 0.1 * jax.random.normal(jax.random.PRNGKey(2), (24, 33)),
            "b": jnp.zeros((33,)),
        }
        grads = jax.tree_util.tree_map(
            lambda p: jnp.full_like(p, jnp.inf), params
        )
        for mode in ("fp32", "int8"):
            dist = distributed_fused_adam(
                1e-3, axis_name="data", comm_dtype=mode
            )

            def local(params, grads):
                state = dist.init(params)
                updates, state2, info = dist.update(
                    grads, state, params, inv_scale=0.5, with_info=True
                )
                master_same = jnp.asarray(
                    [
                        jnp.all(a == b)
                        for a, b in zip(state.master, state2.master)
                    ]
                ).all()
                return (
                    updates,
                    info["found_inf"],
                    master_same,
                    state2.count,
                )

            updates, found_inf, master_same, count = jit_shmap(
                local, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                check_vma=False,
            )(params, grads)
            assert bool(found_inf), mode
            assert bool(master_same), mode
            assert int(count) == 0, mode
            for leaf in jax.tree_util.tree_leaves(updates):
                arr = np.asarray(leaf)
                assert (arr == 0.0).all(), mode
