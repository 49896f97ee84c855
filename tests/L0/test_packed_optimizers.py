"""Packed-buffer optimizer step vs the tree path.

The packed pipeline (optimizers/packed.py) must be the SAME math as the
tree-fused optimizers, just traced at dtype-group granularity:

* Adam parity is **bitwise** — fp32 and bf16, with and without weight
  decay — under ONE COMPILED STEP per path reused across iterations
  (the training condition: a scan body or a jitted step compiles the
  update once). Two trace shapes break exactness without changing the
  math, both XLA rewrite variance: op-by-op eager execution misses the
  algebraic rewrites a jitted program gets (e.g. ``(a/b)/c ->
  a/(b*c)``, ~2e-9 on updates), and tracing MULTIPLE steps into one
  program lets XLA fuse across the step boundary with per-path FMA
  grouping (~1e-7 after 5 steps). Per-step jit on both paths holds the
  comparison exactly bitwise.
* LAMB fp32 parity is to a documented ~1e-6 tolerance: the trust-ratio
  norms are segmented ROW reductions whose order differs from the tree
  path's per-leaf `jnp.sum` (bf16 params still round to equal values).
* The overflow skip is a kernel-level freeze: bit-identical state, and
  bit-identical CONTINUATION versus a caller-driven `skip=True` step.
* `monitor.audit` pins the fusion-granularity claim: the packed update
  phase emits O(dtype-groups) equations — constant in the leaf count —
  while the tree path grows O(leaves).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from _helpers import assert_close

from rocm_apex_tpu import monitor
from rocm_apex_tpu.optimizers import fused_adam, fused_lamb
from rocm_apex_tpu.optimizers.mixed import MixedPrecisionAdam
from rocm_apex_tpu.optimizers.packed import PackedOptimizerStep, packed_adam
from rocm_apex_tpu.ops.packing import (
    WIDTH,
    build_pack_spec,
    pack_tree,
    respec,
)


def make_params(key, dtype=jnp.float32):
    k1, _, k3 = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(k1, (33, 65), dtype),
        "b": jnp.zeros((65,), dtype),
        "deep": {"k": jax.random.normal(k3, (7, 3, 11), dtype) * 0.3},
    }


def make_grads(key, params, steps):
    ks = jax.random.split(key, len(jax.tree_util.tree_leaves(params)))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    gl = [
        jax.random.normal(k, (steps,) + x.shape, jnp.float32).astype(x.dtype)
        for k, x in zip(ks, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, gl)


def jit_step(opt):
    """Compile the update ONCE and reuse it every iteration — the
    training condition the bitwise claims hold under (module docstring).
    `skip` is a traced argument so skipped and live steps share the
    same executable (the tree path has no kernel skip and ignores it)."""
    has_skip = getattr(opt.update, "kernel_skip", False)

    @jax.jit
    def step(params, state, g, skip):
        if has_skip:
            updates, state = opt.update(g, state, params, skip=skip)
        else:
            updates, state = opt.update(g, state, params)
        return optax.apply_updates(params, updates), state

    return step


def run_stepped(opt, params, gsteps, steps, skips=None):
    step = jit_step(opt)
    state = opt.init(params)
    for t in range(steps):
        g = jax.tree_util.tree_map(lambda s: s[t], gsteps)
        skip = jnp.asarray(False if skips is None else skips[t])
        params, state = step(params, state, g, skip)
    return params, state


def assert_tree_equal(a, b):
    """Bitwise on the CPU suite. On a chip the packed Mosaic kernels and
    the XLA-fused tree update round the same fp32 Adam math differently
    in the last place (measured: max relative difference 2.9e-6 after 5
    steps), so the on-chip sweep allows a few ulps."""
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert x.dtype == y.dtype
        assert_close(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=0.0, atol=0.0, tpu_rtol=1e-5, tpu_atol=1e-8,
        )


class TestAdamParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("wd", [0.0, 0.01])
    def test_bitwise(self, dtype, wd):
        params = make_params(jax.random.PRNGKey(0), dtype)
        gsteps = make_grads(jax.random.PRNGKey(1), params, 5)
        tree = fused_adam(1e-3, weight_decay=wd)
        packed = fused_adam(1e-3, weight_decay=wd, packed=True)
        want, _ = run_stepped(tree, params, gsteps, 5)
        got, _ = run_stepped(packed, params, gsteps, 5)
        assert_tree_equal(got, want)

    def test_weight_decay_mask(self):
        params = make_params(jax.random.PRNGKey(2))
        gsteps = make_grads(jax.random.PRNGKey(3), params, 3)
        mask = {"w": True, "b": False, "deep": {"k": True}}
        tree = fused_adam(1e-3, weight_decay=0.1, weight_decay_mask=mask)
        packed = fused_adam(
            1e-3, weight_decay=0.1, weight_decay_mask=mask, packed=True
        )
        want, _ = run_stepped(tree, params, gsteps, 3)
        got, _ = run_stepped(packed, params, gsteps, 3)
        assert_tree_equal(got, want)
        # the mask did something: decayed vs exempt leaves diverge from
        # a no-decay run
        nodecay, _ = run_stepped(fused_adam(1e-3), params, gsteps, 3)
        assert not np.array_equal(np.asarray(got["w"]), np.asarray(nodecay["w"]))
        # packed (masked) against tree (no decay): see assert_tree_equal
        assert_tree_equal(got["b"], nodecay["b"])


class TestLambParity:
    def test_fp32_tolerance(self):
        params = make_params(jax.random.PRNGKey(4))
        gsteps = make_grads(jax.random.PRNGKey(5), params, 3)
        tree = fused_lamb(1e-2, weight_decay=0.01)
        packed = fused_lamb(1e-2, weight_decay=0.01, packed=True)
        want, _ = run_stepped(tree, params, gsteps, 3)
        got, _ = run_stepped(packed, params, gsteps, 3)
        # segmented-row-reduction order differs from per-leaf jnp.sum:
        # ~1e-6 relative, NOT bitwise (module docstring)
        for x, y in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
        ):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6
            )

    def test_bf16_rounds_equal(self):
        params = make_params(jax.random.PRNGKey(6), jnp.bfloat16)
        gsteps = make_grads(jax.random.PRNGKey(7), params, 3)
        tree = fused_lamb(1e-2, weight_decay=0.01)
        packed = fused_lamb(1e-2, weight_decay=0.01, packed=True)
        want, _ = run_stepped(tree, params, gsteps, 3)
        got, _ = run_stepped(packed, params, gsteps, 3)
        assert_tree_equal(got, want)


class TestPackedStepWrapper:
    def test_matches_mixed_precision_adam(self):
        params = make_params(jax.random.PRNGKey(8))
        gsteps = make_grads(jax.random.PRNGKey(9), params, 4)
        gsteps = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.bfloat16), gsteps
        )
        mp = MixedPrecisionAdam(1e-3, weight_decay=0.01)
        pk = PackedOptimizerStep("adam", 1e-3, weight_decay=0.01)
        step_m = jax.jit(lambda s, g: mp.step_and_probe(s, g, grad_scale=1.0))
        step_p = jax.jit(lambda s, g: pk.step_and_probe(s, g, grad_scale=1.0))
        sm, sp = mp.init(params), pk.init(params)
        for t in range(4):
            g = jax.tree_util.tree_map(lambda s: s[t], gsteps)
            sm, fm = step_m(sm, g)
            sp, fp = step_p(sp, g)
        assert not bool(fm) and not bool(fp)
        assert_tree_equal(pk.model_params(sp), mp.model_params(sm))
        assert_tree_equal(pk.masters(sp), sm.master)

    def test_padding_stays_zero(self):
        params = make_params(jax.random.PRNGKey(10))
        pk = PackedOptimizerStep(
            "adam", 1e-3, weight_decay=0.1, compute_dtype=jnp.float32
        )
        gsteps = make_grads(jax.random.PRNGKey(11), params, 3)

        @jax.jit
        def run(params, gsteps):
            s = pk.init(params)
            for t in range(3):
                g = jax.tree_util.tree_map(lambda x: x[t], gsteps)
                s = pk.step(s, g)
            return s

        s = run(params, gsteps)
        spec = build_pack_spec(s.model)
        for bufs in (s.master, s.m, s.v):
            for buf, group in zip(bufs, spec.groups):
                mask = np.ones((group.rows, WIDTH), bool)
                for ls in group.leaf_specs:
                    flat = mask.reshape(-1)
                    flat[ls.row_start * WIDTH:
                         ls.row_start * WIDTH + ls.numel] = False
                # everything outside live leaf elements — intra-row
                # tails and whole padding rows — must still be zero
                # (weight decay of a zero master is zero)
                assert np.all(np.asarray(buf)[mask] == 0.0)


class TestOverflowSkip:
    def test_frozen_step_is_bitwise_noop(self):
        params = make_params(jax.random.PRNGKey(12))
        pk = PackedOptimizerStep("adam", 1e-3, weight_decay=0.01)
        g = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            make_params(jax.random.PRNGKey(13)),
        )
        g_inf = dict(g, b=g["b"].at[0].set(jnp.inf))
        # one executable serves the live AND the overflowed step
        step = jax.jit(lambda s, g: pk.step_and_probe(s, g, grad_scale=1.0))
        s1, f1 = step(pk.init(params), g)
        s2, f2 = step(s1, g_inf)
        assert not bool(f1) and bool(f2)
        assert int(s1.count) == 1 and int(s2.count) == 1
        assert_tree_equal(s2.model, s1.model)
        assert_tree_equal(s2.master, s1.master)
        assert_tree_equal(s2.m, s1.m)
        assert_tree_equal(s2.v, s1.v)

    def test_found_inf_matches_caller_skip(self):
        # inf-grad freeze must be bit-identical — INCLUDING the steps
        # after it — to the same schedule driven by skip=True on finite
        # grads (the tree path's caller-skip contract)
        params = make_params(jax.random.PRNGKey(14))
        opt = packed_adam(1e-3, weight_decay=0.01)
        gsteps = make_grads(jax.random.PRNGKey(15), params, 3)
        binf = gsteps["b"].at[1, 0].set(jnp.inf)
        gsteps_inf = dict(gsteps, b=binf)
        pa, sa = run_stepped(opt, params, gsteps_inf, 3)
        pb, sb = run_stepped(opt, params, gsteps, 3,
                             skips=[False, True, False])
        assert int(sa.count) == int(sb.count) == 2
        assert_tree_equal(pa, pb)
        assert_tree_equal(sa.m, sb.m)
        assert_tree_equal(sa.v, sb.v)


class TestScalerPackedUnscale:
    def test_one_pass_unscale_and_probe(self):
        from rocm_apex_tpu import amp

        scaler = amp.LossScaler(init_scale=1024.0)
        state = scaler.init()
        grads = make_params(jax.random.PRNGKey(16))
        scaled = jax.tree_util.tree_map(lambda g: g * 1024.0, grads)
        spec = build_pack_spec(scaled)

        @jax.jit
        def go(scaled):
            pg = pack_tree(scaled, spec)
            return scaler.unscale_packed(state, pg)

        out, found = go(scaled)
        assert not bool(found)
        # 1024 is a power of two: the unscale is exact
        assert_tree_equal(
            out.buffers,
            pack_tree(grads, respec(spec, jnp.float32)).buffers,
        )
        bad = dict(scaled, b=scaled["b"].at[0].set(jnp.nan))
        _, found = go(bad)
        assert bool(found)


class TestAuditEqnCount:
    """The tentpole's regression guard: the packed UPDATE PHASE
    (`adam_phase`: buffers in, buffers out — pack/unpack excluded, they
    are pure data movement) traces O(dtype-groups) equations, exactly
    constant in the leaf count; the tree path grows O(leaves). At the
    whole-transformation level — pack and unpack included — the packed
    step still traces far fewer equations with a far smaller per-leaf
    slope (a pad+concat per leaf, not a fused-Adam expression tree)."""

    @staticmethod
    def _flat_params(n_leaves, dtype=jnp.float32):
        k = jax.random.split(jax.random.PRNGKey(17), n_leaves)
        return {
            f"p{i}": jax.random.normal(k[i], (9 + i, 13), dtype)
            for i in range(n_leaves)
        }

    @staticmethod
    def _eqns(opt, params):
        grads = jax.tree_util.tree_map(lambda p: p * 1e-2, params)
        state = opt.init(params)
        rep = monitor.audit(
            lambda s, g, p: opt.update(g, s, p), state, grads, params
        )
        return int(rep.eqn_count)

    @staticmethod
    def _phase_eqns(params):
        from rocm_apex_tpu.optimizers import _common as c
        from rocm_apex_tpu.optimizers.packed import adam_phase

        grads = jax.tree_util.tree_map(lambda p: p * 1e-2, params)
        spec, pp, pg = c.pack_params_and_grads(params, grads)
        m = c.zero_group_buffers(spec)
        v = c.zero_group_buffers(spec)
        wd_cols = c.wd_columns(spec, 0.01, None)
        rep = monitor.audit(
            lambda pp, pg, m, v: adam_phase(
                pp, pg, m, v, wd_cols,
                lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
                bc1=jnp.float32(0.1), bc2=jnp.float32(1e-3),
                grad_scale=jnp.float32(1.0),
            ),
            pp, pg, m, v,
        )
        return int(rep.eqn_count)

    def test_update_phase_constant_in_leaves(self):
        # the phase program is IDENTICAL for 3 and 10 leaves of one
        # dtype: one scale+sumsq pass + one Adam pass per GROUP
        assert self._phase_eqns(self._flat_params(3)) == self._phase_eqns(
            self._flat_params(10)
        )

    @staticmethod
    def _wrapper_eqns(opt, params):
        state = opt.init(params)
        grads = jax.tree_util.tree_map(
            lambda p: p * 1e-2, opt.model_params(state)
        )
        rep = monitor.audit(
            lambda s, g: opt.step_and_probe(s, g, grad_scale=1.0),
            state, grads,
        )
        return int(rep.eqn_count)

    def test_train_step_beats_tree_and_slope(self):
        # the bench's A/B (bench.py --packed-update): the whole
        # mixed-precision step — probe + update + model cast — packed vs
        # tree. Packed per-leaf growth is pack(grads)/unpack(model) data
        # movement only; the tree path re-traces the full fused-Adam
        # expression per leaf.
        mp = MixedPrecisionAdam(1e-3, weight_decay=0.01)
        pk = PackedOptimizerStep("adam", 1e-3, weight_decay=0.01)
        p6, p16 = self._flat_params(6), self._flat_params(16)
        packed6, packed16 = (
            self._wrapper_eqns(pk, p6), self._wrapper_eqns(pk, p16),
        )
        tree6, tree16 = (
            self._wrapper_eqns(mp, p6), self._wrapper_eqns(mp, p16),
        )
        assert tree16 > tree6
        assert packed16 < tree16
        assert (packed16 - packed6) < (tree16 - tree6)

    def test_packed_scales_with_dtype_groups(self):
        two_groups = dict(
            self._flat_params(3),
            **{
                f"q{i}": v.astype(jnp.bfloat16)
                for i, v in enumerate(self._flat_params(3).values())
            },
        )
        # a second dtype group adds phase equations; leaves within a
        # group don't (test_update_phase_constant_in_leaves)
        assert self._phase_eqns(two_groups) > self._phase_eqns(
            self._flat_params(6)
        )
