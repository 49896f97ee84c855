"""Seeded-violation mutation tests for the graph-contract linter
(monitor/lint.py) and its CI gate (tools/graphlint.py).

Each test plants exactly the regression a rule exists to catch — an
fp32 upcast inside a bf16 region, a dropped donation, a cond that pays
collectives on the skip branch, materialized full logits, manifest
drift — and asserts the lint FAILS with a message naming the rule and
the offending scope/shape/dtype. A linter is only as good as its red
path: the green path is already exercised by the suite's contract
tests and by `tools/graphlint.py --check` on the committed manifest.

Everything here is abstract tracing (make_jaxpr) — nothing compiles,
so the whole file costs trace time only.
"""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from rocm_apex_tpu import monitor
from rocm_apex_tpu.monitor import (
    CollectiveContract,
    DonationContract,
    LintSubject,
    NoMaterialization,
    PrecisionPolicy,
    TraceStability,
    run_lint,
)

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools import graphlint  # noqa: E402


def _mesh(n):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} simulated devices")
    return Mesh(np.array(devs[:n]), ("tensor",))


X32 = jnp.ones((8, 8), jnp.float32)
X16 = jnp.ones((8, 8), jnp.bfloat16)


def _lint(fn, rules, *args, **kw):
    return run_lint(LintSubject.from_fn("mutant", fn, *args, **kw), rules)


# ---------------------------------------------------------------------------
# precision-policy
# ---------------------------------------------------------------------------


class TestPrecisionPolicy:
    def test_fp32_upcast_in_bf16_region_caught(self):
        """The classic cast-list leak: someone 'fixes' numerics by
        upcasting a matmul to fp32 inside the O4 region."""

        def leaky(x):
            h = x @ x  # policy-conformant bf16 dot
            return (
                h.astype(jnp.float32) @ h.astype(jnp.float32).T
            )  # the leak

        report = _lint(leaky, [PrecisionPolicy("bfloat16")], X16)
        assert not report.ok
        (v,) = report.by_rule("precision-policy")
        msg = str(v)
        assert "fp32 dot_general" in msg and "bfloat16 region" in msg
        assert v.dtype == "float32" and v.shape == (8, 8)
        with pytest.raises(AssertionError, match="precision-policy"):
            report.raise_if_failed()

    def test_allowlisted_scope_passes(self):
        """The SAME fp32 dot under an allowlisted named_scope (the
        optimizer is policy-fp32 under O4) is not a violation."""

        def policied(x):
            h = x @ x
            with jax.named_scope("optimizer"):
                return h.astype(jnp.float32) @ h.astype(jnp.float32).T

        report = _lint(
            policied,
            [PrecisionPolicy("bfloat16", allow_fp32_scopes=("optimizer",))],
            X16,
        )
        report.raise_if_failed()

    def test_fp64_caught_anywhere(self):
        """fp64 sneaking in (an un-dtyped np scalar, a python float
        under x64) is flagged regardless of scope or policy dtype."""
        with jax.enable_x64(True):

            def f(x):
                return x.astype(jnp.float64) * 2.0

            subject = LintSubject.from_fn(
                "x64_mutant", f, jnp.ones((4,), jnp.float32)
            )
            report = run_lint(subject, [PrecisionPolicy("float32")])
        assert not report.ok
        assert any(
            v.dtype == "float64" and "fp64" in v.message
            for v in report.by_rule("precision-policy")
        )

    def test_missing_f32_accumulator_caught(self):
        rule = PrecisionPolicy("bfloat16", require_f32_accum=True)

        def no_accum(x):
            return jax.lax.dot(x, x)  # bf16 in, bf16 out

        def with_accum(x):
            return jax.lax.dot(
                x, x, preferred_element_type=jnp.float32
            )

        assert not _lint(no_accum, [rule], X16).ok
        _lint(with_accum, [rule], X16).raise_if_failed()


# ---------------------------------------------------------------------------
# no-materialization
# ---------------------------------------------------------------------------


class TestNoMaterialization:
    def test_materialized_logits_caught(self):
        """The naive head (x @ W^T then softmax-CE) materializes the
        (rows, vocab) logits the fused head exists to avoid — the rule
        flags the exact forbidden shape."""
        x = jnp.ones((12, 8), jnp.float32)
        w = jnp.ones((20, 8), jnp.float32)
        y = jnp.zeros((12,), jnp.int32)

        def naive_head(x, w):
            logits = x @ w.T  # (12, 20): the forbidden buffer
            return jnp.sum(
                jax.nn.logsumexp(logits, axis=-1)
                - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            )

        report = _lint(
            jax.grad(naive_head, (0, 1)),
            [NoMaterialization(forbidden_shapes=((12, 20),))],
            x, w,
        )
        assert not report.ok
        (v, *_) = report.by_rule("no-materialization")
        assert v.shape == (12, 20)
        assert "must never exist whole" in v.message

    def test_byte_cap_catches_unpredicted_shapes(self):
        def blowup(x):
            return jnp.sum(x[:, None, :] * x[None, :, :], axis=(0, 1))

        report = _lint(
            blowup,
            [NoMaterialization(max_intermediate_bytes=512.0)],
            jnp.ones((16, 16), jnp.float32),
        )
        assert not report.ok
        vs = report.by_rule("no-materialization")
        assert all("exceeds the per-buffer budget" in v.message for v in vs)
        assert any(
            v.shape == (16, 16, 16) and v.dtype == "float32" for v in vs
        )


# ---------------------------------------------------------------------------
# collective-contract
# ---------------------------------------------------------------------------


class TestCollectiveContract:
    def _shmapped(self, fn):
        mesh = _mesh(2)
        return shard_map(
            fn, mesh=mesh, in_specs=(P("tensor"),), out_specs=P("tensor"),
            check_vma=False,
        )

    def test_count_and_forbid_mutations_caught(self):
        """Dropping one ring hop (count drift) and reintroducing a
        blocking gather (forbidden primitive) both fail with counts in
        the message."""

        def one_hop(x):
            return jax.lax.ppermute(x, "tensor", [(0, 1), (1, 0)])

        report = _lint(
            self._shmapped(one_hop),
            [CollectiveContract(expect={"ppermute": 2})],
            X32,
        )
        assert not report.ok
        (v,) = report.by_rule("collective-contract")
        assert "expected exactly 2 `ppermute`" in v.message
        assert "has 1" in v.message

        def gathers(x):
            return jax.lax.all_gather(x, "tensor", tiled=True)[:8]

        report = _lint(
            self._shmapped(gathers),
            [CollectiveContract(forbid=("all_gather",))],
            X32,
        )
        assert not report.ok
        assert "forbidden collective `all_gather`" in str(
            report.violations[0]
        )

    def test_skip_branch_collective_caught(self):
        """The found_inf-guard mutation: someone hoists a psum into
        BOTH cond branches, so a skipped (overflowed) step now pays
        comm. The rule names the per-branch counts."""

        def both_pay(x):
            return jax.lax.cond(
                x.sum() > 0,
                lambda v: jax.lax.psum(v * 2.0, "tensor"),
                lambda v: jax.lax.psum(v, "tensor"),
                x,
            )

        def guarded(x):
            return jax.lax.cond(
                x.sum() > 0,
                lambda v: jax.lax.psum(v * 2.0, "tensor"),
                lambda v: v,  # the skip branch: no comm
                x,
            )

        rule = CollectiveContract(
            skip_branches_collective_free=True, require_skip_cond=True
        )
        report = _lint(self._shmapped(both_pay), [rule], X32)
        assert not report.ok
        assert any(
            "EVERY branch" in v.message for v in report.violations
        )
        # and the guard-existence probe: a program with NO guarded cond
        # at all also fails (the skip structure was optimized away)
        assert any(
            "guard structure is gone" in v.message
            for v in report.violations
        )
        _lint(self._shmapped(guarded), [rule], X32).raise_if_failed()


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------


class TestDonationContract:
    def test_dropped_donation_caught(self):
        """Removing donate_argnums from a step jit is invisible to
        numerics and doubles peak memory — the rule names the exact
        argument path and size."""
        state = {"master": jnp.zeros((64, 64), jnp.float32)}

        def step(state, g):
            return {"master": state["master"] - g}, g.sum()

        g = jnp.ones((64, 64), jnp.float32)
        rule = DonationContract(min_bytes=1024.0, ignore=("args[1]",))
        ok = _lint(step, [rule], state, g, donate_argnums=(0,))
        ok.raise_if_failed()

        report = _lint(step, [rule], state, g)  # the mutation
        assert not report.ok
        (v,) = report.by_rule("donation")
        assert "args[0]['master']" in v.message
        assert "not donated" in v.message
        assert v.shape == (64, 64) and v.dtype == "float32"

    def test_require_pattern_and_bare_jaxpr_fail_loudly(self):
        def f(x):
            return x * 2.0

        report = _lint(
            f,
            [DonationContract(min_bytes=float("inf"), require=("args[0]",))],
            jnp.ones((4,), jnp.float32),
        )
        assert not report.ok
        assert "must be donated" in report.violations[0].message

        # a bare jaxpr has no donation metadata: the contract cannot
        # silently pass
        subject = LintSubject.from_jaxpr(
            "bare", jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
        )
        report = run_lint(subject, [DonationContract()])
        assert not report.ok
        assert "no argument/donation metadata" in report.violations[0].message


# ---------------------------------------------------------------------------
# trace-stability
# ---------------------------------------------------------------------------


class TestTraceStability:
    def test_weak_typed_scalar_caught(self):
        def f(x, lr):
            return x * lr

        report = _lint(f, [TraceStability()], X32, 0.1)
        assert not report.ok
        (v,) = report.by_rule("trace-stability")
        assert "weak-typed input" in v.message and "args[1]" in v.message

        _lint(
            f, [TraceStability()], X32, jnp.float32(0.1)
        ).raise_if_failed()

    def test_unhashable_static_arg_caught(self):
        subject = LintSubject.from_fn(
            "static_mutant",
            lambda x: x + 1.0,
            X32,
            static_args=(("shard_spec", [1, 2, 3]),),
        )
        report = run_lint(subject, [TraceStability()])
        assert not report.ok
        assert "unhashable" in report.violations[0].message


# ---------------------------------------------------------------------------
# tools/graphlint.py: manifest round-trip and drift
# ---------------------------------------------------------------------------


class TestGraphlintManifest:
    """In-process CLI tests against the CHEAPEST registry config
    (packed_opt: ~100 eqns, milliseconds to trace) so the red path of
    the CI gate is itself under test without re-tracing the fleet."""

    ONLY = ["--only", "packed_opt"]

    def test_committed_manifest_covers_registry_and_passes(self):
        doc = json.loads((REPO / "tools" / "graph_contracts.json").read_text())
        assert set(doc["configs"]) == set(graphlint.REGISTRY)
        # the gate itself, on the checked-in baseline
        assert graphlint.main(["--check", *self.ONLY]) == 0

    def test_drift_caught_with_field_level_message(self, tmp_path, capsys):
        """Perturb one fingerprint field in a copy of the committed
        manifest: --check must exit non-zero naming config and field."""
        doc = json.loads((REPO / "tools" / "graph_contracts.json").read_text())
        doc["configs"]["packed_opt"]["eqn_count"] += 7
        drifted = tmp_path / "drifted.json"
        drifted.write_text(json.dumps(doc))

        rc = graphlint.main(
            ["--check", *self.ONLY, "--manifest", str(drifted)]
        )
        err = capsys.readouterr().err
        assert rc == 1
        assert "manifest drift" in err
        assert "packed_opt.eqn_count" in err
        assert "--update" in err  # the re-baseline hint is printed

    def test_update_rebaselines_and_check_then_passes(self, tmp_path):
        fresh = tmp_path / "contracts.json"
        assert (
            graphlint.main(
                ["--update", *self.ONLY, "--manifest", str(fresh)]
            )
            == 0
        )
        doc = json.loads(fresh.read_text())
        assert "packed_opt" in doc["configs"]
        assert doc["configs"]["packed_opt"]["eqn_count"] > 0
        assert (
            graphlint.main(
                ["--check", *self.ONLY, "--manifest", str(fresh)]
            )
            == 0
        )

    def test_unknown_config_rejected(self, capsys):
        assert graphlint.main(["--check", "--only", "nope"]) == 2
        assert "unknown config" in capsys.readouterr().err
