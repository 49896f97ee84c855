"""Graph-contract linter: declarative rules over traced jaxprs.

`audit.py` answers "what does this program move?"; this module answers
"is that ALLOWED?". Each policy invariant the repo has accumulated —
bf16-only compute inside O4/O5 regions, no materialized
``(rows, vocab)`` logits, 16-ppermute SP/CM rings, collective-free
found_inf skip branches, donated step buffers — used to live as a
one-off jaxpr grep in some test, silently rotting everywhere else.
Here each becomes a **rule object** checked against a **subject** (one
traced program plus its argument/donation metadata):

    subject = LintSubject.from_fn("train_step", step, state, batch,
                                  donate_argnums=(0,))
    report = run_lint(subject, [
        PrecisionPolicy(compute_dtype="bfloat16",
                        allow_fp32_scopes=("optimizer",)),
        NoMaterialization(forbidden_shapes=((512, 50304),)),
        CollectiveContract(expect={"ppermute": 16},
                           forbid=("all_gather",)),
        DonationContract(min_bytes=1 << 20),
        TraceStability(),
    ])
    report.raise_if_failed()

Rules are plain frozen dataclasses — a contract is DATA, so
`tools/graphlint.py` can keep a registry of named configs and diff
their fingerprints against a checked-in manifest (CI gate). Every
`Violation` names the rule, the enclosing `jax.named_scope`, and the
offending shape/dtype, so a red lint is actionable without re-tracing.

Tracing is abstract (`jax.make_jaxpr` / `jax.jit(...).trace`): nothing
compiles or runs, so linting a config costs milliseconds. Donation
metadata comes either from ``donate_argnums`` handed to
:meth:`LintSubject.from_fn` or, authoritatively, from a jitted
function's lowered ``args_info`` via :meth:`LintSubject.from_jit`.

The five shipped rule classes:

* :class:`PrecisionPolicy` — dot_general operand dtypes must conform
  to the amp compute dtype (fp32 dots outside an allowlist of scopes
  flag an O4/O5 leak); any fp64 anywhere is an error; optionally bf16
  dots must carry an fp32 accumulator.
* :class:`NoMaterialization` — per-config shape budgets generalizing
  `assert_no_intermediate`: forbidden exact shapes (full logits, full
  ``(b, s, h)`` gathers in SP regions) and an optional hard byte cap
  on any single intermediate.
* :class:`CollectiveContract` — exact collective counts (optionally
  per named scope), forbidden collectives, wire-byte caps, and
  `lax.cond` skip-branch proofs (the cheap branch of every
  collective-bearing cond must itself be collective-free).
* :class:`DonationContract` — large resident buffers (packed optimizer
  buffers, KV pools) must be donated into their step functions;
  an un-donated buffer over the threshold means doubled peak memory.
* :class:`TraceStability` — weak-type invars (python scalars promoted
  at the jit boundary) and unhashable static args, both classic
  silent-retrace generators.
"""

import dataclasses
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend import core as jax_core

from rocm_apex_tpu.monitor.audit import (
    _ALIASES,
    _COLLECTIVES,
    _aval_bytes,
    _eqn_scope,
    _inner_jaxprs,
    _scope_join,
    AuditReport,
    audit_jaxpr,
)

__all__ = [
    "Violation",
    "LintReport",
    "LintSubject",
    "run_lint",
    "walk_eqns",
    "PrecisionPolicy",
    "NoMaterialization",
    "CollectiveContract",
    "DonationContract",
    "TraceStability",
]


# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Violation:
    """One rule failure, carrying everything an actionable message
    needs: the rule name, the enclosing named_scope path, and the
    offending shape/dtype when there is one."""

    rule: str
    message: str
    scope: str = ""
    shape: Optional[Tuple[int, ...]] = None
    dtype: str = ""

    def __str__(self) -> str:
        extra = []
        if self.scope:
            extra.append(f"scope={self.scope}")
        if self.shape is not None:
            extra.append(f"shape={tuple(self.shape)}")
        if self.dtype:
            extra.append(f"dtype={self.dtype}")
        tail = f" [{', '.join(extra)}]" if extra else ""
        return f"[{self.rule}] {self.message}{tail}"


@dataclasses.dataclass(frozen=True)
class LintReport:
    """All violations from running a rule set against one subject."""

    subject: str
    violations: Tuple[Violation, ...]

    @property
    def ok(self) -> bool:
        return not self.violations

    def by_rule(self, rule: str) -> Tuple[Violation, ...]:
        return tuple(v for v in self.violations if v.rule == rule)

    def summary(self) -> str:
        if self.ok:
            return f"lint[{self.subject}]: OK"
        lines = [
            f"lint[{self.subject}]: {len(self.violations)} violation(s)"
        ]
        lines += [f"  {v}" for v in self.violations]
        return "\n".join(lines)

    def raise_if_failed(self) -> "LintReport":
        if not self.ok:
            raise AssertionError(self.summary())
        return self


# ---------------------------------------------------------------------------
# subjects: one traced program + its argument/donation metadata
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ArgRecord:
    """One flattened argument leaf of the traced function."""

    path: str
    shape: Tuple[int, ...]
    dtype: str
    nbytes: float
    donated: bool
    weak: bool = False


def _leaf_meta(leaf) -> Tuple[Tuple[int, ...], str, float]:
    aval = getattr(leaf, "aval", leaf)
    shape = tuple(getattr(aval, "shape", ()) or ())
    try:
        dt = str(np.dtype(aval.dtype))
        nbytes = float(np.prod(shape, dtype=np.float64)) * np.dtype(
            aval.dtype
        ).itemsize
    except Exception:  # noqa: BLE001 - python scalars, opaque leaves
        dt = type(leaf).__name__
        nbytes = 0.0
    return shape, dt, nbytes


@dataclasses.dataclass(frozen=True)
class LintSubject:
    """A traced program plus the metadata rules need.

    ``closed_jaxpr`` is the whole program; ``args`` (may be None when
    the subject was built from a bare jaxpr) is the flat list of
    argument-leaf records with donation flags; ``static_args`` is a
    sequence of ``(label, value)`` pairs the caller marks static at
    the jit boundary (checked for hashability by
    :class:`TraceStability`)."""

    name: str
    closed_jaxpr: Any
    args: Optional[Tuple[ArgRecord, ...]] = None
    static_args: Tuple[Tuple[str, Any], ...] = ()

    @property
    def report(self) -> AuditReport:
        cached = _REPORT_CACHE.get(id(self.closed_jaxpr))
        if cached is None:
            cached = audit_jaxpr(self.closed_jaxpr)
            _REPORT_CACHE[id(self.closed_jaxpr)] = cached
        return cached

    # -- constructors ---------------------------------------------------

    @classmethod
    def from_jaxpr(cls, name: str, closed_jaxpr) -> "LintSubject":
        """Bare jaxpr: structural rules only (no donation metadata)."""
        return cls(name=name, closed_jaxpr=closed_jaxpr)

    @classmethod
    def from_fn(
        cls,
        name: str,
        fn: Callable,
        *args,
        donate_argnums: Sequence[int] = (),
        static_args: Sequence[Tuple[str, Any]] = (),
    ) -> "LintSubject":
        """Trace ``fn(*args)`` abstractly (`jax.make_jaxpr`, nothing
        compiles) and record per-leaf donation from ``donate_argnums``
        — the declared donation a jit of ``fn`` WOULD get."""
        closed = jax.make_jaxpr(fn)(*args)
        donate = set(donate_argnums)
        records: List[ArgRecord] = []
        for i, a in enumerate(args):
            for path, leaf in jax.tree_util.tree_flatten_with_path(a)[0]:
                shape, dt, nbytes = _leaf_meta(leaf)
                records.append(
                    ArgRecord(
                        path=f"args[{i}]{jax.tree_util.keystr(path)}",
                        shape=shape,
                        dtype=dt,
                        nbytes=nbytes,
                        donated=i in donate,
                    )
                )
        records = _mark_weak(records, closed)
        return cls(
            name=name,
            closed_jaxpr=closed,
            args=tuple(records),
            static_args=tuple(static_args),
        )

    @classmethod
    def from_jit(
        cls,
        name: str,
        jitted,
        *args,
        static_args: Sequence[Tuple[str, Any]] = (),
        **kwargs,
    ) -> "LintSubject":
        """Trace an already-jitted function and take donation flags
        from its lowered ``args_info`` — the AUTHORITATIVE record of
        what the executable will actually consume."""
        traced = jitted.trace(*args, **kwargs)
        closed = traced.jaxpr
        records: List[ArgRecord] = []
        flat = jax.tree_util.tree_flatten_with_path(
            traced.lower().args_info
        )[0]
        for path, info in flat:
            shape, dt, nbytes = _leaf_meta(info)
            records.append(
                ArgRecord(
                    path=f"args{jax.tree_util.keystr(path)}",
                    shape=shape,
                    dtype=dt,
                    nbytes=nbytes,
                    donated=bool(getattr(info, "donated", False)),
                )
            )
        records = _mark_weak(records, closed)
        return cls(
            name=name,
            closed_jaxpr=closed,
            args=tuple(records),
            static_args=tuple(static_args),
        )


# AuditReports are pure functions of the jaxpr; keyed by id so repeated
# rule runs over one subject audit once.
_REPORT_CACHE: Dict[int, AuditReport] = {}


def _mark_weak(records: List[ArgRecord], closed) -> List[ArgRecord]:
    """Invars align 1:1 with the flattened argument leaves; copy their
    weak_type flags onto the records (defensive on length mismatch)."""
    invars = closed.jaxpr.invars
    if len(invars) != len(records):
        return records
    return [
        dataclasses.replace(
            rec, weak=bool(getattr(iv.aval, "weak_type", False))
        )
        for rec, iv in zip(records, invars)
    ]


# ---------------------------------------------------------------------------
# the shared walker: every equation anywhere in the program, with scope
# ---------------------------------------------------------------------------


def walk_eqns(jaxpr, _outer: str = ""):
    """Yield ``(eqn, scope_path)`` for every primitive equation
    anywhere in the program — pjit/scan/cond/while/custom_*/remat/
    shard_map/closed_call bodies included (via the same param scan the
    auditor uses). BOTH cond branches are yielded: a lint must see the
    branch that executes on the other predicate value too."""
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        scope = _scope_join(_outer, _eqn_scope(eqn))
        yield eqn, scope
        for sub in _inner_jaxprs(eqn.params):
            yield from walk_eqns(sub, scope)


def _iter_conds(jaxpr, _outer: str = ""):
    """Yield ``(cond_eqn, scope, branches)`` for every `lax.cond`
    anywhere in the program (branches as ClosedJaxprs)."""
    for eqn, scope in walk_eqns(jaxpr, _outer):
        if eqn.primitive.name == "cond":
            yield eqn, scope, tuple(_inner_jaxprs(eqn.params))


def _canon(name: str) -> str:
    return _ALIASES.get(name, name)


def _np_dtype(dt) -> Optional[np.dtype]:
    """`np.dtype` or None for extended dtypes (PRNG keys, tokens)."""
    try:
        return np.dtype(dt)
    except TypeError:
        return None


# ---------------------------------------------------------------------------
# rule 1: precision policy
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """dot_general/reduction dtypes must conform to the amp opt-level.

    ``compute_dtype`` is the policy dtype of the checked region
    ("bfloat16" for the O4/O5 cast lists, "float32" for O0). When the
    policy is a low-precision dtype, any dot_general contracting two
    fp32 operands OUTSIDE ``allow_fp32_scopes`` (substring match on
    the named_scope path) is a leak — fp32 math the cast list was
    supposed to demote. fp64 outputs are flagged anywhere regardless
    of scope (``forbid_fp64``); no TPU path wants them. With
    ``require_f32_accum``, low-precision dots must accumulate in fp32
    (fp32 output / preferred_element_type), the matmul-accumulator
    half of the apex O2 recipe."""

    compute_dtype: str = "bfloat16"
    allow_fp32_scopes: Tuple[str, ...] = ()
    forbid_fp64: bool = True
    require_f32_accum: bool = False

    name = "precision-policy"

    def check(self, subject: LintSubject) -> List[Violation]:
        out: List[Violation] = []
        low_precision = self.compute_dtype in ("bfloat16", "float16")
        for eqn, scope in walk_eqns(subject.closed_jaxpr):
            if self.forbid_fp64:
                for ov in eqn.outvars:
                    aval = getattr(ov, "aval", None)
                    dt = _np_dtype(getattr(aval, "dtype", None))
                    if dt is not None and dt == np.float64:
                        out.append(
                            Violation(
                                rule=self.name,
                                message=(
                                    f"fp64 output from `{eqn.primitive.name}`"
                                    " — double precision never belongs in"
                                    " an accelerator step"
                                ),
                                scope=scope,
                                shape=tuple(aval.shape),
                                dtype="float64",
                            )
                        )
            if eqn.primitive.name != "dot_general":
                continue
            lhs, rhs = (iv.aval for iv in eqn.invars[:2])
            odt = _np_dtype(eqn.outvars[0].aval.dtype)
            ldt = _np_dtype(lhs.dtype)
            rdt = _np_dtype(rhs.dtype)
            if odt is None or ldt is None or rdt is None:
                continue
            # jnp's lattice, not np's: bf16/fp8 are kind-'V' to numpy
            if not jax.numpy.issubdtype(odt, jax.numpy.floating):
                continue  # integer/quantized dots are out of scope
            opd = {str(ldt), str(rdt)}
            if (
                low_precision
                and opd == {"float32"}
                and not any(s in scope for s in self.allow_fp32_scopes)
            ):
                out.append(
                    Violation(
                        rule=self.name,
                        message=(
                            "fp32 dot_general inside a "
                            f"{self.compute_dtype} region — cast-list "
                            "leak (allow via allow_fp32_scopes if this "
                            "is policy)"
                        ),
                        scope=scope,
                        shape=tuple(eqn.outvars[0].aval.shape),
                        dtype="float32",
                    )
                )
            if (
                self.require_f32_accum
                and opd == {self.compute_dtype}
                and str(odt) == self.compute_dtype
            ):
                out.append(
                    Violation(
                        rule=self.name,
                        message=(
                            f"{self.compute_dtype} dot_general without an "
                            "fp32 accumulator (preferred_element_type)"
                        ),
                        scope=scope,
                        shape=tuple(eqn.outvars[0].aval.shape),
                        dtype=str(odt),
                    )
                )
        return out


# ---------------------------------------------------------------------------
# rule 2: materialization budget
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NoMaterialization:
    """Forbidden intermediate shapes + an optional per-buffer byte cap.

    The generalization of `assert_no_intermediate`: each shape in
    ``forbidden_shapes`` must not be OUTPUT by any equation anywhere
    in the program (arguments and constants don't count — a region
    boundary may legitimately consume a full tensor it never
    rebuilds). ``max_intermediate_bytes`` additionally caps any single
    intermediate buffer, catching materializations whose exact shape
    the contract author didn't predict."""

    forbidden_shapes: Tuple[Tuple[int, ...], ...] = ()
    max_intermediate_bytes: Optional[float] = None

    name = "no-materialization"

    def check(self, subject: LintSubject) -> List[Violation]:
        out: List[Violation] = []
        report = subject.report
        for shape in self.forbidden_shapes:
            if report.has_intermediate(shape):
                out.append(
                    Violation(
                        rule=self.name,
                        message=(
                            "forbidden intermediate materialized (budget "
                            "says this buffer must never exist whole)"
                        ),
                        shape=tuple(shape),
                    )
                )
        if self.max_intermediate_bytes is not None:
            seen = set()
            for eqn, scope in walk_eqns(subject.closed_jaxpr):
                for ov in eqn.outvars:
                    aval = getattr(ov, "aval", None)
                    if aval is None:
                        continue
                    nbytes = _aval_bytes(aval)
                    key = (tuple(getattr(aval, "shape", ()) or ()),
                           str(getattr(aval, "dtype", "")))
                    if nbytes > self.max_intermediate_bytes and key not in seen:
                        seen.add(key)
                        out.append(
                            Violation(
                                rule=self.name,
                                message=(
                                    f"intermediate of {nbytes / 1e6:.2f} MB "
                                    "exceeds the per-buffer budget "
                                    f"({self.max_intermediate_bytes / 1e6:.2f}"
                                    " MB)"
                                ),
                                scope=scope,
                                shape=key[0],
                                dtype=key[1],
                            )
                        )
        return out


# ---------------------------------------------------------------------------
# rule 3: collective contract
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CollectiveContract:
    """Exact collective counts, forbidden collectives, wire-byte caps,
    and skip-branch proofs.

    ``expect`` pins exact trip-multiplied execution counts (within
    ``scope`` when given — substring match on the named_scope path,
    the auditor's ``count_in_scope`` convention). ``forbid`` lists
    collectives that must not appear at all (the ZeRO int8 path is
    all_gather-free: everything rides ppermute rings).
    ``max_wire_bytes`` caps the ring wire-byte estimate per
    collective. With ``skip_branches_collective_free``, every
    `lax.cond` that runs collectives in its expensive branch must have
    a collective-free cheap branch — the found_inf skip contract: an
    overflowed step must not pay the gather. ``require_skip_cond``
    additionally demands at least one such guarded cond EXISTS (probe
    sanity: the contract fails loudly if the skip structure was
    optimized away entirely)."""

    expect: Mapping[str, float] = dataclasses.field(default_factory=dict)
    forbid: Tuple[str, ...] = ()
    scope: str = ""
    max_wire_bytes: Mapping[str, float] = dataclasses.field(
        default_factory=dict
    )
    skip_branches_collective_free: bool = False
    require_skip_cond: bool = False

    name = "collective-contract"

    def check(self, subject: LintSubject) -> List[Violation]:
        out: List[Violation] = []
        report = subject.report
        for prim, want in dict(self.expect).items():
            got = (
                report.count_in_scope(self.scope, prim)
                if self.scope
                else report.count(prim)
            )
            if got != int(want):
                where = f" in scope '{self.scope}'" if self.scope else ""
                out.append(
                    Violation(
                        rule=self.name,
                        message=(
                            f"expected exactly {int(want)} `{_canon(prim)}`"
                            f"{where}, traced program has {got}"
                        ),
                        scope=self.scope,
                        dtype=_canon(prim),
                    )
                )
        for prim in self.forbid:
            got = (
                report.count_in_scope(self.scope, prim)
                if self.scope
                else report.count(prim)
            )
            if got:
                out.append(
                    Violation(
                        rule=self.name,
                        message=(
                            f"forbidden collective `{_canon(prim)}` appears "
                            f"{got}x (contract says this path must not "
                            "use it)"
                        ),
                        scope=self.scope,
                        dtype=_canon(prim),
                    )
                )
        for prim, cap in dict(self.max_wire_bytes).items():
            got = report.wire_bytes(prim)
            if got > float(cap):
                out.append(
                    Violation(
                        rule=self.name,
                        message=(
                            f"`{_canon(prim)}` wire bytes {got:.0f} exceed "
                            f"the contract cap {float(cap):.0f}"
                        ),
                        dtype=_canon(prim),
                    )
                )
        if self.skip_branches_collective_free or self.require_skip_cond:
            out += self._check_skip_branches(subject)
        return out

    def _check_skip_branches(self, subject: LintSubject) -> List[Violation]:
        out: List[Violation] = []
        found_guarded = False
        for eqn, scope, branches in _iter_conds(subject.closed_jaxpr):
            per_branch = [
                audit_jaxpr(b).collective_count for b in branches
            ]
            if not per_branch or max(per_branch) == 0:
                continue  # collective-free cond: nothing to prove
            if min(per_branch) == 0:
                found_guarded = True
            elif self.skip_branches_collective_free:
                out.append(
                    Violation(
                        rule=self.name,
                        message=(
                            "cond runs collectives in EVERY branch "
                            f"(per-branch counts {per_branch}) — the skip "
                            "branch must be collective-free so a skipped "
                            "step pays no comm"
                        ),
                        scope=scope,
                    )
                )
        if self.require_skip_cond and not found_guarded:
            out.append(
                Violation(
                    rule=self.name,
                    message=(
                        "no cond with a collective-free skip branch found "
                        "— the found_inf guard structure is gone from the "
                        "traced program"
                    ),
                )
            )
        return out


# ---------------------------------------------------------------------------
# rule 4: donation / aliasing
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DonationContract:
    """Large resident buffers must be donated into the step function.

    Every argument leaf of at least ``min_bytes`` whose path matches
    no ``ignore`` pattern must carry the donated flag — an un-donated
    carry (optimizer state, KV pool) means the executable holds input
    AND output copies alive, doubling peak memory for the largest
    buffers in the program. ``require`` lists path substrings that
    must be donated regardless of size. A subject with no argument
    metadata fails loudly: donation cannot be verified from a bare
    jaxpr, and silently passing would defeat the gate."""

    min_bytes: float = float(1 << 20)
    ignore: Tuple[str, ...] = ()
    require: Tuple[str, ...] = ()

    name = "donation"

    def check(self, subject: LintSubject) -> List[Violation]:
        if subject.args is None:
            return [
                Violation(
                    rule=self.name,
                    message=(
                        "subject carries no argument/donation metadata — "
                        "build it with LintSubject.from_fn(..., "
                        "donate_argnums=...) or from_jit so donation is "
                        "checkable"
                    ),
                )
            ]
        out: List[Violation] = []
        for rec in subject.args:
            if any(pat in rec.path for pat in self.ignore):
                continue
            if rec.nbytes >= self.min_bytes and not rec.donated:
                out.append(
                    Violation(
                        rule=self.name,
                        message=(
                            f"resident buffer `{rec.path}` "
                            f"({rec.nbytes / 1e6:.2f} MB) is not donated — "
                            "peak memory holds it twice across the step"
                        ),
                        shape=rec.shape,
                        dtype=rec.dtype,
                    )
                )
        for pat in self.require:
            hits = [r for r in subject.args if pat in r.path]
            if not hits:
                out.append(
                    Violation(
                        rule=self.name,
                        message=(
                            f"required-donation pattern `{pat}` matches no "
                            "argument leaf"
                        ),
                    )
                )
            elif not all(r.donated for r in hits):
                bad = next(r for r in hits if not r.donated)
                out.append(
                    Violation(
                        rule=self.name,
                        message=(
                            f"buffer `{bad.path}` must be donated "
                            f"(matches required pattern `{pat}`)"
                        ),
                        shape=bad.shape,
                        dtype=bad.dtype,
                    )
                )
        return out


# ---------------------------------------------------------------------------
# rule 5: trace stability
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TraceStability:
    """Flag the two classic silent-retrace generators.

    Weak-typed invars mean a python scalar crossed the jit boundary as
    a traced argument: each DISTINCT value in a weak-vs-strong mix can
    shift promotion, and passing it static instead retraces per value
    — either way the fix is an explicit `jnp.asarray(x, dtype)` at the
    call site. Unhashable static args (lists, dicts, arrays) fail or
    degrade the jit cache outright; the subject's declared
    ``static_args`` are each checked for hashability."""

    forbid_weak_invars: bool = True

    name = "trace-stability"

    def check(self, subject: LintSubject) -> List[Violation]:
        out: List[Violation] = []
        if self.forbid_weak_invars and subject.args is not None:
            for rec in subject.args:
                if rec.weak:
                    out.append(
                        Violation(
                            rule=self.name,
                            message=(
                                f"weak-typed input `{rec.path}` — a python "
                                "scalar crossed the trace boundary; pass "
                                "jnp.asarray(value, dtype) to pin dtype "
                                "and promotion"
                            ),
                            shape=rec.shape,
                            dtype=rec.dtype,
                        )
                    )
        for label, value in subject.static_args:
            try:
                hash(value)
            except TypeError:
                out.append(
                    Violation(
                        rule=self.name,
                        message=(
                            f"static arg `{label}` is unhashable "
                            f"({type(value).__name__}) — every call misses "
                            "the jit cache and retraces"
                        ),
                        dtype=type(value).__name__,
                    )
                )
        return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_lint(subject: LintSubject, rules: Sequence[Any]) -> LintReport:
    """Check every rule against one subject; violations concatenate in
    rule order. Rules are any objects with ``.name`` and
    ``.check(subject) -> list[Violation]`` — the five shipped classes
    or project-local ones."""
    violations: List[Violation] = []
    for rule in rules:
        violations.extend(rule.check(subject))
    return LintReport(subject=subject.name, violations=tuple(violations))
