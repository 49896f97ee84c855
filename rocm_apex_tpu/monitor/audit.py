"""Static comms/FLOPs auditor: walk a jaxpr, report what a step MOVES.

The PR-3 collective-matmul work (arXiv 2305.06942) is only verifiable
by looking at the traced program: did the blocking `all_gather` really
become a `ppermute` ring, does a full-sequence activation still hide
between the sequence-parallel regions, how many bytes does one train
step put on the ICI? Until now those questions lived as ad-hoc
``"2,32,64]" in str(jax.make_jaxpr(...))`` greps scattered through
tests/L0. This module owns them:

    report = audit(step_fn, *example_args)     # jax.make_jaxpr, no compile
    report.count("ppermute")                   # collective counts
    report.bytes("all_gather")                 # payload bytes moved
    report.dot_flops                           # total dot_general FLOPs
    report.has_intermediate((2, 32, 64))       # shape-existence probe
    print(report.summary())

The walk recurses into every subjaxpr — pjit, `lax.scan` (inner counts
multiply by the trip count), cond (branches merge by MAX: one branch
executes), while (body counted once, flagged as a lower bound),
custom_jvp_call/custom_vjp_call, closed_call, remat, shard_map — so
counts reflect the whole program, not its top level (`_inner_jaxprs`
is the coverage contract, regression-pinned per primitive in
tests/L0/test_monitor.py).

Accounting conventions (kept deliberately simple and documented, not
clever):

* **counts** are primitive-execution counts after trip-count
  multiplication. `lax.psum_scatter` traces as the ``reduce_scatter``
  primitive; `count()` accepts either name.
* **bytes** per collective = the payload (sum of output-aval bytes),
  NOT wire bytes — ring/algorithm factors (the 2(n−1)/n of an
  all-reduce) depend on the implementation the compiler picks and are
  not knowable from the jaxpr. ``bytes_by_dtype()`` splits the same
  payload totals by element dtype, which is how an int8-quantized ring
  (ops/quantized_collectives.py) shows its byte win next to the fp32
  scale sidecar it ships alongside.
* **wire_bytes** per collective = a ring-algorithm traffic ESTIMATE:
  `ppermute` payloads are exact wire bytes by construction; tiled
  `all_gather` / `reduce_scatter` carry their ``axis_size`` n in the
  jaxpr params, so the per-link ring traffic is out·(n−1)/n resp.
  in·(n−1)/n. Reduction collectives without a size param (`psum`,
  `pmax`, ...) fall back to the payload — a floor, flagged as such.
  This is the apples-to-apples number for comparing a one-equation
  lax collective against the ppermute ring that replaces it (the
  payload convention would credit `psum_scatter` with 1/n of the
  bytes its wire actually moves).
* **scopes**: every collective is also attributed to the
  `jax.named_scope` stack enclosing its equation
  (``count_in_scope``), so a ring's 2m(n−1) ppermute hops are
  distinguishable from one-shot collectives in the same program.
* **dot_flops** = 2·|out|·k per `dot_general` (MAC-counting, the
  profiler's convention), trip-count multiplied.
* **shapes** is the set of every intermediate (equation-output) aval
  shape anywhere in the program — inputs and constants are NOT
  intermediates, so a probe for a forbidden materialization cannot be
  fooled by the operand that legitimately enters at a region boundary.
* **eqn_count** is the total number of primitive equations the program
  executes (trip-count multiplied like ``counts``; cond branches merge
  by MAX; a container equation counts itself plus its body). This is
  the fusion-granularity regression metric (arXiv 2301.13062): a
  tree_map'd optimizer update emits O(num_leaves) equations while the
  packed-buffer path emits O(dtype_groups) — asserting the count pins
  the program SHAPE, where wall-clock only samples it.
"""

import dataclasses
from typing import Any, Dict, FrozenSet, Optional, Tuple

import jax
import numpy as np
from jax.extend import core as jax_core

__all__ = ["AuditReport", "audit", "audit_jaxpr", "assert_no_intermediate"]

# collective primitives worth counting/sizing (cross-device traffic)
_COLLECTIVES = {
    "psum",
    "pmax",
    "pmin",
    "all_gather",
    "reduce_scatter",
    "ppermute",
    "all_to_all",
    "pgather",
}
# user-facing aliases -> primitive names
_ALIASES = {"psum_scatter": "reduce_scatter", "collective_permute": "ppermute"}


@dataclasses.dataclass(frozen=True)
class AuditReport:
    """What one traced program moves and multiplies.

    ``counts``/``bytes_moved`` key on primitive names (`_ALIASES`
    accepted through the accessors); ``shapes`` holds every
    intermediate aval shape. ``while_lower_bound`` marks that a
    `lax.while_loop` body was counted once — totals are then lower
    bounds, not exact."""

    counts: Dict[str, float]
    bytes_moved: Dict[str, float]
    dot_flops: float
    dot_count: float
    shapes: FrozenSet[Tuple[int, ...]]
    eqn_count: float = 0.0
    while_lower_bound: bool = False
    # (primitive, dtype-name) -> payload bytes of that element dtype
    dtype_bytes: Dict[Tuple[str, str], float] = dataclasses.field(
        default_factory=dict
    )
    # (named_scope path, primitive) -> execution count
    scope_counts: Dict[Tuple[str, str], float] = dataclasses.field(
        default_factory=dict
    )
    # primitive -> estimated per-link ring wire bytes (module docstring)
    wire_bytes_moved: Dict[str, float] = dataclasses.field(
        default_factory=dict
    )

    # -- accessors ------------------------------------------------------

    def count(self, name: str) -> int:
        name = _ALIASES.get(name, name)
        return int(self.counts.get(name, 0))

    def bytes(self, name: str) -> float:
        name = _ALIASES.get(name, name)
        return float(self.bytes_moved.get(name, 0.0))

    def bytes_by_dtype(self, name: str) -> Dict[str, float]:
        """Payload bytes of collective ``name`` split by element dtype,
        e.g. ``{"int8": 196608, "float32": 768}`` for a quantized ring
        and its fp32 scale sidecar."""
        name = _ALIASES.get(name, name)
        return {
            dt: float(b)
            for (p, dt), b in sorted(self.dtype_bytes.items())
            if p == name
        }

    def wire_bytes(self, name: str) -> float:
        """Estimated ring wire bytes for collective ``name`` (exact for
        ppermute, out·(n−1)/n / in·(n−1)/n for tiled gather/scatter,
        payload floor for size-less reductions)."""
        name = _ALIASES.get(name, name)
        return float(self.wire_bytes_moved.get(name, 0.0))

    def count_in_scope(self, scope: str, name: str) -> int:
        """Executions of collective ``name`` whose enclosing
        `jax.named_scope` path contains ``scope`` as a substring."""
        name = _ALIASES.get(name, name)
        return int(
            sum(
                v
                for (sc, p), v in self.scope_counts.items()
                if p == name and scope in sc
            )
        )

    @property
    def collective_count(self) -> int:
        return int(sum(self.counts.values()))

    @property
    def collective_bytes(self) -> float:
        return float(sum(self.bytes_moved.values()))

    @property
    def collective_wire_bytes(self) -> float:
        return float(sum(self.wire_bytes_moved.values()))

    def has_intermediate(self, shape) -> bool:
        """True iff some equation anywhere in the program OUTPUTS an
        array of exactly this shape."""
        return tuple(shape) in self.shapes

    def intermediates_matching(self, shape):
        """All intermediate shapes equal to ``shape`` up to leading
        batch dims (diagnostic helper)."""
        shape = tuple(shape)
        return sorted(
            s for s in self.shapes if s[-len(shape):] == shape and shape
        )

    def summary(self) -> str:
        """Human-readable table (the bench --audit report body)."""
        lines = [
            "collective            count        MB payload        MB wire"
        ]
        for name in sorted(self.counts):
            lines.append(
                f"{name:<20} {int(self.counts[name]):>6} "
                f"{self.bytes_moved.get(name, 0.0) / 1e6:>13.3f} "
                f"{self.wire_bytes_moved.get(name, 0.0) / 1e6:>13.3f}"
            )
            by_dt = self.bytes_by_dtype(name)
            if len(by_dt) > 1:
                for dt, b in by_dt.items():
                    lines.append(f"  .{dt:<17} {'':>6} {b / 1e6:>13.3f}")
        if not self.counts:
            lines.append("(none)")
        scoped = sorted(
            (sc, p, v) for (sc, p), v in self.scope_counts.items() if sc
        )
        if scoped:
            lines.append("by named_scope:")
            for sc, p, v in scoped:
                lines.append(f"  {sc:<30} {p:<16} x{int(v)}")
        lines.append(
            f"dot_general: {int(self.dot_count)} ops, "
            f"{self.dot_flops / 1e9:.3f} GFLOP"
            + (" (while-loop: lower bounds)" if self.while_lower_bound
               else "")
        )
        lines.append(f"equations: {int(self.eqn_count)}")
        return "\n".join(lines)


def _aval_bytes(aval) -> float:
    try:
        return float(np.prod(aval.shape, dtype=np.float64)) * np.dtype(
            aval.dtype
        ).itemsize
    except Exception:  # noqa: BLE001 - abstract token/opaque avals
        return 0.0


def _merge(dst: Dict[Any, float], src: Dict[Any, float], scale: float):
    for k, v in src.items():
        dst[k] = dst.get(k, 0.0) + v * scale


def _merge_max(dst: Dict[Any, float], src: Dict[Any, float]):
    for k, v in src.items():
        dst[k] = max(dst.get(k, 0.0), v)


def _eqn_scope(eqn) -> str:
    """The `jax.named_scope` path enclosing this equation, '' if none
    (or on jax versions without source_info name stacks)."""
    try:
        return str(eqn.source_info.name_stack)
    except Exception:  # noqa: BLE001 - defensive across jax versions
        return ""


def _scope_join(outer: str, inner: str) -> str:
    if outer and inner:
        return f"{outer}/{inner}"
    return outer or inner


def _prefix_scopes(
    src: Dict[Tuple[str, str], float], outer: str
) -> Dict[Tuple[str, str], float]:
    if not outer:
        return src
    return {(_scope_join(outer, sc), p): v for (sc, p), v in src.items()}


def _wire_estimate(name, eqn, payload: float) -> float:
    """Per-link ring wire-byte estimate (AuditReport docstring)."""
    if name == "ppermute":
        return payload
    n = eqn.params.get("axis_size")
    if n and n > 0:
        if name == "all_gather":
            return payload * (n - 1) / n
        if name == "reduce_scatter":
            in_bytes = sum(_aval_bytes(iv.aval) for iv in eqn.invars)
            return in_bytes * (n - 1) / n
    return payload


def _inner_jaxprs(params):
    """Every (Closed)Jaxpr hiding in an equation's params.

    This is the walker's coverage contract: any call-like primitive
    whose body rides in its params — pjit, scan/cond/while branches,
    custom_jvp_call / custom_vjp_call (``call_jaxpr`` + the rule
    thunks), `closed_call`, remat, shard_map — is found here, so rules
    and audits see primitives hidden under them. Containers recurse to
    any depth (cond carries a tuple of branches; some primitives stash
    jaxprs in dicts or nested tuples)."""
    yield from _jaxprs_in(list(params.values()))


def _jaxprs_in(value):
    if isinstance(value, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
        yield value
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _jaxprs_in(item)
    elif isinstance(value, dict):
        for item in value.values():
            yield from _jaxprs_in(item)


def _walk(jaxpr) -> AuditReport:
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    counts: Dict[str, float] = {}
    nbytes: Dict[str, float] = {}
    dtype_bytes: Dict[Tuple[str, str], float] = {}
    scope_counts: Dict[Tuple[str, str], float] = {}
    wire: Dict[str, float] = {}
    dot_flops = 0.0
    dot_count = 0.0
    eqns_total = 0.0
    shapes = set()
    lower_bound = False

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        eqns_total += 1.0  # the equation itself (containers add bodies below)
        for ov in eqn.outvars:
            aval = getattr(ov, "aval", None)
            if aval is not None and getattr(aval, "shape", None) is not None:
                shapes.add(tuple(aval.shape))

        if name in _COLLECTIVES:
            counts[name] = counts.get(name, 0.0) + 1.0
            payload = sum(_aval_bytes(ov.aval) for ov in eqn.outvars)
            nbytes[name] = nbytes.get(name, 0.0) + payload
            for ov in eqn.outvars:
                aval = getattr(ov, "aval", None)
                try:
                    dt = str(np.dtype(aval.dtype))
                except Exception:  # noqa: BLE001 - token/opaque avals
                    dt = "?"
                key = (name, dt)
                dtype_bytes[key] = dtype_bytes.get(key, 0.0) + _aval_bytes(
                    aval
                )
            sckey = (_eqn_scope(eqn), name)
            scope_counts[sckey] = scope_counts.get(sckey, 0.0) + 1.0
            wire[name] = wire.get(name, 0.0) + _wire_estimate(
                name, eqn, payload
            )
            continue
        if name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            k = float(np.prod([lhs.shape[d] for d in lc], dtype=np.float64))
            out_n = float(
                np.prod(eqn.outvars[0].aval.shape, dtype=np.float64)
            )
            dot_flops += 2.0 * out_n * max(k, 1.0)
            dot_count += 1.0
            continue

        inner = list(_inner_jaxprs(eqn.params))
        if not inner:
            continue
        outer_scope = _eqn_scope(eqn)
        if name == "cond":
            # one branch executes: merge branch audits by max
            b_counts: Dict[str, float] = {}
            b_bytes: Dict[str, float] = {}
            b_dtype: Dict[Tuple[str, str], float] = {}
            b_scopes: Dict[Tuple[str, str], float] = {}
            b_wire: Dict[str, float] = {}
            b_flops = b_dots = b_eqns = 0.0
            for br in inner:
                r = _walk(br)
                _merge_max(b_counts, r.counts)
                _merge_max(b_bytes, r.bytes_moved)
                _merge_max(b_dtype, r.dtype_bytes)
                _merge_max(
                    b_scopes, _prefix_scopes(r.scope_counts, outer_scope)
                )
                _merge_max(b_wire, r.wire_bytes_moved)
                b_flops = max(b_flops, r.dot_flops)
                b_dots = max(b_dots, r.dot_count)
                b_eqns = max(b_eqns, r.eqn_count)
                shapes |= r.shapes
                lower_bound |= r.while_lower_bound
            _merge(counts, b_counts, 1.0)
            _merge(nbytes, b_bytes, 1.0)
            _merge(dtype_bytes, b_dtype, 1.0)
            _merge(scope_counts, b_scopes, 1.0)
            _merge(wire, b_wire, 1.0)
            dot_flops += b_flops
            dot_count += b_dots
            eqns_total += b_eqns
            continue
        scale = 1.0
        if name == "scan":
            scale = float(eqn.params.get("length", 1))
        elif name == "while":
            # trip count is dynamic: count the body once, flag totals
            lower_bound = True
        for sub in inner:
            r = _walk(sub)
            _merge(counts, r.counts, scale)
            _merge(nbytes, r.bytes_moved, scale)
            _merge(dtype_bytes, r.dtype_bytes, scale)
            _merge(
                scope_counts,
                _prefix_scopes(r.scope_counts, outer_scope),
                scale,
            )
            _merge(wire, r.wire_bytes_moved, scale)
            dot_flops += r.dot_flops * scale
            dot_count += r.dot_count * scale
            eqns_total += r.eqn_count * scale
            shapes |= r.shapes
            lower_bound |= r.while_lower_bound

    return AuditReport(
        counts=counts,
        bytes_moved=nbytes,
        dot_flops=dot_flops,
        dot_count=dot_count,
        shapes=frozenset(shapes),
        eqn_count=eqns_total,
        while_lower_bound=lower_bound,
        dtype_bytes=dtype_bytes,
        scope_counts=scope_counts,
        wire_bytes_moved=wire,
    )


def audit_jaxpr(closed_jaxpr) -> AuditReport:
    """Audit an already-traced `ClosedJaxpr` (or raw `Jaxpr`)."""
    return _walk(closed_jaxpr)


def audit(fn, *args, **kwargs) -> AuditReport:
    """Trace ``fn(*args, **kwargs)`` with `jax.make_jaxpr` (abstract —
    nothing compiles or runs) and audit the result. ``fn`` must be the
    COMPLETE unit of interest: to audit a shard_map'd step, pass the
    wrapped function, not the body."""
    return _walk(jax.make_jaxpr(fn, **{})(*args, **kwargs))


def assert_no_intermediate(
    target, shape, *args, msg: Optional[str] = None
) -> AuditReport:
    """Assert no equation in the program outputs an array of ``shape``.

    ``target`` is a `ClosedJaxpr`/`AuditReport`, or a callable (then
    ``*args`` are its example arguments). Returns the report so
    callers can chain count assertions. The executable form of the
    PR-3 acceptance bar: no full ``(b, s, h)`` gathered activation
    between sequence-parallel regions."""
    if isinstance(target, AuditReport):
        report = target
    elif callable(target) and not isinstance(
        target, (jax_core.Jaxpr, jax_core.ClosedJaxpr)
    ):
        report = audit(target, *args)
    else:
        report = audit_jaxpr(target)
    if report.has_intermediate(shape):
        raise AssertionError(
            msg
            or f"forbidden intermediate of shape {tuple(shape)} found "
            "in the traced program"
        )
    return report
