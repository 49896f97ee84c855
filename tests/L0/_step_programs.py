"""The serving engines of the benchmark's cells at toy size, and a count
of what their two step programs trace: every equation of the jaxpr,
those of nested jaxprs (a `pjit`, a loop's body, a kernel's)
included."""

import jax
import numpy as np
from jax.extend import core as jex_core

from benchmarks.harness import rehearsal
from benchmarks.harness.manifest import Manifest

CELLS = ("gpt1p3b-serve-chat", "granite4hs-serve-chat",
         "longcat-serve-agent-sat", "smallthinker-serve-longmix-sat")


def toy_engine(cell_name, seed=7):
    manifest = Manifest()
    cell = manifest.cell(cell_name)
    config = rehearsal.shrink(cell["config"])
    mix = rehearsal.shrink(cell["mix"])
    return manifest.family(config).serve_setup(config, mix, seed)


def _nested(value):
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _nested(v)


def every_equation(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in _nested(value):
                yield from every_equation(inner)


def count_equations(jaxpr):
    return sum(1 for _ in every_equation(jaxpr))


def step_program_jaxprs(engine):
    """(`_mixed`, `_decode`) as the engine would trace them for a tick."""
    programs = engine.programs
    slots, budget = engine.num_slots, engine.prefill_token_budget
    by_name = dict(
        params=engine.params, cache=engine.cache, key=engine._rng,
        chunk_tokens=np.zeros((budget,), np.int32),
        chunk_slots=np.full((budget,), slots, np.int32),
        chunk_pos=np.zeros((budget,), np.int32),
        lengths_before=np.zeros((slots,), np.int32),
        lengths_after=np.zeros((slots,), np.int32),
        completion_idx=np.full((slots,), -1, np.int32),
        dec_tokens=np.zeros((slots,), np.int32),
        dec_active=np.zeros((slots,), bool),
        chunk_poison=np.zeros((budget,), np.float32),
        dec_poison=np.zeros((slots,), np.float32),
        tokens=np.zeros((slots,), np.int32),
        active=np.zeros((slots,), bool),
        poison=np.zeros((slots,), np.float32),
    )
    out = []
    for fn, names in ((programs.mixed_fn, programs.mixed_operands),
                      (programs.decode_fn, programs.decode_operands)):
        out.append(jax.make_jaxpr(fn)(*(by_name[n] for n in names)).jaxpr)
    return tuple(out)


def step_program_counts(engine):
    """(equations of `_mixed`, equations of `_decode`)."""
    return tuple(count_equations(j) for j in step_program_jaxprs(engine))
