"""What the tests of the declared-layer models served by the engine
share (`test_hybrid_serving.py`, `test_latent_serving.py`,
`test_windowed_serving.py`, `test_step_programs.py`): drive an engine to
the end, seeded prompts, a record of every logits array the engine's
programs sample from, and the three models at toy widths in float32."""

import functools
import importlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocm_apex_tpu.inference import programs as programs_mod

VOCAB = 257


def run(eng, prompts, max_new):
    for p in prompts:
        eng.add_request(p, max_new)
    out = {}
    while eng.has_work():
        for r in eng.step():
            out[r.request_id] = r
    return [out[i] for i in sorted(out)]


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=n).tolist() for n in lengths]


@pytest.fixture()
def recorded(monkeypatch):
    """Every logits array the engine's programs sample from."""
    rows = []

    def recording_sample(rng, logits, **kw):
        jax.debug.callback(
            lambda x: rows.extend(np.asarray(x, np.float32)), logits)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(programs_mod, "sample", recording_sample)
    return rows


#: kind -> (benchmark family, its configuration, the model's module and
#: class, what the toy's configuration changes, engine geometry)
SERVED = {
    "hybrid": (
        "granite_hybrid", "granite-4.0-h-small", "hybrid", "HybridModel",
        # multipliers that let the layers, not the token's own embedding,
        # decide the next token
        dict(embedding_multiplier=1.0, residual_multiplier=1.5,
             logits_scaling=1.0),
        dict(capacity=64, page_size=4, num_pages=48)),
    "latent": (
        "longcat_flash", "longcat-flash-omni", "latent", "LatentModel", {},
        dict(capacity=64, page_size=4, num_pages=48)),
    "windowed": (
        "smallthinker", "smallthinker-21b-a3b", "windowed", "WindowedModel",
        {}, dict(capacity=128, page_size=8, num_pages=(48, 48))),
}


@functools.lru_cache(maxsize=None)
def served_toy(kind, two_applies=False):
    """``(model, params, engine geometry)`` of one of the three served
    models at its benchmark configuration shrunk, float32 throughout.
    ``two_applies``: the same model under a class that does NOT declare
    the mixed tick of one apply, so that the engine's programs take the
    body every other model has (the chunk applied, then the grid)."""
    from benchmarks.harness import rehearsal

    family, name, module, cls, changes, geometry = SERVED[kind]
    fam = importlib.import_module(f"benchmarks.families.{family}")
    root = pathlib.Path(__file__).resolve().parents[2]
    config = dict(rehearsal.shrink(json.loads(
        (root / f"benchmarks/configs/{name}.json").read_text())), **changes)
    cls = getattr(
        importlib.import_module(f"rocm_apex_tpu.models.{module}"), cls)
    if two_applies:
        cls = type(f"{cls.__name__}InTwoApplies", (cls,), dict(
            mixed_in_one_pass=False))
    model = cls(fam.model_config(
        config, params_dtype=jnp.float32, dtype=jnp.float32))
    return model, fam.make_params(config, 5, jnp.float32), geometry


def served_engine(kind, two_applies=False, slots=3, budget=16, **more):
    from rocm_apex_tpu.inference import InferenceEngine, SamplingParams

    model, params, geometry = served_toy(kind, two_applies)
    return InferenceEngine(
        model, params, num_slots=slots,
        sampling=SamplingParams(temperature=0.0),
        prefill_token_budget=budget, paged=True, **geometry, **more)
