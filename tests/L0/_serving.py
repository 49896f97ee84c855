"""What the tests of the declared-layer models served by the engine
share (`test_hybrid_serving.py`, `test_latent_serving.py`): drive an
engine to the end, seeded prompts, and a record of every logits array
the engine's programs sample from."""

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
