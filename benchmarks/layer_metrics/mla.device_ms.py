"""Device time a traced tick of the operations named after the `mla_*`
scopes: the decode grid's paged latent kernel, and in a mixed tick the
chunk's segment kernel and its read of cached prefixes (`_latent.py` says
what a scope's name reaches and what it does not: the projections around
the kernels are fusions)."""

from benchmarks.layer_metrics import _hybrid


def read(context):
    return _hybrid.device_ms_per_tick(context, "mla_")
