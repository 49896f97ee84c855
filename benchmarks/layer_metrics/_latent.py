"""What the readers of the latent-attention model's metrics share: the
operations and bytes its decode kernel needs, worked out from the tick's
counters. These are the benchmark's own counts; the program reports only
what it counted (`engine.tick`'s `latent_rows_read`, `decodes`,
`moe_zero_assignments`).

The trace keeps a `jax.named_scope` only in the instruction names of the
Mosaic kernels traced under it (`_hybrid.py`): `mla_decode` is the
decode grid's paged latent kernel (`ops/mla.py::mla_decode_paged`),
`mla_chunk` the packed chunk's segment kernel over its own expanded rows,
`mla_chunk_prefix` the chunk's read of its rows' cached prefixes (the
same kernel as the decode grid's, a chunk row standing where a slot
does). `mla_proj`, `dense_mlp` and `moe_zero` hold no kernel and read as
nothing.
"""


def mla_decode_counts(rows_read, row_blocks, heads, rank, rope, itemsize=2):
    """(flops, bytes) of the absorbed latent read for ``rows_read``
    cached positions (summed over live rows and attention blocks) by
    ``row_blocks`` (live row, block) queries. Per position read: every
    head's score over rank + rope values and its weighted sum over rank
    values, 2 operations a multiply-add; the row itself once, at the
    published rank + rope values (the pool pads a row to whole 128-lane
    tiles: bytes the mathematics does not need, so they are not
    credited). Per query: the heads' absorbed queries in, their weighted
    latents out."""
    flops = rows_read * 2.0 * heads * ((rank + rope) + rank)
    nbytes = (
        rows_read * (rank + rope) * itemsize
        + row_blocks * heads * ((rank + rope) + rank) * itemsize)
    return flops, nbytes


def attention_blocks(context):
    """Latent-attention blocks of the configuration (two a layer)."""
    fam = context["family"]
    return fam.BLOCKS * fam.sizes(context["config"])["layers"]
