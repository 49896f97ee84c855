"""Toy sizes for the CPU rehearsal: every configuration and mix file
carries its own under the key "rehearsal", merged over the real values.
A rehearsal exists to find wrong paths and arguments before chip time is
spent; it prints no result line."""

import copy


def shrink(data):
    """``data`` with its own "rehearsal" overrides merged in
    (dictionaries merge key by key; anything else is replaced). A block
    inside it that came from a file of its own (an engine's geometry)
    carries its own overrides and is shrunk by them."""
    out = copy.deepcopy(data)
    _merge(out, out.pop("rehearsal", {}))
    for key, value in out.items():
        if isinstance(value, dict) and "rehearsal" in value:
            out[key] = shrink(value)
    return out


def _merge(dst, src):
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge(dst[k], v)
        else:
            dst[k] = v
