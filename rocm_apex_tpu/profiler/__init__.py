"""TPU-native observability (the pyprof replacement).

The reference's pyprof (reference: apex/pyprof/, deprecated in-tree)
monkey-patches torch ops to emit NVTX ranges (nvtx/nvmarker.py:1-50),
parses nvprof SQLite dumps (parse/), and maps kernels back to ops with
FLOP/byte accounting (prof/). The TPU equivalents:

* `annotate(name, **payload)` — an ``apex/<name>`` trace scope
  (`monitor.trace.phase`) carrying the op's shape/dtype payload as
  annotation metadata (the NVTX marker analogue);
* `annotate_function(fn)` — decorator form (nvmarker wraps functions);
* `trace(log_dir)` — capture context manager over `jax.profiler.trace`.

Reading a capture (the parse/ + prof/ analogue) lives with the
benchmark: `benchmarks/trace_summary.py` prints busy and idle time, the
top operations and the labelled idle gaps of an `.xplane.pb`, over
`benchmarks/harness/xplane.py` and `program_trace.py`.
"""

import functools
from typing import Optional

import jax

from rocm_apex_tpu.monitor.trace import phase

__all__ = ["annotate", "annotate_function", "trace"]


def annotate(name: str, **payload):
    """Named trace scope; the payload (shapes/dtypes/args) rides as the
    annotation's metadata like the reference's marker payload
    (reference: nvmarker.py traceMarker dict); a value that is no
    number rides as its string, which must hold no comma."""
    return phase(name, **payload)


def annotate_function(fn=None, *, name: Optional[str] = None):
    """Decorator: run `fn` inside a named scope with arg shape/dtype
    payload (the nvmarker function-wrap analogue)."""
    if fn is None:
        return functools.partial(annotate_function, name=name)

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        # space-joined: the profiler's metadata encoding splits on commas
        shapes = " ".join(
            f"{getattr(a, 'dtype', type(a).__name__)}"
            f"[{'x'.join(map(str, getattr(a, 'shape', ())))}]"
            for a in args
        )
        with annotate(name or fn.__qualname__, args=shapes):
            return fn(*args, **kwargs)

    return wrapped


class trace:
    """`with profiler.trace('/tmp/tb'):` capture context
    (wraps jax.profiler.trace so the import point is this package)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._cm = None

    def __enter__(self):
        self._cm = jax.profiler.trace(self.log_dir)
        return self._cm.__enter__()

    def __exit__(self, *exc):
        return self._cm.__exit__(*exc)
