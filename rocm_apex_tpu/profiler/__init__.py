"""TPU-native observability (the pyprof replacement).

The reference's pyprof (reference: apex/pyprof/, deprecated in-tree)
monkey-patches torch ops to emit NVTX ranges (nvtx/nvmarker.py:1-50),
parses nvprof SQLite dumps (parse/), and maps kernels back to ops with
FLOP/byte accounting (prof/). The TPU equivalents:

* `annotate(name, **payload)` — `jax.profiler.TraceAnnotation` scopes
  carrying the op name + shape/dtype payload (the NVTX marker analogue);
* `annotate_function(fn)` — decorator form (nvmarker wraps functions);
* `trace(log_dir)` — capture context manager over `jax.profiler.trace`;
* `op_stats(log_dir)` — per-op device-time aggregation from the
  captured trace (the parse/ + prof/ analogue, reading XLA's own op
  breakdown instead of nvprof databases).
"""

import collections
import functools
import glob
import gzip
import json
import re
from typing import Any, Dict, List, Optional

import jax

from rocm_apex_tpu.monitor.flops import UnknownDeviceError, chip_peaks

__all__ = ["annotate", "annotate_function", "trace", "op_stats", "OpStat"]


def annotate(name: str, **payload):
    """Named trace scope; payload (shapes/dtypes/args) is folded into
    the annotation string like the reference's marker payload
    (reference: nvmarker.py traceMarker dict)."""
    if payload:
        name = f"{name}|{json.dumps(payload, default=str, sort_keys=True)}"
    return jax.profiler.TraceAnnotation(name)


def annotate_function(fn=None, *, name: Optional[str] = None):
    """Decorator: run `fn` inside a named scope with arg shape/dtype
    payload (the nvmarker function-wrap analogue)."""
    if fn is None:
        return functools.partial(annotate_function, name=name)

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        shapes = [
            f"{getattr(a, 'dtype', type(a).__name__)}{list(getattr(a, 'shape', []))}"
            for a in args
        ]
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


class OpStat(
    collections.namedtuple(
        "OpStat",
        [
            "name", "total_ms", "count", "category",
            # pyprof-style accounting (estimates from HLO shapes):
            "flops",        # total FLOPs attributed to this op row
            "bytes",        # total HBM bytes moved (operands + outputs)
            "tflops_sec",   # achieved TFLOP/s over the row's device time
            "gb_sec",       # achieved GB/s over the row's device time
            "pct_peak",     # roofline % of peak: max(flops-, bytes-bound);
                            # 0.0 when device_kind is not in
                            # monitor.flops.CHIP_PEAKS
                            # (no made-up placeholder peaks)
        ],
    )
):
    __slots__ = ()


_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\]")


def _dtype_bytes(dt: str):
    if dt.startswith("f8"):
        return 1
    return _DTYPE_BYTES.get(dt)


def _split_result(long_name: str):
    """(result_text, rest_text) for an HLO line.

    ``%f = bf16[...]{...} fusion(...)`` → result token before the
    opcode; tuple results ``= (t1, t2) fusion(...)`` need a balanced
    paren scan because layouts contain parens (``{1,0:T(8,128)}``).
    """
    eq = long_name.find("= ")
    if eq < 0:
        return "", long_name
    body = long_name[eq + 2 :]
    if body.startswith("("):
        depth = 0
        for i, ch in enumerate(body):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return body[: i + 1], body[i + 1 :]
        return body, ""
    sp = body.find(" ")
    if sp < 0:
        return body, ""
    return body[:sp], body[sp:]


def _parse_shapes(text: str):
    """[(dtype_bytes, element_count, dims), ...] for one HLO fragment."""
    out = []
    for dt, dims_s in _SHAPE_RE.findall(text):
        size = _dtype_bytes(dt)
        if size is None:
            continue
        dims = tuple(int(d) for d in dims_s.split(",") if d)
        n = 1
        for d in dims:
            n *= d
        out.append((size, n, dims))
    return out


def _matmul_flops(out_dims, a_dims, b_dims, out_n):
    """2·|C|·k when (a, b) → out looks like a contraction.

    Transpose-agnostic dim-multiset test: for C = A·B the dims of A
    and B combined, minus C's dims, leave the contraction dim twice
    (plus batch dims once each, which C also carries). Most
    elementwise pairs fail the exactly-one-dim-left-twice test; a
    SQUARE same-shape pair ([N,N], [N,N] → [N,N]) is genuinely
    ambiguous from shapes alone and is counted as a matmul — callers
    only take this path for fusion categories XLA says carry a
    dot/conv, which is the right prior for that ambiguity.
    """
    rem = collections.Counter(a_dims) + collections.Counter(b_dims)
    rem.subtract(collections.Counter(out_dims))
    doubles = [d for d, c in rem.items() if c >= 2 and d > 1]
    if len(doubles) != 1:
        return None
    if any(c < 0 for c in rem.values()):
        return None
    return 2.0 * out_n * doubles[0]


def _event_accounting(category: str, long_name: str):
    """(flops, bytes) estimate for one device op.

    The pyprof analogue (reference: apex/pyprof/prof/blas.py, conv.py —
    per-op-class formulas from shapes). Bytes = sum of operand + result
    buffer sizes. FLOPs: fusions whose category says they carry a dot/
    conv ("convolution fusion", kOutput "custom fusion") get the
    contraction recovered by `_matmul_flops` over the two largest
    operands; everything elementwise/reduce counts one FLOP per output
    element; custom-calls (Pallas kernels) and copies claim bytes only.
    """
    res_text, ops_text = _split_result(long_name)
    results = _parse_shapes(res_text)
    operands = _parse_shapes(ops_text)
    if not results and not operands:
        return 0.0, 0.0
    nbytes = float(
        sum(s * n for s, n, _ in results)
        + sum(s * n for s, n, _ in operands)
    )
    # the LARGEST result element is the op's real output; a tuple's
    # small extras (fused probe scalars etc.) are epilogues
    out = max(results, key=lambda t: t[1]) if results else None
    out_n = out[1] if out else 0
    cat = (category or "").lower()
    if "custom-call" in cat:
        # Pallas kernels: operand shapes say nothing about internal
        # math — report the (real) HBM traffic, no FLOP claim
        return 0.0, nbytes
    if "convolution" in cat or cat == "custom fusion":
        # tuple-result elements are NOT candidate matmul operands —
        # only the true operand list qualifies
        ops = sorted(operands, key=lambda t: -t[1])
        if len(ops) >= 2 and out is not None and out_n:
            f = _matmul_flops(out[2], ops[0][2], ops[1][2], out_n)
            if f is not None:
                return f, nbytes
        return float(out_n), nbytes
    if "copy" in cat or "data formatting" in cat:
        return 0.0, nbytes
    return float(out_n), nbytes


_probed_kind = None


def _probe_device_kind() -> str:
    """Device kind for the roofline peaks, probed at most once (a live
    jax.devices() call initializes the backend — not something a pure
    trace-analysis function should do more than once, and callers can
    bypass it entirely via op_stats(device_kind=...))."""
    global _probed_kind
    if _probed_kind is None:
        try:
            _probed_kind = getattr(
                jax.devices()[0], "device_kind", ""
            ).lower()
        except Exception:  # no live backend: kind unknown, pct_peak=0.0
            _probed_kind = ""
    return _probed_kind


def op_stats(
    log_dir: str,
    top: int = 0,
    merge_numeric_suffix: bool = True,
    device_kind: Optional[str] = None,
) -> List[OpStat]:
    """Aggregate per-op device time + FLOP/byte/roofline accounting
    from the newest capture in `log_dir` (reads the trace.json.gz
    XLA-op timeline; the pyprof parse/prof analogue).
    `merge_numeric_suffix` folds fusion.12 / fusion.34 into one row;
    `device_kind` overrides the peak table row (e.g. "tpu v5e") for
    offline analysis."""
    # NOTE: jax 0.9's profiler writes only `.xplane.pb` by default, not
    # this file; the reduction from xplane belongs to the benchmark PR.
    files = sorted(
        glob.glob(f"{log_dir}/plugins/profile/*/*.trace.json.gz")
    )
    if not files:
        raise FileNotFoundError(f"no captured trace under {log_dir}")
    with gzip.open(files[-1]) as f:
        data = json.load(f)

    names: Dict[Any, str] = {}
    tids: Dict[Any, str] = {}
    for e in data.get("traceEvents", []):
        if e.get("ph") == "M":
            if e.get("name") == "process_name":
                names[e["pid"]] = e["args"].get("name", "")
            elif e.get("name") == "thread_name":
                tids[(e["pid"], e["tid"])] = e["args"].get("name", "")
    # any process with an "XLA Ops" thread is a device timeline (CPU
    # traces lack them)
    device_pids = {
        p for (p, t), n in tids.items() if n == "XLA Ops"
    } | {p for p, n in names.items() if "TPU" in n or "GPU" in n}

    if device_kind is None:
        device_kind = _probe_device_kind()
    try:
        peak_f, peak_b = chip_peaks(device_kind)
    except UnknownDeviceError:
        peak_f = peak_b = None
    # unknown chip: pct_peak stays 0.0 rather than being computed
    # against made-up peaks (achieved TFLOP/s + GB/s columns still hold)

    tot = collections.Counter()
    cnt = collections.Counter()
    flops = collections.Counter()
    nbytes = collections.Counter()
    cat = {}
    for e in data.get("traceEvents", []):
        if (
            e.get("ph") == "X"
            and e.get("dur", 0) > 0
            and e.get("pid") in device_pids
            and tids.get((e["pid"], e["tid"])) == "XLA Ops"
        ):
            base = e["name"]
            if merge_numeric_suffix:
                base = re.sub(r"[.\d]+$", "", base)
            args = e.get("args") or {}
            tot[base] += e["dur"]
            cnt[base] += 1
            cat.setdefault(base, args.get("hlo_category", ""))
            # account with THIS event's category: merged rows can mix
            # categories (fusion.1 loop fusion, fusion.2 conv fusion)
            f, b = _event_accounting(
                args.get("hlo_category", "") or base,
                args.get("long_name", ""),
            )
            flops[base] += f
            nbytes[base] += b

    def row(n):
        ms = tot[n] / 1e3
        sec = ms / 1e3
        tf = flops[n] / sec / 1e12 if sec else 0.0
        gb = nbytes[n] / sec / 1e9 if sec else 0.0
        if peak_f is None or not sec:
            pct = 0.0
        else:
            pct = max(
                flops[n] / sec / peak_f,
                nbytes[n] / sec / peak_b,
            ) * 100.0
        return OpStat(
            n, ms, cnt[n], cat.get(n, ""),
            flops[n], nbytes[n], round(tf, 3), round(gb, 2), round(pct, 2),
        )

    stats = [row(n) for n in tot]
    stats.sort(key=lambda s: -s.total_ms)
    return stats[:top] if top else stats
