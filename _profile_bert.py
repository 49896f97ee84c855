"""Dev driver: device-profile the BERT bench step and print the
per-fusion breakdown.

Usage: python _profile_bert.py [iters] [--dropout=R] [--batch=N]
[--remat] — runs the EXACT bench step (imported from
bench.build_bert_train, so this profile cannot drift from the
benchmark) under jax.profiler.trace and aggregates with
profiler.op_stats.
"""

import re as _re
import sys
import tempfile

import jax

from bench import build_bert_train
from rocm_apex_tpu import profiler

_pos = [a for a in sys.argv[1:] if not a.startswith("--")]
ITERS = int(_pos[0]) if _pos else 20
DROPOUT = 0.0
BATCH = 0
REMAT = "--remat" in sys.argv[1:]
for _a in sys.argv[1:]:
    if _a.startswith("--dropout="):
        DROPOUT = float(_a.split("=", 1)[1])
    elif _a.startswith("--batch="):
        BATCH = int(_a.split("=", 1)[1])


def main():
    runN, state0, rng0, cfg, batch, seq, _ = build_bert_train(
        DROPOUT, BATCH, REMAT, ITERS
    )
    carry, losses = runN(state0, rng0)
    float(losses[-1])  # warmup

    log_dir = tempfile.mkdtemp(prefix="bert_prof_")
    with profiler.trace(log_dir):
        carry, losses = runN(state0, rng0)
        float(losses[-1])

    stats = profiler.op_stats(log_dir, merge_numeric_suffix=False)
    total = sum(s.total_ms for s in stats if s.name != "while")
    print(f"device total (sans while): {total:.1f} ms over {ITERS} steps "
          f"= {total / ITERS:.2f} ms/step")

    hlo = runN.lower(state0, rng0).compile().as_text()
    defs = {}
    for line in hlo.splitlines():
        t = line.strip()
        if t.startswith("%") and "= " in t:
            nm = t[1:].split(" ")[0]
            defs.setdefault(nm, t[:240])

    opnames = {}
    for line in hlo.splitlines():
        t = line.strip()
        if t.startswith("%") and "op_name=" in t:
            nm = t[1:].split(" ")[0]
            m = _re.search(r'op_name="([^"]+)"', t)
            if m:
                opnames[nm] = m.group(1)

    def sig(s):
        d = defs.get(s.name, "")
        m = _re.match(r"%\S+ = (\(?[a-z0-9]+\[[\d,]*\])", d)
        shape = m.group(1) if m else "?"
        op = opnames.get(s.name, "")
        op = op.replace("jit(runN)/while/body/closed_call/", "")
        bwd = "transpose(jvp" in op
        op = _re.sub(r"transpose\(jvp\(BertModel\)\)/", "", op)
        op = _re.sub(r"jvp\(BertModel\)/", "", op)
        op = _re.sub(r"layer_\d+", "layer", op)
        op = _re.sub(r"rematted_computation\[?", "", op)
        kind = _re.sub(r"\.\d+$", "", s.name)
        tag = "BWD " if bwd else ""
        return f"{tag}{op or kind} -> {shape}"

    groups = {}
    for s in stats:
        if s.name == "while":
            continue
        k = sig(s)
        g = groups.setdefault(k, [0.0, 0, 0.0])
        g[0] += s.total_ms
        g[1] += s.count
        g[2] = max(g[2], s.tflops_sec)
    print(f"{'ms/step':>8} {'cnt/step':>8} {'tflops':>7}  signature")
    for k, (ms, cnt, tf) in sorted(groups.items(), key=lambda kv: -kv[1][0]):
        if ms / ITERS < 0.04:
            continue
        print(f"{ms / ITERS:8.3f} {cnt / ITERS:8.1f} {tf:7.1f}  {k[:120]}")


if __name__ == "__main__":
    main()
