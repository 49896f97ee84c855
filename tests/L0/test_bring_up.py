"""What keeps a CPU run from passing for a chip run: the compile-cache
policy, `chip_smoke.py`'s refusal to run off the chip, and the bench's
record choke point."""

import io
import json
import os
import pathlib
import subprocess
import sys

import jax

from rocm_apex_tpu import monitor
from rocm_apex_tpu.utils import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[2]


def _run(args, **env):
    base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    base.pop("JAX_COMPILATION_CACHE_DIR", None)
    base.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=base, capture_output=True,
        text=True, timeout=120,
    )


class TestCompileCache:
    def _recorded_updates(self, monkeypatch):
        calls = []
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: calls.append((k, v))
        )
        compile_cache.enable_compile_cache()
        return dict(calls)

    def test_env_var_places_the_cache_and_code_sets_no_directory(
        self, monkeypatch
    ):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        updates = self._recorded_updates(monkeypatch)
        assert "jax_compilation_cache_dir" not in updates

    def test_default_is_one_fixed_path_inside_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        updates = self._recorded_updates(monkeypatch)
        assert updates["jax_compilation_cache_dir"] == str(REPO / ".jax_cache")
        # another process resolves the same directory
        code = (
            "from rocm_apex_tpu.utils.compile_cache import "
            "enable_compile_cache; print(enable_compile_cache())"
        )
        out = _run(["-c", code])
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == str(REPO / ".jax_cache")


def test_chip_smoke_refuses_to_run_without_an_accelerator():
    out = _run(["chip_smoke.py"])
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert '"ok"' not in out.stdout and out.stdout.strip() == ""


def test_cpu_bench_record_names_the_device_and_no_device_metric(monkeypatch):
    import bench

    buf = io.StringIO()
    monkeypatch.setattr(
        bench, "_REPORT_LOGGER",
        monitor.MetricsLogger(
            writers=[monitor.JsonlWriter(stream=buf)], memory_stats=False
        ),
    )
    bench._report("gpt_train_tokens_per_sec_per_chip", 7921.4, "tokens/s", 0.04)
    record = json.loads(buf.getvalue())
    assert record["platform"] == "cpu" and record["device_count"] >= 1
    assert record["device_kind"] == jax.devices()[0].device_kind
    assert record["metric"] == "not_measured"
    assert "value" not in record and "vs_baseline" not in record
    assert "per_chip" not in buf.getvalue() and "mfu" not in buf.getvalue()
    # and no utilization against a made-up peak
    assert bench._mfu(1e12, 1.0) != bench._mfu(1e12, 1.0)  # NaN
