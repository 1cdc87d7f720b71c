"""What every cell shares: finding a cell, its configuration and its
metric readers by name; the device check; host spans; the count of
compilations; and the result line.

Everything is found by file name under ``bench/``:

* ``configs/<config>.json``  — a model configuration as it is run;
* ``workloads/<cell>.json``  — a cell: its configuration, kind, chips,
  traffic parameters and why it exists;
* ``kinds/<kind>.py``         — the driver of one kind of cell;
* ``metrics/<metric>.py``     — a per-layer metric reader, ``read(rec)``
  returning a number or ``None`` where the run has nothing to read;
* ``models/<model>.py``       — the model a configuration names under
  ``"model"``, as five functions: ``program_config(spec)``, the
  configuration's keys mapped to the program's ``ArchConfig``;
  ``reference_spec(spec)``, the widths the reference and the FLOP count
  read; ``loss(params, tokens, rspec, dtype=jnp.float32)``, the plain
  reference's loss, from a file under ``reference/`` that imports
  nothing of the program; ``train_flops_per_token(rspec, seq)``; and
  ``tiny(spec)``, the keys to change for the CPU size the tests run a
  cell at.

``BENCHMARK.json`` at the checkout's root says which metrics each cell
reports.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_dir: str = None) -> dict:
    """The cell file with its configuration file loaded under
    ``"config_spec"``, and the directory it was found in under
    ``"bench_dir"``."""
    bench_dir = bench_dir or BENCH_DIR
    cell = _json(os.path.join(bench_dir, "workloads", f"{name}.json"))
    cell["name"], cell["bench_dir"] = name, bench_dir
    cell["config_spec"] = _json(os.path.join(bench_dir, "configs",
                                             f"{cell['config']}.json"))
    return cell


def benchmark_spec(root: str = None) -> dict:
    return _json(os.path.join(root or ROOT, "BENCHMARK.json"))


def metrics_of(spec: dict, cell: str, section: str) -> list:
    """The entries of ``section`` ("end_to_end" or "per_layer") that the
    cell reports.  A per-layer metric with no ``workloads`` key goes
    with every cell that reports the end-to-end metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if section == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def _load(bench_dir: str, sub: str, name: str, prefix: str):
    path = os.path.join(bench_dir or BENCH_DIR, sub, f"{name}.py")
    mod_name = prefix + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench_dir: str = None):
    """The ``read`` function of ``metrics/<metric>.py``."""
    return _load(bench_dir, "metrics", metric, "bench_metric_").read


def model_of(config_spec: dict, bench_dir: str = None):
    """The module ``models/<model>.py`` of a configuration's ``"model"``."""
    return _load(bench_dir, "models", config_spec["model"], "bench_model_")


def require_chips(count: int) -> dict:
    """The devices JAX sees, or exit non-zero (and print no result) when
    they are not TPU chips or fewer than the cell asks for."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"no accelerator: {e}")
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit(f"no TPU: JAX found {d0.platform} devices")
    if len(devices) < count:
        sys.exit(f"the cell needs {count} TPU chips, JAX sees {len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts tracing, lowering and backend compilation events JAX
    reports while ``active``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.active, self.count, self.names = False, 0, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1
            self.names.append(event)


@contextlib.contextmanager
def profiled(enabled: bool, trace_dir: str):
    """jax.profiler.trace into ``trace_dir`` when enabled."""
    if not enabled:
        yield
        return
    import jax
    with jax.profiler.trace(trace_dir):
        yield


def check(name: str, value, limit, *, kind: str = "max") -> dict:
    """One compared number beside its limit: ``max`` holds value <=
    limit, ``eq`` holds value == limit."""
    ok = value == limit if kind == "eq" else \
        (value is not None and value == value and value <= limit)
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


def emit(result: dict, checks: list) -> None:
    """Print the checks as the last lines of standard error and the
    result as the last line of standard output, the checks last in it."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    out = dict(result)
    out["correct"] = bool(result.get("correct", True)) and \
        all(c["ok"] for c in checks)
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    print(json.dumps(out), flush=True)
