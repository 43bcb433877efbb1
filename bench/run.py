#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload e3sm-compress --seed 7 --seconds 30 --trace 0

The cell, its configuration, traffic and metrics come from BENCHMARK.json;
each configuration, traffic mix, mode driver, per-layer metric and cell
limit sits in a file of its own under bench/, found by its name.  A run

1. refuses any backend but a TPU with the chips the cell asks for;
2. places JAX's compile cache inside the checkout (``exec.use_compile_cache``);
3. makes the data on the device from ``--seed`` and does the mode's set-up,
   which runs every program of the window at its shapes;
4. measures units of work for ``--seconds``, letting the last one finish and
   timing to its end, and fails if anything compiles or retraces inside;
5. checks what the window produced against the plain reference and prints
   each number beside its limit.

``--trace 1`` is a run of its own: the window is traced with the JAX
profiler and the per-layer metrics are printed instead of the end-to-end
ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

#: JAX events that mean a program was traced, compiled or loaded
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class BenchError(Exception):
    """The run cannot give a result."""


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything BENCHMARK.json and the cell's files say about one cell."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in reported
                                  else [])]
    bench_dir = root / "bench"
    return {
        "cell": cell,
        "config": json.loads((root / config_entry["file"]).read_text()),
        "traffic": json.loads(
            (bench_dir / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads(
            (bench_dir / "workloads" / f"{name}.json").read_text())["limits"],
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def derive_seeds(seed: int) -> dict:
    """Independent seeds for dealing the slices and for the check's sample,
    from any whole number."""
    import numpy as np
    deal, check = np.random.SeedSequence(seed).generate_state(2)
    return {"deal_seed": int(deal), "check_seed": int(check)}


class CompileCounter:
    """Counts the programs JAX traces, compiles or loads while active."""

    def __init__(self):
        self.events: list[str] = []

    def _listener(self, event: str, duration: float, **kwargs) -> None:
        if event in COMPILE_EVENTS:
            self.events.append(f"{event} {kwargs.get('fun_name', '')}".strip())

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._listener)
        return False


@contextlib.contextmanager
def traced_stages():
    """Re-emit the program's ``exec.stage`` spans as profiler annotations,
    so the trace's idle gaps can be told by what the host was doing."""
    import jax

    from repro.core import exec as exec_mod

    original = exec_mod.stage

    @contextlib.contextmanager
    def stage(name: str, n_values: int = 0):
        with jax.profiler.TraceAnnotation(f"bench/{name}"), \
                original(name, n_values):
            yield

    exec_mod.stage = stage
    try:
        yield
    finally:
        exec_mod.stage = original


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, overrides: dict | None = None,
             require_tpu: bool = True, control: bool = False,
             t_start: float = T_START) -> dict:
    """One run of a cell; returns the result line as a dict.

    ``overrides`` replaces top-level keys of the configuration
    (``config``) and the traffic (``traffic``), for rehearsals at small
    sizes.  ``control`` adds the control's numbers under ``control``."""
    import jax

    from repro.core import exec as exec_mod

    spec = load_cell(name, root)
    for part, values in (overrides or {}).items():
        spec[part] = dict(spec[part], **values)
    chips = spec["cell"]["chips"]
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise BenchError(f"{name} needs {chips} TPU chip(s); JAX found "
                         f"{len(devices)} {devices[0].platform} device(s)")
    used = devices[:chips]
    exec_mod.use_compile_cache()

    ctx = SimpleNamespace(config=spec["config"], traffic=spec["traffic"],
                          facts={}, setup_parts={}, chips=chips,
                          device_kind=used[0].device_kind,
                          **derive_seeds(seed))
    mode = importlib.import_module(f"bench.modes.{spec['traffic']['mode']}")
    mode.setup(ctx)

    exec_mod.reset_stage_stats()
    retraces = exec_mod.retrace_counts()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    units: list[dict] = []
    with contextlib.ExitStack() as stack:
        compiles = stack.enter_context(CompileCounter())
        if trace:
            stack.enter_context(jax.profiler.trace(trace_dir))
            stack.enter_context(traced_stages())
            stack.enter_context(jax.profiler.TraceAnnotation("bench/window"))
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with (jax.profiler.TraceAnnotation(f"bench/{spec['traffic']['mode']}")
                  if trace else contextlib.nullcontext()):
                units.append(mode.unit(ctx, len(units)))
            if time.perf_counter() >= deadline:
                break
        elapsed = time.perf_counter() - t0
    setup_s = t0 - t_start
    if compiles.events or exec_mod.retrace_counts() != retraces:
        raise BenchError(f"compiled inside the window: {compiles.events}; "
                         f"retraces {retraces} -> {exec_mod.retrace_counts()}")

    ctx.stages = exec_mod.stage_stats()
    ctx.window_seconds = elapsed
    ctx.window_bytes = sum(u["bytes"] for u in units)
    ctx.trace = None
    breakdown = None
    if trace:
        from bench import trace_reduce
        extracted = trace_reduce.extract_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.trace = trace_reduce.reduce(extracted)
        if ctx.trace is not None:
            breakdown = {"device_ops": ctx.trace["device_ops"],
                         "idle_gaps": ctx.trace["idle_gaps"]}

    e2e = mode.end_to_end(ctx, units, elapsed)
    e2e["setup_s"] = setup_s
    metrics = {}
    if trace:
        from bench import metrics as readers
        for m in spec["per_layer"]:
            value = readers.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            if m["name"] not in e2e:
                raise BenchError(f"mode {spec['traffic']['mode']} gives no "
                                 f"{m['name']}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": _memory_peak(used)}
    if trace:
        device["busy_s"] = ctx.trace["busy_s"] if ctx.trace else 0.0
        device["window_s"] = ctx.trace["window_s"] if ctx.trace else elapsed

    from bench import checks
    numbers = mode.check(ctx, units, control=False)
    ok, table = checks.judge(numbers, spec["limits"])
    result = {"correct": ok, "attempted": len(units), "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        ctrl = mode.check(ctx, units, control=True)
        result["control"] = {"correct": checks.judge(ctrl, spec["limits"])[0],
                             "numbers": ctrl}
    result["setup_parts"] = ctx.setup_parts
    result["checks"] = table
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program to measure: {ROOT / 'src' / 'repro'} is missing",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # JAX reads these when it is imported: the compile cache lives at one
    # fixed place inside the checkout and keeps every program, so only the
    # first run of a cell in a checkout compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    parts = result.pop("setup_parts")
    print("set-up: " + ", ".join(f"{k} {v:.3f} s" for k, v in parts.items()),
          file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    for check, row in result["checks"].items():
        print(f"check {check}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
