"""One run of one benchmark cell, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
harness reads ``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``
(whose ``driver`` names ``bench/drivers/<driver>.py``),
``bench/limits/<cell>.json`` and, in a traced run,
``bench/metrics/<metric>.py`` for each per-layer metric of the cell.  A new
cell or metric is new files and entries; nothing here names one.

A run: check the chips, set up (weights, warm-up) through the mix's module,
measure a window of ``--seconds`` with the profiler off (``--trace 0``) or
a short traced window (``--trace 1``), read the device's peak memory, free
the program's state, compare what the window produced with the plain
reference, and print one JSON line.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import logging
import os
import pathlib
import sys
import tempfile
import time
from typing import Any, Dict, Optional

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


# -- lookup by name ----------------------------------------------------------
def read_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    return read_json(CHECKOUT / "BENCHMARK.json")


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_module(path: pathlib.Path):
    """Import a file by path (metric files carry dots in their names)."""
    mod_name = "bench_file_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(BENCH)))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(spec: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def peaks(device_kind: str) -> dict:
    table = read_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


# -- the device --------------------------------------------------------------
def tpu_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``; every program is cached, however small."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Counts XLA backend compiles (``jax.monitoring``) and keeps the names
    JAX logs for them while ``naming`` is on."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.names: list = []
        self.naming = False
        monitoring.register_event_duration_secs_listener(self._on_event)
        handler = logging.Handler()
        handler.emit = self._on_log
        logging.getLogger("jax").addHandler(handler)

    def _on_event(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.count += 1

    def _on_log(self, record):
        msg = record.getMessage()
        if self.naming and "Finished XLA compilation of" in msg:
            self.names.append(msg.split("Finished XLA compilation of", 1)[1]
                              .split(" in ", 1)[0].strip())


def bank_counts() -> Dict[str, Dict[str, int]]:
    from repro.runtime import telemetry
    return {k: b.as_dict() for k, b in telemetry.banks().items()}


def bank_delta(before, after) -> Dict[str, Dict[str, int]]:
    out = {}
    for domain, counts in after.items():
        old = before.get(domain, {})
        out[domain] = {k: v - old.get(k, 0) for k, v in counts.items()}
    return out


# -- one run -----------------------------------------------------------------
class Run:
    """What one run knows: the cell, its configuration and mix, the seed,
    and what its window saw.  Drivers and metric readers read it."""

    def __init__(self, workload: str, seed: int, seconds: float, trace: bool,
                 *, spec: dict, config: dict, mix: dict, limits: dict,
                 devices, peaks: dict):
        self.workload, self.seed, self.seconds, self.trace = (
            workload, int(seed), float(seconds), bool(trace))
        self.spec, self.config, self.mix, self.limits = spec, config, mix, limits
        self.devices, self.peaks = devices, peaks
        self.facts: Dict[str, Any] = {}       # counts of the window
        self.banks: Dict[str, Dict[str, int]] = {}   # counter deltas of the window
        self.process_banks: Dict[str, Dict[str, int]] = {}  # since start
        self.trace_data = None                # bench.trace.reduce.Trace


def prepare(workload: str, seed: int, seconds: float, trace: bool, *,
            require_tpu: bool = True, config: Optional[dict] = None,
            mix: Optional[dict] = None, limits: Optional[dict] = None):
    """Find the cell's files, check the chips, and load its driver.
    ``config``, ``mix`` and ``limits`` replace the files (tests run cells at
    a small size on the CPU with them, and with ``require_tpu=False``).
    Returns (run, driver module)."""
    spec = benchmark_spec()
    cell = find_cell(spec, workload)
    if require_tpu:
        devices = tpu_devices(int(cell["chips"]))
    else:
        import jax
        devices = jax.devices()[: int(cell["chips"])]
    config = config or read_json(BENCH / "configs" / f"{cell['config']}.json")
    mix = mix or read_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = limits or read_json(BENCH / "limits" / f"{workload}.json")
    if require_tpu:
        enable_compile_cache()
    run = Run(workload, seed, seconds, trace, spec=spec, config=config,
              mix=mix, limits=limits, devices=devices,
              peaks=peaks(devices[0].device_kind) if require_tpu else {})
    return run, load_module(BENCH / "drivers" / f"{mix['driver']}.py")


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, **kw) -> dict:
    """Run one cell; returns the result object (``kw``: see ``prepare``)."""
    import jax
    run, driver = prepare(workload, seed, seconds, trace, **kw)
    spec, devices, mix = run.spec, run.devices, run.mix
    kind = devices[0].device_kind
    compiles = CompileLog()
    session = driver.Session(run)             # set-up: weights, warm-up
    banks0, n0 = bank_counts(), compiles.count
    compiles.naming = True
    jax.config.update("jax_log_compiles", True)      # names what compiles
    device_extra: Dict[str, float] = {}
    if trace:
        from bench.trace.reduce import WINDOW_SPAN, Trace
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # runtime events, no Python calls
            jax.profiler.start_trace(tdir, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                    t0 = time.perf_counter()
                    run.facts = session.window(float(mix["trace_seconds"]))
            finally:
                jax.profiler.stop_trace()
            run.trace_data = Trace.from_dir(tdir)
        device_extra = {"busy_s": run.trace_data.busy_s(),
                        "window_s": run.trace_data.window_s}
    else:
        t0 = time.perf_counter()
        run.facts = session.window(seconds)
    setup_s = t0 - t_start
    compiles.naming = False
    jax.config.update("jax_log_compiles", False)
    run.banks = bank_delta(banks0, bank_counts())
    run.process_banks = bank_counts()
    run.facts["compiles_in_window"] = compiles.count - n0
    run.facts["compiled_in_window"] = list(compiles.names)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    session.release()                         # the program's state goes
    gc.collect()
    t_check = time.perf_counter()
    checks, failed = session.check()
    check_s = time.perf_counter() - t_check

    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in metrics_for(spec, workload, "per_layer"):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = session.end_to_end(run.facts)
        values["setup_s"] = setup_s
        for m in metrics_for(spec, workload, "end_to_end"):
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    correct = failed == 0 and all(v <= lim for v, lim in checks.values())
    result = {
        "correct": bool(correct),
        "attempted": int(run.facts["attempted"]),
        "failed": int(failed),
        "metrics": metrics,
        "device": dict({"platform": devices[0].platform, "kind": kind,
                        "count": len(devices), "memory_peak_bytes": peak},
                       **device_extra),
    }
    if trace:
        result["breakdown"] = {"device_ops": run.trace_data.top_ops(10),
                               "idle_gaps": run.trace_data.idle_gaps(10)}
    result["window"] = {k: v for k, v in run.facts.items()
                        if isinstance(v, (int, float, str, list))
                        and not (isinstance(v, list) and len(v) > 16)}
    result["timings"] = {"setup_s": setup_s, "check_s": check_s}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def main(args, t_start: float) -> int:
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"check failed requests: {result['failed']} of "
          f"{result['attempted']} (limit 0)", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
