"""The per-layer readers of the program's spans and ``sched`` counters, on
a hand-built trace (``bench/metrics/engine.step_ms.py`` and the rest)."""
import types

import pytest

from bench import harness
from bench.trace import reduce as R

READERS = ("engine.step_ms", "engine.page_host_share",
           "sched.flush_us_per_task", "sched.batched_share",
           "device_idle.serve.unnamed", "sched.tasks_per_program")
WINDOW = (500, 10_000)
OPS = [(1400, 1450, "fusion"), (2100, 2900, "fusion"), (6100, 7900, "fusion"),
       (8560, 8600, "fusion")]
HOST = [
    (0, 10_000, R.WINDOW_SPAN),
    # a step that starts before the window: left out everywhere
    (0, 400, "engine.step"), (100, 300, "engine.gather"),
    (50, 150, "sched.flush"),
    (1000, 4000, "engine.step"),
    (1000, 1200, "engine.admit"),
    (1200, 1600, "engine.gather"), (1300, 1500, "sched.flush"),
    (1600, 2000, "engine.compose"),
    (2000, 3000, "engine.decode"),
    (3000, 3800, "engine.scatter"), (3100, 3700, "sched.flush"),
    (3700, 3790, "pool.commit"),
    (4400, 4600, "DeferredTpuAllocator::Allocate"),    # names no layer
    (5000, 9000, "engine.step"),
    (5000, 5500, "engine.gather"), (5500, 6000, "engine.compose"),
    (6000, 8000, "engine.decode"), (8000, 8500, "engine.scatter"),
    (8500, 8800, "engine.defrag"), (8550, 8750, "sched.flush"),
    (9200, 9400, "pool.commit"),
]


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def run_of(host, banks):
    return types.SimpleNamespace(
        trace_data=R.from_events({"/device:TPU:0": OPS}, host, WINDOW),
        banks=banks, facts={}, peaks={})


def test_span_and_counter_readers_read_exact_values():
    run = run_of(HOST, {"links": {"tasks:link0": 3, "tasks:link1": 1,
                                  "bytes:link0": 4096},
                        "sched": {"batched_tasks": 3, "programs": 2}})
    got = {name: reader(name).read(run) for name in READERS}
    # steps starting in the window: 3000 ns and 4000 ns
    assert got["engine.step_ms"] == pytest.approx(3500 / 1e6)
    # gather/compose/scatter 400+400+800, then 500+500+500, defrag 300
    assert got["engine.page_host_share"] == pytest.approx(100 * 3400 / 7000)
    # flushes in the window: 200 + 600 + 200 ns over 4 tasks
    assert got["sched.flush_us_per_task"] == pytest.approx(1000 / 1e3 / 4)
    assert got["sched.batched_share"] == pytest.approx(75.0)
    assert got["sched.tasks_per_program"] == pytest.approx(4 / 2)
    # idle gaps: 500-1400 (mid 950, no span), 1450-2100 (compose),
    # 2900-6100 (mid 4500: only a runtime event), 7900-8560 (scatter),
    # 8600-10000 (mid 9300: pool.commit)
    assert got["device_idle.serve.unnamed"] == pytest.approx(
        100 * (900 + 3200) / (900 + 650 + 3200 + 660 + 1400))


def test_the_container_span_alone_names_no_idle_time():
    host = [(0, 10_000, R.WINDOW_SPAN), (500, 10_000, "engine.step")]
    run = run_of(host, {})
    assert reader("device_idle.serve.unnamed").read(run) == pytest.approx(100.0)
    assert reader("engine.page_host_share").read(run) == 0.0


def test_readers_are_silent_without_the_programs_spans_and_counters():
    run = run_of([(0, 10_000, R.WINDOW_SPAN),
                  (600, 900, "DeferredTpuAllocator::Allocate")],
                 {"serving": {"steps": 3}, "links": {"tasks:link0": 5}})
    assert {name: reader(name).read(run) for name in READERS} == dict.fromkeys(
        READERS)
    run.trace_data = None
    assert {name: reader(name).read(run) for name in READERS} == dict.fromkeys(
        READERS)


def test_phase_tool_splits_idle_time_by_engine_phase():
    """``scripts/trace_phases.py``: each idle gap goes to the phase covering
    its midpoint, else to the step between phases, else outside steps."""
    import importlib.util

    path = harness.CHECKOUT / "scripts" / "trace_phases.py"
    spec = importlib.util.spec_from_file_location("trace_phases", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    host = [(0, 10_000, R.WINDOW_SPAN),
            (500, 5000, "engine.step"), (500, 700, "engine.admit"),
            (700, 2500, "engine.gather"), (800, 2400, "sched.flush"),
            (2600, 4000, "engine.decode"),
            (5000, 9400, "engine.step"), (5100, 5900, "engine.compose")]
    tr = R.from_events({"/device:TPU:0": [(0, 1000, "fusion"),
                                          (3000, 3500, "fusion"),
                                          (6000, 9000, "fusion")]},
                       host, (0, 10_000))
    got = tool.by_phase(tr)
    # gaps 1000-3000 (mid 2000, gather, in the flush), 3500-6000 (mid 4750,
    # first step between phases), 9000-10000 (mid 9500, after the steps)
    assert got["idle_s"] == pytest.approx(
        {"engine.gather": 2e-6, tool.BETWEEN: 2.5e-6, tool.OUTSIDE: 1e-6})
    assert got["idle_in_flush_s"] == pytest.approx({"engine.gather": 2e-6})
    assert got["steps"] == 2
    assert got["busy_s"] == pytest.approx(4.5e-6)
    assert got["host_s"] == pytest.approx(
        {"engine.step": 8.9e-6, "engine.admit": 2e-7, "engine.gather": 1.8e-6,
         "sched.flush": 1.6e-6, "engine.decode": 1.4e-6,
         "engine.compose": 8e-7})
    assert got["busy_in_phase_s"] == pytest.approx(
        {"engine.admit": 2e-7, "engine.gather": 3e-7, "engine.decode": 5e-7,
         "engine.compose": 0.0})


def test_tasks_per_program_is_silent_on_a_program_without_the_counter():
    """A ``sched`` bank without ``programs`` (the parent of the grouped
    flush) reads as absent, not as zero or infinity."""
    read = reader("sched.tasks_per_program").read
    banks = {"links": {"tasks:d2h0": 6, "tasks:d2h1": 6},
             "sched": {"batched_tasks": 12}}
    assert read(run_of(HOST, banks)) is None
    banks["sched"]["programs"] = 3
    assert read(run_of(HOST, banks)) == pytest.approx(4.0)


def test_serve_cell_flushes_run_grouped():
    """A traced serving run at the small CPU size launches fewer XDMA
    programs than it dispatches tasks: its flushes run grouped."""
    import time

    from bench import smoke
    r = harness.execute(smoke.SERVE_CELL, 2**33 + 11, 0.5, 1,
                        t_start=time.perf_counter(), **smoke.kw(smoke.SERVE_CELL))
    assert r["correct"]
    assert r["metrics"]["sched.tasks_per_program"]["value"] > 1
