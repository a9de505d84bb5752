"""Driving ``perf/run.py`` from the tests at a size a test run holds:
each cell's ``shrink/<cell>.json`` beside this file, merged over its
configuration and traffic.  Shapes and mixes stay the cell's."""

import json
import os

import run as perf_run


def shrink(workload, config=None, runner=perf_run):
    """The test size of ``workload`` in ``runner``'s copy of ``perf/``,
    with ``config`` merged over its configuration's part."""
    path = os.path.join(runner.PERF, "tests", "shrink", workload + ".json")
    with open(path) as f:
        s = json.load(f)
    if config:
        s["config"] = dict(s.get("config", {}), **config)
    return s


def cell(workload, shrunk=True, config=None, runner=perf_run):
    """``workload``'s cell as a run builds it, at the test size if
    ``shrunk``, with ``config`` merged over its configuration."""
    with open(os.path.join(runner.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    s = (shrink(workload, config, runner) if shrunk
         else {"config": config or {}})
    return runner.Cell(bench, workload, s)


def run(workload, seed=2**31 + 5, wrap=None, trace=0, config=None,
        runner=perf_run):
    """One run at the test size, ``config`` merged over its
    configuration; ``runner`` is the ``perf/run.py`` module to drive."""
    result, _checks = runner.run_cell(
        ["--workload", workload, "--seed", str(seed), "--seconds", "0.5",
         "--trace", str(trace)],
        allow_cpu=True, wrap_check=wrap,
        shrink=shrink(workload, config, runner))
    return result
