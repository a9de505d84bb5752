"""A configuration of another data model joins the benchmark as new
files alone.  In a copy of ``perf/`` and ``BENCHMARK.json``, the toy in
``toy/`` (Jepsen counter histories, checked by
``jepsen_tpu.checker.counter()`` against a plain sum-bounds reference)
adds its configuration, generator, entry, reference, traffic and test
size beside the harness's files, and one cell and one per-layer metric
to ``BENCHMARK.json``: ``window_compiles.counter_toy``, read by the
existing ``window_compiles`` reader.  A run of the cell reads
``correct`` true, and false once its entry plants a wrong verdict or
its reference's search contradicts what the generator knows.  The real
``BENCHMARK.json`` never holds the toy."""

import importlib.util
import json
import os
import shutil

import harness
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
PERF = os.path.dirname(TESTS)
ROOT = os.path.dirname(PERF)
TOY = os.path.join(TESTS, "toy")
#: appended to the toy's entry: one item's verdict turned over
PLANTED = '''

_rows = rows


def rows(results, n_items):
    verdicts, engines = _rows(results, n_items)
    verdicts[0]["valid?"] = not verdicts[0]["valid?"]
    return verdicts, engines
'''
#: appended to the toy's reference: a search that turns every verdict
#: over, against what the generator knows
INVERTED = '''


def search(cfg, traffic, item, labels):
    return {"valid?": not valid(item)}
'''


@pytest.fixture
def checkout(tmp_path):
    """A checkout whose ``perf/`` has the toy's files added, each new."""
    root = tmp_path / "checkout"
    shutil.copytree(PERF, root / "perf",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "jepsen_tpu"), root / "jepsen_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(TOY, "benchmark.json")) as f:
        for key, entries in json.load(f).items():
            bench[key] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for d, _dirs, files in os.walk(TOY):
        rel = os.path.relpath(d, TOY)
        for name in files:
            if rel == "." and name == "benchmark.json":
                continue
            dst = root / "perf" / rel / name
            assert not dst.exists(), dst
            shutil.copy(os.path.join(d, name), dst)
    return root


def load_runner(root):
    spec = importlib.util.spec_from_file_location(
        "toy_checkout_run", root / "perf" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_toy_pool_has_both_verdicts(checkout):
    cell = harness.cell("counter_toy", runner=load_runner(checkout))
    ref = cell.module("reference")
    pool = cell.module("generator").make_pool(cell.config, cell.traffic,
                                              2**31 + 5)
    verdicts = [ref.valid(h) for req in pool for h in req]
    assert True in verdicts and False in verdicts
    assert verdicts == [ref.known(cell.config, cell.traffic, h)["valid?"]
                        for req in pool for h in req]


def test_a_new_data_model_joins_as_new_files(checkout):
    runner = load_runner(checkout)
    res = harness.run("counter_toy", runner=runner)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"ops_per_s", "setup_s"}

    res = harness.run("counter_toy", runner=runner, trace=1)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"window_compiles.counter_toy"}

    entry = checkout / "perf" / "entries" / "counter_checker.py"
    kept = entry.read_text()
    entry.write_text(kept + PLANTED)
    res = harness.run("counter_toy", runner=runner)
    assert res["correct"] is False
    assert res["checks"]["verdict_mismatches"]["value"] > 0
    entry.write_text(kept)

    ref = checkout / "perf" / "reference" / "counter.py"
    ref.write_text(ref.read_text() + INVERTED)
    res = harness.run("counter_toy", runner=runner)
    assert res["correct"] is False
    assert res["checks"]["reference_vs_known"]["value"] > 0
