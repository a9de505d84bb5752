"""Pipeline smoke check: ``python -m jepsen_tpu.engine.smoke``.

Runs a small mixed-length CAS-register batch — short, long, and
high-concurrency histories (landing in different (E, C) shape
buckets), a corrupted minority (invalid verdicts), and one
slot-cap-exceeding history (concurrent oracle fallback) — through the
production ``check_batch`` path at window sizes 1 (the
serial-equivalent baseline) and 4, on both kernel routes (dense
automaton, and the generic frontier kernel via an explicit closure
cap).  Fails loudly on:

- verdict divergence between window sizes, or against the CPU oracle;
- missing pipeline telemetry: ``jepsen_engine_inflight_depth`` must
  exceed 1 on the window-4 run (proof the overlap actually happened —
  the acceptance gate on hosts without a chip), equal 1 on the
  window-1 run, with ``jepsen_engine_bucket_count`` ≥ 2 and recorded
  ``jepsen_engine_bubble_seconds`` observations.

Wired into ``make pipeline-smoke`` / ``make check`` so a refactor that
silently serializes the engine (or skews its verdicts) breaks CI, not
a benchmark window three rounds later.

Exit codes: 0 ok, 1 divergence or missing metrics.
"""

from __future__ import annotations

import random
import sys


def _corpus():
    """Seeded mixed-shape batch: two event buckets × two concurrency
    buckets, ~1/3 corrupted, plus one unencodable (slot-cap) history."""
    from jepsen_tpu.history import History, invoke_op
    from jepsen_tpu.synth import generate_history

    rng = random.Random(45100)
    hists = []
    for i in range(5):  # short, low concurrency → (E=64, C=4)
        hists.append(
            generate_history(
                rng, n_procs=3, n_ops=10, crash_p=0.02, corrupt=(i % 3 == 0)
            )
        )
    for i in range(5):  # long → (E=128, C=4)
        hists.append(
            generate_history(
                rng, n_procs=3, n_ops=80, crash_p=0.01, corrupt=(i % 3 == 0)
            )
        )
    for i in range(4):  # high concurrency → (E=64, C=8)
        hists.append(
            generate_history(
                rng, n_procs=8, n_ops=14, crash_p=0.02, corrupt=(i % 2 == 0)
            )
        )
    wide = History([invoke_op(p, "write", 1) for p in range(40)])
    wide.index_ops()  # 40 concurrently-open ops > slot_cap: oracle row
    hists.append(wide)
    return hists


def _bubble_count(reg) -> int:
    for d in reg.snapshot():
        if d["name"] == "jepsen_engine_bubble_seconds":
            return d.get("count", 0)
    return 0


def main(argv=None) -> int:
    from jepsen_tpu import models as m
    from jepsen_tpu import obs
    from jepsen_tpu.checker import linear
    from jepsen_tpu.ops import wgl

    hists = _corpus()
    model = m.cas_register(0)
    slot_cap = 32

    failures = []

    def check(cond, msg):
        if not cond:
            failures.append(msg)

    oracle = [
        linear.analysis(model, h, pure_fs=("read",))["valid?"]
        for h in hists
    ]
    check(False in oracle and True in oracle,
          f"corpus should mix verdicts, got {oracle}")

    # both kernel routes: default routing (dense automaton for this
    # value domain) and the generic frontier kernel (explicit closure
    # cap); max_dispatch=4 forces several chunks per bucket so the
    # window genuinely fills
    configs = {
        "dense": dict(slot_cap=slot_cap, max_dispatch=4),
        "frontier": dict(slot_cap=slot_cap, max_dispatch=4, max_closure=9),
    }
    for name, kw in configs.items():
        baseline = None
        for window in (1, 4):
            obs.enable(reset=True)
            outs = wgl.check_batch(model, hists, window=window, **kw)
            verdicts = [o["valid?"] for o in outs]
            check(
                verdicts == oracle,
                f"{name} w={window}: verdicts {verdicts} != oracle {oracle}",
            )
            if baseline is None:
                baseline = outs
            else:
                check(
                    verdicts == [o["valid?"] for o in baseline],
                    f"{name} w={window} diverged from the serial "
                    "baseline",
                )
            check(
                outs[-1].get("engine") == "oracle-fallback",
                f"{name} w={window}: slot-cap history should be "
                f"oracle-fallback, got {outs[-1].get('engine')}",
            )
            reg = obs.registry()
            depth = reg.value("jepsen_engine_inflight_depth")
            if window == 1:
                check(
                    depth == 1,
                    f"{name} window=1 must be serial-equivalent "
                    f"(inflight depth {depth})",
                )
            else:
                # the acceptance gate: >1 proves host/device overlap
                # actually happened, even on the CPU backend
                check(
                    depth is not None and depth > 1,
                    f"{name} window=4: no overlap recorded "
                    f"(inflight depth {depth})",
                )
            check(
                (reg.value("jepsen_engine_bucket_count") or 0) >= 2,
                f"{name}: mixed-shape corpus produced "
                f"{reg.value('jepsen_engine_bucket_count')} buckets",
            )
            check(
                _bubble_count(reg) > 0,
                f"{name} w={window}: no bubble-time observations",
            )

    if failures:
        for f_ in failures:
            print(f"pipeline-smoke: FAIL — {f_}", file=sys.stderr)
        return 1
    print(
        "pipeline-smoke: ok (windows 1/4, dense + frontier routes, "
        f"{len(hists)} mixed-shape histories)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
