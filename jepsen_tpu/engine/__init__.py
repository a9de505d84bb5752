"""Pipelined checker engine: the one production path from histories to
verdicts.

``wgl.check_batch`` / ``wgl.analysis`` (and everything above them —
``checker.linearizable``, ``independent.batched_linearizable``) route
through :mod:`jepsen_tpu.engine.pipeline`, which overlaps the three
stages the serial path used to run back-to-back:

1. **host encode** — each history encodes (ops/encode.py) and lands in
   a per-padded-(E, C)-shape bucket, so short histories stop paying the
   longest history's padding;
2. **device dispatch** — bucket chunks dispatch asynchronously through
   a bounded :class:`~jepsen_tpu.engine.pipeline.DispatchWindow` (encode
   chunk *k+1* while chunk *k* computes; sync only when the window
   fills);
3. **oracle fallback** — unencodable/overflowed histories run
   ``checker.linear`` searches on a worker pool *concurrently* with
   device work instead of after it.

Verdicts are independent of the window size — window=1
is exactly the historical serial dispatch-sync-dispatch path (pinned
by ``tests/test_engine.py`` and ``make pipeline-smoke``).  Pipeline
bubble time, in-flight depth, and bucket counts report through the
``obs`` metrics registry, and encode, plan, issue, dispatch and settle
through ``engine/*`` spans (doc/observability.md).

The engine is split into a pure per-run **planning** layer
(:mod:`jepsen_tpu.engine.planning`: ``RunContext``, ``Planner``) and a
device-owning **execution** layer (:mod:`jepsen_tpu.engine.execution`:
``DispatchWindow``, ``Executor``); :mod:`~jepsen_tpu.engine.pipeline`
composes them per run, while the resident checker service
(:mod:`jepsen_tpu.serve`) shares one executor across concurrent runs.
Ahead of planning, the P-compositionality front-end
(:mod:`jepsen_tpu.engine.decompose`) splits partitionable models'
histories into per-partition sub-histories and ANDs the sub-verdicts
at settle — wide-keyspace workloads check as thousands of tiny dense
rows instead of one oracle-bound search.
"""

from .decompose import (  # noqa: F401
    DecomposedRun,
    SubmodelCache,
    merge_partition_results,
    split_history,
)
from .execution import (  # noqa: F401
    DEFAULT_WINDOW,
    DispatchWindow,
    Executor,
    default_window,
)
from .pipeline import run  # noqa: F401
from .planning import (  # noqa: F401
    DEFAULT_FLUSH_ROWS,
    Planner,
    PlannedBucket,
    RunContext,
    estimated_cost,
    merge_buckets,
)
