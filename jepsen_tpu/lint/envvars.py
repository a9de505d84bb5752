"""Central registry of every ``JEPSEN_TPU_*`` environment variable.

The knobs grew organically — engine tuning, serve admission, client
timeouts — and each one was documented (or not) wherever it was read.
This table is now the single source of truth: the ``seam-env-read``
rule (:mod:`jepsen_tpu.lint.contracts`) fails the build when code
reads a ``JEPSEN_TPU_*`` name that is not registered here, and
``seam-env-doc`` keeps the generated markdown table in
doc/configuration.md byte-identical to :func:`render_table`, so the
operator doc can never drift from the code again.

Regenerate the doc table with::

    python -m jepsen_tpu.lint.envvars > /tmp/t.md   # or paste inline

Registration is one tuple: name, default (as the operator sees it),
the module that reads it, and a one-line meaning.  Precedence for the
engine knobs is uniform (``tune.artifact.resolve_knob``): env var >
active calibration > pinned default.
"""

from __future__ import annotations

from typing import Iterator, NamedTuple, Tuple


class EnvVar(NamedTuple):
    name: str
    default: str
    read_in: str
    meaning: str


#: every environment variable the package reads, alphabetical
REGISTRY: Tuple[EnvVar, ...] = (
    EnvVar("JEPSEN_TPU_BREAKER_COOLDOWN", "5.0",
           "serve/client.py",
           "seconds an open circuit breaker waits before a half-open "
           "`/healthz` probe may close it again"),
    EnvVar("JEPSEN_TPU_BREAKER_FAILURES", "3",
           "serve/client.py",
           "consecutive connection-level failures that trip the "
           "breaker open; tripped calls fast-fail to in-process"),
    EnvVar("JEPSEN_TPU_CALIBRATION", "auto-discover",
           "tune/artifact.py",
           "calibration artifact path; `0`/`off` disables, unset "
           "auto-discovers `calibration.json`"),
    EnvVar("JEPSEN_TPU_CLIENT_BACKOFF", "0.1",
           "serve/client.py",
           "base seconds for client retry backoff (exponential with "
           "full jitter, capped by the deadline budget)"),
    EnvVar("JEPSEN_TPU_CLIENT_DEADLINE", "630.0",
           "serve/client.py",
           "per-request wall-clock budget in seconds across ALL retry "
           "attempts; a stalled daemon costs at most this"),
    EnvVar("JEPSEN_TPU_CLIENT_RETRIES", "2",
           "serve/client.py",
           "connection-level retries after the first attempt (never "
           "retries a 503 — the daemon answered)"),
    EnvVar("JEPSEN_TPU_CYCLES_CLOSURE", "auto",
           "ops/cycles.py",
           "closure kernel variant (`fixed`/`earlyexit`); env > "
           "calibration > default"),
    EnvVar("JEPSEN_TPU_CYCLES_IMPL", "auto",
           "ops/cycles.py",
           "closure squaring arithmetic (`uint8`/`packed32`/`bf16`); "
           "env > calibration > default"),
    EnvVar("JEPSEN_TPU_DENSE_UNION", "auto",
           "ops/dense.py",
           "dense-kernel subset-union lowering (`matmul`/`scan`); env "
           "> calibration > default"),
    EnvVar("JEPSEN_TPU_DRIFT", "1",
           "serve/daemon.py",
           "cost-model drift sentinel at the `serve()` production "
           "entry (rides the dispatch journal); falsy disables"),
    EnvVar("JEPSEN_TPU_DRIFT_THRESHOLD", "2.0",
           "obs/drift.py",
           "per-shape EWMA residual deviation (max(r, 1/r)) at which "
           "a dispatch shape counts as stale and the sentinel "
           "recommends a retune; must exceed 1.0"),
    EnvVar("JEPSEN_TPU_ELLE_SCREEN", "auto",
           "elle/cycles.py",
           "Elle cycle-screen routing: `auto`/`1` (device screens) or "
           "`0` (pure CPU classify)"),
    EnvVar("JEPSEN_TPU_ENGINE_DECOMPOSE", "1",
           "engine/decompose.py",
           "key-partition decomposition front-end; `0` disables"),
    EnvVar("JEPSEN_TPU_ENGINE_FLUSH_ROWS", "calibration or 1024",
           "engine/planning.py",
           "planner flush threshold in rows; env > calibration > "
           "default"),
    EnvVar("JEPSEN_TPU_ENGINE_MESH", "auto",
           "parallel/mesh.py",
           "device-mesh resolution: `auto`, `0` (single device), `1` "
           "(force, virtualizing on CPU)"),
    EnvVar("JEPSEN_TPU_ENGINE_ROW_BUCKET", "calibration or auto",
           "engine/execution.py",
           "dispatch row-bucket size; env > calibration > default"),
    EnvVar("JEPSEN_TPU_ENGINE_WINDOW", "calibration or 4",
           "engine/execution.py",
           "in-flight dispatch-window depth (1 = serial); env > "
           "calibration > default"),
    EnvVar("JEPSEN_TPU_FRONTIER_COMPACTION", "auto",
           "ops/wgl.py",
           "frontier hot-path compaction mode (`auto`/`on`/`off`)"),
    EnvVar("JEPSEN_TPU_JOURNAL", "dispatch-journal.jsonl",
           "serve/daemon.py",
           "dispatch-journal path for the `serve()` production entry; "
           "falsy disables"),
    EnvVar("JEPSEN_TPU_LINT_CACHE", "lint/.jaxpr_cache.json",
           "lint/jaxpr_audit.py",
           "jaxpr-audit incremental result cache path (package-"
           "relative default); falsy disables caching, every lint run "
           "re-traces"),
    EnvVar("JEPSEN_TPU_LINT_JAXPR", "1",
           "lint/jaxpr_audit.py",
           "`0` disables the traced half of the jaxpr audit (budget/"
           "shape-pin/host-sync/retrace); the AST rules still run"),
    EnvVar("JEPSEN_TPU_LIVE", "unset",
           "interpreter.py",
           "`1` ships history events to the checker daemon as they "
           "land (online checking); never blocks or fails the "
           "workload — a full buffer drops and counts.  Requires a "
           "test-level wire model; keyed workloads stay post-hoc"),
    EnvVar("JEPSEN_TPU_OBS", "1",
           "obs/__init__.py",
           "observability master switch; `0` disables span + metric "
           "recording globally"),
    EnvVar("JEPSEN_TPU_OBS_MAX_SERIES", "512",
           "obs/metrics.py",
           "per-family label-cardinality cap; overflow folds into an "
           "`{overflow=\"1\"}` series"),
    EnvVar("JEPSEN_TPU_ORACLE_WORKERS", "4",
           "checker/linear.py",
           "CPU-oracle worker-pool width for concurrent fallback "
           "searches"),
    EnvVar("JEPSEN_TPU_ROUTE_PROBE_INTERVAL", "1.0",
           "serve/router.py",
           "seconds between the fleet router's `/healthz` membership "
           "sweeps; a dead member's keys re-route within one "
           "interval"),
    EnvVar("JEPSEN_TPU_ROUTE_PROBE_TIMEOUT", "0.5",
           "serve/router.py",
           "per-member timeout for one router health probe"),
    EnvVar("JEPSEN_TPU_SERVE_AOT_CACHE", "unset",
           "serve/daemon.py",
           "shared fleet-wide AOT executable manifest directory "
           "(its compiled code loads from the persistent compile "
           "cache, `JAX_COMPILATION_CACHE_DIR` or `.jax_cache/`); a "
           "restarted member "
           "warms from it before `/healthz` goes ready and answers "
           "its first request with zero cold dispatches; unset "
           "disables"),
    EnvVar("JEPSEN_TPU_SERVE_COALESCE_WAIT", "0.0",
           "serve/daemon.py",
           "seconds the device thread lingers after the first queued "
           "request, collecting coalescing company"),
    EnvVar("JEPSEN_TPU_SERVE_HOST", "127.0.0.1",
           "serve/client.py",
           "daemon host the service client targets"),
    EnvVar("JEPSEN_TPU_SERVE_MAX_QUEUE", "8",
           "serve/daemon.py",
           "admission bound in queued runs; excess requests get 503 "
           "and fall back in-process"),
    EnvVar("JEPSEN_TPU_SERVE_PORT", "8519",
           "serve/client.py",
           "daemon TCP port (client and daemon sides)"),
    EnvVar("JEPSEN_TPU_SERVE_REQUEST_TIMEOUT", "600.0",
           "serve/daemon.py",
           "seconds a handler waits on the device thread before "
           "answering 500"),
    EnvVar("JEPSEN_TPU_SERVICE", "unset",
           "serve/client.py",
           "service routing: `1` requires the resident daemon, `auto` "
           "spawns one, `0`/unset stays in-process"),
    EnvVar("JEPSEN_TPU_WAL", "verdict-wal.jsonl",
           "serve/daemon.py",
           "verdict write-ahead-log path for the `serve()` production "
           "entry; settled verdicts survive kill -9 and replay into "
           "retried request ids; falsy disables"),
    EnvVar("JEPSEN_TPU_WAL_COMPACT_BYTES", "33554432",
           "serve/daemon.py",
           "WAL size past which the daemon compacts away completed "
           "runs' rows during idle turns; `0` disables"),
)


def names() -> frozenset:
    return frozenset(v.name for v in REGISTRY)


def render_table() -> str:
    """The generated markdown table for doc/configuration.md —
    ``seam-env-doc`` pins the committed doc to exactly this output."""
    lines = [
        "| variable | default | read in | meaning |",
        "|---|---|---|---|",
    ]
    for v in REGISTRY:
        lines.append(
            f"| `{v.name}` | {v.default} | `{v.read_in}` | {v.meaning} |"
        )
    return "\n".join(lines)


def iter_registry() -> Iterator[EnvVar]:
    return iter(REGISTRY)


if __name__ == "__main__":
    print(render_table())
