"""Resident checker service: the device, the compiled-kernel cache,
and the oracle worker pool stay warm in one long-lived daemon; client
runs ship encoded histories over a local HTTP seam and share
coalesced device dispatches.

Why: every ``cli test`` run pays backend init and per-shape re-jit,
and init alone can eat the accelerator win.
For the ROADMAP's millions-of-users traffic the device must be
resident; the paper's ``check(self, test, history, opts)`` seam was
designed exactly so the execution substrate could swap without
touching tests, and this daemon is the next substrate.

The split that makes it possible lives in :mod:`jepsen_tpu.engine`:
the pure per-run **planning** layer runs on the daemon's request
handler threads (and unchanged in every in-process run), while ONE
resident device-owning **executor** serves every client — same-shape
buckets from concurrent runs merge into shared dispatch chunks, with
per-row ``(ctx, idx)`` tokens routing each verdict home.

Layout:

- :mod:`~jepsen_tpu.serve.protocol` — wire forms (models, histories,
  opts), endpoint contract, ``UnsupportedModel`` fallback rule.
- :mod:`~jepsen_tpu.serve.daemon` — :class:`CheckerDaemon`: admission
  control, cross-run coalescing, the device thread, live
  ``/metrics``+``/healthz``+``/status``.
- :mod:`~jepsen_tpu.serve.client` — :class:`ServiceClient`,
  :func:`~jepsen_tpu.serve.client.check_batch` (transparent
  fallback), :func:`ServiceChecker` (the ``check(...)`` seam).
- :mod:`~jepsen_tpu.serve.smoke` — ``make serve-smoke``: verdict
  equality vs the in-process engine, warm-cache proof, metrics
  validity, drain-on-shutdown.
- :mod:`~jepsen_tpu.serve.router` — :class:`Router`, the fleet tier's
  routing front: rendezvous-hashes shape keys over ``--member``
  daemons so same-shape traffic coalesces on one resident executor,
  with breaker-driven spillover and probe-driven re-routing.
- :mod:`~jepsen_tpu.serve.aotcache` — the shared on-disk AOT
  executable cache: a restarted member warms from the fleet manifest
  and answers its first request with zero cold dispatches.
- :mod:`~jepsen_tpu.serve.fleet_smoke` — ``make fleet-smoke``: routed
  verdict byte-equality, coalescing proof, kill/spill/rejoin drill,
  warm-restart zero-rejit assertion.

Start one with ``jepsen-tpu serve --checker`` (or ``python -m
jepsen_tpu.serve``); ``jepsen-tpu status`` / ``jepsen-tpu shutdown``
manage it.  ``JEPSEN_TPU_SERVICE=1`` routes checkers through a
reachable daemon, ``=auto`` spawns one on demand.  See
doc/checker-service.md.
"""

from .client import (  # noqa: F401
    ServiceChecker,
    ServiceClient,
    ServiceError,
    ServiceUnavailable,
    analysis,
    check_batch,
    probe_healthz,
    resolve_client,
    service_mode,
    spawn_daemon,
)
from .daemon import CheckerDaemon, serve  # noqa: F401
from .protocol import DEFAULT_HOST, DEFAULT_PORT, UnsupportedModel  # noqa: F401
from .router import Router  # noqa: F401
