"""Client side of the resident checker service.

:class:`ServiceClient` is the thin HTTP wrapper; :func:`check_batch`
is the transparent seam — try the daemon, fall back to the in-process
engine on ANY service problem (no daemon listening, backlogged 503,
unsupported model, mid-request failure).  The fallback is the same
``wgl.check_batch`` the daemon itself runs, so verdicts cannot depend
on which side did the work.

:class:`ServiceChecker` puts the service behind the unchanged
``check(self, test, history, opts)`` protocol: it IS the
linearizable checker with ``algorithm="service"`` — the whole
post-processing tail (failure witness rendering, field truncation) is
inherited, only the analysis hop changes.  ``checker.linearizable``
resolves ``algorithm="auto"`` to the service when
``JEPSEN_TPU_SERVICE`` opts in, so a fleet can flip every run to the
warm daemon with one environment variable and zero test edits.

Resilience (doc/checker-service.md "Failure modes & recovery"): every
``/check``/``/elle`` POST carries an idempotent request id and runs
through bounded exponential backoff with jitter under an overall
per-request deadline budget, behind a per-address circuit breaker
(N consecutive connection failures trip it open; after a cooldown a
single half-open ``/healthz`` probe decides).  An open breaker
fast-fails to :class:`ServiceUnavailable`, which the transparent seam
turns into the in-process engine — a dead daemon costs one probe per
cooldown, not a connect timeout per batch.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Tuple

from .. import obs
from ..obs import propagate
from . import protocol
from .protocol import UnsupportedModel  # noqa: F401 (re-export)


#: default socket timeout for client requests: a bit above the
#: daemon's own device-thread request timeout (600 s), so a healthy
#: daemon's timeout answer arrives first and a FROZEN daemon (stopped
#: process, dead keep-alive socket) still bounds the checker run —
#: the fallback contract covers hangs, not just refusals
DEFAULT_CLIENT_TIMEOUT_S = 630.0

#: retry/breaker defaults (env-overridable; doc/configuration.md)
DEFAULT_CLIENT_RETRIES = 2
DEFAULT_CLIENT_BACKOFF_S = 0.1
DEFAULT_BREAKER_FAILURES = 3
DEFAULT_BREAKER_COOLDOWN_S = 5.0


def _env_pos_float(name: str, default: float) -> float:
    try:
        v = float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default
    return v if v > 0 else default


def _env_nonneg_int(name: str, default: int) -> int:
    try:
        v = int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default
    return v if v >= 0 else default


class ServiceError(Exception):
    """The daemon was reachable but could not serve the request."""


class ServiceUnavailable(ServiceError):
    """No healthy daemon at the configured address."""


class CircuitBreaker:
    """Per-address breaker: closed → open after ``failures``
    consecutive connection failures → half-open after ``cooldown_s``
    (one probe decides: success closes, failure re-opens).

    Shared by every :class:`ServiceClient` pointed at one address (the
    transparent seam constructs a fresh client per call, so per-client
    state would never accumulate failures) — see :func:`breaker_for`.
    """

    def __init__(self, failures: int = DEFAULT_BREAKER_FAILURES,
                 cooldown_s: float = DEFAULT_BREAKER_COOLDOWN_S):
        self.failures = max(1, failures)
        self.cooldown_s = cooldown_s
        self._lock = threading.Lock()
        self._consecutive = 0  # jt: guarded-by(_lock)
        self._opened_at: Optional[float] = None  # jt: guarded-by(_lock)
        self.trips = 0  #: times the breaker tripped open
        self.probes = 0  #: half-open probes attempted

    def state(self) -> str:
        with self._lock:
            if self._opened_at is None:
                return "closed"
            if time.monotonic() - self._opened_at >= self.cooldown_s:
                return "half-open"
            return "open"

    def allow(self, probe=None) -> bool:
        """True when a request may proceed.  While open within the
        cooldown: False (fast-fail).  After the cooldown: half-open —
        run ``probe()`` (a cheap liveness check); its verdict closes or
        re-opens the breaker."""
        with self._lock:
            if self._opened_at is None:
                return True
            if time.monotonic() - self._opened_at < self.cooldown_s:
                return False
        # half-open: probe outside the lock (it does network I/O)
        ok = bool(probe()) if probe is not None else False
        with self._lock:
            self.probes += 1
            if ok:
                self._opened_at = None
                self._consecutive = 0
                return True
            self._opened_at = time.monotonic()
            return False

    def record_success(self) -> None:
        with self._lock:
            self._consecutive = 0
            self._opened_at = None

    def record_failure(self) -> bool:
        """Count one connection failure; True when this one TRIPPED
        the breaker open."""
        with self._lock:
            self._consecutive += 1
            if (self._opened_at is None
                    and self._consecutive >= self.failures):
                self._opened_at = time.monotonic()
                self.trips += 1
                return True
            return False


#: one breaker per daemon address, process-wide — resolve_client()
#: builds a fresh ServiceClient per seam call, so breaker state must
#: outlive any single client instance
_BREAKERS: Dict[Tuple[str, Optional[int]], CircuitBreaker] = {}
_breakers_lock = threading.Lock()


def breaker_for(host: str, port: Optional[int]) -> CircuitBreaker:
    key = (host, port)
    with _breakers_lock:
        br = _BREAKERS.get(key)
        if br is None:
            br = _BREAKERS[key] = CircuitBreaker(
                failures=_env_nonneg_int("JEPSEN_TPU_BREAKER_FAILURES",
                                         DEFAULT_BREAKER_FAILURES)
                or DEFAULT_BREAKER_FAILURES,
                cooldown_s=_env_pos_float("JEPSEN_TPU_BREAKER_COOLDOWN",
                                          DEFAULT_BREAKER_COOLDOWN_S),
            )
        return br


def reset_breakers() -> None:
    """Forget all breaker state (tests, and a fresh daemon spawn)."""
    with _breakers_lock:
        _BREAKERS.clear()


def probe_healthz(addr: str, timeout: float = 0.5) -> bool:
    """THE ``/healthz`` probe — the one implementation behind both the
    client breaker's half-open cooldown probe and the router's member
    health sweeps, so the two share a single timeout/exception taxonomy
    (connection-level failures AND malformed bodies are both "down")
    instead of drifting apart as hand-rolled urlopen paths.  ``addr``
    is ``HOST:PORT``.  Counted in ``jepsen_probe_healthz_total`` by
    outcome; never raises."""
    req = urllib.request.Request(f"http://{addr}/healthz", method="GET")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            ok = (resp.status == 200
                  and bool(protocol.decode_body(resp.read()).get("ok")))
    except (urllib.error.URLError, ConnectionError, OSError, ValueError):
        ok = False
    obs.count("jepsen_probe_healthz_total", outcome="up" if ok else "down")
    return ok


def service_mode() -> str:
    """``JEPSEN_TPU_SERVICE``: ``""``/``0`` off (default), ``1``/any
    truthy = use a reachable daemon, ``auto`` = additionally spawn one
    when none is listening."""
    v = os.environ.get("JEPSEN_TPU_SERVICE", "").strip().lower()
    if v in ("", "0", "false", "off", "no"):
        return "off"
    if v == "auto":
        return "auto"
    return "on"


class ServiceClient:
    """HTTP client for one daemon address (default: localhost
    ``JEPSEN_TPU_SERVE_PORT`` / :data:`protocol.DEFAULT_PORT`)."""

    def __init__(
        self,
        host: Optional[str] = None,
        port: Optional[int] = None,
        timeout: Optional[float] = None,
    ):
        self.host = host or os.environ.get(
            "JEPSEN_TPU_SERVE_HOST", protocol.DEFAULT_HOST)
        try:
            self.port: Optional[int] = int(
                port
                if port is not None
                else os.environ.get("JEPSEN_TPU_SERVE_PORT",
                                    protocol.DEFAULT_PORT)
            )
        except (TypeError, ValueError):
            # a mis-set JEPSEN_TPU_SERVE_PORT must degrade like an
            # absent daemon (the seam promises in-process fallback for
            # ANY service problem), never crash the checker run —
            # and silently retargeting the default port could hit a
            # daemon the user didn't intend
            self.port = None
        self.timeout = timeout
        self.last_diag: dict = {}
        self.spawned_pid: Optional[int] = None

    def _url(self, path: str) -> str:
        return f"http://{self.host}:{self.port}{path}"

    def _request(self, path: str, body: Optional[bytes] = None,
                 timeout: Optional[float] = None):
        if self.port is None:
            raise ServiceUnavailable(
                "invalid JEPSEN_TPU_SERVE_PORT "
                f"({os.environ.get('JEPSEN_TPU_SERVE_PORT')!r})")
        req = urllib.request.Request(
            self._url(path),
            data=body,
            method="POST" if body is not None else "GET",
            headers={"Content-Type": "application/json"}
            if body is not None else {},
        )
        try:
            with urllib.request.urlopen(
                req,
                timeout=timeout or self.timeout or DEFAULT_CLIENT_TIMEOUT_S,
            ) as resp:
                return resp.status, resp.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()
        except (urllib.error.URLError, ConnectionError, OSError) as e:
            raise ServiceUnavailable(f"no daemon at {self._url('')}: {e}")

    def _resilient_post(self, path: str, body: bytes):
        """POST with retry/backoff/deadline through the address's
        circuit breaker (the body — and its idempotent request id —
        is byte-identical across attempts, so the daemon can dedupe).

        - **deadline budget**: the whole call (attempts + backoff
          sleeps) is bounded by ``JEPSEN_TPU_CLIENT_DEADLINE`` (or the
          client's own timeout when smaller) — a stalled daemon can
          never hang the checker past it.
        - **retries**: connection-level failures retry up to
          ``JEPSEN_TPU_CLIENT_RETRIES`` times with exponential backoff
          + full jitter from ``JEPSEN_TPU_CLIENT_BACKOFF``.  HTTP-level
          errors (503 backlog, daemon-side 500) do NOT retry: the
          daemon answered; retrying would fight its load shedding.
        - **breaker**: open → immediate :class:`ServiceUnavailable`
          (the seam falls back in-process); half-open → one
          ``/healthz`` probe decides.
        """
        br = breaker_for(self.host, self.port)
        if not br.allow(lambda: self._probe(br)):
            raise ServiceUnavailable(
                f"circuit open for {self.host}:{self.port} "
                f"(state {br.state()})")
        attempt_timeout = self.timeout or DEFAULT_CLIENT_TIMEOUT_S
        budget = min(
            _env_pos_float("JEPSEN_TPU_CLIENT_DEADLINE",
                           DEFAULT_CLIENT_TIMEOUT_S),
            attempt_timeout if self.timeout else float("inf"),
        )
        deadline = time.monotonic() + budget
        retries = _env_nonneg_int("JEPSEN_TPU_CLIENT_RETRIES",
                                  DEFAULT_CLIENT_RETRIES)
        backoff = _env_pos_float("JEPSEN_TPU_CLIENT_BACKOFF",
                                 DEFAULT_CLIENT_BACKOFF_S)
        attempt = 0
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                obs.count("jepsen_client_deadline_exhausted_total")
                raise ServiceUnavailable(
                    f"deadline budget ({budget:.1f}s) exhausted for "
                    f"{self._url(path)}")
            try:
                code, resp = self._request(
                    path, body=body,
                    timeout=min(attempt_timeout, remaining))
            except ServiceUnavailable:
                if br.record_failure():
                    obs.count("jepsen_client_breaker_trips_total")
                attempt += 1
                remaining = deadline - time.monotonic()
                delay = min(backoff * (2 ** (attempt - 1)), remaining)
                delay *= 0.5 + random.random() / 2  # full jitter
                if attempt > retries or remaining <= delay:
                    raise
                obs.count("jepsen_client_retries_total")
                time.sleep(delay)
                continue
            br.record_success()
            return code, resp

    def _probe(self, br: CircuitBreaker) -> bool:
        """The half-open liveness probe (cheap, hard-bounded)."""
        obs.count("jepsen_client_breaker_probes_total")
        return self.healthy(timeout=0.5)

    def healthy(self, timeout: float = 0.5) -> bool:
        if self.port is None:
            return False
        return probe_healthz(f"{self.host}:{self.port}", timeout=timeout)

    def status(self) -> dict:
        code, body = self._request("/status", timeout=self.timeout or 5)
        if code != 200:
            raise ServiceError(f"status returned {code}")
        return protocol.decode_body(body)

    def metrics_text(self) -> str:
        code, body = self._request("/metrics", timeout=self.timeout or 5)
        if code != 200:
            raise ServiceError(f"metrics returned {code}")
        return body.decode()

    def shutdown(self) -> dict:
        code, body = self._request("/shutdown", body=b"{}",
                                   timeout=self.timeout or 5)
        if code != 200:
            raise ServiceError(f"shutdown returned {code}")
        return protocol.decode_body(body)

    def profile(self, seconds: float = 1.0, label: str = "",
                out_dir: Optional[str] = None) -> dict:
        """``POST /profile``: one bounded on-demand profiling window on
        the daemon (obs.profiling) — returns ``{dir, manifest}``.  The
        timeout covers the capture window itself, plus headroom."""
        req: dict = {"seconds": float(seconds)}
        if label:
            req["label"] = str(label)
        if out_dir:
            req["dir"] = out_dir
        code, body = self._request(
            "/profile", body=protocol.encode_body(req),
            timeout=max(self.timeout or 0.0, float(seconds) + 30.0))
        if code != 200:
            raise ServiceError(f"profile returned {code}")
        return protocol.decode_body(body)

    def _trace_ctx(self, span) -> Optional[dict]:
        """Wire ``trace_ctx`` for the current client ``span`` — None
        when tracing is off (NULL_SPAN has no sid), so untraced runs
        send exactly the pre-telemetry body."""
        sid = getattr(span, "sid", None)  # NULL_SPAN has no sid
        if not obs.enabled() or sid is None:
            return None
        ctx = propagate.make_ctx(parent_sid=sid)
        span.set(propagate.ATTR_TRACE_ID, ctx["trace_id"])
        span.set(propagate.ATTR_ROLE, "client")
        return ctx

    def fetch_trace(self, trace_id: str) -> int:
        """Pull the daemon's span dump for ``trace_id`` (``GET
        /trace?ctx=``) and adopt it into the local tracer so
        ``obs.export_all`` stitches one merged Chrome trace.  Degrades
        silently — telemetry must never fail a checker run."""
        try:
            code, body = self._request(
                f"/trace?ctx={trace_id}", timeout=self.timeout or 5)
            if code != 200:
                return 0
            payload = protocol.decode_body(body)
            return propagate.adopt(
                payload.get("spans") or [],
                pid=payload.get("pid"),
                wall_origin=payload.get("wall_origin"),
                origin_ns=payload.get("origin_ns"),
            )
        except (ServiceError, ServiceUnavailable, ValueError, KeyError,
                TypeError):
            return 0

    def screen_graphs(self, encs) -> list:
        """Screen encoded dependency graphs on the daemon (``POST
        /elle``); same ScreenResult shapes the in-process
        ``ops.cycles.screen_graphs`` returns.  Raises like
        :meth:`check_batch` — the caller decides whether to fall
        back."""
        with obs.span("client/elle", cat="serve", graphs=len(encs)) as sp:
            ctx = self._trace_ctx(sp)
            body = protocol.elle_request(encs, trace_ctx=ctx,
                                         req=protocol.request_id())
            code, resp = self._resilient_post("/elle", body)
            payload = protocol.decode_body(resp)
            if code == 503:
                raise ServiceError(
                    f"daemon backlogged: {payload.get('error')}")
            if code != 200:
                raise ServiceError(
                    f"/elle returned {code}: {payload.get('error')}")
            results = payload["results"]
            if len(results) != len(encs):
                raise ServiceError(
                    f"result count {len(results)} != batch {len(encs)}")
            self.last_diag = payload.get("diag") or {}
            out = protocol.elle_results_from_wire(results, encs)
        if ctx:
            self.fetch_trace(ctx["trace_id"])
        return out

    def check_batch(self, model, histories, **opts) -> List[dict]:
        """Check a batch on the daemon; raises
        :class:`~jepsen_tpu.serve.protocol.UnsupportedModel` (no wire
        form / unserviceable opt), :class:`ServiceUnavailable`, or
        :class:`ServiceError` (backlogged, daemon-side failure) — the
        caller decides whether to fall back."""
        with obs.span(
            "client/check", cat="serve", histories=len(histories),
        ) as sp:
            ctx = self._trace_ctx(sp)
            body = protocol.check_request(model, histories, opts,
                                          trace_ctx=ctx,
                                          req=protocol.request_id())
            code, resp = self._resilient_post("/check", body)
            payload = protocol.decode_body(resp)
            if code == 503:
                raise ServiceError(
                    f"daemon backlogged: {payload.get('error')}")
            if code != 200:
                raise ServiceError(
                    f"/check returned {code}: {payload.get('error')}")
            results = payload["results"]
            if len(results) != len(histories):
                raise ServiceError(
                    f"result count {len(results)} != batch"
                    f" {len(histories)}")
            self.last_diag = payload.get("diag") or {}
        if ctx:
            self.fetch_trace(ctx["trace_id"])
        return results

    def open_feed(self, model, opts: Optional[dict] = None,
                  req: Optional[str] = None) -> "FeedSession":
        """Open a streaming-ingest session (``POST /feed`` op=open) and
        return its :class:`FeedSession`.  ``req`` doubles as the
        session id and the verdict-WAL run id, so passing the same id
        after a daemon crash resumes against the replayed WAL rows."""
        return FeedSession(self, model, opts=opts, req=req).open()

    def watch(self, last_id: int = -1, timeout: Optional[float] = None):
        """Subscribe to the daemon's verdict channel (``GET /watch``)
        and yield ``(offset, row)`` tuples as verdicts settle.

        One generator == one HTTP connection.  ``last_id`` >= 0 is sent
        as ``Last-Event-ID`` so replay resumes *after* that WAL row.
        The generator ends (rather than raising) when the connection
        drops or the read ``timeout`` expires with the daemon quiet —
        callers reconnect with the last offset they saw.  Raises
        :class:`ServiceUnavailable` only when the initial connection
        fails.
        """
        headers = {}
        if last_id >= 0:
            headers["Last-Event-ID"] = str(last_id)
        request = urllib.request.Request(self._url("/watch"),
                                         headers=headers)
        try:
            resp = urllib.request.urlopen(
                request, timeout=timeout or self.timeout or 30.0)
        except urllib.error.HTTPError as e:
            raise ServiceError(f"/watch returned {e.code}")
        except (urllib.error.URLError, ConnectionError, OSError) as e:
            raise ServiceUnavailable(
                f"no daemon at {self._url('/watch')}: {e}")
        try:
            event_id = None
            data: Optional[str] = None
            for raw in resp:
                line = raw.decode("utf-8", "replace").rstrip("\r\n")
                if not line:  # blank line terminates one SSE event
                    if data is not None:
                        try:
                            row = json.loads(data)
                        except ValueError:
                            row = None
                        if isinstance(row, dict):
                            try:
                                off = int(event_id)
                            except (TypeError, ValueError):
                                off = -1
                            yield off, row
                    event_id, data = None, None
                elif line.startswith(":"):
                    pass  # keep-alive comment
                elif line.startswith("id:"):
                    event_id = line[3:].strip()
                elif line.startswith("data:"):
                    chunk = line[5:].strip()
                    data = chunk if data is None else data + chunk
        except (ConnectionError, OSError):
            return  # subscriber-side disconnect: end of stream
        finally:
            resp.close()


class FeedSession:
    """Client half of one streaming-ingest session.

    Appends carry a monotonically increasing ``seq``; the daemon acks
    ``seq <= last_seq`` as a duplicate without re-dispatching, so a
    retried append (connection reset after the daemon ingested it) is
    safe.  ``append`` only advances ``seq`` after a 200, which makes
    the retry loop in the caller trivially idempotent.
    """

    def __init__(self, client: ServiceClient, model,
                 opts: Optional[dict] = None,
                 req: Optional[str] = None):
        self.client = client
        self.model = model
        self.opts = dict(opts or {})
        self.req = req or protocol.request_id()
        self.sid: Optional[str] = None
        self.seq = 0
        self.resumed = False
        self.closed = False
        self.last_diag: dict = {}

    def open(self) -> "FeedSession":
        body = protocol.feed_open_request(self.model, self.opts,
                                          req=self.req)
        code, resp = self.client._resilient_post("/feed", body)
        payload = protocol.decode_body(resp)
        if code == 503:
            raise ServiceError(
                f"daemon backlogged: {payload.get('error')}")
        if code != 200:
            raise ServiceError(
                f"/feed open returned {code}: {payload.get('error')}")
        self.sid = payload["session"]
        self.resumed = bool(payload.get("resumed"))
        return self

    def append(self, histories=None, ops=None,
               t_inv: Optional[float] = None) -> dict:
        """Ship one delta — whole histories and/or raw op events (both
        invocations and completions, in history-append order).  Returns
        the daemon's ack (``accepted``/``rows``/``settled``/``diag``)."""
        if self.sid is None:
            raise ServiceError("feed session not open")
        body = protocol.feed_append_request(
            self.sid, self.seq, histories=histories, ops=ops,
            t_inv=t_inv)
        code, resp = self.client._resilient_post("/feed", body)
        payload = protocol.decode_body(resp)
        if code == 503:
            raise ServiceError(
                f"daemon backlogged: {payload.get('error')}")
        if code != 200:
            raise ServiceError(
                f"/feed append returned {code}: {payload.get('error')}")
        self.seq += 1
        self.last_diag = payload.get("diag") or {}
        return payload

    def close(self) -> List[dict]:
        """Finalize the session; returns the settled results (client
        histories in feed order, assembled op-history last when ops
        were fed) — byte-identical to a one-shot ``/check`` of the same
        histories."""
        if self.sid is None:
            raise ServiceError("feed session not open")
        body = protocol.feed_close_request(self.sid, self.seq,
                                           req=self.req + ":close")
        code, resp = self.client._resilient_post("/feed", body)
        payload = protocol.decode_body(resp)
        if code != 200:
            raise ServiceError(
                f"/feed close returned {code}: {payload.get('error')}")
        self.closed = True
        self.last_diag = payload.get("diag") or {}
        return payload["results"]


def _reap(proc, grace_s: float = 10.0) -> None:
    """Terminate a child without ever leaking it: SIGTERM → bounded
    wait → SIGKILL → bounded wait.  The second wait can still time out
    (a child stuck in uninterruptible sleep survives SIGKILL until the
    kernel releases it); that is swallowed — the caller's error path
    must not be replaced by ``TimeoutExpired``, and the kernel will
    reap the KILLed child without us."""
    proc.terminate()
    try:
        proc.wait(timeout=grace_s)
        return
    except subprocess.TimeoutExpired:
        pass
    proc.kill()
    try:
        proc.wait(timeout=grace_s)
    except subprocess.TimeoutExpired:
        pass


def spawn_daemon(port: Optional[int] = None,
                 wait_s: float = 60.0) -> ServiceClient:
    """Start a daemon subprocess (``python -m jepsen_tpu.serve``) and
    wait until it answers /healthz.  Used by
    ``JEPSEN_TPU_SERVICE=auto``."""
    client = ServiceClient(port=port)
    if client.port is None:
        raise ServiceUnavailable("invalid JEPSEN_TPU_SERVE_PORT")
    if client.healthy():
        return client
    argv = [sys.executable, "-m", "jepsen_tpu.serve",
            "--port", str(client.port)]
    proc = subprocess.Popen(
        argv, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        start_new_session=True,
    )
    deadline = time.monotonic() + wait_s
    while time.monotonic() < deadline:
        if client.healthy():
            client.spawned_pid = proc.pid
            return client
        if proc.poll() is not None:
            raise ServiceUnavailable(
                f"spawned daemon exited with {proc.returncode}")
        time.sleep(0.25)
    # reap it: an unwaited child is a zombie for our lifetime, and a
    # half-initialized daemon surviving SIGTERM would squat the port
    # in an unknown state for the next auto-start
    _reap(proc)
    raise ServiceUnavailable(f"daemon not healthy within {wait_s}s")


def resolve_client(auto_start: Optional[bool] = None
                   ) -> Optional[ServiceClient]:
    """A healthy client per the environment policy, or None (caller
    runs in-process).  ``auto_start`` overrides the ``auto`` half of
    :func:`service_mode`; a process that already holds the accelerator
    never starts a daemon child, since the child could not have the
    chip — it logs that and checks in-process."""
    mode = service_mode()
    if auto_start is None:
        auto_start = mode == "auto"
    client = ServiceClient()
    if client.healthy():
        return client
    if auto_start:
        from ..platform import holds_accelerator

        if holds_accelerator():
            # the chip is this process's until it exits: a daemon
            # child could not load it, so check in-process, and say so
            import logging

            logging.getLogger(__name__).warning(
                "JEPSEN_TPU_SERVICE=auto: this process already holds "
                "the accelerator, so no daemon was started; checking "
                "in-process")
            return None
        try:
            return spawn_daemon()
        except ServiceUnavailable:
            return None
    return None


def mesh_matches_daemon(client: ServiceClient, mesh) -> bool:
    """True when the daemon's resident mesh has the same SHAPE as the
    caller's requested ``mesh`` (the full device grid, compared
    against ``/status`` ``mesh_shape`` — a same-size mesh with a
    different axis layout is NOT a match; the daemon would partition
    differently than the caller asked).  A Mesh object cannot cross
    the wire, but it doesn't need to: when the shapes agree the
    daemon's own resident mesh partitions the batch exactly as the
    client's in-process engine would — so the explicit-mesh opt is
    droppable, not unserviceable (the PR-6 restriction, lifted
    shape-wise)."""
    try:
        shape = list(mesh.devices.shape)
    except (AttributeError, TypeError):
        return False
    try:
        st = client.status()
    except (ServiceError, ServiceUnavailable):
        return False
    return st.get("mesh_shape") == shape and st.get("n_devices") == int(
        mesh.devices.size
    )


def check_batch(model, histories, *, client: Optional[ServiceClient] = None,
                auto_start: Optional[bool] = None,
                require_opt_in: bool = False, **opts) -> List[dict]:
    """The transparent seam: daemon when reachable, in-process
    otherwise — same verdicts either way (serve-smoke pins it).
    ``oracle_budget_s``, ``window``, and an explicit ``decomposed``
    override are engine-side only and force the in-process path (the
    daemon owns its own window and decomposition policy; budget
    semantics need the run's serial drain — see protocol.py).  An
    explicit ``mesh`` is serviceable when its shape MATCHES the
    daemon's resident mesh (``/status`` ``n_devices``): the daemon
    shards identically through its own mesh, so the opt is dropped
    from the wire rather than forcing the batch in-process; a
    mismatched shape still runs in-process — the caller asked for a
    partitioning the daemon cannot honor.

    ``require_opt_in=True`` is for default-path callers (the batched
    linearizable seam): the daemon is only consulted when
    ``JEPSEN_TPU_SERVICE`` opts the run in, so a stray listener can
    never silently take another run's traffic.  Explicit service users
    (``ServiceChecker``, ``algorithm="service"``, a passed ``client``)
    leave it False."""
    from ..ops import wgl

    mesh = opts.get("mesh")
    serviceable = (
        opts.get("oracle_budget_s") is None
        and opts.get("window") is None
        # an explicit decomposed= override is engine-side only (the
        # daemon decomposes per ITS environment): honoring it means
        # running in-process, not silently dropping the opt on the wire
        and opts.get("decomposed") is None
        and not (require_opt_in and client is None
                 and service_mode() == "off")
    )
    if serviceable:
        if client is None:
            client = resolve_client(auto_start)
        if (client is not None and mesh is not None
                and not mesh_matches_daemon(client, mesh)):
            client = None  # shape mismatch: honor the mesh in-process
        if client is not None:
            wire_opts = {
                k: v for k, v in opts.items()
                if k in protocol.CHECK_OPTS and v is not None
            }
            try:
                return client.check_batch(model, histories, **wire_opts)
            except (UnsupportedModel, ServiceError):
                pass  # transparent fallback below
    return wgl.check_batch(model, histories, **opts)


def analysis(model, history, **kw) -> dict:
    """Single-history :func:`check_batch` (the checker-seam shape)."""
    return check_batch(model, [history], **kw)[0]


def screen_graphs(encs, *, client: Optional[ServiceClient] = None,
                  auto_start: Optional[bool] = None) -> Optional[list]:
    """The Elle screens' transparent service seam: screen on a
    reachable daemon (coalescing with concurrent runs' graphs on its
    resident executor), or return ``None`` so the caller runs the
    in-process engine path.  Like the batched-linearizable seam this
    is opt-in by default: with ``JEPSEN_TPU_SERVICE`` off and no
    explicit client, a stray listener never takes the traffic."""
    if client is None:
        if service_mode() == "off":
            return None
        client = resolve_client(auto_start)
    if client is None:
        return None
    try:
        return client.screen_graphs(encs)
    except (ServiceError, ServiceUnavailable):
        return None  # transparent in-process fallback


def ServiceChecker(model, pure_fs=("read",), oracle_budget_s=None):
    """The resident-service linearizability checker, behind the
    unchanged ``check(self, test, history, opts)`` seam: connects to
    (or, under ``JEPSEN_TPU_SERVICE=auto``, starts) the local daemon
    and falls back transparently to the in-process engine when none is
    reachable.  This is ``checker.linearizable(algorithm="service")``
    — witness rendering and result truncation are shared with every
    other algorithm."""
    from ..checker import linearizable

    return linearizable(
        model, algorithm="service", pure_fs=pure_fs,
        oracle_budget_s=oracle_budget_s,
    )


def format_status(st: dict) -> str:
    """Render a /status dict as the CLI `status` table."""
    mesh_shape = st.get("mesh_shape")
    devices = (
        f"{st.get('n_devices')} devices (mesh {mesh_shape})"
        if mesh_shape else f"{st.get('n_devices') or 1} device"
    )
    lines = [
        "── checker service " + "─" * 29,
        f"  pid {st.get('pid')} on platform {st.get('platform')}"
        f" · {devices}"
        f" · up {st.get('uptime_s', 0):.0f}s"
        + (" · DRAINING" if st.get("stopping") else ""),
        f"  requests: {st.get('requests', 0)}"
        f" ({st.get('histories', 0)} histories,"
        f" {st.get('rejected', 0)} rejected,"
        f" {st.get('errors', 0)} errors)",
        f"  queue: {st.get('queue_depth', 0)}/{st.get('max_queue_runs')}"
        f" · coalesced: {st.get('coalesced', 0)}"
        f" · window: {st.get('window')}"
        f" · calibration: {st.get('calibration') or 'defaults'}",
    ]
    ratio = st.get("warm_hit_ratio")
    warm = (f"{ratio:.0%}" if isinstance(ratio, (int, float)) else "n/a")
    lines.append(
        f"  dispatches: {st.get('cold_dispatches', 0)} cold"
        f" + {st.get('warm_dispatches', 0)} warm"
        f" (warm-hit ratio {warm})"
    )
    if (st.get("feed_open") or st.get("feed_sessions")
            or st.get("watch_subscribers")):
        lines.append(
            f"  online: {st.get('feed_open', 0)} open feed(s)"
            f" ({st.get('feed_sessions', 0)} sessions,"
            f" {st.get('feed_deltas', 0)} deltas,"
            f" {st.get('feed_histories', 0)} histories)"
            f" · watchers {st.get('watch_subscribers', 0)}"
            f" ({st.get('watch_events', 0)} events)"
            f" · compactions {st.get('wal_compactions', 0)}"
        )
    quarantine = st.get("quarantine") or []
    if quarantine:
        lines.append(
            "  quarantine: "
            + ", ".join(f"{q.get('route')} → oracle ({q.get('error')})"
                        for q in quarantine)
        )
    live = st.get("live")
    if live:
        lines.append("  " + format_live(live))
    jp = st.get("journal_path")
    if jp:
        lines.append(
            f"  journal: {st.get('journal_rows', 0)} rows → {jp}")
    drift = st.get("drift")
    if drift:
        lines.append("  " + format_drift(drift))
    return "\n".join(lines)


def format_fleet_status(rows) -> str:
    """The fleet table for ``jepsen_tpu status --daemon … --daemon …``:
    one row per member with the operator-facing columns (devices,
    mesh, calibration identity, drift score, quarantined routes, live
    busy ratio, and the routing weight the router's prober would
    derive from that busy ratio — ``router.weight_from_busy``, so the
    table shows the same number ``jepsen_route_weight`` exports).
    ``rows`` is a sequence of ``(addr, status_or_None)`` — ``None``
    marks a member that did not answer ``/status``."""
    from . import router as router_mod  # client ← router is the cycle

    cols = ["member", "devices", "mesh", "calibration", "drift",
            "quarantined", "busy", "weight"]
    table = [cols]
    for addr, st in rows:
        if st is None:
            table.append([addr, "-", "-", "unreachable",
                          "-", "-", "-", "-"])
            continue
        drift = st.get("drift") or {}
        score = drift.get("score")
        busy = (st.get("live") or {}).get("device_busy_ratio")
        weight = router_mod.weight_from_busy(
            busy if isinstance(busy, (int, float)) else None)
        table.append([
            addr,
            str(st.get("n_devices") or 1),
            str(st.get("mesh_shape") or "-"),
            str(st.get("calibration") or "defaults"),
            (f"{score:.2f}×" + ("!" if drift.get("retune_recommended")
                                else "")
             if isinstance(score, (int, float)) else "n/a"),
            str(len(st.get("quarantine") or [])),
            f"{busy:.0%}" if isinstance(busy, (int, float)) else "n/a",
            f"{weight:.2f}",
        ])
    widths = [max(len(r[i]) for r in table) for i in range(len(cols))]
    lines = ["── fleet " + "─" * 39]
    for i, r in enumerate(table):
        lines.append("  " + "  ".join(
            c.ljust(w) for c, w in zip(r, widths)).rstrip())
        if i == 0:
            lines.append("  " + "  ".join("─" * w for w in widths))
    return "\n".join(lines)


def format_drift(drift: dict) -> str:
    """One-line drift-sentinel view of a /status ``drift`` block
    (obs.drift): aggregate score vs threshold, shape census, and —
    when the sentinel recommends one — the retune call-out naming the
    stale shapes."""
    score = drift.get("score")
    score_s = (f"{score:.2f}×" if isinstance(score, (int, float))
               else "n/a")
    line = (
        f"drift: score {score_s}"
        f" (threshold {drift.get('threshold')}×)"
        f" · {drift.get('shapes', 0)} shape(s)"
        f" · {drift.get('rows_scored', 0)} rows scored"
    )
    stale = drift.get("stale") or []
    if drift.get("retune_recommended"):
        shapes = ", ".join(
            f"{s.get('kernel')}(E={s.get('E')},C={s.get('C')},"
            f"F={s.get('F')})@{s.get('ratio')}×"
            for s in stale
        )
        line += f" · RETUNE RECOMMENDED: {shapes or 'aggregate'}"
    return line


def _rate(live: dict, key: str) -> str:
    v = live.get(key)
    return f"{v:.2f}/s" if isinstance(v, (int, float)) else "n/a"


def format_live(live: dict) -> str:
    """One-line last-60 s view of a /status ``live`` dict (the
    sliding-window rates; doc/observability.md 'Fleet telemetry')."""
    qw = live.get("queue_wait_mean_s")
    busy = live.get("device_busy_ratio")
    return (
        f"last 60s: req {_rate(live, 'requests_per_s')}"
        f" · hist {_rate(live, 'histories_per_s')}"
        f" · elle {_rate(live, 'elle_graphs_per_s')}"
        f" · disp {_rate(live, 'dispatches_per_s')}"
        f" · feed {_rate(live, 'feed_deltas_per_s')}"
        f" · watch {_rate(live, 'watch_events_per_s')}"
        f" · wait "
        + (f"{qw * 1e3:.1f}ms" if isinstance(qw, (int, float)) else "n/a")
        + " · busy "
        + (f"{busy:.0%}" if isinstance(busy, (int, float)) else "n/a")
    )


def format_top(host: str, port, st: dict) -> str:
    """One daemon's fleet-view block for ``jepsen_tpu top``: identity
    line, last-60 s rates, queue/journal line."""
    mesh = st.get("mesh_shape")
    live = st.get("live") or {}
    head = (
        f"● {host}:{port}  pid {st.get('pid')}"
        f" · {st.get('n_devices') or 1} device(s)"
        + (f" · mesh {mesh}" if mesh else "")
        + f" · up {st.get('uptime_s', 0):.0f}s"
        + (" · DRAINING" if st.get("stopping") else "")
    )
    jp = st.get("journal_path")
    # quarantined routes + drift score ride the same summary line:
    # the two "this daemon needs an operator" signals the fleet view
    # previously never showed
    quarantined = len(st.get("quarantine") or [])
    drift = st.get("drift") or {}
    score = drift.get("score")
    tail = (
        f"  queue {st.get('queue_depth', 0)}/{st.get('max_queue_runs')}"
        f" · in-flight {st.get('in_flight', 0)}"
        f" · coalesced {st.get('coalesced', 0)}"
        + (f" · feeds {st.get('feed_open', 0)}"
           if st.get("feed_open") else "")
        + (f" · watchers {st.get('watch_subscribers', 0)}"
           if st.get("watch_subscribers") else "")
        + (f" · journal {st.get('journal_rows', 0)} rows" if jp else "")
        + f" · quarantined {quarantined}"
        + (f" · drift {score:.2f}×"
           + ("!" if drift.get("retune_recommended") else "")
           if isinstance(score, (int, float)) else "")
    )
    return "\n".join([head, "  " + format_live(live), tail])


def format_verdicts(events, limit: int = 8) -> str:
    """Render the newest settled verdicts as ``jepsen_tpu top``'s
    verdicts pane.  ``events`` is a sequence of ``(addr, offset, row)``
    tuples collected off one or more ``/watch`` channels (newest
    last); only the trailing ``limit`` rows are shown."""
    lines = ["── verdicts " + "─" * 36]
    rows = list(events)[-limit:]
    if not rows:
        lines.append("  (no settled verdicts yet)")
        return "\n".join(lines)
    now = time.time()
    for addr, off, row in rows:
        res = row.get("result") or {}
        valid = res.get("valid?")
        mark = "✗" if valid is False else ("✓" if valid is True else "?")
        ts = row.get("ts")
        age = (f"{max(0.0, now - float(ts)):.0f}s ago"
               if isinstance(ts, (int, float)) else "t?")
        extra = ""
        if valid is False:
            anom = res.get("anomaly-types") or res.get("anomalies")
            if anom:
                extra = f" · {anom}"
        lines.append(
            f"  {mark} {addr} #{off}"
            f" req {str(row.get('req'))[:8]}"
            f" {row.get('stream')}[{row.get('idx')}]"
            f" · {age}{extra}"
        )
    return "\n".join(lines)
