"""``python -m jepsen_tpu.serve`` — run the resident checker daemon.

Equivalent to ``jepsen-tpu serve --checker``; exists so the client's
auto-start (``JEPSEN_TPU_SERVICE=auto``) has a suite-independent
entry point.
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m jepsen_tpu.serve",
        description="resident checker service (doc/checker-service.md)",
    )
    p.add_argument("--host", default=None, help="bind address "
                   "(default 127.0.0.1 — the seam is local)")
    p.add_argument("--port", type=int, default=None,
                   help="TCP port (default JEPSEN_TPU_SERVE_PORT or 8519)")
    p.add_argument("--window", type=int, default=None,
                   help="resident dispatch-window bound "
                   "(default JEPSEN_TPU_ENGINE_WINDOW or 4)")
    p.add_argument("--max-queue", type=int, default=None,
                   help="admission bound: queued client runs before "
                   "/check answers 503 (default 8)")
    p.add_argument("--wal", default=None,
                   help="verdict write-ahead log path (default "
                   "JEPSEN_TPU_WAL or verdict-wal.jsonl; 'off' "
                   "disables crash-safe resumption)")
    p.add_argument("--supervise", action="store_true",
                   help="run the daemon as a supervised child and "
                   "restart it on abnormal exit (crash recovery; "
                   "doc/checker-service.md)")
    p.add_argument("--fleet", type=int, default=1, metavar="N",
                   help="with --supervise: run N daemons on ports "
                   "--port..--port+N-1 with per-member WAL/journal "
                   "paths and one shared AOT cache (doc/"
                   "checker-service.md \"Fleet tier\")")
    args = p.parse_args(argv)

    from . import daemon, protocol

    if args.fleet > 1 and not args.supervise:
        print("--fleet requires --supervise", file=sys.stderr)
        return 2
    if args.supervise:
        # re-exec ourselves minus the supervisor flags; the child
        # inherits the environment, so journal/WAL/compile-cache paths
        # carry over and a restart resumes where the crash left off
        raw = list(argv if argv is not None else sys.argv[1:])
        child = []
        skip = False
        for a in raw:
            if skip:
                skip = False
                continue
            if a == "--supervise":
                continue
            if a == "--fleet":
                skip = True
                continue
            if a.startswith("--fleet="):
                continue
            child.append(a)
        if args.fleet > 1:
            try:
                return daemon.supervise_fleet(args.fleet, child,
                                              base_port=args.port)
            except ValueError as e:
                print(f"jepsen-tpu serve: {e}", file=sys.stderr)
                return 2
        return daemon.supervise(child)
    kw = {}
    if args.wal is not None:
        kw["wal_path"] = (
            None if args.wal.lower() in ("0", "false", "off", "no", "")
            else args.wal
        )
    daemon.serve(
        host=args.host or protocol.DEFAULT_HOST,
        port=args.port,
        window=args.window,
        max_queue_runs=args.max_queue,
        block=True,
        **kw,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
