"""The program's obs spans as the profiler recorded them.

While obs is on, the program's tracer opens a ``jax.profiler``
annotation of each span's name on the span's thread, so the trace
holds the spans on the host plane, on the device's clock.  The reduced
profile that ``perf/run.py`` hands the readers keeps only the
harness's requests; this reloads the newest trace of the run for the
mirrored spans.
"""

import glob
import os

#: where ``perf/run.py`` writes the window's trace
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".cache", "trace")


def host_events(profile, name, lo, hi):
    """[(start, end)] ns of the host-plane events called ``name`` in
    a ``ProfileData``-like ``profile``, clipped to [lo, hi); events
    wholly outside it are left out."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != name:
                    continue
                s = max(ev.start_ns, lo)
                e = min(ev.start_ns + ev.duration_ns, hi)
                if e > s:
                    out.append((s, e))
    return sorted(out)


def mirrored(run, name, directory=TRACE_DIR):
    """The mirrored spans called ``name`` inside the traced requests
    of ``run``, or None if the run has no trace."""
    if run.profile is None:
        return None
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    from jax.profiler import ProfileData

    lo, hi = run.profile["window"]
    return host_events(ProfileData.from_file(max(paths,
                                                 key=os.path.getmtime)),
                       name, lo, hi)
