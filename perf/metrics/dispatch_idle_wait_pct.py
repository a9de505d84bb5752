"""Engine host: of the time the host waited on a dispatch (the
engine/dispatch spans as the profiler recorded them, inside the traced
requests), the share in which no device operation ran on any chip.
Near 0, the host waits on device work; above it, on a device that does
nothing: a stall, a launch or transfer delay, or the profiler."""

from _mirror import mirrored
from _self_time import covered


def read(run):
    prof = run.profile
    if prof is None or not prof["chips"]:
        return None
    waits = mirrored(run, "engine/dispatch")
    if not waits:
        return None
    busy = [iv for c in prof["chips"] for iv in c["busy"]]
    total = sum(e - s for s, e in waits)
    idle = sum(e - s - covered(s, e, busy) for s, e in waits)
    return 100.0 * idle / total if total else None
