"""The benchmark's own tests, run by hand on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest perf/tests -q

They never look for a chip; four virtual CPU devices stand in for the
mesh."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# after the standard library, as in perf/run.py: a file added by name
# never shadows a module that jax or the program imports
sys.path += [PERF, os.path.join(PERF, "reference"),
             os.path.join(PERF, "metrics")]
