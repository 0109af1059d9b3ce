import os

# Tests must see the real single CPU device — the 512-device flag belongs
# ONLY to launch/dryrun.py (never set globally).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from repro.core.backends import get_backend  # noqa: E402

# The suite runs the Pallas kernels on the CPU, which only interpret mode
# can do.  The served path never turns this on: on a TPU the kernels always
# compile (repro.kernels.check_interpret refuses interpret mode there).
get_backend("pallas").interpret = True
