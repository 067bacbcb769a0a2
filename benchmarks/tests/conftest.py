"""Run by hand, on the CPU:  python -m pytest benchmarks/tests -q

Four virtual CPU devices (so that a four-chip cell can be rehearsed), x64
off as on the chip. The repo's own tests/ are a different suite with a
different conftest."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 4)

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                    # benchmarks/
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))   # the repo
