"""Test environment: JAX on the CPU with 8 virtual devices, so the
multi-chip sharding paths compile and run without a chip. The
environment is set here, before any test module imports JAX."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from babble_tpu.devices import ensure_virtual_devices  # noqa: E402

ensure_virtual_devices(8)
