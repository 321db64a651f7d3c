"""The benchmark's own tests run on the CPU backend: they check the
yardstick (generator, references, trace reduction, the comparison that
decides ``correct``), never a speed."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual devices, for the cells that span a mesh
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
