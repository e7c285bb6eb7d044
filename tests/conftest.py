import os
import sys

# NOTE: no XLA_FLAGS by default on purpose — smoke tests and benches must
# see the single real device.  REPRO_HOST_DEVICES=N opts a run into N
# forced host devices for the mesh tests (tests/test_mesh.py; the CI mesh
# job sets 4).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

_n = os.environ.get("REPRO_HOST_DEVICES")
if _n:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={int(_n)}").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
