"""The benchmark's own tests run on the CPU, by hand:

    python -m pytest benchmark/tests -q

They are not part of the repo's tier-1 command.  Four virtual devices
for the data-parallel rehearsal; set before JAX is imported."""

import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

# the program's AOT executable cache defaults to <checkout>/artifacts:
# keep CPU executables of the rehearsal out of it (as tests/conftest.py)
if "PADDLE_AOT_CACHE_DIR" not in os.environ:
    os.environ["PADDLE_AOT_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="paddle_aot_benchtest_")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
