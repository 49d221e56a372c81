"""CPU tests of the chip benchmark: four virtual CPU devices, the program's
Pallas kernels in interpret mode, tiny sizes.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 -m pytest benchmarks/chip/tests -q
"""

import os
import pathlib
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ["REPRO_STRICT"] = "1"
os.environ["REPRO_SFC_TUNE_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="bench_test_"), "knobs.json")

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
