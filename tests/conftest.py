import os
import sys

# kernels tests need f64 (DGEMM/ZGEMM parity with the paper)
os.environ.setdefault("JAX_ENABLE_X64", "True")
# NOTE: never set xla_force_host_platform_device_count here — smoke tests
# and benches must see exactly 1 device.

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
