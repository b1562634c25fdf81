import os
import sys

# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (multi-device tests use a subprocess).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
