"""The benchmark's own tests: ``pytest bench/tests`` from the checkout root.

They run on the CPU, with four virtual devices for the cell that spans four
chips; the flag has to be set before JAX starts.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flag = "--xla_force_host_platform_device_count=4"
if flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + flag).strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
