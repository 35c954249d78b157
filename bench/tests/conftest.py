"""The harness's tests run on the CPU with eight virtual devices, as the
repository's own tests do; set before JAX starts a backend."""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
