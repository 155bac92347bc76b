"""Eight virtual CPU devices for every test under benchmarks/tests,
set in os.environ before jax is imported anywhere, so that both the
in-process runs and the subprocesses that test_rehearsal.py starts
(it passes os.environ through) can rehearse a four-chip cell: a
deployment with `mesh_devices: 4` needs four devices to start."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
