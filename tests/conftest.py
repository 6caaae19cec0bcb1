"""Test configuration: run everything on a virtual 8-device CPU mesh so
sharding tests work on any machine (SURVEY.md §4). JAX_PLATFORMS=cpu is
honoured by jax and is also what lets TPUPlace code run here
(places.cpu_only_env)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the chip is for chip_smoke.py and benchmark/run.py
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
