"""Test-session set-up shared by every file.

Four CPU host devices, so that mesh and multi-client tests have a real
`pod` axis. XLA reads the flag once, when jax starts, so it is set here,
before any test module imports jax; a value given from outside wins.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
