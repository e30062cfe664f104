"""Run a script on N virtual CPU devices (default 8).

Usage: python tools/run_cpu.py [N] script.py [args...]

The same as ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=N python script.py``:
the environment, set before the script imports jax, decides the platform
and the device count.
"""

import os
import runpy
import sys

n = "8"
args = sys.argv[1:]
if args and args[0].isdigit():
    n, args = args[0], args[1:]
if not args:
    sys.exit("usage: run_cpu.py [N] script.py [args...]")

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()

sys.argv = args
sys.path.insert(0, os.path.dirname(os.path.abspath(args[0])))
runpy.run_path(args[0], run_name="__main__")
