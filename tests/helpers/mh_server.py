"""Launcher for the 2-process multi-host serving test.

Run as: python mh_server.py <server args...> with TPU_WORKER_ID /
TPU_WORKER_HOSTNAMES / KAITO_COORDINATOR in the env (the same contract
the rendered StatefulSet injects).  Forces the CPU platform with 2
local devices per process BEFORE the backend initializes.
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" \
    + os.environ.get("MH_LOCAL_DEVICES", "2")
os.environ["JAX_PLATFORMS"] = "cpu"

from kaito_tpu.engine.server import main

if __name__ == "__main__":
    main(sys.argv[1:])
