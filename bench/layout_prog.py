"""Subprocess body of bench/test_bench_layout.py: a tiny run of the
sharded cell over four forced host devices, sound and with the served
path broken underneath. The parent pytest process keeps one device.

Usage: layout_prog.py <cache dir>

Prints one JSON object as its last line: {"sound": <result line>,
"half": <result line>}.
"""
import os
import sys
import time

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"

import json  # noqa: E402

import harness  # noqa: E402
from test_bench_run import Broken, tiny_config  # noqa: E402

WORKLOAD = "lubm20-sharded.complex"


def main(cache: str) -> None:
    out = {}
    for name, wrapper in (("sound", None),
                          ("half", lambda s: Broken(s, "half"))):
        out[name] = harness.run(
            WORKLOAD, 2**31 + 11, 3.0, False, time.perf_counter(),
            require_platform=None, config_override=tiny_config(),
            server_wrapper=wrapper, cache_root=cache)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
