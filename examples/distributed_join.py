"""The paper's join at multi-chip scale: hash-shuffle (all_to_all) + local
MapReduce join on an 8-device mesh — the same code path the 512-chip
dry-run lowers, executed for real on host devices.

    PYTHONPATH=src python examples/distributed_join.py
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import compat  # noqa: E402
from repro.core.distributed import make_distributed_join  # noqa: E402
from repro.core.relation import Relation  # noqa: E402

mesh = compat.make_mesh((2, 4), ("data", "model"))
n = 1 << 12
rng = np.random.default_rng(0)
left = Relation.from_numpy(("?x", "?y"), np.stack(
    [rng.integers(0, 256, n), np.arange(n)], 1))
right = Relation.from_numpy(("?y", "?z"), np.stack(
    [np.arange(n) % 256, rng.integers(0, 99, n)], 1))
# note: left keys ?y are in column 1... schemas share ?y (left col0 is ?x)

join = make_distributed_join(mesh, ("data", "model"), bucket_capacity=2048,
                             join_capacity=1 << 16,
                             left_schema=("?x", "?y"),
                             right_schema=("?y", "?z"))
with compat.set_mesh(mesh):
    out, totals, overflows = join(left, right)
per_shard = np.asarray(totals)
print(f"8 shards hold {per_shard.sum()} join rows "
      f"(per-shard: {per_shard.tolist()})")
assert not bool(np.asarray(overflows).any())

# verify against the single-device join
from repro.core import mr_join as mj

total_ref = int(mj.mr_join_count(left, right))
assert per_shard.sum() == total_ref, (per_shard.sum(), total_ref)
print(f"matches single-device Algorithm 1 count: {total_ref}")
print("DISTRIBUTED JOIN OK")
