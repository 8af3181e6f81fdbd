"""Subprocess body: distributed MR join on 8 fake CPU devices vs oracle.

Run via tests/test_distributed.py (sets XLA_FLAGS before jax import).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import compat  # noqa: E402
from repro.core import distributed as dj  # noqa: E402
from repro.core.relation import Relation  # noqa: E402


def oracle_join(l_schema, l_rows, r_schema, r_rows):
    shared = [v for v in l_schema if v in r_schema]
    r_extra = [v for v in r_schema if v not in l_schema]
    out = []
    for lr in l_rows:
        for rr in r_rows:
            if all(lr[l_schema.index(v)] == rr[r_schema.index(v)] for v in shared):
                out.append(tuple(lr) + tuple(rr[r_schema.index(v)] for v in r_extra))
    return out


def run_case(mesh, axis_names, l_rows, r_rows, seed):
    l_schema, r_schema = ("?k", "?a"), ("?k", "?b")
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    left = Relation.from_numpy(l_schema, l_rows,
                               capacity=_pad(len(l_rows), n_shards))
    right = Relation.from_numpy(r_schema, r_rows,
                                capacity=_pad(len(r_rows), n_shards))
    fn = dj.make_distributed_join(mesh, axis_names, bucket_capacity=64,
                                  join_capacity=256, left_schema=l_schema,
                                  right_schema=r_schema)
    out, totals, ov = fn(left, right)
    assert not bool(np.any(np.asarray(ov))), "bucket/join overflow"
    expected = sorted(oracle_join(l_schema, l_rows.tolist(), r_schema,
                                  r_rows.tolist()))
    got = sorted(map(tuple, out.to_numpy().tolist()))
    assert got == expected, (len(got), len(expected))
    assert int(np.asarray(totals).sum()) == len(expected)
    print(f"ok seed={seed} axes={axis_names} results={len(expected)}")


def _pad(n, m):
    return ((max(n, 1) + m - 1) // m) * m


def main():
    assert jax.device_count() == 8, jax.device_count()
    rng = np.random.RandomState(0)
    # flat shuffle on one axis
    mesh1 = compat.make_mesh((8,), ("data",))
    # hierarchical: pod x data
    mesh2 = compat.make_mesh((2, 4), ("pod", "data"))
    for seed in range(3):
        rng = np.random.RandomState(seed)
        l_rows = rng.randint(0, 12, size=(rng.randint(8, 60), 2)).astype(np.int32)
        r_rows = rng.randint(0, 12, size=(rng.randint(8, 60), 2)).astype(np.int32)
        run_case(mesh1, ("data",), l_rows, r_rows, seed)
        run_case(mesh2, ("pod", "data"), l_rows, r_rows, seed)
    print("ALL DISTRIBUTED JOIN CASES PASSED")


if __name__ == "__main__":
    main()
