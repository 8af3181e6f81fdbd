"""§Perf GNN machinery correctness on a 1-device mesh: the shuffle
gather/scatter and the streamed edge blocks must match the plain paths
exactly (multi-device equivalence is covered by tests/distributed/)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compat
from repro.data.graphs import make_full_graph
from repro.models.gnn import graphcast as gc
from repro.models.gnn import meshgraphnet as mgn


@pytest.fixture(scope="module")
def mesh():
    return compat.make_mesh((1, 1), ("data", "model"))


def _graph(arch, d_feat, seed=3):
    g = make_full_graph(arch, n=64, e=512, e_cap=512, d_feat=d_feat,
                        n_classes=1, seed=seed)
    return jax.tree.map(jnp.asarray, g)


def test_graphcast_streamed_matches_plain(mesh):
    base = gc.GraphCastConfig(n_layers=2, d_hidden=16, n_vars=6)
    g = _graph("graphcast", 6)
    p = gc.init_params(jax.random.PRNGKey(0), base)
    opt = dataclasses.replace(
        base, node_spec=("data", "model"), shuffle_gather=True,
        edge_stream_chunks=4, remat=True)
    with compat.set_mesh(mesh):
        np.testing.assert_allclose(
            np.asarray(gc.apply(p, g, base)),
            np.asarray(gc.apply(p, g, opt)), rtol=2e-4, atol=2e-4)
        g1 = jax.grad(lambda p: gc.loss_fn(p, g, base))(p)
        g2 = jax.grad(lambda p: gc.loss_fn(p, g, opt))(p)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_meshgraphnet_shuffle_matches_plain(mesh):
    base = mgn.MGNConfig(n_layers=3, d_hidden=16, d_node_in=8)
    g = _graph("meshgraphnet", 8)
    p = mgn.init_params(jax.random.PRNGKey(1), base)
    opt = dataclasses.replace(base, node_spec=("data", "model"),
                              shuffle_gather=True, remat=True)
    with compat.set_mesh(mesh):
        np.testing.assert_allclose(
            np.asarray(mgn.apply(p, g, base)),
            np.asarray(mgn.apply(p, g, opt)), rtol=2e-4, atol=2e-4)
