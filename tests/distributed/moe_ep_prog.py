"""8-device validation of the MapSQ-dispatch MoE and the sharded embedding
lookup: outputs AND gradients must match the single-path dense references.

Run via tests/test_distributed.py in a subprocess (device count locks at
first jax init, so the main pytest process keeps 1 device).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402

from repro.core import compat  # noqa: E402

import jax.numpy as jnp
import numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P

from repro.models import moe as M

TOL = dict(rtol=2e-3, atol=2e-3)


def dense_moe_reference(p: M.MoEParams, x, st: M.MoESettings, e_pad: int):
    """Every expert applied to every token, combined by top-k gates —
    O(E) compute but exact (no capacity drops at high cf)."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ p.router.astype(jnp.float32)
    logits = jnp.where(jnp.arange(e_pad) < st.n_experts, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, st.top_k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(xf.shape[0])[:, None], eidx].set(gate_vals)
    g = jnp.einsum("td,edf->etf", xf, p.we_gate)
    u = jnp.einsum("td,edf->etf", xf, p.we_up)
    h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    eo = jnp.einsum("etf,efd->etd", h.astype(x.dtype), p.we_down)
    y = jnp.einsum("te,etd->td", gates.astype(jnp.float32),
                   eo.astype(jnp.float32))
    return y.astype(x.dtype).reshape(b, s, d)


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = compat.make_mesh((2, 4), ("data", "model"))
    st = M.MoESettings(n_experts=6, top_k=2, d_expert_ff=32,
                       capacity_factor=8.0)  # high cf => no drops
    ep = 4
    e_pad = st.e_pad(ep)  # 8
    d_model = 16
    key = jax.random.PRNGKey(0)
    p = M.init_moe_params(key, d_model, st, ep, jnp.float32)
    b, s = 4, 8
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, d_model), jnp.float32)

    token_spec = P(("data",), "model", None)
    pspec = M.MoEParams(router=P(None, None), we_gate=P("model", None, None),
                        we_up=P("model", None, None),
                        we_down=P("model", None, None))
    ep_fn = jax.jit(compat.shard_map(
        partial(M.moe_ffn_ep_local, st=st, expert_axis="model"),
        mesh=mesh, in_specs=(pspec, token_spec), out_specs=token_spec,
        check_vma=False,
    ))
    with compat.set_mesh(mesh):
        y_ep = ep_fn(p, x)
    y_ref = dense_moe_reference(p, x, st, e_pad)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), **TOL)
    print("forward: EP(shard_map, 8dev) == dense reference")

    y_oh = M.moe_ffn_onehot(p, x, st, e_pad)
    np.testing.assert_allclose(np.asarray(y_oh), np.asarray(y_ref), **TOL)
    print("forward: one-hot dispatch == dense reference")

    # gradient exactness through the all_to_all round trip
    tgt = jax.random.normal(jax.random.PRNGKey(2), (b, s, d_model))

    def loss_ep(p, x):
        return jnp.mean((ep_fn(p, x) - tgt) ** 2)

    def loss_ref(p, x):
        return jnp.mean((dense_moe_reference(p, x, st, e_pad) - tgt) ** 2)

    g_ep = jax.grad(loss_ep, argnums=(0, 1))(p, x)
    g_ref = jax.grad(loss_ref, argnums=(0, 1))(p, x)
    for a, b_ in zip(jax.tree.leaves(g_ep), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), **TOL)
    print("grads: EP == dense reference (params AND activations)")

    # ---- sharded embedding lookup (deepfm path) --------------------------
    from repro.models.recsys import deepfm as D

    table = jax.random.normal(jax.random.PRNGKey(3), (64, 5))
    ids = jax.random.randint(jax.random.PRNGKey(4), (128,), 0, 64)
    lookup = jax.jit(D.make_sharded_lookup(mesh, ("data",), cap=64))
    with compat.set_mesh(mesh):
        rows = lookup(table, ids)
    np.testing.assert_allclose(np.asarray(rows), np.asarray(table[ids]),
                               **TOL)
    print("lookup: sharded all_to_all == take")

    def loss_l(t):
        return jnp.sum(lookup(t, ids) ** 2)

    g1 = jax.grad(loss_l)(table)
    g2 = jax.grad(lambda t: jnp.sum(t[ids] ** 2))(table)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), **TOL)
    print("lookup grads: scatter-add transpose exact")

    print("ALL MOE/LOOKUP DISTRIBUTED CASES PASSED")


if __name__ == "__main__":
    main()
