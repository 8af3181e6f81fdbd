"""8-device LM validation: the full train_step + serve_step lower, compile
AND execute on a (1,2,4) pod mesh with real (reduced) weights — catching
sharding bugs that the abstract dry-run can't (numerics, donation).
Also checks multi-device loss == single-device loss (sharding-invariance).
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402

from repro.core import compat  # noqa: E402

import dataclasses
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig, adamw_init


def main():
    assert jax.device_count() == 8
    cfg = T.TransformerConfig(
        name="mesh-test", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
        d_head=8, d_ff=64, vocab=250,  # 250 -> padded_vocab 256 exercised
        n_experts=6, top_k=2, d_expert_ff=32, capacity_factor=8.0,
        kv_chunk=8, remat=True,
    )
    mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
    params = T.init_params(jax.random.PRNGKey(0), cfg, ep=2)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    labels = jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": labels}

    opt = adamw_init(params)
    step = jax.jit(T.make_train_step(cfg, mesh, AdamWConfig(), True))
    with compat.set_mesh(mesh):
        p2, s2, m = step(params, opt, batch)
        loss_mesh = float(m["loss"])
    assert np.isfinite(loss_mesh)
    for leaf in jax.tree.leaves(p2):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
    print(f"train_step on 2x2x2 mesh: loss={loss_mesh:.4f}")

    # sharding invariance: same loss on a single-device mesh
    mesh1 = compat.make_mesh((1, 1, 1), ("pod", "data", "model"))
    params1 = T.init_params(jax.random.PRNGKey(0), cfg, ep=2)
    step1 = jax.jit(T.make_loss_fn(cfg, mesh1, True))
    with compat.set_mesh(mesh1):
        loss1, _ = step1(params1, tokens, labels)
    stepm = jax.jit(T.make_loss_fn(cfg, mesh, True))
    with compat.set_mesh(mesh):
        lossm, _ = stepm(params, tokens, labels)
    np.testing.assert_allclose(float(lossm), float(loss1), rtol=2e-3)
    print(f"loss sharding-invariance: {float(lossm):.5f} == {float(loss1):.5f}")

    # serve_step on the mesh (donated caches)
    serve = jax.jit(T.make_serve_step(cfg, mesh, True), donate_argnums=(1, 2))
    kc, vc = T.init_decode_cache(cfg, 8, 64)
    with compat.set_mesh(mesh):
        nxt, kc, vc = serve(params, kc, vc, jnp.int32(0), tokens[:, 0])
        nxt2, kc, vc = serve(params, kc, vc, jnp.int32(1), nxt)
    assert nxt2.shape == (8,) and int(nxt2.max()) < cfg.vocab
    print("serve_step on mesh: two decode steps OK")
    print("LM MESH TRAIN/SERVE PASSED")


if __name__ == "__main__":
    main()
