"""BigFCM → MoE router initialization (integration/router_init.py).

Clusters the token-embedding table with BigFCM (one cluster per expert,
olmoe-family reduced config), seeds every router with the centroids, and
shows (1) the router routes coherently from step 0 — each token goes to
the expert owning its embedding cluster (vs ≈1/E agreement for random
init), and (2) a few train steps run normally on the seeded params.

    PYTHONPATH=src python examples/moe_router_init.py
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.core.bigfcm import BigFCMConfig
from repro.integration import fcm_router_init
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.train import build
from repro.models import transformer as tf
from repro.models.moe import router_load
from repro.models.params import tree_init
from repro.sharding.rules import mesh_context

enable_compile_cache()

cfg = dataclasses.replace(reduced(get_config("olmoe-1b-7b")),
                          n_experts=16, top_k=4)
mesh = make_host_mesh()

with mesh_context(mesh), mesh:
    params = tree_init(jax.random.PRNGKey(0), tf.decl(cfg),
                       jnp.dtype(cfg.param_dtype))

    # A trained model's token embeddings cluster semantically; emulate
    # that structure with a mixture so the demo mirrors the real use-case
    # (cluster a TRAINED embed table / probe hidden states).
    from repro.data.synth import make_blobs
    tab, _ = make_blobs(cfg.vocab_padded, cfg.d_model, cfg.n_experts,
                        spread=0.15, sep=1.0, seed=3)
    params["embed"]["table"] = jnp.asarray(
        tab * cfg.d_model ** -0.5, params["embed"]["table"].dtype)

    # token "corpus" = the embedding table itself (N=vocab vectors)
    embeds = params["embed"]["table"].astype(jnp.float32)
    fcm_cfg = BigFCMConfig(n_clusters=cfg.n_experts, combiner_eps=1e-6,
                           max_iter=200, sample_size=256)
    seeded, res = fcm_router_init(params, cfg, embeds, mesh=mesh,
                                  fcm_cfg=fcm_cfg, scale=4.0)

    # routing coherence: does the router's top-1 expert agree with the
    # token's FCM cluster?  (Random init routes arbitrarily ≈ 1/E; the
    # seeded router routes each embedding cluster to "its" expert.)
    from repro.core.fcm import hard_assign
    toks = jax.random.randint(jax.random.PRNGKey(1), (512,), 0, cfg.vocab)
    xt = jnp.take(params["embed"]["table"], toks, axis=0) \
        .astype(jnp.float32)
    cluster = np.asarray(hard_assign(xt, res.centers))

    def agreement(p):
        moe_p = jax.tree_util.tree_map(
            lambda a: a[0], p["stages"][0])     # layer 0 of the scanned stack
        logits = xt @ np.asarray(moe_p["moe"]["w_router"], np.float32)
        return float((logits.argmax(1) == cluster).mean()), \
            np.asarray(router_load(cfg, moe_p["moe"], xt[None]))

    agr_rand, load_rand = agreement(params)
    agr_fcm, load_fcm = agreement(seeded)
    print(f"router/cluster top-1 agreement  random: {agr_rand:.3f}   "
          f"FCM-seeded: {agr_fcm:.3f}  (chance = {1 / cfg.n_experts:.3f})")
    print(f"random load: {load_rand.tolist()}")
    print(f"fcm    load: {load_fcm.tolist()}")
    assert agr_fcm > 0.9 > agr_rand

    # the seeded params train normally
    state, step_fn, _ = build(cfg, mesh)
    state = state._replace(params=jax.device_put(seeded))
    tok = jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfg.vocab)
    losses = []
    for i in range(5):
        state, metrics = step_fn(state, {"tokens": tok, "labels": tok})
        losses.append(float(metrics["loss"]))
    print(f"5 train steps on seeded params, loss: "
          f"{[round(l, 3) for l in losses]}")
    assert losses[-1] < losses[0]
    print("OK -- FCM-seeded router routes coherently "
          f"({agr_fcm:.0%} cluster agreement vs {agr_rand:.0%} random) "
          "and trains.")
