"""Batched serving: prefill a batch of prompts, then step the KV-cached
decode loop — the `serve_step` the decode_32k/long_500k dry-run cells
lower, exercised end-to-end on a reduced config.

    PYTHONPATH=src python examples/serve_batch.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tf
from repro.models.params import tree_init
from repro.serve import greedy_generate, make_prefill, make_serve_step
from repro.sharding.rules import mesh_context

enable_compile_cache()

cfg = reduced(get_config("stablelm-12b"))
mesh = make_host_mesh()
BATCH, PROMPT, NEW = 8, 24, 16

with mesh_context(mesh), mesh:
    params = tree_init(jax.random.PRNGKey(0), tf.decl(cfg), jnp.float32)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (BATCH, PROMPT), 0, cfg.vocab)

    # decode path: prefill once, then one token per serve_step
    t0 = time.perf_counter()
    toks = greedy_generate(cfg, params, {"tokens": prompts},
                           max_new=NEW, max_len=PROMPT + NEW)
    t_gen = time.perf_counter() - t0
    assert toks.shape == (BATCH, NEW)
    assert int(toks.max()) < cfg.vocab and int(toks.min()) >= 0

    # consistency: cached decode == uncached full forward (greedy)
    full = jnp.concatenate([prompts, toks[:, :-1]], axis=1)
    hidden = tf.forward(cfg, params, full)
    logits = tf.logits_fn(cfg, params, hidden)
    uncached = jnp.argmax(logits[:, PROMPT - 1:], axis=-1)
    agree = float((uncached == toks).mean())
    print(f"generated {BATCH}×{NEW} tokens in {t_gen:.2f}s "
          f"({BATCH * NEW / t_gen:.0f} tok/s on CPU)")
    print(f"cached-decode vs full-forward agreement: {agree:.3f}")
    assert agree > 0.99, agree
    print("OK -- batched KV-cached serving matches the uncached oracle.")
