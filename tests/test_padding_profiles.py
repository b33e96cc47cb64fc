"""Head/vocab padding semantics + sharding-profile machinery.

The §Perf optimizations must not change model semantics:
  * a head-padded model == the unpadded model on shared real weights,
  * padded vocab logit columns never receive probability mass,
  * the FSDP profile resolves valid, divisibility-safe PartitionSpecs,
  * the a2a MoE path == the local MoE path (multi-device subprocess).
"""
import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import transformer as tf
from repro.models.params import tree_init


def _pad_cfg():
    base = dataclasses.replace(reduced(get_config("starcoder2-7b")),
                               n_heads=6, n_kv_heads=2, head_dim=16)
    return base, dataclasses.replace(base, head_pad_quantum=8)


def test_head_padding_quantums():
    for arch, expect in [("starcoder2-7b", 48), ("qwen2-1.5b", 16),
                         ("gemma-7b", 16), ("stablelm-12b", 32),
                         ("kimi-k2-1t-a32b", 64)]:
        cfg = get_config(arch)
        assert cfg.n_heads_padded == expect, (arch, cfg.n_heads_padded)
        assert cfg.n_heads_padded % cfg.n_kv_heads == 0


def test_head_padded_model_matches_unpadded():
    base, pad = _pad_cfg()
    assert pad.n_heads_padded == 8
    pp = tree_init(jax.random.PRNGKey(0), tf.decl(pad), jnp.float32)
    kv, rep, rep_pad, hd = 2, 3, 4, 16

    def select(w):
        if w.ndim == 1:         # bq (kv*rep_pad*hd,)
            return w.reshape(kv, rep_pad, hd)[:, :rep].reshape(-1)
        if w.shape[-1] == kv * rep_pad * hd:    # wq (d, ·)
            return w.reshape(w.shape[0], kv, rep_pad, hd)[:, :, :rep] \
                .reshape(w.shape[0], kv * rep * hd)
        return w.reshape(kv, rep_pad, hd, w.shape[-1])[:, :rep] \
            .reshape(kv * rep * hd, w.shape[-1])   # wo (·, d)

    def walk(t):
        if isinstance(t, dict):
            t = {k: walk(v) for k, v in t.items()}
            if "wq" in t:
                t = dict(t)
                for key in ("wq", "wo", "bq"):
                    if key in t:
                        w = t[key]
                        t[key] = (jax.vmap(select)(w)
                                  if w.ndim > (1 if key == "bq" else 2)
                                  else select(w))
            return t
        if isinstance(t, (list, tuple)):
            return type(t)(walk(x) for x in t)
        return t

    pu = walk(pp)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, base.vocab)
    np.testing.assert_allclose(np.asarray(tf.forward(pad, pp, tok)),
                               np.asarray(tf.forward(base, pu, tok)),
                               atol=2e-4)


def test_vocab_padding_masked():
    cfg = dataclasses.replace(reduced(get_config("mamba2-2.7b")),
                              vocab=500)   # pads to 512
    assert cfg.vocab_padded == 512
    params = tree_init(jax.random.PRNGKey(0), tf.decl(cfg), jnp.float32)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    hidden = tf.forward(cfg, params, tok)
    logits = tf.logits_fn(cfg, params, hidden)
    assert logits.shape[-1] == 512
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    assert float(probs[..., cfg.vocab:].max()) == 0.0
    # loss is finite and gradients flow
    loss = tf.lm_loss(cfg, params, hidden, tok)
    assert np.isfinite(float(loss))


def test_fsdp_profile_specs():
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AbstractMesh
    from repro.sharding.rules import (logical_to_spec, mesh_context,
                                      profile_context)
    mesh = AbstractMesh((2, 8), ("data", "model"))
    with mesh_context(mesh), profile_context("fsdp"):
        # duplicate-axis dedupe: experts take model before embed can
        spec = logical_to_spec(("experts", "embed", None),
                               dims=(16, 64, 8))
        flat = [a for e in spec if e for a in
                ((e,) if isinstance(e, str) else e)]
        assert len(flat) == len(set(flat))
        # divisibility trim: batch 3 can't shard anywhere
        assert logical_to_spec(("batch",), dims=(3,)) == P(None)


@pytest.mark.slow
def test_moe_a2a_matches_local_subprocess():
    """a2a dispatch == replicated-psum dispatch == single-device MoE,
    on 8 fake CPU devices (subprocess so XLA_FLAGS applies cleanly)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced
from repro.models.moe import moe, _moe_local
from repro.models.params import tree_init
from repro.models import moe as moe_lib
from repro.sharding.rules import mesh_context, profile_context

cfg = dataclasses.replace(reduced(get_config("olmoe-1b-7b")),
                          n_experts=8, top_k=2, capacity_factor=8.0)
key = jax.random.PRNGKey(0)
p = tree_init(key, moe_lib.moe_decl(cfg), jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 64), jnp.float32)

ref = _moe_local(x, p["w_router"], p["w_in"], p["w_out"], cfg=cfg,
                 n_ranks=1, axis_name=None)

mesh = jax.make_mesh((2, 4), ("data", "model"))
with mesh_context(mesh), mesh:
    y_tp = jax.jit(lambda x: moe(cfg, p, x))(x)
    with profile_context("fsdp"):
        y_a2a = jax.jit(lambda x: moe(cfg, p, x))(x)
np.testing.assert_allclose(np.asarray(y_tp), np.asarray(ref),
                           atol=1e-4, rtol=1e-4)
np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(ref),
                           atol=1e-4, rtol=1e-4)
print("MOE_OK")
"""
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert "MOE_OK" in res.stdout, res.stderr[-3000:]
