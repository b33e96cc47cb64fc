"""Device-side generators of the paper's evaluation sets, from a seed.

The sets are Gaussian mixtures with the shapes of KDD Cup 99 and HIGGS,
as `repro.data.synth` describes them, drawn with `jax.random` in one
jitted call on the device: no gigabytes are made on the host.

- ``zipf_classes`` (KDD Cup 99): ``classes`` components whose weights
  are the Zipf(``zipf_exponent``) probabilities of 1 .. classes-1, with
  the rest of the tail mass on the last class (the histogram
  `make_kdd_like` samples, here at its expectation), means drawn
  N(0, sep^2) per feature, spread ``spread``.
- ``uniform_classes`` (HIGGS): ``classes`` equally weighted components,
  means N(0, sep^2), spread ``spread``.

The means come from the mixture's ``means_key``, fixed in the
configuration; the seed draws the records.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole-number seed: both 32-bit halves of it
    are folded in, so seeds above 2**32 stay distinct."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, s >> 32)
    return jax.random.fold_in(key, s & 0xFFFFFFFF)


DATA_KEY = 0xFFFFFFFE     # the data's key, folded into the seed's


def make_data(cfg: dict, seed: int, rows: int | None = None):
    """The configuration's records for ``seed``, on the default device."""
    x, _, _ = make_mixture(cfg, jax.random.fold_in(seed_key(seed),
                                                   DATA_KEY), rows)
    return jax.block_until_ready(x)


def zipf_weights(classes: int, exponent: float) -> np.ndarray:
    """Zipf(exponent) probabilities of 1 .. classes-1, and the tail
    mass P(k >= classes) on the last class."""
    k = np.arange(1, classes, dtype=np.float64)
    big = 1_000_000
    head = np.sum(np.arange(1, big + 1, dtype=np.float64) ** -exponent)
    # Euler-Maclaurin tail of the zeta sum beyond ``big``
    zeta = head + big ** (1 - exponent) / (exponent - 1) \
        - big ** -exponent / 2
    p = k ** -exponent / zeta
    return np.append(p, 1.0 - p.sum())


def class_weights(mix: dict) -> np.ndarray:
    if mix["kind"] == "zipf_classes":
        return zipf_weights(mix["classes"], mix["zipf_exponent"])
    if mix["kind"] == "uniform_classes":
        return np.full(mix["classes"], 1.0 / mix["classes"])
    raise ValueError(f"unknown mixture kind {mix['kind']!r}")


@partial(jax.jit, static_argnames=("n", "d", "sep", "spread"))
def _mixture(key, means_key, logw, n: int, d: int, sep: float,
             spread: float):
    _, k_lab, k_noise = jax.random.split(key, 3)
    means = sep * jax.random.normal(means_key, (logw.shape[0], d),
                                    jnp.float32)
    labels = jax.random.categorical(k_lab, logw, shape=(n,))
    x = means[labels] + spread * jax.random.normal(k_noise, (n, d),
                                                    jnp.float32)
    return x, labels.astype(jnp.int32), means


def make_mixture(cfg: dict, key, rows: int | None = None):
    """``(x, labels, means)`` on the default device for the
    configuration ``cfg`` (its ``rows``, ``features`` and ``mixture``).
    The class means are the set's own, drawn from the mixture's fixed
    ``means_key``; ``key`` draws the records.  So every seed clusters
    the same set's law, and a seed changes which records and fits, not
    how much work they are."""
    mix = cfg["mixture"]
    logw = jnp.asarray(np.log(class_weights(mix)), jnp.float32)
    return _mixture(key, jax.random.PRNGKey(int(mix["means_key"])), logw,
                    int(rows or cfg["rows"]),
                    int(cfg["features"]), float(mix["sep"]),
                    float(mix["spread"]))
