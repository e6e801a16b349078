"""The benchmark's own copies of the data and channel generators.

Copied from ``repro.data.libsvm_like.make_classification`` and
``benchmarks.paper_common.straggler_edge_channel`` so that no change to
the program can move the benchmark's inputs. A tier-1 test holds each
copy bit for bit to its original at a small size; where the original
changes on purpose, this copy stays and the test says so.

``make_classification`` runs as one jitted call on the default device,
so the data of a full-size configuration never passes through the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("n", "dim", "spectrum_decay",
                                             "label_noise", "dtype"))
def make_classification(key: jax.Array, n: int, dim: int, *,
                        spectrum_decay: float = 1.0,
                        label_noise: float = 0.05, dtype=jnp.float32):
    """Logistic-model data with power-law feature covariance:
    X (n, dim), y (n,) in {-1, +1}."""
    kx, kw, kn = jax.random.split(key, 3)
    evals = jnp.arange(1, dim + 1, dtype=dtype) ** (-spectrum_decay)
    X = jax.random.normal(kx, (n, dim), dtype) * jnp.sqrt(evals)[None, :]
    w_true = jax.random.normal(kw, (dim,), dtype)
    w_true = w_true / jnp.linalg.norm(w_true) * 4.0
    logits = X @ w_true
    p = jax.nn.sigmoid(logits)
    u = jax.random.uniform(kn, (n,), dtype)
    y = jnp.where(u < p, 1.0, -1.0).astype(dtype)
    kf = jax.random.fold_in(kn, 1)
    flip = jax.random.uniform(kf, (n,), dtype) < label_noise
    y = jnp.where(flip, -y, y)
    return X, y


def straggler_edge_channel(m: int) -> dict:
    """The straggler edge channel as ``ChannelModel`` keyword arguments:
    log-spaced uplinks across two decades, 10x faster downlinks, 30%
    stragglers at 10x slowdown, no dropout."""
    rates = np.logspace(np.log10(3e4), np.log10(3e6), m)
    return dict(
        uplink_bytes_per_s=rates,
        downlink_bytes_per_s=10.0 * rates,
        latency_s=0.05,
        straggler_prob=0.30,
        straggler_slowdown=10.0,
    )
