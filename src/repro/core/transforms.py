"""Orthogonal transforms used by DCO estimators.

The paper's core object is an orthogonal matrix ``W_D`` applied once at index
build time.  DADE derives ``W_D`` from the data second-moment matrix
``E[X X^T]`` (PCA, Lemma 4); ADSampling uses a random orthogonal matrix
(data-oblivious).  Both store the rotated corpus once; queries are rotated at
query time (one (D,D) matvec per query, amortized over all DCOs).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "OrthogonalTransform",
    "fit_pca",
    "random_orthogonal",
    "identity_transform",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class OrthogonalTransform:
    """An orthogonal basis of R^D plus per-direction variances.

    Attributes:
      basis: (D, D) orthogonal matrix; column k is direction w_k.
      variances: (D,) Var(w_k^T X) under the fitted data.  For PCA these are
        the eigenvalues lambda_k sorted descending; for a random basis they
        are the empirical variances along each random direction.
      cum_variances: (D,) inclusive cumulative sum sigma^2(1, d).
    """

    basis: jax.Array
    variances: jax.Array
    cum_variances: jax.Array

    @property
    def dim(self) -> int:
        return self.basis.shape[0]

    def apply(self, x: jax.Array) -> jax.Array:
        """Rotate vectors: x (..., D) -> W^T x (..., D)."""
        return jnp.matmul(x, self.basis, precision=jax.lax.Precision.HIGHEST)

    def scale(self, d: jax.Array) -> jax.Array:
        """Unbiased estimation scale sigma^2(1,D)/sigma^2(1,d) (Eq. 13).

        ``d`` is 1-indexed dimension count; supports array input.
        """
        total = self.cum_variances[-1]
        return total / self.cum_variances[jnp.asarray(d) - 1]

    def tree_flatten(self):
        return (self.basis, self.variances, self.cum_variances), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def _finalize(basis: jax.Array, data: jax.Array) -> OrthogonalTransform:
    proj = jnp.matmul(data, basis, precision=jax.lax.Precision.HIGHEST)
    variances = jnp.mean(proj * proj, axis=0)  # zero-mean by Lemma 1 handling
    cum = jnp.cumsum(variances)
    # Guard: strictly positive cumulative variance so scale() is finite.
    cum = jnp.maximum(cum, jnp.finfo(cum.dtype).tiny)
    return OrthogonalTransform(basis=basis, variances=variances, cum_variances=cum)


@partial(jax.jit, static_argnames=("center",))
def fit_pca(data: jax.Array, *, center: bool = False) -> OrthogonalTransform:
    """Fit the DADE transform: eigenbasis of E[X X^T], descending eigenvalue.

    The paper (Lemma 1) works with the *second moment* E[XX^T] of the raw
    vectors — squared Euclidean distances are invariant to a common mean
    shift, so centering is optional and off by default to match Eq. 10/11.

    Args:
      data: (N, D) corpus sample (float32 recommended for the eigensolve).
      center: subtract the sample mean first (classical PCA).  Distances are
        unaffected either way (Lemma 1); estimator variances differ slightly.
    """
    data = data.astype(jnp.float32)
    if center:
        data = data - jnp.mean(data, axis=0, keepdims=True)
    n = data.shape[0]
    second_moment = jnp.matmul(  # (D, D), PSD
        data.T, data, precision=jax.lax.Precision.HIGHEST) / n
    eigvals, eigvecs = jnp.linalg.eigh(second_moment)  # ascending
    order = jnp.argsort(eigvals)[::-1]
    basis = eigvecs[:, order]
    return _finalize(basis, data)


def random_orthogonal(key: jax.Array, dim: int) -> jax.Array:
    """Haar-ish random orthogonal matrix via QR of a Gaussian (ADSampling)."""
    g = jax.random.normal(key, (dim, dim), dtype=jnp.float32)
    q, r = jnp.linalg.qr(g)
    # Fix signs so the distribution is uniform over O(D).
    q = q * jnp.sign(jnp.diagonal(r))[None, :]
    return q


@jax.jit
def fit_random_orthogonal(key: jax.Array, data: jax.Array) -> OrthogonalTransform:
    """ADSampling's transform, wrapped with empirical per-direction variances

    so the same estimator machinery (scale tables, calibration) applies.
    """
    data = data.astype(jnp.float32)
    basis = random_orthogonal(key, data.shape[1])
    return _finalize(basis, data)


def identity_transform(data: jax.Array) -> OrthogonalTransform:
    """No rotation (FDScanning operates in the original space)."""
    data = jnp.asarray(data, jnp.float32)
    basis = jnp.eye(data.shape[1], dtype=jnp.float32)
    return _finalize(basis, data)


def orthogonality_error(t: OrthogonalTransform) -> float:
    """max |W^T W - I| — used by tests/benchmarks as a sanity metric."""
    w = t.basis
    return float(jnp.max(jnp.abs(w.T @ w - jnp.eye(w.shape[0], dtype=w.dtype))))
