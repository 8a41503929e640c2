"""Per-feature summary statistics.

Reference: photon-lib stat/FeatureDataStatistics.scala:44,59 (mean,
variance, count, min, max, numNonzeros via the spark.ml summarizer) —
feeds NormalizationContext building and the persisted feature summaries.

Computed in one jitted program over the (possibly sharded) feature
matrix; implicit zeros of sparse rows are accounted for exactly.

The variance is taken in TWO passes, about the mean: ``sum((x - mean)^2)``
less the correction ``(sum(x - mean))^2 / n`` that takes the mean's own
rounding out (Chan, Golub, LeVeque 1983). The one-pass ``sum(x^2) -
n mean^2`` it replaces (PR 38) cancels: in float32 it loses
``(mean^2 + var) / var`` of its digits, a tenth of them where a feature's
mean lies three standard deviations from zero and all of them (a variance
clamped to 0, a factor of 1) from a few hundred, which is what a feature
in raw units (a year, a price) looks like and exactly when a job asks for
STANDARDIZATION. A second read of a matrix that is already on the device
costs 5 ms at 530,000 x 2,000.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from photon_tpu.ops import features as F

Array = jax.Array


class FeatureDataStatistics(NamedTuple):
    count: int
    mean: Array          # [d]
    variance: Array      # [d] (sample variance, ddof=1, as spark.ml)
    min: Array           # [d]
    max: Array           # [d]
    num_nonzeros: Array  # [d]
    abs_max: Array       # [d]

    @property
    def dim(self) -> int:
        return self.mean.shape[0]


def _sparse_stats(x: F.SparseFeatures, dim: int):
    n = x.values.shape[0]
    idx = x.indices.ravel()
    val = x.values.ravel()
    stored = val != 0
    # pad slots are (0, 0.0): they contribute 0 to sums and counts
    sums = jnp.zeros((dim,), val.dtype).at[idx].add(val)
    nnz = jnp.zeros((dim,), jnp.int32).at[idx].add(stored.astype(jnp.int32))
    maxs = jnp.full((dim,), -jnp.inf, val.dtype).at[idx].max(
        jnp.where(stored, val, -jnp.inf))
    mins = jnp.full((dim,), jnp.inf, val.dtype).at[idx].min(
        jnp.where(stored, val, jnp.inf))
    # features with implicit zeros include 0 in their min/max
    has_zero = nnz < n
    maxs = jnp.where(has_zero, jnp.maximum(maxs, 0.0), maxs)
    mins = jnp.where(has_zero, jnp.minimum(mins, 0.0), mins)
    # about the mean: the stored nonzeros by scatter, every other cell of
    # the column (implicit zeros, stored zeros, pads' none) is -mean
    mean = sums / n
    centred = jnp.where(stored, val - mean[idx], 0.0)
    zeros = (n - nnz).astype(val.dtype)
    dev = jnp.zeros((dim,), val.dtype).at[idx].add(centred) - zeros * mean
    sq_dev = (jnp.zeros((dim,), val.dtype).at[idx].add(centred * centred)
              + zeros * mean * mean)
    return mean, dev, sq_dev, nnz, mins, maxs


def _dense_stats(x: Array):
    n = x.shape[0]
    mean = jnp.sum(x, axis=0) / n
    nnz = jnp.sum(x != 0, axis=0).astype(jnp.int32)
    mins = jnp.min(x, axis=0)
    maxs = jnp.max(x, axis=0)
    centred = x - mean              # fused into the second pass's sums
    return (mean, jnp.sum(centred, axis=0),
            jnp.sum(centred * centred, axis=0), nnz, mins, maxs)


@functools.partial(jax.jit, static_argnums=1)
def _stats(x: F.FeatureMatrix, dim: int):
    if isinstance(x, F.SparseFeatures):
        mean, dev, sq_dev, nnz, mins, maxs = _sparse_stats(x, dim)
        n = x.values.shape[0]
    else:
        mean, dev, sq_dev, nnz, mins, maxs = _dense_stats(x)
        n = x.shape[0]
    # sample variance with ddof=1 (spark.ml summarizer semantics)
    var = jnp.maximum(sq_dev - dev * dev / n, 0.0) / max(n - 1, 1)
    return mean, var, mins, maxs, nnz


def compute_feature_stats(x: F.FeatureMatrix, dim: int) -> FeatureDataStatistics:
    mean, var, mins, maxs, nnz = _stats(x, dim)
    n = (x.values if isinstance(x, F.SparseFeatures) else x).shape[0]
    return FeatureDataStatistics(
        count=n, mean=mean, variance=var, min=mins, max=maxs,
        num_nonzeros=nnz, abs_max=jnp.maximum(jnp.abs(mins), jnp.abs(maxs)),
    )
