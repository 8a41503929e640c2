"""Plain reference of ``fe-epsilon-standardized``: L2-regularised logistic
regression on STANDARDISED features, its parameters the published
(original-space) coefficients.

Upstream Photon ML's ``NormalizationType.STANDARDIZATION``
(``NormalizationType.scala:26-41``, ``NormalizationContext.scala:80-126``):
with the training rows' per-feature ``mean`` and ``std`` (the sample
standard deviation, ddof = 1, as spark.ml's summarizer gives it) and the
intercept feature (the LAST column, a column of ones) left as it is,

    x'_j      = (x_j - mean_j) / std_j             j < d - 1;   x'_{d-1} = 1
    theta'_j  = theta_j * std_j                    j < d - 1
    theta'_{d-1} = theta_{d-1} + sum_j theta_j * mean_j
    margin_i  = x'_i . theta'                      (= x_i . theta: invariant)
    loss      = sum_i w_i * (log(1 + exp(margin_i)) - y_i * margin_i)
    objective = loss + (l2 / 2) * |theta'|^2       L2 on the TRANSFORMED
                                                   vector, intercept included

The parameters are ``{"fixed": [d]}`` in ORIGINAL space (what a Photon job
publishes and what scores raw rows), and the gradient is with respect to
them, by the chain rule through ``theta'`` (``jax.value_and_grad``). ``x'``
is formed explicitly, a block of rows at a time. Straightforward
``jax.numpy`` in float32 at full matmul precision, nothing of photon_tpu.

UNBOUND (this module's own functions) the statistics are the identity
(mean 0, std 1): ``fe-epsilon``'s reference, to the operation. ``bind(x)``
returns the same four functions bound to the statistics of the training
rows ``x``, computed HERE in float64 numpy (two passes), not taken from the
program. The objective is a row SUM and ``l2`` is not scaled by the number
of rows, as in the program and in Photon ML.
"""

import concurrent.futures

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK_ROWS = 1 << 14


def statistics(x):
    """(mean, std) of every column of ``x`` in float64, two passes, a block
    of rows at a time; the last column (the intercept) reads (0, 1), and so
    does a constant column's std."""
    n, d = x.shape
    blocks = range(0, n, _BLOCK_ROWS)
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        mean = sum(pool.map(lambda lo: x[lo:lo + _BLOCK_ROWS].sum(
            axis=0, dtype=np.float64), blocks)) / n

        def squares(lo):
            c = x[lo:lo + _BLOCK_ROWS] - mean           # float64
            return np.einsum("nk,nk->k", c, c)

        var = sum(pool.map(squares, blocks)) / max(n - 1, 1)
    std = np.sqrt(var)
    std[std == 0] = 1.0
    mean[-1], std[-1] = 0.0, 1.0
    return mean, std


class Reference:
    """The four functions ``benchmark/correct.py`` calls, under one pair of
    statistics (``None``: the identity)."""

    def __init__(self, mean=None, std=None):
        self.mean64, self.std64 = mean, std
        self.mean = None if mean is None else jnp.asarray(mean, jnp.float32)
        self.std = None if std is None else jnp.asarray(std, jnp.float32)
        self.loss_and_gradient = jax.value_and_grad(self.loss)
        self._regulariser_gradient = jax.grad(self.regulariser)

    def standardised(self, x):
        return x if self.mean is None else (x - self.mean) / self.std

    def to_transformed(self, theta):
        if self.mean is None:
            return theta
        return (theta * self.std).at[-1].add(jnp.sum(theta * self.mean))

    def gradient_in_transformed_space(self, g):
        """A gradient with respect to the original-space coefficients,
        float64 ``[d]`` on the host, as the gradient with respect to
        ``theta'``: ``to_transformed`` is linear, and this is its inverse
        transpose, ``g'_j = (g_j - mean_j g_{d-1}) / std_j`` with the
        intercept's entry as it is (its mean is 0 and its std 1)."""
        return g if self.mean64 is None else \
            (g - self.mean64 * g[-1]) / self.std64

    def score(self, params, x, ids):
        with jax.default_matmul_precision("highest"):
            return (self.standardised(x["features"])
                    @ self.to_transformed(params["fixed"]))

    def loss(self, params, x, ids, y, weight):
        """The data term over these rows; ``weight`` is 0 on padding rows."""
        z = self.score(params, x, ids)
        return jnp.sum(weight * (jnp.logaddexp(0.0, z) - y * z))

    def regulariser(self, params, l2):
        return 0.5 * l2 * sum(jnp.sum(self.to_transformed(p) ** 2)
                              for p in params.values())

    def regulariser_gradient(self, params, l2):
        return self._regulariser_gradient(params, l2)


def bind(x) -> Reference:
    """The reference under the statistics of the training rows ``x``."""
    return Reference(*statistics(x))


_UNBOUND = Reference()
score = _UNBOUND.score
loss = _UNBOUND.loss
loss_and_gradient = _UNBOUND.loss_and_gradient
regulariser = _UNBOUND.regulariser
regulariser_gradient = _UNBOUND.regulariser_gradient
