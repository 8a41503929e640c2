"""Plain reference of ``fe-epsilon-variance``: L2-regularised logistic
regression, and the FULL coefficient variances a Photon job publishes
beside the means.

    margin_i  = x_i . theta
    loss      = sum_i w_i * (log(1 + exp(margin_i)) - y_i * margin_i)
    objective = loss + (l2 / 2) * |theta|^2
    H         = sum_i w_i s(margin_i) (1 - s(margin_i)) x_i x_i^T + l2 I
    variances = diag(H^-1)                     (s the logistic function)

Straightforward ``jax.numpy`` in float32 at full matmul precision, nothing
of photon_tpu. The parameters are ``{"fixed": [2000]}``. The objective is a
row SUM and ``l2`` is not scaled by the number of rows, as in the program
and in Photon ML; the Hessian is the regularised objective's own, which for
a canonical link is the Gauss-Newton matrix upstream aggregates
(HessianMatrixAggregator.scala:31), the regulariser's ``l2`` on its
diagonal (DistributedOptimizationProblem.scala:82-100). The first three
equations are the configuration's own copy of ``fe-epsilon``'s.

``curvature`` is the data term of ``H`` over one block of rows, so that the
caller can read X in blocks beside the program's copy and add the blocks up
(in float64, on the host); ``variances`` inverts the sum. Two departures
from a one-line ``diag(inv(H))`` over all rows in float32, both so that the
reference is more exact than what it judges (PERF.md section 5, my chip
runs, PR 40: against a float64 oracle on the host it read 3.9e-6 without
them and reads 9.0e-8 with them; a float32 program 7e-7): inside a block the rows are contracted
``SUM_ROWS`` at a time and the partial matrices added, because on a TPU one
float32 contraction over n rows adds them into its accumulator one after
another and comes out off by some ``sqrt(n) x 3.5e-8`` of the sum (6e-6
over a 32,768-row block); and the inverse of the float64 sum is taken in
float64, with numpy, on the host (a float32 inverse of this matrix adds
7e-7 of its own).
"""

import jax
import jax.numpy as jnp
import numpy as np


def score(params, x, ids):
    with jax.default_matmul_precision("highest"):
        return x["features"] @ params["fixed"]


def loss(params, x, ids, y, weight):
    """The data term over these rows; ``weight`` is 0 on padding rows."""
    z = score(params, x, ids)
    return jnp.sum(weight * (jnp.logaddexp(0.0, z) - y * z))


loss_and_gradient = jax.value_and_grad(loss)


def regulariser(params, l2):
    return 0.5 * l2 * sum(jnp.sum(p * p) for p in params.values())


def regulariser_gradient(params, l2):
    return {k: l2 * p for k, p in params.items()}


SUM_ROWS = 1024


def curvature(params, x, ids, y, weight):
    """``{"fixed": [width, width]}``: the Hessian of ``loss`` over these
    rows, ``X^T diag(w s (1 - s)) X``; the labels do not enter it."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(score(params, x, ids))
        d = weight * s * (1.0 - s)
        rows, width = x["features"].shape
        part = SUM_ROWS if rows % SUM_ROWS == 0 else rows

        def add(h, xd):
            xb, db = xd
            return h + xb.T @ (db[:, None] * xb), None

        h, _ = jax.lax.scan(
            add, jnp.zeros((width, width), jnp.float32),
            (x["features"].reshape(rows // part, part, width),
             d.reshape(rows // part, part)))
        return {"fixed": h}


def variances(hessian, l2):
    """``{"fixed": [width] float64}``: ``diag((hessian + l2 I)^-1)``,
    ``hessian`` the data term summed over every block of rows."""
    return {k: np.diag(np.linalg.inv(
        np.asarray(h, np.float64) + l2 * np.eye(len(h))))
        for k, h in hessian.items()}
