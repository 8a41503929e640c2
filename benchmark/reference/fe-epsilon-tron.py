"""Plain reference of ``fe-epsilon-tron``: L2-regularised logistic
regression.

    margin_i  = x_i . theta
    loss      = sum_i w_i * (log(1 + exp(margin_i)) - y_i * margin_i)
    objective = loss + (l2 / 2) * |theta|^2

Straightforward ``jax.numpy`` in float32 at full matmul precision, nothing
of photon_tpu. The parameters are ``{"fixed": [2000]}``. The objective is a
row SUM and ``l2`` is not scaled by the number of rows, as in the program
and in Photon ML. This is the configuration's own copy of ``fe-epsilon``'s
three equations: the model is the same and the SOLVER (trust-region Newton)
is no part of a reference, ``correct`` judges the optimum it reached.
"""

import jax
import jax.numpy as jnp


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
