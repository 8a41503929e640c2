"""Plain reference of ``glmix-ml20m``: logistic GLMix in its one-hot form.

    margin_i = x_global_i . theta
             + x_user_i  . U[user_i]
             + x_movie_i . M[movie_i]
    loss     = sum_i w_i * (log(1 + exp(margin_i)) - y_i * margin_i)
    objective = loss + (l2 / 2) * (|theta|^2 + |U|^2 + |M|^2)

Straightforward ``jax.numpy`` in float32 at full matmul precision, nothing
of photon_tpu: no buckets, no projection, no coordinate descent. The
parameters are ``{"fixed": [128], "per_user": [users, 20], "per_movie":
[movies, 8]}`` with a row for every entity of the configuration (an entity
without rows keeps zeros). The objective is a row SUM and ``l2`` is not
scaled by the number of rows, as in the program and in Photon ML.
"""

import jax
import jax.numpy as jnp


def score(params, x, ids):
    with jax.default_matmul_precision("highest"):
        return (x["global"] @ params["fixed"]
                + jnp.sum(x["per_user"] * params["per_user"][ids["userId"]], 1)
                + jnp.sum(x["per_movie"] * params["per_movie"][ids["movieId"]], 1))


def loss(params, x, ids, y, weight):
    """The data term over these rows; ``weight`` is 0 on padding rows."""
    z = score(params, x, ids)
    return jnp.sum(weight * (jnp.logaddexp(0.0, z) - y * z))


loss_and_gradient = jax.value_and_grad(loss)


def regulariser(params, l2):
    return 0.5 * l2 * sum(jnp.sum(p * p) for p in params.values())


def regulariser_gradient(params, l2):
    return {k: l2 * p for k, p in params.items()}
