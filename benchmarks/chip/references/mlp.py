"""The paper's network (§2.2, Eqs. 4–11) and its inputs, from the seed.

``train_f64`` is Listing 2's NumPy loop in float64, or with each result
rounded to float32; ``train_jnp`` is the same loop in ``jax.numpy`` at a
stated product precision (the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import precision as P


def make_inputs(key, n_batches: int, rows: int, features: int,
                classes: int, hidden: int):
    """MNIST-shaped batches (ten prototypes in [0, 1), each row half its
    class prototype and half uniform noise), one-hot labels, and weights
    uniform in [-1, 1) as Listing 2 draws them. One call, on the device."""
    k_proto, k_lab, k_noise, k_xh, k_ho = jax.random.split(key, 5)
    protos = jax.random.uniform(k_proto, (classes, features))
    labels = jax.random.randint(k_lab, (n_batches, rows), 0, classes)
    noise = jax.random.uniform(k_noise, (n_batches, rows, features))
    x = protos[labels] * 0.5 + noise * 0.5
    y = jax.nn.one_hot(labels, classes, dtype=jnp.float32)
    w = {"w_xh": jax.random.uniform(k_xh, (features, hidden),
                                    minval=-1.0, maxval=1.0),
         "w_ho": jax.random.uniform(k_ho, (hidden, classes),
                                    minval=-1.0, maxval=1.0)}
    return x, y, w


def train_f64(x, y, w, n_iters: int, lr: float, rounded: bool = False
              ) -> dict:
    """Listing 2: sigmoid layers, squared error, plain gradient descent.

    ``rounded`` rounds every result to float32 as it is made: each
    operation exact, then stored as a float32 system stores it. That is
    the least error a float32 system can have, and the unit in which the
    check measures the program's."""
    r = ((lambda a: np.asarray(a, np.float32).astype(np.float64))
         if rounded else (lambda a: a))
    w_xh = np.array(w["w_xh"], np.float64)
    w_ho = np.array(w["w_ho"], np.float64)
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    for _ in range(n_iters):
        a_xh = r(1.0 / (1.0 + np.exp(-r(x.dot(w_xh)))))
        a_ho = r(1.0 / (1.0 + np.exp(-r(a_xh.dot(w_ho)))))
        d_ho = r(2.0 * r(a_ho - y) * a_ho * r(1.0 - a_ho))
        d_xh = r(r(d_ho.dot(w_ho.T)) * a_xh * r(1.0 - a_xh))
        w_ho = r(w_ho - lr * r(a_xh.T.dot(d_ho)))
        w_xh = r(w_xh - lr * r(x.T.dot(d_xh)))
    return {"w_xh": w_xh, "w_ho": w_ho}


def train_jnp(x, y, w, n_iters: int, lr: float, mode: str) -> dict:
    """The loop of :func:`train_f64` in float32 with products at ``mode``."""
    def body(w, _):
        w_xh, w_ho = w["w_xh"], w["w_ho"]
        a_xh = jax.nn.sigmoid(P.dot(x, w_xh, mode))
        a_ho = jax.nn.sigmoid(P.dot(a_xh, w_ho, mode))
        d_ho = 2.0 * (a_ho - y) * a_ho * (1.0 - a_ho)
        d_xh = P.dot(d_ho, w_ho.T, mode) * a_xh * (1.0 - a_xh)
        return {"w_ho": w_ho - lr * P.dot(a_xh.T, d_ho, mode),
                "w_xh": w_xh - lr * P.dot(x.T, d_xh, mode)}, None

    return jax.lax.scan(body, w, None, length=n_iters)[0]
