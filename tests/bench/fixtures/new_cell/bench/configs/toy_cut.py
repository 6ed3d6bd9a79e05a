"""A test configuration cut in depth: a stack of tanh layers on a pytree.

    loss(params, x) = 0.5 mean_rows |h_L|^2,  h_0 = x,
    h_{l+1} = tanh(h_l W_l + b_l)

``objective`` is the library's side: the HVP of the loss with respect to
the weights, as a user computes it with ``repro.core.curvature``.
``formula`` is the benchmark's own plain copy of the loss, which the
reference differentiates; it takes nothing from the program.
"""

import jax.numpy as jnp


def objective(spec):
    from repro.core.curvature import pytree_hvp

    def loss(params, x):
        h = x
        for layer in params:
            h = jnp.tanh(h @ layer["w"] + layer["b"])
        return 0.5 * jnp.mean(jnp.sum(h * h, axis=-1))

    def hvp(params, x, v):
        return pytree_hvp(lambda p: loss(p, x), params, v)
    return hvp


def formula(spec):
    def loss(params, x):
        h = x
        for layer in params:
            h = jnp.tanh(jnp.dot(h, layer["w"], precision="highest")
                         + layer["b"])
        return 0.5 * jnp.mean(jnp.sum(h * h, axis=-1))
    return loss
