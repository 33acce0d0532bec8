"""Weights made on the device from the seed, in one jitted call.

``TransformerLM.init`` draws every array with NumPy on the host in float32;
at 7B widths that is tens of seconds a run and four bytes a parameter. The
benchmark draws against ``model.param_shapes()`` instead, in the type the
configuration serves or trains in, with the program's own initial
distributions (Glorot-uniform matrices over the trailing two dimensions,
0.02-std embeddings, unit norm scales, zero biases).
"""

import math


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _draw(key, name, shape, dtype):
    import jax
    import jax.numpy as jnp

    if name.endswith("_s"):                      # norm scales
        return jnp.ones(shape, dtype)
    if name.startswith(("ln", "b")):             # norm offsets, biases
        return jnp.zeros(shape, dtype)
    if name in ("tok", "pos"):
        limit = 0.02 * math.sqrt(3.0)            # uniform with std 0.02
    else:
        limit = math.sqrt(6.0 / (shape[-2] + shape[-1]))

    def one(k, shp):
        return jax.random.uniform(k, shp, dtype, -limit, limit)

    if len(shape) < 3:
        return one(key, shape)
    # stacked leaves layer by layer: the generator's temporaries stay one
    # layer large however deep the stack
    keys = jax.random.split(key, shape[0])
    return jax.lax.map(lambda k: one(k, shape[1:]), keys)


def make_weights(model, seed: int, dtype: str, float32_leaves=(),
                 sharding=None):
    """``{name: array}`` against ``model.param_shapes()``: every leaf in
    ``dtype`` except ``float32_leaves``. With ``sharding`` (a replicated
    ``NamedSharding``) every chip draws its own identical copy."""
    import jax
    import jax.numpy as jnp

    shapes = {k: tuple(v.shape) for k, v in model.param_shapes().items()}
    names = sorted(shapes)
    dtypes = {k: jnp.float32 if k in float32_leaves else jnp.dtype(dtype)
              for k in names}

    def draw(key):
        return {k: _draw(jax.random.fold_in(key, i), k, shapes[k], dtypes[k])
                for i, k in enumerate(names)}

    out = None if sharding is None else {k: sharding for k in names}
    return jax.jit(draw, out_shardings=out)(seed_key(seed))
