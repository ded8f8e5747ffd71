"""From ``--seed`` to a JAX PRNG key, in one place."""
import jax


def key(seed, stream=0):
    """A key from any whole number a driver may pass (they exceed 32 signed
    bits); ``stream`` keeps the weights' draws apart from the batches'."""
    seed = int(seed)
    base = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(base, seed >> 31), stream)
