"""Traffic kind ``device_resident``: a few distinct batches made on the
device from the seed and visited in a fixed order — a training job whose
input pipeline costs nothing, so that the compiled step sets the pace."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

import seeds


def batches(config, size, mesh, seed, params):
    """``params["distinct_batches"]`` pairs ``(data, label)``, sharded over
    the mesh's ``data`` axis, every row different from every other.  The
    window visits them round-robin."""
    if config["items"] != "img":
        raise ValueError("device_resident makes image batches; the "
                         "configuration's items are %r" % config["items"])
    chips = mesh.devices.size
    n = int(size["batch_per_chip"]) * chips
    side, classes = int(size["side"]), int(size["classes"])
    if config["layout"] != "NHWC":
        raise ValueError("layout %r: only NHWC is made" % config["layout"])
    sharding = NamedSharding(mesh, PartitionSpec("data"))
    key = seeds.key(seed, stream=1)

    def draw(key):
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, (n, side, side, 3), jnp.float32)
        y = jax.random.randint(ky, (n,), 0, classes)
        return x.astype(config["dtype"]), y.astype(jnp.float32)

    draw = jax.jit(draw, out_shardings=(sharding, sharding))
    return [draw(jax.random.fold_in(key, i))
            for i in range(int(params["distinct_batches"]))]
