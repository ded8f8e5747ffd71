"""Traffic kind ``device_resident_tokens``: a few distinct batches of token
ids made on the device from the seed and visited in a fixed order — a
language-model training job fed from a tokenised corpus that is already on
the device, so that the compiled step sets the pace."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

import seeds


def batches(config, size, mesh, seed, params):
    """``params["distinct_batches"]`` pairs ``(ids, labels)`` of int32
    ``(batch, seq_len)``, sharded over the mesh's ``data`` axis: ids uniform
    over the ``vocab_size`` rows held, labels the id that follows each
    position (one more id is drawn a row)."""
    if config["items"] != "seq":
        raise ValueError("device_resident_tokens makes token batches; the "
                         "configuration's items are %r" % config["items"])
    n = int(size["batch_per_chip"]) * mesh.devices.size
    seq_len, vocab = int(size["seq_len"]), int(size["vocab_size"])
    sharding = NamedSharding(mesh, PartitionSpec("data"))
    key = seeds.key(seed, stream=1)

    def draw(key):
        ids = jax.random.randint(key, (n, seq_len + 1), 0, vocab, jnp.int32)
        return ids[:, :-1], ids[:, 1:]

    draw = jax.jit(draw, out_shardings=(sharding, sharding))
    return [draw(jax.random.fold_in(key, i))
            for i in range(int(params["distinct_batches"]))]
