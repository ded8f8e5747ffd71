"""mxnet_tpu — a TPU-native deep-learning framework with MXNet's capabilities.

Built from scratch on JAX/XLA/Pallas (see SURVEY.md for the blueprint):
XLA replaces the dependency engine + graph executor + memory planner of the
reference (yjxiong/mxnet), Pallas kernels replace CUDA/cuDNN ops, and
ICI/DCN collectives replace the NCCL/ps-lite KVStore backends.

Import as ``import mxnet_tpu as mx`` — the public surface mirrors the
reference's ``mx.*`` namespaces.
"""
from __future__ import annotations

import os as _os

# join a jax.distributed cluster from the env tools/launch.py sets — must
# happen before anything touches a jax backend, hence at import.  A rank
# that cannot join raises: carrying on alone would train an unsynchronized
# replica under the cluster's name.
if _os.environ.get("JAX_COORDINATOR_ADDRESS") and \
        _os.environ.get("JAX_NUM_PROCESSES") and \
        _os.environ.get("JAX_PROCESS_ID"):
    import jax as _jax
    _jax.distributed.initialize(
        coordinator_address=_os.environ["JAX_COORDINATOR_ADDRESS"],
        num_processes=int(_os.environ["JAX_NUM_PROCESSES"]),
        process_id=int(_os.environ["JAX_PROCESS_ID"]))

__version__ = "0.1.0"

from .base import MXNetError
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus, num_tpus
from . import base
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import executor
from .executor import Executor
from . import analysis
from . import autograd
from . import random
from . import initializer
from . import initializer as init
from . import optimizer
from . import lr_scheduler
from . import metric
from . import kvstore
from . import kvstore as kv
from . import io
from . import recordio
from . import image
from . import model
from . import module
from . import module as mod
from . import callback
from . import monitor
from .monitor import Monitor
from . import visualization
from . import visualization as viz
from . import rnn
from . import gluon
from . import parallel
from . import profiler
from . import telemetry
from . import engine
from . import rtc
from . import contrib
from . import serving
from . import operator
from . import kvstore_server
from . import attribute
from .attribute import AttrScope
from . import name
from . import test_utils
