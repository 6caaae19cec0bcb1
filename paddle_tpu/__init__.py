"""paddle_tpu: a TPU-native deep-learning framework with the capabilities of
PaddlePaddle Fluid (reference: mozga-intel/Paddle).

The public surface mirrors `import paddle.fluid as fluid`:

    import paddle_tpu as fluid
    x = fluid.layers.data(name="x", shape=[13], dtype="float32")
    y = fluid.layers.fc(input=x, size=1)
    ...
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed={...}, fetch_list=[...])

Execution is whole-program XLA compilation (core/lowering.py), autodiff is
jax.vjp over op lowering rules (core/backward.py), and multi-device runs ride
jax.sharding Meshes (parallel/).
"""
import time as _time
_t_import = _time.perf_counter()    # this file's first line to its last:
# ptpu_import_seconds{module="paddle_tpu"}, booked at the end

# Sharding-invariant PRNG, process-wide: with the legacy (non-
# partitionable) threefry, the SAME program traced under a tensor-
# parallel mesh draws DIFFERENT random bits than single-device (XLA's
# partition of the counter math changes the stream) — a dropout mask
# that silently depends on the distribution plan would break every
# mesh-1/replicated bit-exactness contract in parallel/plan.py. The
# partitionable formulation makes every draw a pure function of
# (key, position) regardless of mesh, at the cost of a one-time stream
# change vs the legacy formulation (no test pins legacy absolute
# values; trace_env_key() carries the flag so stale AOT artifacts
# re-key rather than silently serving legacy-stream executables).
import jax as _jax
_jax.config.update("jax_threefry_partitionable", True)

from .core import framework
from .core.framework import (Program, Operator, Variable, Parameter,
                             default_main_program, default_startup_program,
                             program_guard, switch_main_program,
                             switch_startup_program)
from .core.executor import (Executor, FetchHandle, Scope, global_scope,
                            scope_guard)
from .core.readers import EOFException
from .core.backward import append_backward, calc_gradient
from .core.framework import Block, get_var
from .core.executor import switch_scope, fetch_var
from .core.lod import LoDTensor, create_lod_tensor
from .core.param_attr import ParamAttr, WeightNormParamAttr
from .core import initializer
from .core import unique_name
from .places import CPUPlace, CUDAPlace, TPUPlace, is_compiled_with_cuda, \
    is_compiled_with_tpu

from . import ops as _ops  # registers all op lowerings
from . import layers
from . import optimizer
from . import regularizer
from . import clip
from .clip import ErrorClipByValue, GradientClipByValue, GradientClipByNorm, \
    GradientClipByGlobalNorm
from . import nets
from . import io
from .io import save_params, load_params, save_persistables, \
    load_persistables, save_inference_model, load_inference_model
from . import metrics
from . import profiler
from . import observability
from . import evaluator
from . import average
from .average import WeightedAverage
from . import debuger
from . import graphviz
from . import memory_optimization_transpiler
from .memory_optimization_transpiler import memory_optimize, release_memory
from .data_feeder import DataFeeder
from . import backward
from .parallel.parallel_executor import ParallelExecutor
from . import transpiler
from .transpiler import DistributeTranspiler, SimpleDistributeTranspiler
from .transpiler import distributed_spliter
from . import default_scope_funcs
from . import net_drawer
from . import concurrency
from .concurrency import (make_channel, channel_send, channel_recv,
                          channel_close, Select)
from . import reader
from .reader import batch
from . import datasets
from . import recordio
from . import recordio_writer
from . import analysis
from .analysis import ProgramVerificationError
from . import serving
from . import checkpoint
from .checkpoint import CheckpointManager
from . import resilience
from .resilience import (Supervisor, TrainingAborted,
                         install_numeric_guards, NumericalGuardError,
                         DispatchTimeoutError)

Tensor = LoDTensor

__version__ = "0.1.0"

observability.registry.note_import(
    "paddle_tpu", _time.perf_counter() - _t_import)
