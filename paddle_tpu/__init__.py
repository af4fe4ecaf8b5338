"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of PaddlePaddle (reference: dut3062796s/Paddle, Fluid era).

The public API mirrors ``paddle.fluid`` so reference users can write::

    import paddle_tpu as fluid
    x = fluid.layers.data(name="x", shape=[784])
    y = fluid.layers.fc(x, size=10, act="softmax")
    ...
    exe = fluid.Executor(fluid.TPUPlace())

while the implementation is jax/XLA/pallas end to end: programs lower to
single fused XLA executables, parallelism is jax.sharding over device
meshes, and hot kernels are Pallas.
"""
# op lowering rules must register before any program executes
from .ops import basic as _ops_basic          # noqa: F401
from .ops import nn as _ops_nn                # noqa: F401
from .ops import optimizer_ops as _ops_opt    # noqa: F401
from .ops import transformer_ops as _ops_tf   # noqa: F401
from .ops import moe as _ops_moe              # noqa: F401
from .ops import sequence as _ops_seq         # noqa: F401
from .ops import rnn as _ops_rnn              # noqa: F401
from .ops import control_flow as _ops_cf      # noqa: F401
from .ops import crf_ctc as _ops_crf          # noqa: F401
from .ops import detection as _ops_det        # noqa: F401
from .ops import eval_ops as _ops_eval        # noqa: F401
from .ops import extras as _ops_extras        # noqa: F401
from .ops import fused_loss as _ops_fused     # noqa: F401

from .core.framework import (                  # noqa: F401
    Program, Block, Variable, Parameter, Operator,
    default_main_program, default_startup_program, program_guard,
    switch_main_program, switch_startup_program, name_scope, get_var)
from .core.executor import (                   # noqa: F401
    force_cpu, enable_compile_cache)
from .core.executor import (                   # noqa: F401
    Executor, Scope, global_scope, scope_guard, _switch_scope,
    Place, CPUPlace, TPUPlace, CUDAPlace)
from .core.backward import append_backward     # noqa: F401
from .core.sequence import SequenceBatch, to_sequence_batch  # noqa: F401
from .core import unique_name                  # noqa: F401

from . import layers                           # noqa: F401
from . import nets                             # noqa: F401
from . import parallel                         # noqa: F401
from .parallel import (ParallelExecutor, ExecutionStrategy,
                       BuildStrategy)          # noqa: F401
from .parallel.transpiler import DistributeTranspiler  # noqa: F401
from .transpiler import (InferenceTranspiler, memory_optimize,
                         release_memory)       # noqa: F401
from . import initializer                      # noqa: F401
from . import optimizer                        # noqa: F401
from . import regularizer                      # noqa: F401
from . import clip                             # noqa: F401
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from .data_feeder import DataFeeder            # noqa: F401
from . import io                               # noqa: F401
from . import resilience                       # noqa: F401
from . import serving                          # noqa: F401
from . import cluster                          # noqa: F401
from . import reader                           # noqa: F401
from . import dataset                          # noqa: F401
from .reader import batch                      # noqa: F401
from . import metrics                          # noqa: F401
from . import profiler                         # noqa: F401
from . import contrib                          # noqa: F401
from . import average                          # noqa: F401
from .trainer import (Trainer, BeginEpochEvent, EndEpochEvent,
                      BeginStepEvent, EndStepEvent,
                      CheckpointConfig)        # noqa: F401
from .inferencer import Inferencer             # noqa: F401
from . import evaluator                        # noqa: F401
from . import debugger                         # noqa: F401
from . import transpiler                       # noqa: F401
from . import lod_tensor                       # noqa: F401
from .lod_tensor import (create_lod_tensor,
                         create_random_int_lodtensor)  # noqa: F401
from . import recordio_writer                  # noqa: F401
from . import default_scope_funcs              # noqa: F401
from . import concurrency                      # noqa: F401
from .concurrency import (make_channel, channel_send, channel_recv,
                          channel_close, Select)  # noqa: F401

__version__ = "0.1.0"
