"""paddle_tpu — a TPU-native deep-learning framework with the capabilities
of PaddlePaddle Fluid (reference: codeWorm2015/Paddle @ /root/reference).

Declarative Program/Block/Op IR + layers API like `paddle.fluid`, but the
execution engine lowers whole programs to single jitted XLA computations
(MXU-shaped kernels, lax control flow, pjit/shard_map distribution) instead
of per-op CUDA kernel dispatch.

Typical use — identical in shape to fluid:

    import paddle_tpu as fluid
    x = fluid.layers.data(name="x", shape=[784])
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, 128, act="relu")
    logits = fluid.layers.fc(h, 10)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, y))
    fluid.optimizer.Adam(1e-3).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))  # CPUPlace() for the host
    exe.run(fluid.default_startup_program())
    exe.run(feed={"x": xs, "y": ys}, fetch_list=[loss])
"""

# kernels register themselves on import
from .ops import math as _k_math  # noqa: F401
from .ops import nn as _k_nn  # noqa: F401
from .ops import rnn as _k_rnn  # noqa: F401
from .ops import optim as _k_optim  # noqa: F401
from .ops import sequence as _k_sequence  # noqa: F401
from .ops import metric as _k_metric  # noqa: F401
from .ops import control_flow as _k_control_flow  # noqa: F401
from .ops import decode as _k_decode  # noqa: F401
from .ops import attention as _k_attention  # noqa: F401
from .ops import fused_loss as _k_fused_loss  # noqa: F401
from .ops import kv_cache as _k_kv_cache  # noqa: F401
from .ops import sampling as _k_sampling  # noqa: F401
from .ops import speculative as _k_speculative  # noqa: F401
from .ops import quant as _k_quant  # noqa: F401
from .ops import ssm as _k_ssm  # noqa: F401
from .ops import rope as _k_rope  # noqa: F401
from .ops import moe as _k_moe  # noqa: F401
from .ops import diff_attn as _k_diff_attn  # noqa: F401
from .ops import mla as _k_mla  # noqa: F401
from .ops import kda as _k_kda  # noqa: F401
from .ops import dsa as _k_dsa  # noqa: F401
from .ops import eva as _k_eva  # noqa: F401
from .ops import detection as _k_detection  # noqa: F401

from .framework import (  # noqa: F401
    Block,
    CPUPlace,
    CUDAPlace,
    Operator,
    Parameter,
    Program,
    Scope,
    TPUPlace,
    Variable,
    default_main_program,
    default_startup_program,
    global_scope,
    grad_var_name,
    name_scope,
    program_guard,
    scope_guard,
    switch_main_program,
    switch_startup_program,
    unique_name,
)
from .executor import Executor  # noqa: F401
from .io.reader import EOFException  # noqa: F401  (reference: core.EOFException)
from .io.dataloader import DataLoader  # noqa: F401  (multiprocess input fast path)
from .backward import append_backward  # noqa: F401
from . import layers  # noqa: F401
from . import nets  # noqa: F401
from . import initializer  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from .layer_helper import LayerHelper  # noqa: F401
from . import io  # noqa: F401
from . import reader  # noqa: F401
from .reader import batch  # noqa: F401  (reference: paddle.batch)
from .data_feeder import DataFeeder  # noqa: F401
from . import dataset  # noqa: F401
from . import parallel  # noqa: F401
from .parallel import ParallelExecutor, ExecutionStrategy, BuildStrategy  # noqa: F401
from . import transpiler  # noqa: F401
from .transpiler import (  # noqa: F401
    DistributeTranspiler,
    DistributeTranspilerConfig,
    InferenceTranspiler,
    memory_optimize,
    optimize_program,
    release_memory,
)

from . import metrics  # noqa: F401
from . import evaluator  # noqa: F401
from . import profiler  # noqa: F401
from . import debugger  # noqa: F401
from .framework.verifier import verify_program, ProgramVerifyError  # noqa: F401
from . import analysis  # noqa: F401
from .analysis import analyze_program, AnalysisError  # noqa: F401
from .ops.registry import op_support_tpu, registered_ops, OpProtoHolder  # noqa: F401
from .trainer import (  # noqa: F401
    BeginEpochEvent,
    BeginStepEvent,
    CheckpointConfig,
    EndEpochEvent,
    EndStepEvent,
    Inferencer,
    Trainer,
)
from . import checkpoint  # noqa: F401  (elastic training subsystem)
from .checkpoint import (  # noqa: F401
    CheckpointManager,
    ResumableLoop,
)
from . import quant  # noqa: F401  (int8 post-training quantization tier)

from . import inference  # noqa: F401
from . import lod_tensor  # noqa: F401
from .lod_tensor import create_lod_tensor, create_random_int_lodtensor  # noqa: F401

from . import annotations  # noqa: F401
from . import average  # noqa: F401
from . import core  # noqa: F401  (fluid.core compat shim)
from . import inferencer  # noqa: F401
from . import parallel_executor  # noqa: F401
from .framework.scope import CUDAPinnedPlace  # noqa: F401  (pinned host mem -> plain host mem on TPU)
from .lod_tensor import SequenceTensor as LoDTensor  # noqa: F401  (dense+lengths stand-in)
from .layers import learning_rate_scheduler as learning_rate_decay  # noqa: F401
from . import concurrency  # noqa: F401
from .concurrency import (  # noqa: F401
    Go,
    Select,
    channel_close,
    channel_recv,
    channel_send,
    make_channel,
)
from . import contrib  # noqa: F401
from . import default_scope_funcs  # noqa: F401
from . import graphviz  # noqa: F401
from . import net_drawer  # noqa: F401
from . import op  # noqa: F401
from . import recordio_writer  # noqa: F401
from .runtime.recordio import recordio_convert, recordio_sample_reader  # noqa: F401

# operator sugar on Variable (x + y, x * 0.5, ...) — reference
# layers/math_op_patch.py applies this at fluid import time too
from .framework.math_op_patch import monkey_patch_variable as _mpv

_mpv()

__version__ = "0.1.0"
