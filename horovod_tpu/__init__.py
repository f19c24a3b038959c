"""horovod_tpu: a TPU-native distributed training framework with the
capabilities of Horovod (reference: JayjeetAtGithub/horovod), re-designed
for XLA/ICI rather than ported from NCCL/MPI.

Quick start (the reference's ``import horovod.torch as hvd`` idiom)::

    import horovod_tpu as hvd
    hvd.init()
    out = hvd.allreduce(stacked, op=hvd.Sum)       # eager collective
    # ... or call the same ops inside a jitted shard_map step.

Layer map (vs SURVEY.md §1): the user API here is L5; collectives compile
to XLA HLOs over the device mesh (replacing L2b/L1's NCCL/MPI data plane).
"""

import sys as _sys
import time as _time

# The set-up account counts from this line (``hvd.cache_stats()["setup"]``):
# the tracer's clock is ``time.time``, and tracing imports the stdlib alone.
_import_t0 = _time.time()
_modules_before = sum(name.startswith("horovod_tpu.") for name in _sys.modules)

from . import tracing  # noqa: E402,F401

tracing.get_tracer().open_setup(_import_t0)

from .version import __version__  # noqa: E402,F401

from .basics import (  # noqa: F401
    ccl_built,
    cuda_built,
    ddl_built,
    gloo_built,
    gloo_enabled,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    rocm_built,
    config,
    cross_rank,
    cross_size,
    enable_compile_cache,
    global_axis_name,
    global_mesh,
    init,
    is_homogeneous,
    is_initialized,
    local_rank,
    local_size,
    process_count,
    process_rank,
    rank,
    shutdown,
    size,
)
from .exceptions import (  # noqa: F401
    HorovodInternalError,
    HorovodTpuError,
    HostsUpdatedInterrupt,
    NotInitializedError,
    RecoveryExhaustedError,
    SyncModeIneligibleError,
)
from .ops import (  # noqa: F401
    Adasum,
    Average,
    Max,
    Min,
    Product,
    Sum,
    allgather,
    allreduce,
    alltoall,
    barrier,
    broadcast,
    grouped_allgather,
    grouped_allreduce,
    grouped_reducescatter,
    reducescatter,
)
from .process_sets import (  # noqa: F401
    ProcessSet,
    add_process_set,
    get_process_set_ids,
    global_process_set,
    remove_process_set,
)
from .compression import Compression  # noqa: F401
from .optimizer import (  # noqa: F401
    DistributedOptimizer,
    ReduceSpec,
    grad,
    init_sharded_state,
    reduce_spec_of,
    reshard_opt_state,
    resolve_sync_mode,
    sharded_step_update,
    unshard_opt_state,
)
from .ops.collective_ops import cache_stats, run_comms_microprobe  # noqa: F401
from .functions import (  # noqa: F401
    allgather_object,
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
    join,
    masked_average,
    to_local,
)
from . import abort  # noqa: F401
from . import attribution  # noqa: F401
from .attribution import set_model_flops_per_step  # noqa: F401
from . import autotune  # noqa: F401
from . import comms_model  # noqa: F401
from . import memory  # noqa: F401
from .ops import comms_planner  # noqa: F401
from . import faults  # noqa: F401
from . import metrics  # noqa: F401
from . import peercheck  # noqa: F401
from . import profiler  # noqa: F401
from . import callbacks  # noqa: F401
from . import elastic  # noqa: F401
from . import parallel  # noqa: F401
from .parallel import data_parallel  # noqa: F401
from .parallel.data_parallel import (  # noqa: F401
    DeferredParams,
    make_overlapped_train_step,
    overlap_gradient_sync,
    shard_state,
)
from .parallel.param_sharding import (  # noqa: F401
    ShardedParams,
    reshard_params,
    shard_params,
    unshard_params,
)
from .stall import fetch  # noqa: F401
from .sync_batch_norm import SyncBatchNorm  # noqa: F401
from .timeline import start_timeline, stop_timeline  # noqa: F401

tracing.get_tracer().record(
    attribution.SPAN_SETUP_IMPORT, attribution.CAT_HOST, _import_t0,
    _time.time() - _import_t0,
    {"modules": sum(name.startswith("horovod_tpu.") for name in _sys.modules)
     - _modules_before})
