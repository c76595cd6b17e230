"""Core library: the paper's contribution (parallel indexing + coalescing for
indirect access) as composable JAX modules, plus the cycle-level perf model
reproducing the paper's evaluation."""

from .coalescer import (  # noqa: F401
    BlockSchedule,
    SENTINEL,
    build_block_schedule,
    coalesce_stats,
    cshr_reference_trace,
    schedule_gather_reference,
    trim_schedule_warps,
    window_unique_counts,
)
from .formats import (  # noqa: F401
    CSRMatrix,
    SELLMatrix,
    coo_to_csr,
    csr_to_sell,
    dense_to_csr,
)
from .engine import (  # noqa: F401
    SpMVEngine,
    cached_block_schedule,
    clear_engine_cache,
    clear_schedule_cache,
    engine_cache_stats,
    get_engine,
    resolve_backend,
    resolve_window,
    schedule_cache_stats,
    stream_digest,
)
from .dist import (  # noqa: F401
    ShardedSpMVEngine,
    device_str,
    row_shard_sells,
)
from .partition import (  # noqa: F401
    PARTITION_STRATEGIES,
    balanced_bounds,
    even_bounds,
    resolve_partition,
    shard_bounds,
    shard_costs_for_bounds,
    slice_costs,
    slice_nnz,
)
from .runtime import (  # noqa: F401
    BatchFailure,
    DrainResult,
    Executor,
    StreamHandle,
    StreamTimeout,
    StreamingExecutor,
    column_groups,
    microbatch_slices,
    normalize_to_sell,
    parse_stream_spec,
)
from .faults import (  # noqa: F401
    FaultInjected,
    FaultPlan,
    parse_fault_spec,
)
from .schedule_store import (  # noqa: F401
    CACHE_DIR_ENV,
    ScheduleCacheMismatch,
    load_schedule,
    save_schedule,
    schedule_path,
)
from .indirect_stream import coalesced_gather  # noqa: F401
from .gather_engine import (  # noqa: F401
    GatherEngine,
    clear_gather_engine_cache,
    gather_engine_cache_stats,
    get_gather_engine,
    resolve_gather_backend,
)
from .perfmodel import (  # noqa: F401
    DEFAULT_HW,
    HWConfig,
    adapter_area_model,
    gather_perf,
    indirect_stream_perf,
    matmat_spmv_perf,
    plan_matmat_cycles,
    sharded_spmv_perf,
    spmv_perf,
    streaming_spmv_perf,
)
from .tune import (  # noqa: F401
    TUNE_CACHE_ENV,
    TunedPlan,
    autotune,
    clear_tune_cache,
    get_tuned_engine,
    tune_stats,
)
from .solvers import (  # noqa: F401
    SolveResult,
    cg,
    jacobi,
    pagerank,
    power_iteration,
    transition_matrix,
)
from .spmv import spmv_csr, spmv_sell, spmv_sell_coalesced  # noqa: F401
