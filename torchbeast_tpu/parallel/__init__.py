from torchbeast_tpu.parallel.dp import (  # noqa: F401
    initialize_distributed,
    make_parallel_update_step,
    replicate,
    shard_batch,
)
from torchbeast_tpu.parallel.mesh import (  # noqa: F401
    batch_sharding,
    create_mesh,
    replicated,
    state_sharding,
)
from torchbeast_tpu.parallel.ep import (  # noqa: F401
    expert_param_shardings,
    place_expert_params,
)
from torchbeast_tpu.parallel.sebulba import (  # noqa: F401
    SebulbaServing,
    ShardedStateTables,
    SliceRouter,
    build_sebulba_serving,
)
from torchbeast_tpu.parallel.pp import (  # noqa: F401
    pipeline_apply,
    stack_stages,
    stage_param_shardings,
)
from torchbeast_tpu.parallel.tp import (  # noqa: F401
    dense_kernel_shardings,
    merge_param_shardings,
    place_params,
    transformer_tp_shardings,
)
