from gymfx_tpu.parallel.mesh import (  # noqa: F401
    make_mesh,
    mesh_from_config,
    validate_batch_axis,
    validate_population_axis,
    batch_sharding,
    replicated_sharding,
    initialize_distributed,
    CoordinatorTimeoutError,
)
from gymfx_tpu.parallel.runtime import (  # noqa: F401
    ShardedRuntime,
    StatePlan,
)
from gymfx_tpu.parallel.elastic import (  # noqa: F401
    ElasticReplanError,
    MeshSupervisor,
    elastic_entry,
    is_device_loss,
    plan_survivor_shape,
    run_elastic,
    stream_preserving,
    survivor_devices,
)
