from bigdl_tpu.parallel.mesh import (
    init_distributed, make_mesh, make_hybrid_mesh, local_mesh, P,
    NamedSharding,
)
from bigdl_tpu.parallel.data_parallel import (
    DataParallel, FullyShardedDataParallel,
)
from bigdl_tpu.parallel.grad_comm import (
    COMPRESS_MODES, DEFAULT_BUCKET_BYTES, GradCommConfig, BucketPlan,
    make_config as make_grad_comm_config, build_bucket_plan,
    apply_grad_comm, compressed_psum,
)
from bigdl_tpu.parallel.tensor_parallel import (
    TensorParallel, megatron_specs, replicated_specs,
)
from bigdl_tpu.parallel.sequence import ring_attention, make_ring_attention
from bigdl_tpu.parallel.pipeline import (
    PipelineStack, pipeline_forward, place_pipeline_params,
    make_pipeline_train_step,
)
