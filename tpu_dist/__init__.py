"""tpu_dist: a TPU-native distributed training framework.

Brand-new implementation of the capabilities of
Jackxiini/Tensorflow-distributed-learning (synchronous data-parallel
multi-worker training: TF_CONFIG cluster bring-up, mirrored variables,
per-batch gradient all-reduce, shard-policy input pipelines, compile/fit
training API), designed TPU-first on JAX/XLA: named device meshes and sharding
in place of distribution-strategy objects, XLA-compiled ICI/DCN collectives in
place of NCCL/gRPC-RING, one jitted SPMD program in place of per-replica
graph execution. See SURVEY.md for the reference analysis and the
file:line parity citations throughout the docstrings.

Reference example, ported (tf_dist_example.py:1-59):

    import os, json
    import tpu_dist as td

    os.environ["TF_CONFIG"] = json.dumps({...})          # or TPU autodetect
    strategy = td.MultiWorkerMirroredStrategy()

    dataset = (td.data.load("mnist", split="train")
               .map(scale).cache().shuffle(10000)
               .batch(GLOBAL_BATCH_SIZE))
    options = td.data.Options()
    options.experimental_distribute.auto_shard_policy = td.AutoShardPolicy.OFF
    dataset = dataset.with_options(options)

    with strategy.scope():
        model = td.models.build_and_compile_cnn_model()
    model.fit(dataset, epochs=10, steps_per_epoch=20)
"""

from tpu_dist.utils import compile_cache as _compile_cache

# Before anything can compile: see utils/compile_cache.py for the rule.
_compile_cache.configure()

from tpu_dist import (cluster, data, models, observe, ops, parallel,  # noqa: E402
                      training, utils)
from tpu_dist.cluster import ClusterConfig, barrier, initialize, is_chief
from tpu_dist.data import AutoShardPolicy, Dataset, Options
from tpu_dist.models import Model, Sequential, build_and_compile_cnn_model
from tpu_dist.parallel import (
    CollectiveCommunication,
    InputContext,
    MirroredStrategy,
    MultiWorkerMirroredStrategy,
    ParameterServerStrategy,
    ReduceOp,
    Strategy,
    get_strategy,
)
from tpu_dist.training import (
    Callback,
    EarlyStopping,
    History,
    JSONLogger,
    LambdaCallback,
    ModelCheckpoint,
    TensorBoard,
    checkpoint,
)

__version__ = "0.1.0"

__all__ = [
    "cluster", "data", "models", "observe", "ops", "parallel", "training",
    "utils",
    "ClusterConfig", "barrier", "initialize", "is_chief",
    "AutoShardPolicy", "Dataset", "Options",
    "Model", "Sequential", "build_and_compile_cnn_model",
    "CollectiveCommunication", "InputContext", "MirroredStrategy",
    "MultiWorkerMirroredStrategy", "ParameterServerStrategy", "ReduceOp",
    "Strategy", "get_strategy",
    "Callback", "EarlyStopping", "History", "JSONLogger", "LambdaCallback",
    "ModelCheckpoint", "TensorBoard", "checkpoint",
    "__version__",
]
