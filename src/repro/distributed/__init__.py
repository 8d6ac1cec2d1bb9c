from .topk import (sharded_flat_topk, tournament_topk_merge,
                   global_topk_merge, MERGE_SCHEDULES, resolve_merge)
from .sharding import batch_spec, replicated, shard_or_replicate
from .fault import HeartbeatRegistry
from .deployment import DeploymentSpec, ShardedDeployment
