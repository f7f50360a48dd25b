from repro.sharding.ctx import (  # noqa: F401
    ShardingCtx,
    current_ctx,
    logical_sharding,
    set_ctx,
    shard_constraint,
    use_ctx,
)
