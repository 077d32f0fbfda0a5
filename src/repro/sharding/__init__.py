from .specs import (
    ACT_RULES,
    replicate,
    shard_cache_kv,
    shard_cache_latent,
    shard_decode_logits,
    get_mesh,
    logical,
    set_act_rules,
    set_mesh,
    shard,
    use_mesh,
)

__all__ = [
    "ACT_RULES",
    "replicate",
    "shard_cache_kv",
    "shard_cache_latent",
    "shard_decode_logits",
    "get_mesh",
    "logical",
    "set_act_rules",
    "set_mesh",
    "shard",
    "use_mesh",
]
