"""Sharding subsystem: logical-axis rules + ambient rule context.

``sharding`` defines the rule machinery (:class:`ShardingRules`,
:func:`default_rules`, :func:`divisible_spec`), ``context`` the ambient
install/query hooks model code uses.
Importing this package never touches jax device state.
"""
from repro.dist.context import current_rules, install_rules, maybe_shard
from repro.dist.sharding import (ShardingRules, default_rules,
                                 divisible_spec, make_mesh,
                                 replicated_serving_rules,
                                 serving_shard_devices,
                                 sharded_serving_rules)

__all__ = [
    "ShardingRules", "default_rules", "divisible_spec", "make_mesh",
    "replicated_serving_rules", "sharded_serving_rules",
    "serving_shard_devices", "current_rules", "install_rules",
    "maybe_shard",
]
