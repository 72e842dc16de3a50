from repro.sharding.rules import (  # noqa: F401
    DEFAULT_RULES,
    ShardingRules,
    batch_shardings,
    constrain,
    current_rules,
    param_shardings,
    param_specs,
    use_rules,
)
