"""Device milliseconds per round of the routed experts
(``models/moe.apply_expert_share``, scope ``routed_experts``): router,
top-k, sort, gathers, grouped products and combine, read as
``mla_ms`` reads its scope.  Nothing where no op carries the scope."""
from bench.metrics.mla_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, "routed_experts")
