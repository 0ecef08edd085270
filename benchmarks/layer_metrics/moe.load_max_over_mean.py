"""Layer: experts.  The busiest expert's (token, expert) assignments over
the mean expert's, window delta of the program's counters
`generation.moe_assignments_max_expert` (a layer's largest count, summed
over layers and steps) and `generation.moe_assignments_total` over the
builder's `n_routed_experts`: 1 is an even load, `n_routed_experts`
is one expert taking everything.  A step of few rows reads high by
nature (16 rows x 4 choices over 64 experts cannot be even)."""


def read(obs):
    counters = obs["result"].get("counters") or {}
    total = counters.get("generation.moe_assignments_total")
    busiest = counters.get("generation.moe_assignments_max_expert")
    experts = obs["config"]["builder"]["model_args"].get("n_routed_experts")
    if not total or busiest is None or not experts:
        return None
    return busiest / (total / float(experts))
