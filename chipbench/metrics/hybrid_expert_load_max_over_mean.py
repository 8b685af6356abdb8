"""How unevenly the router loads the held experts: the busiest (layer,
held expert) pair's routed tokens over the mean pair's, per window step
and averaged over the steps (the trainer's routing counters
``moe_expert_load_max`` and ``moe_routed_tokens``), in x. 1 is even."""


def read(ctx):
    c = ctx.counters
    routed, pairs = c.get("moe_routed_tokens"), c.get("moe_layer_experts")
    if not routed or not pairs or "moe_expert_load_max" not in c:
        return None
    return c["moe_expert_load_max"] / (routed / pairs)
