"""Load on the busiest expert over the mean load, a program call and a
layer, over the window: the experts times the program's counter
`moe_expert_max_tokens` (sum over calls and layers of the busiest
expert's tokens) over `moe_tokens_routed` (token-expert pairs routed).
1 is a perfectly even router; the grouped matmuls of a prefill pay for
what is above it in tiles that are not full."""


def read(run):
    c, m = run.get("counters") or {}, run.get("model") or {}
    if not c.get("moe_tokens_routed") or "moe_expert_max_tokens" not in c:
        return None
    return m["experts"] * c["moe_expert_max_tokens"] / c["moe_tokens_routed"]
