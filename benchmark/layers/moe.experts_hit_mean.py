"""The held routed experts a decode iteration's rows reach in one expert
layer, mean over the window's decode iterations (the flight recorder's
`moe_experts_hit`: held experts picked at least once in a forward, mean over
expert layers and the iteration's forwards). What a step would have to read of
the expert weights if only the picked were read; the every-held-expert path
reads all of them whatever this says. None where the program records none."""


def read(ctx):
    hit = [i["moe_experts_hit"] for i in ctx["counters"]["iterations"]
           if i["decode_seqs"] > 0 and i.get("moe_experts_hit")]
    return sum(hit) / len(hit) if hit else None
