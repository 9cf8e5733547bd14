"""Of the window's routed token-slots (real tokens x experts a token), the
share that fell to the experts this chip holds (%): `moe_held_slots` over
`moe_token_slots`, both summed over the window's iterations. 25 where a
quarter of the experts is held and the routing is even, 100 where every
expert is; the rest is work the deployment's other chips would do. None where
the program records no `moe_held_slots` (a program from before the counter)."""


def read(ctx):
    its = [i for i in ctx["counters"]["iterations"] if i.get("moe_token_slots")]
    if not its or not all("moe_held_slots" in i for i in its):
        return None
    return 100.0 * sum(i["moe_held_slots"] for i in its) / sum(i["moe_token_slots"] for i in its)
