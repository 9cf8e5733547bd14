"""The share of a forward's tokens that picked an expert layer's fullest held
expert, mean over the window's iterations that routed anything (the flight
recorder's `moe_load_max_share`; k / n_experts is an even load, 1 is every
token on one expert: the straggler of an expert-parallel stage). A fraction.
None where the program records none."""


def read(ctx):
    share = [i["moe_load_max_share"] for i in ctx["counters"]["iterations"]
             if i.get("moe_token_slots")]
    return sum(share) / len(share) if share else None
