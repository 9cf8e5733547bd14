"""The part of `engine.exposed_host_pct` spent in `engine.emit` (stop checks,
handing tokens to asyncio): sum of `exposed_emit_s` over sum of `wall_s`,
undisturbed iterations (%)."""
from _host import share_pct


def read(ctx):
    return share_pct(ctx, "exposed_emit_s")
