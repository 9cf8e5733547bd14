"""The part of `engine.exposed_host_pct` spent in `engine.stage` (the
runner's host-to-device staging): sum of `exposed_stage_s` over sum of
`wall_s`, undisturbed iterations (%)."""
from _host import share_pct


def read(ctx):
    return share_pct(ctx, "exposed_stage_s")
