"""Share of the step loop's busy time the step thread spent blocked on the
device (`engine.readback`, the `device_get` of a dispatch's tokens): sum of
`host_readback_s` over sum of `wall_s`, undisturbed iterations (%). The
device-bound share of the loop: at 100 the host hides under the device
entirely."""
from _host import share_pct


def read(ctx):
    return share_pct(ctx, "host_readback_s")
