"""Share of the step loop's busy time in which the step thread ran a host
phase with NOTHING enqueued on the device and not collected, so that the
device was provably idle for want of the host: sum of `exposed_s` over sum of
`wall_s`, iterations the profiler did not disturb (%). What is hidden under a
queued program is not in it, though the device may idle there too where the
host outlasts the program: `device.idle_pct` less this and less `engine.wait`
is that share."""
from _host import share_pct


def read(ctx):
    return share_pct(ctx, "exposed_s")
