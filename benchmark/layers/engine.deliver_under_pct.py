"""Share of the window's undisturbed iterations whose items and publish were
delivered while a program of the engine's was enqueued and not collected
(flight recorder `deliver_under`, PR 55) (%): every iteration delivers its
publish, so the rest delivered before an idle pass, a whole step or a
failure, with the device waiting."""
from _host import undisturbed


def read(ctx):
    its = undisturbed(ctx)
    if not its or any("deliver_under" not in i for i in its):
        return None
    return 100.0 * sum(bool(i["deliver_under"]) for i in its) / len(its)
