"""What the readers of a hybrid state-space model's decode step share."""
import costs_ssm
from _common import attention_calls, decode_loop_modules


def decode_step_ms(ctx):
    """Median device time of one decode step: each decode-loop execution's
    duration over the steps it ran, the steps counted as the `attention`
    kernel calls inside it over the configuration's ATTENTION layers."""
    if not ctx["model"].get("mamba_d_state"):
        return None
    layers = costs_ssm.attn_layers(ctx["model"])
    per = [d / (k / layers) for m in decode_loop_modules(ctx)
           for d, k in zip(m["durations_ms"], attention_calls(m))
           if layers and k >= layers and k % layers == 0]
    return ctx["percentile"](per, 50) if per else None


def captured(ctx, its):
    """Those of `its` (flight-recorder iterations) that began inside one of
    the run's profiler captures: the record's `ts` against counters.json's
    `trace.captures`, both on the wall clock. The trace's times come from
    those seconds alone and the batch climbs through a window, so the counts
    a share joins with them have to come from the same seconds. (The device
    side of a capture holds about half of its second: the join is good to
    the capture, not to the event.) All of `its` where the run recorded no
    capture or none began inside one."""
    caps = (ctx["counters"].get("trace") or {}).get("captures") or []
    inside = [i for i in its
              if any(c["start_wall"] <= i.get("ts", -1.0) < c["stop_wall"] for c in caps)]
    return inside or its
