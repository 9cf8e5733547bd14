"""What the readers of a model with KDA layers (models/ling.py) share."""
import costs_kda
from _common import attention_calls, decode_loop_modules
from _ssm import captured  # noqa: F401  (the readers import it from here)


def decode_step_ms(ctx):
    """Median device time of one decode step: each decode-loop execution's
    duration over the steps it ran, the steps counted as the `attention`
    kernel calls inside it over the configuration's MLA layers (3 of
    ling-3.0-flash-vl's 18). None for a model without KDA layers, and for a
    program that knows none (the parent of the PR that brought them)."""
    if not ctx["model"].get("kda_layer_period"):
        return None
    layers = costs_kda.mla_layers(ctx["model"])
    per = [d / (k / layers) for m in decode_loop_modules(ctx)
           for d, k in zip(m["durations_ms"], attention_calls(m))
           if layers and k >= layers and k % layers == 0]
    return ctx["percentile"](per, 50) if per else None


def kernel_seconds_a_call(ctx, name: str):
    """Device seconds over calls of the kernels whose name holds `name`."""
    kernels = (ctx.get("trace") or {}).get("kernels") or {}
    mine = [k for n, k in kernels.items() if name in n and k.get("calls")]
    if not mine:
        return None
    return sum(k["total_s"] for k in mine) / sum(k["calls"] for k in mine)
