"""The full layer's live keys and values as a decode step reads them (once by
the layer itself, once by each cross attention layer: eight readings on
Phi-4-mini-flash) as a share of all the bytes that step has to move
(costs_sambay.decode_step_bytes) (%), at the median rows and context of the
window's decode iterations (`_sambay.rows_and_context`; the flight recorder's
counters alone, so an untraced run reports it too). It says whether the cell
is where the shared cache does the work: a few per cent at short contexts,
where the step is the weights', and a fifth at 40 rows of 1.25 k. None for
another model."""
import costs_sambay
from _sambay import is_sambay, rows_and_context
from _swa import decode_its, loop_steps


def read(ctx):
    its = [i for i in decode_its(ctx) if loop_steps(i) > 0]
    if not is_sambay(ctx) or not its:
        return None
    rows, context = rows_and_context(ctx, its)
    need = costs_sambay.decode_step_bytes(ctx["model"], rows, context)
    return 100.0 * costs_sambay.full_kv_step_bytes(ctx["model"], rows * context) / need
