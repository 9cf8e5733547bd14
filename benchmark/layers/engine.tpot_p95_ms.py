"""95th centile of `(e2e_s - ttft_s) / (decode_tokens - 1)` over the window's
requests of two tokens or more (ms): the client's `tpot_p95_ms` arithmetic on
the engine's own stamps, taken where the step thread hands a token to its
stream. The gap to `tpot_p95_ms` is the return path: detokenise, SSE, the
event loop."""
from _tail import decoded, tpot_ms


def read(ctx):
    ps = decoded(ctx)
    if not ps:
        return None
    return ctx["percentile"]([tpot_ms(p) for p in ps], 95)
