"""What the readers of a request's decode account share (PR 56): the spine of
every request that reached an engine inside the window
(`_common.window_phases`), with the interval from the commit mark of its
first token to the mark of its last cut by what the step loop was doing
meanwhile: `decode_s` = `decode_ahead_s` + `decode_cold_s` + `decode_mixed_s`
+ `decode_prefill_s` + `decode_other_s` + `decode_wait_s`, and
`decode_tokens` (docs/observability.md, "Per-request latency spine").

`tpot_p95_ms` is the 95th centile of a per-request quotient, so it is made
of the window's few unluckiest streams: the TAIL here is the
max(3, ceil(n / 10)) requests with the highest engine-side TPOT among the n
that decoded at least two tokens. A program whose spine lacks the keys (any
before PR 56) reads None: the line leaves the metric out, nothing raises."""
import math

from _common import window_phases

NEEDED = ("decode_s", "decode_tokens", "e2e_s", "ttft_s")


def decoded(ctx) -> list:
    """The window's requests with a decode interval of two tokens or more."""
    return [p for p in window_phases(ctx)
            if all(k in p for k in NEEDED) and p["decode_tokens"] >= 2]


def tpot_ms(p) -> float:
    """The client's arithmetic, `(last - first) / (n_tokens - 1)`
    (loadgen.end_to_end), on the engine's own stamps (ms)."""
    return 1e3 * (p["e2e_s"] - p["ttft_s"]) / (p["decode_tokens"] - 1)


def tail(ctx) -> list:
    ps = decoded(ctx)
    n = max(3, math.ceil(len(ps) / 10))
    return sorted(ps, key=tpot_ms, reverse=True)[:n]


def share_pct(requests, *keys):
    """Sum of the spine's `keys` over sum of `decode_s`, these requests (%)."""
    whole = sum(p["decode_s"] for p in requests)
    if not requests or whole <= 0.0 or any(k not in p for p in requests for k in keys):
        return None
    return 100.0 * sum(p[k] for p in requests for k in keys) / whole
