"""Client-side median TTFT minus the engines' own median ttft_s (phase
spine): what HTTP, tokenisation, routing and the request plane add (ms)."""
from _common import window_phases


def read(ctx):
    ph = [p["ttft_s"] * 1e3 for p in window_phases(ctx) if "ttft_s" in p]
    if not ph or "ttft_p50_ms" not in ctx["e2e"]:
        return None
    return ctx["e2e"]["ttft_p50_ms"] - ctx["percentile"](ph, 50)
