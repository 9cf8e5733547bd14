"""Prompt tokens served from warm KV over prompt tokens admitted, through
the window, all replicas (%): scheduler counters at its start and end."""


def read(ctx):
    c = ctx["counters"]
    reused = sum(b["reused_prefix_tokens"] - a["reused_prefix_tokens"]
                 for a, b in zip(c["at0"], c["at1"]))
    total = sum(b["prompt_tokens_total"] - a["prompt_tokens_total"]
                for a, b in zip(c["at0"], c["at1"]))
    return 100.0 * reused / total if total else None
