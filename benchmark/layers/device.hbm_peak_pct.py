"""peak_bytes_in_use / bytes_limit after the window, fullest chip (%)."""


def read(ctx):
    shares = [d["peak_bytes_in_use"] / d["bytes_limit"]
              for d in ctx["final"]["memory"].values()
              if d.get("bytes_limit") and "peak_bytes_in_use" in d]
    return 100.0 * max(shares) if shares else None
