"""The window's chunk tokens on which the cross-decoder did not run, over its
chunk tokens (%): `yoco_skipped_tokens` / `chunk_tokens` of the flight
recorder, summed over the window's iterations. The cross-decoder (14 of
Phi-4-mini-flash's 32 layers, and the head) writes no cache, so a prefill
chunk runs it on its last row alone, and not at all where it is served alone
and does not end its prompt: at 100 every chunk token but the sampled rows
skipped it. None where the program records no such counter."""


def read(ctx):
    its = [i for i in ctx["counters"]["iterations"] if i.get("chunk_tokens")]
    if not its or not any("yoco_skipped_tokens" in i for i in its):
        return None
    return (100.0 * sum(i.get("yoco_skipped_tokens", 0) for i in its)
            / sum(i["chunk_tokens"] for i in its))
