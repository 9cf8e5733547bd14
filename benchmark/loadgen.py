"""The open-loop load generator and HTTP client, and the arithmetic that turns
its records into the end-to-end metrics. No jax here: the parent that runs
this never touches the chip.

A schedule is a list of chains (generators/*.py). A chain is one or more turns
by one user: turn 0 falls due at `due_s` after offering starts, each later
turn a think time after the previous reply ended. Every latency is timed from
when the turn was DUE, not from when it was sent, so a stalled server or a
starved generator cannot hide its own queue; how late the generator ran is
reported beside it (dynamo_tpu/bench/loadgen.py times from the send).
"""

from __future__ import annotations

import asyncio
import json
import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional


def word(i: int) -> str:
    return f"t{i}"


def text_of(ids: List[int]) -> str:
    return " ".join(map(word, ids))


def ids_of(text: str) -> List[int]:
    return [int(w[1:]) for w in text.split()]


def percentile(values: List[float], q: float) -> float:
    """q in [0, 100], linear interpolation between closest ranks (numpy's
    default), on all the values given."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


@dataclass
class Turn:
    chain: int
    index: int
    due: float  # monotonic
    max_tokens: int
    prompt_tokens: int
    sent: Optional[float] = None
    chunks: List[tuple] = field(default_factory=list)  # (t, n_tokens)
    done: Optional[float] = None
    usage: Optional[dict] = None
    error: Optional[str] = None
    reply_ids: List[int] = field(default_factory=list)

    @property
    def n_tokens(self) -> int:
        return sum(n for _, n in self.chunks)

    @property
    def ok(self) -> bool:
        return (self.error is None and self.done is not None
                and self.n_tokens == self.max_tokens
                and (self.usage or {}).get("completion_tokens") == self.max_tokens
                and (self.usage or {}).get("prompt_tokens") == self.prompt_tokens)

    @property
    def first(self) -> Optional[float]:
        return self.chunks[0][0] if self.chunks else None

    @property
    def last(self) -> Optional[float]:
        return self.chunks[-1][0] if self.chunks else None


async def stream_completion(session, url: str, model: str, prompt_ids: List[int],
                            max_tokens: int, turn: Turn) -> None:
    """One streaming /v1/completions call; fills `turn`."""
    body = {
        "model": model, "prompt": text_of(prompt_ids), "max_tokens": max_tokens,
        "temperature": 0.0, "ignore_eos": True, "stream": True,
        "stream_options": {"include_usage": True},
    }
    turn.sent = time.monotonic()
    try:
        async with session.post(url, json=body) as resp:
            if resp.status != 200:
                turn.error = f"http {resp.status}: {(await resp.text())[:200]}"
                return
            async for raw in resp.content:
                now = time.monotonic()
                line = raw.strip()
                if not line.startswith(b"data:"):
                    continue
                data = line[5:].strip()
                if data == b"[DONE]":
                    turn.done = now
                    break
                msg = json.loads(data)
                if msg.get("error"):
                    turn.error = str(msg["error"])[:200]
                    return
                if msg.get("usage"):
                    turn.usage = msg["usage"]
                for ch in msg.get("choices") or []:
                    got = ids_of(ch.get("text") or "")
                    if got:
                        turn.chunks.append((now, len(got)))
                        turn.reply_ids.extend(got)
                    if ch.get("finish_reason") == "error":
                        turn.error = "finish_reason error"
    except Exception as e:  # a refused or broken stream is a failed turn
        turn.error = f"{type(e).__name__}: {e}"[:200]


async def play(chains: List[dict], url: str, model: str, t_offer: float,
               t_stop_offering: float, drain_limit_s: float) -> List[Turn]:
    """Offer every chain on its schedule, starting at monotonic `t_offer`.
    No turn that falls due at or after `t_stop_offering` is sent. Returns
    every turn that was offered; one still unfinished `drain_limit_s` after
    offering stopped is cancelled and stays not-done (a failure)."""
    import aiohttp

    turns: List[Turn] = []

    async def run_chain(ci: int, chain: dict) -> None:
        history = list(chain["prefix_ids"])
        due = t_offer + chain["due_s"]
        for k, spec in enumerate(chain["turns"]):
            if due >= t_stop_offering:
                return
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            prompt = history + spec["user_ids"]
            turn = Turn(ci, k, due, spec["max_tokens"], len(prompt))
            turns.append(turn)
            await stream_completion(session, url, model, prompt, spec["max_tokens"], turn)
            if not turn.ok:
                return  # a broken session offers no later turns
            history = prompt + turn.reply_ids
            due = turn.done + spec["think_s"]

    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        tasks = [asyncio.ensure_future(run_chain(i, c)) for i, c in enumerate(chains)]
        deadline = t_stop_offering + drain_limit_s
        _, pending = await asyncio.wait(tasks, timeout=max(0.0, deadline - time.monotonic()))
        for t in pending:
            t.cancel()
        if pending:
            await asyncio.wait(pending, timeout=10)
    return turns


def end_to_end(turns: List[Turn], w0: float, w1: float) -> Dict[str, object]:
    """The end-to-end numbers from the client's records. The latency sample
    is every turn DUE inside [w0, w1); a failed one has no latency and is
    counted in `failed`. Tokens per second are all tokens streamed inside the
    window, whoever they belong to, over the window's length."""
    sample = [t for t in turns if w0 <= t.due < w1]
    good = [t for t in sample if t.ok]
    ttft = [(t.first - t.due) * 1e3 for t in good]
    tpot = [(t.last - t.first) / (t.n_tokens - 1) * 1e3 for t in good if t.n_tokens > 1]
    late = [(t.sent - t.due) * 1e3 for t in sample if t.sent is not None]
    streamed = sum(n for t in turns for (ts, n) in t.chunks if w0 <= ts < w1)
    gaps = []
    for t in good:
        for (a, _), (b, n) in zip(t.chunks, t.chunks[1:]):
            gaps.append((b - a) / n * 1e3)
    out: Dict[str, object] = {
        "attempted": len(sample), "failed": len(sample) - len(good),
        "n_ttft": len(ttft), "n_tpot": len(tpot),
        "out_tok_s": streamed / (w1 - w0),
        "errors": sorted({t.error for t in sample if t.error})[:5],
    }
    if ttft:
        out.update(ttft_p50_ms=percentile(ttft, 50), ttft_p95_ms=percentile(ttft, 95),
                   ttft_mean_ms=sum(ttft) / len(ttft), ttft_max_ms=max(ttft))
    if tpot:
        out.update(tpot_p50_ms=percentile(tpot, 50), tpot_p95_ms=percentile(tpot, 95),
                   tpot_mean_ms=sum(tpot) / len(tpot))
    if good:
        out["latency_mean_ms"] = sum((t.done - t.due) * 1e3 for t in good) / len(good)
    if late:
        out.update(late_p50_ms=percentile(late, 50), late_p95_ms=percentile(late, 95))
    if gaps:
        out["token_gap_ms"] = {f"p{q}": percentile(gaps, q) for q in (50, 90, 99)}
    return out


def in_flight_series(turns: List[Turn], w0: float, w1: float, step: float = 1.0) -> List[int]:
    """Turns sent and not yet done, sampled through the window: a backlog
    that grows from end to end means the rate is above the knee."""
    out, t = [], w0
    while t <= w1:
        out.append(sum(1 for x in turns if x.due <= t and (x.done is None or x.done > t)))
        t += step
    return out
