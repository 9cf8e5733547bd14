"""Simulated model runner with a TPU step-time model.

The mocker philosophy mirrors the reference (lib/mocker/src/lib.rs:4-9):
run the REAL scheduling stack — PagePool prefix caching, continuous-batching
Scheduler, KV events, FPM — and fake only the accelerator. SimRunner
implements the engine's Runner (engine/runner_api.py) as ModelRunner does,
sleeping per a linear step-time model instead of dispatching XLA programs,
so router/planner/frontend tests and CI run with zero TPUs while exercising
every byte of the orchestration path.

Timing model (fitted to v5e single-chip measurements; override per test):
  prefill(chunk)          = prefill_base_s + chunk_tokens * prefill_per_token_s
  prefill_packed(chunks)  = prefill_base_s + charged * prefill_per_token_s
                            (ONE dispatch base for the whole token-budget
                            packed set; charged = sum(chunk_tokens) under
                            prefill_cost="ragged" [default], or
                            N_bucket * S_bucket under "padded" — the
                            legacy [N, S] device path's real bill)
  decode_multi(T, batch)  = dispatch_overhead_s + T * (decode_base_s +
                            batch * decode_per_seq_s)
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from dynamo_tpu.engine.runner_api import MixedOut, Runner


@dataclass
class SimTiming:
    prefill_base_s: float = 0.004
    prefill_per_token_s: float = 0.00004  # ~25k tok/s prefill
    decode_base_s: float = 0.004
    decode_per_seq_s: float = 0.0003
    dispatch_overhead_s: float = 0.002
    # host→device KV onboarding (import_pages): dispatch setup plus a
    # per-page DMA cost. Charged by BOTH the synchronous admission-time
    # onboard and the prefetch promotion path, so prefetch A/Bs measure
    # overlap, not a fictional free copy.
    onboard_base_s: float = 0.002
    onboard_per_page_s: float = 0.0002
    # layer-streamed onboarding (import_pages layer_groups > 1): each
    # additional layer group issues its own transfer, costing this much
    # setup on top of its share of the per-page DMA. The model is honest
    # about both sides of the trade: only the FIRST group blocks the
    # dispatch (shallow layers must be resident before prefill starts);
    # the remaining groups stream concurrently with subsequent compute,
    # but the compute that CONSUMES the pages cannot finish before the
    # deepest group lands — so the A/B win is bounded by the genuinely
    # overlappable compute, never a fictional free copy. More groups =
    # smaller blocking slice but more per-group setup overhead.
    onboard_group_base_s: float = 0.0005
    # fork-on-branch CoW: one page's KV duplicated on-device when a
    # branch takes a private copy of the shared trunk's partial tail
    page_copy_s: float = 0.0002
    # device n-gram draft ring: ONE fused append+propose dispatch per
    # speculating iteration (engine._device_draft). Billed per call, not
    # per row — the whole point of the ring is that proposal cost stops
    # scaling with batch and history length
    draft_propose_s: float = 0.0002
    speed: float = 1.0  # scale all sleeps; 0 disables (unit tests)
    # prefill_packed cost mode. "ragged" (default) charges
    # sum(chunk_tokens) — the flat-token dispatch the ragged runner path
    # actually issues. "padded" charges N_bucket x S_bucket — the legacy
    # [N, S] bucket-padded dispatch — so pre/post mocker A/Bs compare the
    # ragged kernel against what the padded device path really cost, not
    # against an already-ideal simulator.
    prefill_cost: str = "ragged"
    pack_buckets: tuple = (1, 2, 4, 8, 16, 32)
    chunk_buckets: tuple = (16, 32, 64, 128, 256, 512, 1024)

    def packed_charge_tokens(self, chunk_lens: List[int]) -> int:
        """Token count one packed-prefill dispatch is charged for."""
        if self.prefill_cost == "padded":
            n = _sat_bucket(self.pack_buckets, len(chunk_lens))
            s = _sat_bucket(self.chunk_buckets, max(chunk_lens))
            return n * s
        if self.prefill_cost != "ragged":
            raise ValueError(
                f"unknown prefill_cost {self.prefill_cost!r} "
                "(expected 'ragged' or 'padded')"
            )
        return sum(chunk_lens)

    def spec_charge_tokens(self, draft_lens: List[int]) -> int:
        """Extra flat tokens one spec-verify dispatch is charged for:
        drafted+1 per speculating row (the verify row IS a short prefill
        chunk on the ragged path), bucket-padded under "padded" exactly
        like a packed prefill would be. Rows with no draft are plain
        decode rows and charge nothing here (they are covered by the
        decode term of the dispatch)."""
        lens = [d + 1 for d in draft_lens if d > 0]
        if not lens:
            return 0
        return self.packed_charge_tokens(lens)

    def sleep(self, seconds: float) -> None:
        if self.speed > 0:
            time.sleep(seconds * self.speed)


    @classmethod
    def from_profile(cls, profile, speed: float = 1.0,
                     variant=None) -> "SimTiming":
        """Calibrate from a HARDWARE profile artifact (planner/
        hw_profile.py) — the measured counterpart of fit(): mockers then
        simulate the chip that was actually profiled, not guessed
        constants."""
        from dynamo_tpu.planner.hw_profile import load_profile, profile_fit

        if isinstance(profile, str):
            profile = load_profile(profile)
        f = profile_fit(profile, variant)
        return cls(
            prefill_base_s=f["prefill_base_s"],
            prefill_per_token_s=f["prefill_per_token_s"],
            decode_base_s=f["decode_base_s"],
            decode_per_seq_s=f["decode_per_seq_s"],
            dispatch_overhead_s=0.0,  # measured per-step times include it
            speed=speed,
        )

    @classmethod
    def fit(cls, fpm_history, decode_steps: int = 1, speed: float = 1.0) -> "SimTiming":
        """Fit the linear step-time model to observed ForwardPassMetrics
        (real engine runs → calibrated mocker; the reference's DynoSim
        fits its simulator from profiling data the same way). Accepts
        dataclasses or plain dicts (FPM events off the event plane)."""

        def get(m, k):
            return getattr(m, k, None) if not isinstance(m, dict) else m.get(k)

        def lstsq(xs, ys, d0, s0):
            # shared fitting routine with the hardware profiler
            from dynamo_tpu.planner.hw_profile import fit_line

            return fit_line(zip(xs, ys), d0, s0)

        dec = [(get(m, "n_running"), get(m, "wall_time_s"))
               for m in fpm_history if get(m, "kind") == "decode"]
        pre = [(get(m, "scheduled_tokens"), get(m, "wall_time_s"))
               for m in fpm_history if get(m, "kind") == "prefill"]
        base = cls()
        T = max(decode_steps, 1)
        # fallbacks are expressed per-DISPATCH (x T) so the division below
        # lands back on the per-step defaults when there's nothing to fit
        d_int, d_slope = lstsq([x for x, _ in dec], [y for _, y in dec],
                               base.decode_base_s * T, base.decode_per_seq_s * T)
        p_int, p_slope = lstsq([x for x, _ in pre], [y for _, y in pre],
                               base.prefill_base_s, base.prefill_per_token_s)
        return cls(
            prefill_base_s=p_int,
            prefill_per_token_s=p_slope,
            decode_base_s=d_int / T,
            decode_per_seq_s=d_slope / T,
            dispatch_overhead_s=0.0,  # folded into the decode intercept
            speed=speed,
        )

    @classmethod
    def fit_records(cls, records, speed: float = 1.0) -> "SimTiming":
        """Fit from flight-recorder `IterationRecord`s (runtime/
        flight_recorder.py dumps, `records` key) — the always-on black box
        every engine carries, so a production incident dump doubles as
        mocker calibration input. Accepts dataclasses or dicts.

        Decode iterations fit per-STEP (y = wall_s / decode_steps against
        x = decode_seqs) so dumps taken at different multi-step settings
        land on one model; prefill iterations fit y = wall_s against
        x = charged_tokens (what the dispatch was actually billed).
        `mixed` iterations are skipped — their wall time blends both
        regimes and would bias both fits."""

        def get(m, k, default=None):
            v = getattr(m, k, None) if not isinstance(m, dict) else m.get(k)
            return default if v is None else v

        from dynamo_tpu.planner.hw_profile import fit_line

        dec, pre = [], []
        for r in records:
            kind = get(r, "kind")
            wall = float(get(r, "wall_s", 0.0) or 0.0)
            if wall <= 0.0:
                continue
            if kind == "decode":
                steps = max(1, int(get(r, "decode_steps", 1) or 1))
                dec.append((int(get(r, "decode_seqs", 0) or 0),
                            wall / steps))
            elif kind == "prefill":
                toks = int(get(r, "charged_tokens", 0) or 0)
                if toks <= 0:
                    toks = sum(get(r, "chunk_tokens", []) or [])
                if toks > 0:
                    pre.append((toks, wall))
        base = cls()
        d_int, d_slope = fit_line(dec, base.decode_base_s,
                                  base.decode_per_seq_s)
        p_int, p_slope = fit_line(pre, base.prefill_base_s,
                                  base.prefill_per_token_s)
        return cls(
            prefill_base_s=p_int,
            prefill_per_token_s=p_slope,
            decode_base_s=d_int,
            decode_per_seq_s=d_slope,
            dispatch_overhead_s=0.0,  # folded into the decode intercept
            speed=speed,
        )

    def calibration_error(self, records) -> dict:
        """How well THIS timing model reproduces a set of
        `IterationRecord`s: per-kind MAPE plus the headline itl_p50_err —
        relative error between the median observed per-step decode time
        and the model's prediction at the median decode batch (the bound
        ISSUE/docs track: ≤ 15% means the twin's ITL distribution is
        trustworthy)."""

        def get(m, k, default=None):
            v = getattr(m, k, None) if not isinstance(m, dict) else m.get(k)
            return default if v is None else v

        dec_obs, dec_pred, pre_obs, pre_pred = [], [], [], []
        for r in records:
            kind = get(r, "kind")
            wall = float(get(r, "wall_s", 0.0) or 0.0)
            if wall <= 0.0:
                continue
            if kind == "decode":
                steps = max(1, int(get(r, "decode_steps", 1) or 1))
                n = int(get(r, "decode_seqs", 0) or 0)
                dec_obs.append(wall / steps)
                dec_pred.append(self.decode_base_s
                                + n * self.decode_per_seq_s)
            elif kind == "prefill":
                toks = int(get(r, "charged_tokens", 0) or 0)
                if toks <= 0:
                    toks = sum(get(r, "chunk_tokens", []) or [])
                if toks <= 0:
                    continue
                pre_obs.append(wall)
                pre_pred.append(self.prefill_base_s
                                + toks * self.prefill_per_token_s)

        def mape(obs, pred):
            pairs = [(o, p) for o, p in zip(obs, pred) if o > 0]
            if not pairs:
                return None
            return sum(abs(p - o) / o for o, p in pairs) / len(pairs)

        itl_err = None
        if dec_obs:
            obs_p50 = float(np.median(dec_obs))
            pred_p50 = float(np.median(dec_pred))
            if obs_p50 > 0:
                itl_err = abs(pred_p50 - obs_p50) / obs_p50
        return {
            "n_decode": len(dec_obs),
            "n_prefill": len(pre_obs),
            "decode_mape": mape(dec_obs, dec_pred),
            "prefill_mape": mape(pre_obs, pre_pred),
            "itl_p50_err": itl_err,
        }


def _sat_bucket(buckets, n: int) -> int:
    """Smallest bucket >= n, saturating at the largest (the mocker never
    fails a dispatch — an overflowing pack just pays the biggest shape)."""
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


def _sim_token(seed: int, position: int, vocab: int = 50000) -> int:
    # deterministic, avoids special ids < 16
    return (seed * 1103515245 + position * 2654435761) % (vocab - 16) + 16


class _SimHandle:
    """A SimRunner decode dispatch: its tokens and when the simulated
    device is done with it."""

    def __init__(self, done_at: float):
        self.out = None
        self.done_at = done_at


class SimRunner(Runner):
    """Drop-in for ModelRunner inside InferenceEngine (no JAX)."""

    can_run_ahead = True

    # guided rows ride full multi-step loops: decode_multi honors the
    # engine's host-callback mask context between fused steps, so the
    # scheduler never collapses a constrained plan to n_steps=1
    guided_fused = True
    has_verify_spec = True
    has_draft_ring = True
    has_prefill_packed = True

    def __init__(
        self,
        *,
        num_pages: int = 2048,
        page_size: int = 16,
        max_pages_per_seq: int = 256,
        timing: Optional[SimTiming] = None,
        vocab_size: int = 50000,
        spec_accept_rate: Optional[float] = None,
        kv_export_bytes: bool = False,
    ):
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        self.timing = timing or SimTiming()
        self.vocab_size = vocab_size
        # when set, export_pages emits tiny REAL KV arrays instead of the
        # hash-only marker, so G2/G3 offload writes actual files and the
        # disk tier's read/decode/quarantine machinery runs for real in
        # chaos sims (hash-only blocks never touch the filesystem)
        self.kv_export_bytes = kv_export_bytes
        # oracle drafting knob for spec-decode A/Bs: when set, spec_draft
        # proposes the TRUE sim stream corrupted per-token with
        # probability (1 - rate), so benches sweep acceptance without
        # changing the emitted bytes (verify always corrects mismatches).
        # None = no oracle; the engine falls back to n-gram proposal.
        self.spec_accept_rate = spec_accept_rate
        # dispatched-vs-charged token accounting for packed prefills, so
        # A/Bs can assert what the cost model billed (acceptance: ragged
        # mode bills sum(chunk_tokens), padded bills N_bucket x S_bucket)
        self.stats = {
            # real prompt tokens prefilled through ANY path (single-chunk,
            # packed, or verify-ridealong) — with tree reuse the scheduler
            # only dispatches the un-reused suffix, so this counter is the
            # honest "prefill work actually done" figure A/Bs difference
            "prefill_tokens_real": 0,
            "packed_dispatches": 0,
            "packed_tokens_real": 0,
            "packed_tokens_charged": 0,
            "spec_dispatches": 0,
            "spec_tokens_charged": 0,
            # device draft ring: fused append+propose dispatches billed
            # (engine._device_draft issues at most one per iteration)
            "draft_dispatches": 0,
            "onboards_streamed": 0,
            "onboard_overlap_s": 0.0,
            "page_copies": 0,
        }
        # wall-clock instant the deepest in-flight layer group of a
        # streamed onboard lands (0.0 = nothing in flight). Dispatches
        # that consume onboarded pages block on it before returning.
        self._onboard_ready_t = 0.0
        self._onboard_rest_s = 0.0
        # when the simulated device finishes its newest decode dispatch
        self._device_free_at = 0.0

    def charged_tokens(self) -> int:
        return self.stats["packed_tokens_charged"]

    # -- Runner steps ------------------------------------------------------
    def prefill(self, tokens: List[int], start_pos: int, page_table_row, prior_len: int, adapter: int = 0, mm=None):
        t = self.timing
        self.stats["prefill_tokens_real"] += len(tokens)
        t.sleep(t.prefill_base_s + len(tokens) * t.prefill_per_token_s)
        self._drain_onboard()
        # "logits": seeded by the LAST prompt token + position only, so the
        # first sampled token is identical whether the prefix came from
        # cache or was recomputed (chunk-invariant); subsequent decode
        # tokens chain deterministically off the fed token
        seed = tokens[-1] if tokens else 0
        return ("sim-logits", seed, start_pos + len(tokens))

    def prefill_packed(self, chunks):
        """Token-budget packed prefill: the whole chunk set rides ONE
        simulated dispatch, so the step-time model charges the dispatch
        base once plus the per-token cost of every packed token — the
        timing shape of the runner's fused ragged program. Takes the
        engine's chunk dicts ({"tokens", "start", ...}); returns one
        sim-logits tuple per chunk."""
        t = self.timing
        total = sum(len(c["tokens"]) for c in chunks)
        charged = t.packed_charge_tokens([len(c["tokens"]) for c in chunks])
        self.stats["packed_dispatches"] += 1
        self.stats["prefill_tokens_real"] += total
        self.stats["packed_tokens_real"] += total
        self.stats["packed_tokens_charged"] += charged
        t.sleep(t.prefill_base_s + charged * t.prefill_per_token_s)
        self._drain_onboard()
        out = []
        for c in chunks:
            toks = c["tokens"]
            seed = toks[-1] if toks else 0
            out.append(("sim-logits", seed, c["start"] + len(toks)))
        return out

    def sample_one(self, logits, sampling, step: int, mask=None) -> int:
        _, seed, position = logits
        tok = _sim_token(seed, position, self.vocab_size)
        if mask is not None and not mask[tok]:
            # guided decoding against the mocker: honor the mask by
            # remapping onto the allowed set (deterministic in the seed);
            # an empty mask passes through (engine force-stops it)
            allowed = np.flatnonzero(mask)
            if len(allowed):
                tok = int(allowed[tok % len(allowed)])
        return tok

    def sample_one_ex(self, logits, sampling, step: int, history=None,
                      n_logprobs: int = -1, mask=None):
        # no penalties and no logprob report in the sim: the plain token
        return self.sample_one(logits, sampling, step, mask=mask), None

    def decode_multi(
        self, n_steps: int, tokens: List[int], positions: List[int],
        page_tables, sampling, step: int, adapters=None, masks=None,
        mask_fn=None, guided_dev=None, n_logprobs: int = -1,
        histories=None, prompt_lens=None,
    ):
        return self.decode_collect(self.decode_dispatch(
            n_steps, tokens, positions, page_tables, sampling, step,
            masks=masks, mask_fn=mask_fn, guided_dev=guided_dev,
            n_logprobs=n_logprobs))

    def decode_collect(self, handle):
        """Wait until the simulated device has finished the dispatch."""
        wait = handle.done_at - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        self._drain_onboard()
        return handle.out

    def decode_dispatch(
        self, n_steps: int, tokens, positions: List[int],
        page_tables, sampling, step: int, adapters=None, masks=None,
        biases=None, mask_fn=None, guided_dev=None, n_logprobs: int = -1,
        histories=None, prompt_lens=None, side=None, prev=None,
    ):
        """The tokens at once (they are a pure function of the inputs) and
        the time the device model owes for them on the handle: the device
        starts a dispatch when it has finished the one before, and the host
        pays for it only where decode_collect has to wait. Host time spent
        under a queued dispatch is therefore counted once."""
        t = self.timing
        if prev is not None:
            toks = prev.out[0] if isinstance(prev.out, tuple) else prev.out
            tokens = [int(x) for x in toks[:, -1]]
        cost = t.speed * (
            t.dispatch_overhead_s
            + n_steps * (t.decode_base_s + len(positions) * t.decode_per_seq_s))
        self._device_free_at = max(self._device_free_at,
                                   time.monotonic()) + cost
        handle = _SimHandle(self._device_free_at)
        handle.out = self._decode_tokens(
            n_steps, tokens, positions, masks, mask_fn, guided_dev,
            n_logprobs)
        return handle

    def _decode_tokens(self, n_steps, tokens, positions, masks, mask_fn,
                       guided_dev, n_logprobs):
        # device-resident guided plan: the numpy twin of the runner's
        # in-XLA DFA walk (_decode_loop's `guided` operand) — combined
        # transition/mask tables, per-row global states, advance-before-
        # mask on every step after the first. Byte-identity between this
        # and the mask_fn callback path is what pins the device tables
        # as a pure transport change (tests/test_guided.py).
        gtrans = gmask = gstate = None
        gpend = False
        if guided_dev is not None:
            from dynamo_tpu.guided.device_table import combine_tables

            g_tables, g_rows, gpend = guided_dev
            gtrans, gmask, offs = combine_tables(g_tables)
            gstate = np.full(len(tokens), gtrans.shape[0] - 1, np.int64)
            for i, ent in enumerate(g_rows):
                if ent is not None:
                    ti, st = ent
                    gstate[i] = offs[ti] + int(st)
        # step-outer: each fused step is seeded by the PREVIOUS EMITTED
        # token (like the real on-device feedback loop, where the masked
        # sample is what gets fed back), so the sim stream is a pure
        # function of (prev_emitted_token, position) and is invariant to
        # dispatch boundaries — the property spec-decode and guided
        # byte-identity A/Bs assert. For unguided rows emitted == raw,
        # so this matches the legacy raw-chained stream exactly.
        out = np.zeros((len(tokens), n_steps), np.int32)
        prev = list(tokens)
        for j in range(n_steps):
            if gtrans is not None:
                if j > 0 or gpend:
                    gstate = gtrans[gstate, prev]
                m = gmask[gstate]
            elif mask_fn is not None:
                # the engine's host-callback mask context: advances the
                # per-row DFA state off the step's emitted tokens, same
                # contract the real runner's io_callback uses
                m = np.asarray(mask_fn(j, np.asarray(prev, np.int32)))
            elif masks is not None and j == 0:
                m = masks
            else:
                m = None
            for i in range(len(tokens)):
                tok = _sim_token(prev[i], positions[i] + 1 + j, self.vocab_size)
                if m is not None and not m[i, tok]:
                    allowed = np.flatnonzero(m[i])
                    if len(allowed):
                        tok = int(allowed[tok % len(allowed)])
                out[i, j] = tok
                prev[i] = tok
        # no penalties and no logprob report in the sim (see sample_one_ex)
        return (out, None) if n_logprobs >= 0 else out

    # -- speculative decoding (n-gram / oracle drafting) --------------------
    def spec_draft(self, last_token: int, pos: int, k: int):
        """Oracle draft source for A/Bs: proposes the true chained sim
        stream, corrupting each position independently with probability
        (1 - spec_accept_rate), deterministic in (token, position).
        Returns None when the knob is unset — the engine then uses
        n-gram proposal like on a real runner."""
        rate = self.spec_accept_rate
        if rate is None:
            return None
        drafts: List[int] = []
        prev = last_token
        for j in range(k):
            true = _sim_token(prev, pos + 1 + j, self.vocab_size)
            u = _sim_token(prev ^ 0x5BD1E99, pos + 1 + j, self.vocab_size)
            if (u % 10000) / 10000.0 < rate:
                drafts.append(true)
            else:
                # corrupted draft: a different valid token id (stays >= 16)
                drafts.append((true - 16 + 1) % (self.vocab_size - 16) + 16)
            prev = true  # the oracle keeps proposing along the true stream
        return drafts

    def spec_draft_tree(self, last_token: int, pos: int, k: int,
                        branches: int):
        """Tree-draft oracle: branch 0 is exactly spec_draft's proposal;
        extra branches follow the same true stream with an INDEPENDENT
        corruption pattern at the same per-position accept rate. At equal
        per-branch acceptance, the union of branches accepts strictly
        more prefix than any single branch — the effect tree speculation
        spends its forked verify rows to buy, which is what
        `bench_spec.py --tree` A/Bs measure. Returns None when the
        oracle knob is unset (the engine then uses host tree proposal)."""
        rate = self.spec_accept_rate
        if rate is None:
            return None
        out = [self.spec_draft(last_token, pos, k)]
        for b in range(1, max(1, branches)):
            drafts: List[int] = []
            prev = last_token
            for j in range(k):
                true = _sim_token(prev, pos + 1 + j, self.vocab_size)
                u = _sim_token(
                    (prev ^ 0x5BD1E99) + 7919 * b, pos + 1 + j,
                    self.vocab_size,
                )
                if (u % 10000) / 10000.0 < rate:
                    drafts.append(true)
                else:
                    drafts.append(
                        (true - 16 + 1 + b) % (self.vocab_size - 16) + 16
                    )
                prev = true
            out.append(drafts)
        return out

    # -- device n-gram draft ring (numpy twin of ModelRunner's jitted
    # ring; see model_runner._draft_ring_step) ------------------------------
    def ensure_draft_ring(self, slots: int, k: int, window: int = 512) -> int:
        self._draft_hist: List[List[int]] = [[] for _ in range(int(slots))]
        self._draft_window = int(window)
        return max(16, int(k) + 2)

    def draft_ring_reset(self, slot: int, tokens: List[int]) -> None:
        self._draft_hist[slot] = [int(x) for x in tokens][-self._draft_window:]

    def draft_step(self, updates, k: int):
        """Numpy twin of the fused device proposal: append each (slot,
        delta), then propose per slot with the SAME suffix-match
        semantics as the host scan bounded to the ring window. Billed as
        ONE dispatch regardless of batch — the cost shape that makes
        device drafting worth A/B-ing against the per-sequence scan."""
        from dynamo_tpu.engine.ngram_draft import propose

        t = self.timing
        self.stats["draft_dispatches"] += 1
        t.sleep(t.draft_propose_s)
        W = self._draft_window
        for slot, delta in updates:
            h = self._draft_hist[slot]
            h.extend(int(x) for x in delta)
            if len(h) > W:
                del h[: len(h) - W]
        slots = len(self._draft_hist)
        drafts = np.full((slots, max(1, int(k))), -1, np.int32)
        n_prop = np.zeros(slots, np.int32)
        for s, h in enumerate(self._draft_hist):
            d = propose(h, int(k), window=W)
            n_prop[s] = len(d)
            if d:
                drafts[s, : len(d)] = d
        return drafts, n_prop

    def verify_spec(
        self, tokens: List[int], positions: List[int], page_tables,
        drafts: List[List[int]], sampling, step: int, chunks=(),
        masks=None,
    ):
        """Speculative verify as ONE simulated ragged flat-token dispatch:
        row i contributes len(drafts[i])+1 verify positions (a plain
        decode row when the draft is empty). Returns (rows, chunk_logits)
        where rows[i][j] is the target-sampled token at verify position j
        — the token the target model emits after feeding the row's last
        real token (j=0) or drafts[i][j-1] (j>0).

        Billing: one dispatch paying the decode sweep for every row plus
        the per-token verify compute, charged drafted+1 tokens per
        speculating row under prefill_cost="ragged" (bucket-padded under
        "padded"). Charges land in packed_tokens_charged so the flight
        recorder's per-iteration charged-token delta stays honest."""
        t = self.timing
        spec_lens = [len(d) for d in drafts]
        charged = t.spec_charge_tokens(spec_lens)
        chunk_charged = 0
        if chunks:
            chunk_charged = t.packed_charge_tokens(
                [len(c["tokens"]) for c in chunks]
            )
            real = sum(len(c["tokens"]) for c in chunks)
            self.stats["prefill_tokens_real"] += real
            self.stats["packed_tokens_real"] += real
        self.stats["spec_dispatches"] += 1
        self.stats["spec_tokens_charged"] += charged
        self.stats["packed_dispatches"] += 1
        self.stats["packed_tokens_charged"] += charged + chunk_charged
        t.sleep(
            t.dispatch_overhead_s
            + t.decode_base_s
            + len(tokens) * t.decode_per_seq_s
            + (charged + chunk_charged) * t.prefill_per_token_s
        )
        self._drain_onboard()
        rows = []
        for ri, (tok, pos, d) in enumerate(zip(tokens, positions, drafts)):
            out = np.zeros(len(d) + 1, np.int32)
            m = masks.get(ri) if masks else None
            for j in range(len(d) + 1):
                fed = tok if j == 0 else d[j - 1]
                out[j] = _sim_token(fed, pos + 1 + j, self.vocab_size)
                if m is not None and not m[out[j]]:
                    # guided rows ride verify draft-less (one position);
                    # honor the mask with the same deterministic remap
                    # sample_one / decode_multi use
                    allowed = np.flatnonzero(m)
                    if len(allowed):
                        out[j] = int(allowed[out[j] % len(allowed)])
            rows.append(out)
        chunk_logits = []
        for c in chunks:
            toks = c["tokens"]
            seed = toks[-1] if toks else 0
            chunk_logits.append(("sim-logits", seed, c["start"] + len(toks)))
        return MixedOut(rows, chunk_logits, t.prefill_cost == "ragged")

    def copy_pages(self, src: int, dst: int) -> None:
        """Fork-on-branch CoW page duplication — pure billing in the sim
        (there is no KV payload), but the cost model charges the device
        DMA so fork A/Bs don't measure a fictional free copy."""
        self.timing.sleep(self.timing.page_copy_s)
        self.stats["page_copies"] += 1

    def embed(self, token_lists: List[List[int]]) -> np.ndarray:
        self.timing.sleep(self.timing.prefill_base_s)
        out = np.zeros((len(token_lists), 16), np.float32)
        for i, t in enumerate(token_lists):
            rng = np.random.default_rng(sum(t) % (2**31))
            v = rng.standard_normal(16)
            out[i] = v / np.linalg.norm(v)
        return out

    # -- disagg KV transfer (simulated) ------------------------------------
    def export_pages(self, pages: List[int]):
        if not self.kv_export_bytes:
            return {"data": True, "sim": True, "n_pages": len(pages)}
        from dynamo_tpu.engine.model_runner import kv_arrays_to_payload

        # deterministic per-page planes, [L=1, n, PS, Hk=1, D=4] — small
        # enough that a 500-worker sim's spills stay cheap, real enough
        # that encode/decode_block round-trips (and corruption trips the
        # quarantine) exactly as on a real engine
        k = np.stack([
            np.full((1, self.page_size, 1, 4), float(p), dtype=np.float32)
            for p in pages
        ], axis=1)
        return kv_arrays_to_payload(k, k + 0.5)

    def import_pages(self, target_pages, offset: int, payload,
                     layer_groups: int = 1) -> None:
        # the transfer isn't free: charge the step-time model so KVBM
        # onboarding (sync or prefetched) costs simulated wall time
        t = self.timing
        dma = len(target_pages) * t.onboard_per_page_s
        g = max(1, int(layer_groups))
        if g == 1 or t.speed <= 0:
            t.sleep(t.onboard_base_s + dma)
            return
        # layer-streamed: block only for the first group (shallow layers
        # must be resident before prefill issues); the remaining groups
        # keep streaming while later compute runs. Their landing time is
        # recorded as a wall-clock deadline that the NEXT consuming
        # dispatch waits out — overlapped transfer is hidden only to the
        # extent real compute covers it, never dropped. Each extra group
        # pays its own issue setup (onboard_group_base_s), so very large
        # G values are honestly counter-productive.
        self.stats["onboards_streamed"] += 1
        t.sleep(t.onboard_base_s + dma / g)
        rest = dma * (g - 1) / g + (g - 1) * t.onboard_group_base_s
        self._onboard_ready_t = max(
            self._onboard_ready_t, time.monotonic() + rest * t.speed
        )
        self._onboard_rest_s = rest * t.speed

    def _drain_onboard(self) -> None:
        """Block until in-flight streamed layer groups have landed. Called
        at the tail of every consuming dispatch: the dispatch's own compute
        already advanced the clock, so only the uncovered remainder (if
        any) is slept — that remainder is exactly the non-overlapped part
        of the transfer."""
        if self._onboard_ready_t <= 0.0:
            return
        rem = self._onboard_ready_t - time.monotonic()
        self._onboard_ready_t = 0.0
        hidden = self._onboard_rest_s - max(0.0, rem)
        if hidden > 0:
            self.stats["onboard_overlap_s"] += hidden
        self._onboard_rest_s = 0.0
        if rem > 0:
            time.sleep(rem)
