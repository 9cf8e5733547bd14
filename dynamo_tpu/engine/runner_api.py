"""The door between InferenceEngine and whatever runs its steps.

`Runner` states everything the engine (and the worker around it) may ask
of a runner: the facts it reads, as class attributes with the value a
runner that says nothing has, and the steps it calls. Three classes stand
behind it: engine/model_runner.ModelRunner (the compiled programs),
mocker/sim.SimRunner (a cost model, no jax) and
parallel/multihost.ReplicatingRunner (a ModelRunner whose device steps a
multi-host group replays). The engine reads these names plainly; it never
asks a runner what it has by `getattr`/`hasattr`. What a sequence keeps
beside its KV pages is four of them (`side_kind`, `side_units`,
`side_unit_bytes`, `ensure_side_cache`) and one keyword of the steps
(`side=`); engine/side_cache.py is the host side of it, one kind or
several composed.

This module imports nothing heavy: mocker processes stay jax-free.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence


class BucketOverflowError(ValueError):
    """A dispatch needs a shape past the largest configured bucket. Carries
    what overflowed so the engine can degrade gracefully — shed chunks
    from the pack and defer them to the next iteration — instead of
    failing every sequence in the plan mid-iteration."""

    def __init__(self, n: int, buckets: Sequence[int]):
        super().__init__(f"{n} exceeds largest bucket {buckets[-1]}")
        self.n = n
        self.largest = buckets[-1]


class MixedOut(tuple):
    """What a step that can carry prefill chunks returns: the pair
    `(sampled, chunk_logits)` — chunk_logits one row per chunk served, in
    plan order — and `ragged`, whether the flat-token program ran it (the
    padded [N, S] one otherwise), with `pages_live`, the live (work unit,
    page) pairs one layer's call of its attention kernel walked
    (IterationRecord.ragged_pages_live; 0 where the padded one ran). A
    pair with attributes and not a longer tuple: benchmark/serve.py
    unpacks two (ROADMAP D10)."""

    def __new__(cls, sampled, chunk_logits, ragged: bool,
                pages_live: int = 0):
        self = super().__new__(cls, (sampled, chunk_logits))
        self.ragged = bool(ragged)
        self.pages_live = int(pages_live)
        return self


_REFUSALS = {
    # a model with state-space layers (models/jamba.py)
    "state": ("{what} is not built for a model with state-space layers "
              "({model}): a sequence's recurrent state lives in its state "
              "slot, which this path neither moves, copies nor rolls back"),
    # a model whose window and global attention layers keep caches of their
    # own (models/mimo.py)
    "window": ("{what} is not built for a model with a window pool "
               "({model}): a block's window layers keep the last "
               "sliding_window tokens alone, their older pages are freed, and "
               "nothing snapshots them, so a sequence's KV cannot be matched, "
               "moved, forked or rolled back by pages"),
    # a model that attends to its indexer's choice of tokens (DeepSeek-V3.2:
    # `ModelConfig.has_indexer`). Its pool is two arrays under one page
    # table, the latent pages and the index keys; what moves, copies,
    # exports or offloads pages by their ids carries both and is not refused
    "indexer": ("{what} is not built for a model with an indexer "
                "({model}): its pool holds the latent pages and the index "
                "keys side by side under one page table, the selection reads "
                "both as they were written, and this path was never run on the "
                "pair"),
}


def refusal(kind: str, model_name: str, what: str) -> str:
    """Why a worker whose model keeps a cache of `kind` (a side cache's
    `Runner.side_kind`, or "indexer") does not do `what`: the one sentence
    every such refusal raises or streams, from the runner and the engine
    alike; a model of several kinds ("a+b") hears each kind's."""
    return " ".join(_REFUSALS[k].format(what=what, model=model_name)
                    for k in kind.split("+"))


def device_step(fn):
    """Marks a Runner method that enqueues device work, or changes state
    that device work depends on (the pools, the weights, a compile
    bucket). A multi-host group must run it on every process, in the same
    order (parallel/multihost.ReplicatingRunner broadcasts exactly the
    marked methods; there is no second list)."""
    fn.device_step = True
    return fn


class Runner:
    # -- facts ---------------------------------------------------------------
    num_pages: int
    page_size: int
    max_pages_per_seq: int
    vocab_size: int
    config = None  # the served ModelConfig; None where no model runs
    mesh_config = None  # parallel.mesh.MeshConfig; None: no devices
    platform = "cpu"  # of the devices the steps run on
    max_seq_len = 0  # the model's context bound; 0 = only the pool bounds
    pp = False  # pipeline-parallel programs (no extras, no fusion)
    sp_enabled = False  # sequence-parallel prefill
    has_draft = False  # a draft model: spec_decode_multi / draft_prefill
    spec_gamma = 0  # draft tokens a spec_decode_multi round proposes
    guided_fused = False  # per-step guided masks ride multi-step loops
    supports_logit_bias = False
    lora = None  # the stacked multi-LoRA tree; None: built without slots
    routed = False  # steps hand out expert picks and load counters
    kv_quantize: Optional[str] = None  # "int8": quantized device KV pools
    ragged_mixed = False  # mixed plans ride the flat-token program
    fuses_mixed = True  # the runner has a one-dispatch program for a mixed
    #   plan at all (ragged or padded); False: the engine co-schedules
    #   chunks with the decoding rows as two dispatches on every platform,
    #   and nobody walks or compiles a fused lattice for it
    static_shapes = False  # step shapes are compiled per bucket: a live
    #   retune may not grow past what construction registered
    spec_seg_budget = 0  # rows one verify dispatch can sample (0 = no cap)
    holds_kv = False  # real KV bytes live here (a block without data must
    #   be recomputed) and device-handle export/import exist; False: KV is
    #   tracked by hash alone
    kv_page_shape = None  # (L, PS, Hk, D) of one wire page; None: no pools
    kv_wire_dtype = None
    has_verify_spec = False  # verify_spec
    has_draft_ring = False  # ensure_draft_ring / draft_ring_reset / draft_step
    has_prefill_packed = False  # prefill_packed
    can_run_ahead = False  # decode_dispatch / decode_collect: a decode
    #   dispatch may stay in flight while the next is chained on its tokens
    #   (the engine's step loop keeps one ahead where this says so), and
    #   mixed_dispatch / mixed_collect where the runner fuses mixed plans:
    #   between an enqueue and its readback the engine delivers what the
    #   commit before left for the clients (engine._deliver)
    prefill_enqueues = False  # `prefill` returns once its program is
    #   enqueued, its logits a device value nobody has waited for, so the
    #   engine delivers under it; False: it returns when the chunk is done
    #   (a cost model that sleeps), and the engine delivers before it
    side_kind: Optional[str] = None  # what a sequence keeps beside its KV
    #   pages (engine/side_cache.py): "state", a slot of recurrent state
    #   (models/jamba.py); "window", a second page table into the window
    #   layers' pool, indexed by the same logical page (models/mimo.py);
    #   None: nothing. With one, the steps take the rows' operands as
    #   `side=` (a row a sequence, a chunk's in its dict as "side"; None: the
    #   scratch unit), and whatever matches, moves, forks or rolls back KV
    #   by pages alone is refused (`refusal`)
    #   A model that keeps several kinds names them joined by "+"
    #   ("state+window", models/sambay.py): a row's operand is then the
    #   tuple of what its sequence holds in each, `side_units` and
    #   `side_unit_bytes` are tuples, one entry a kind, and a refusal says
    #   each kind's sentence
    side_units = 0  # units of that pool (slots, pages), scratch unit 0
    #   among them (ensure_side_cache)
    side_unit_bytes = 0  # one unit, all the layers that keep it
    skips_unsampled = False  # `prefill(..., sampled=False)` is understood:
    #   a chunk that does not end its prompt is served without what only
    #   its logits need (models/sambay.py: the cross-decoder and the head)

    # -- steps ---------------------------------------------------------------
    @device_step
    def prefill(self, tokens, start_pos, page_table_row, prior_len,
                adapter=0, mm=None, side=None, sampled=True):
        """One prefill chunk of one sequence; its last-token logits
        (`sampled` False, to a runner that `skips_unsampled`: nobody reads
        them)."""
        raise NotImplementedError

    @device_step
    def prefill_packed(self, chunks):
        """Several chunks in one dispatch (has_prefill_packed); one logits
        row per chunk."""
        raise NotImplementedError

    @device_step
    def draft_prefill(self, tokens, start_pos, page_table_row, prior_len,
                      mm=None):
        raise NotImplementedError

    @device_step
    def sample_one(self, logits, sampling, step, mask=None, bias=None):
        raise NotImplementedError

    @device_step
    def sample_one_ex(self, logits, sampling, step, history=None,
                      n_logprobs=-1, mask=None, bias=None):
        """sample_one with penalties over `history` and/or a logprob
        report: (token, lp | None)."""
        raise NotImplementedError

    @device_step
    def decode_multi(self, n_steps, tokens, positions, page_tables, sampling,
                     step, adapters=None, masks=None, biases=None,
                     mask_fn=None, guided_dev=None, n_logprobs=-1,
                     histories=None, prompt_lens=None, side=None):
        """n_steps fused decode iterations: sampled [rows, n_steps] on the
        host; with n_logprobs >= 0, (sampled, lp | None)."""
        raise NotImplementedError

    # The pair decode_multi is made of, for a runner that can_run_ahead.
    # Not device steps of a multi-host group: a handle names arrays of one
    # process, so a runner that replays its steps elsewhere says it cannot.
    def decode_dispatch(self, n_steps, tokens, positions, page_tables,
                        sampling, step, adapters=None, masks=None,
                        biases=None, mask_fn=None, guided_dev=None,
                        n_logprobs=-1, histories=None, prompt_lens=None,
                        side=None, prev=None):
        """Stage and enqueue decode_multi's work and return a handle
        without reading anything back. `prev`: a handle of the same
        decode_bucket whose last sampled tokens, wherever they are, are
        these rows' first tokens (row i continues row i; `tokens` is not
        looked at). A row with position -1 is a pad row: it keeps a place
        open and nothing is written for it."""
        raise NotImplementedError

    def decode_collect(self, handle):
        """What decode_multi returns, for the dispatch behind `handle`;
        waits for that dispatch alone, not for one queued behind it."""
        raise NotImplementedError

    def decode_bucket(self, n: int) -> int:
        """The rows a decode dispatch of n rows is padded to (handles
        chain only within one); n itself where nothing is padded."""
        return n

    def can_fuse(self, n_decode: int, n_chunks: int, *,
                 constrained: bool) -> bool:
        """Whether decode_multi_with_prefills can serve a plan of this
        shape in one dispatch; `constrained`: a decode row carries a
        guided mask or a logit bias. What the requests themselves rule out
        (logprobs, penalties, chunk-side bias, multimodal chunks) is the
        engine's to check."""
        return False

    @device_step
    def decode_multi_with_prefills(self, n_steps, tokens, positions,
                                   page_tables, sampling, step, chunks,
                                   adapters=None, masks=None, mask_fn=None,
                                   biases=None, guided_dev=None,
                                   side=None) -> MixedOut:
        raise NotImplementedError

    # The pair decode_multi_with_prefills is made of, for a runner that
    # can_run_ahead and fuses mixed plans (not device steps of a
    # multi-host group, as the decode pair is not).
    def mixed_dispatch(self, n_steps, tokens, positions, page_tables,
                       sampling, step, chunks, adapters=None, masks=None,
                       mask_fn=None, biases=None, guided_dev=None, side=None):
        """Stage and enqueue decode_multi_with_prefills' work and return a
        handle without reading anything back."""
        raise NotImplementedError

    def mixed_collect(self, handle) -> MixedOut:
        """What decode_multi_with_prefills returns, for the dispatch
        behind `handle`."""
        raise NotImplementedError

    @device_step
    def verify_spec(self, tokens, positions, page_tables, drafts, sampling,
                    step, chunks=(), masks=None, biases=None) -> MixedOut:
        raise NotImplementedError

    @device_step
    def spec_decode_multi(self, n_rounds, tokens, positions, page_tables,
                          sampling, step, gamma=None, adapters=None):
        raise NotImplementedError

    def spec_draft(self, last_token: int, pos: int, k: int):
        """A draft oracle's proposal, None where there is none (the engine
        then drafts from the sequence's own history)."""
        return None

    def spec_draft_tree(self, last_token: int, pos: int, k: int,
                        branches: int):
        return None

    @device_step
    def ensure_draft_ring(self, slots: int, k: int, window: int = 512) -> int:
        raise NotImplementedError

    @device_step
    def draft_ring_reset(self, slot: int, tokens) -> None:
        raise NotImplementedError

    @device_step
    def draft_step(self, updates, k: int):
        raise NotImplementedError

    @device_step
    def ensure_side_cache(self, units):
        """Hold a side pool of at least `units` units and say how many
        there are (0 where side_kind is None; a tuple in and out, one
        count a kind, where side_kind names several). The engine's side cache
        calls it once, when the engine takes the runner, with what its
        scheduler's limits need."""
        return 0

    @device_step
    def ensure_ragged_bucket(self, t: int) -> None:
        """Register `t` tokens as an exact flat-token compile bucket; a
        runner that compiles nothing has nothing to register."""

    @device_step
    def embed(self, token_lists):
        raise NotImplementedError

    # -- KV pages ------------------------------------------------------------
    @device_step
    def copy_pages(self, src: int, dst: int) -> None:
        raise NotImplementedError

    @device_step
    def export_pages(self, pages: List[int]) -> Dict[str, Any]:
        raise NotImplementedError

    @device_step
    def import_pages(self, target_pages, offset: int, payload,
                     layer_groups: int = 1) -> None:
        raise NotImplementedError

    def export_pages_device(self, pages: List[int]):
        """Colocated handoff of device buffers (holds_kv runners)."""
        raise NotImplementedError

    def import_pages_device(self, target_pages, offset: int, k, v) -> None:
        raise NotImplementedError

    def pools_deleted(self) -> bool:
        """A failed step consumed the donated KV pools."""
        return False

    @device_step
    def reset_kv_pools(self) -> None:
        """Zero the device pools; a runner that holds none has none to."""

    @device_step
    def reload_params(self, path: str) -> None:
        raise NotImplementedError

    # -- adapters ------------------------------------------------------------
    def adapter_names(self) -> List[str]:
        return []

    def adapter_slot(self, name: Optional[str]) -> int:
        if not name:
            return 0
        raise KeyError(name)

    @device_step
    def register_adapter(self, name: str, factors: Dict[str, Any]) -> int:
        raise NotImplementedError

    # -- routed experts (routed runners) ---------------------------------------
    def routed_picks(self):
        raise NotImplementedError

    def take_moe_load(self):
        raise NotImplementedError

    # -- what the runner reports ---------------------------------------------
    def compile_families(self) -> Dict[str, Any]:
        """Step-function families by name, each with `.variants` and
        `.calls`; none where nothing compiles."""
        return {}

    def compile_stats(self) -> Dict[str, Dict[str, Any]]:
        return {}

    def device_report(self) -> Dict[str, Any]:
        """Platform, devices and the dispatch paths in effect; empty where
        there is no device."""
        return {}

    def fill_record(self, record) -> None:
        """Write on an iteration's record (runtime/flight_recorder.
        IterationRecord) what this runner alone knows of its dispatches
        since the last one; nothing by default. A runner that
        `skips_unsampled`: the rows it ran the cross-decoder on and the
        chunk tokens it did not."""

    def charged_tokens(self) -> Optional[int]:
        """Cumulative prefill tokens a cost model billed; None where
        nothing bills (the iteration record keeps the plan's own count)."""
        return None

    def name_step_thread(self) -> None:
        """The calling thread serves this runner from here on."""

    def attach_sanitizer(self, san) -> None:
        """Adopt the engine's runtime sanitizer."""


# the one list a multi-host leader broadcasts (ReplicatingRunner)
DEVICE_STEPS = frozenset(
    name for name, fn in vars(Runner).items()
    if getattr(fn, "device_step", False)
)
