"""Bytes a decode step of a model with a lightning indexer has to move
(DeepSeek-V3.2: `models/mla.py` `_selected_attention`), from shapes alone; kept
with the benchmark like `costs.py` and `costs_mla.py`, which it imports and
leaves untouched.

In every layer a decode step scores each row's cached tokens with the
indexer (it reads the row's live pages of index keys, one `index_head_dim`
vector a token), keeps the `index_topk` best and attends to their latent rows
alone: it reads min(context, index_topk) rows of the latent cache a row, where
dense latent attention reads the whole context. Pages and rows are reckoned as
the device lays them out (`costs_mla.py`: the minor dimension in whole
128-lane tiles, a page's rows in whole sublane groups): an index-key page of
64 tokens x 128 is 16 KiB as its shape multiplies out, a latent row of 576 is
640 lanes, 1280 B. The indexer's operations are not reckoned: a cached token
costs 2 x index_n_heads x index_head_dim operations against 2 x
index_head_dim bytes, 64 operations a byte at 64 heads, under the chip's 240,
so the key stream bounds it."""

from __future__ import annotations

import costs
import costs_mla

BF16 = costs.BYTES["bf16"]


def indexer_params(model: dict) -> int:
    """One layer's indexer: queries from the compressed query, a key and a
    weight a head from the hidden state (the LayerNorm is left out, as norms
    are everywhere)."""
    hi, di = int(model["index_n_heads"]), int(model["index_head_dim"])
    return int(model["q_lora_rank"]) * hi * di + int(model["dim"]) * (di + hi)


def index_page_bytes(model: dict, page_size: int) -> int:
    """One layer's page of index keys as the device lays it out."""
    rows = costs_mla._round_up(int(page_size), costs_mla.SUBLANE_BYTES // BF16)
    return rows * costs_mla._round_up(int(model["index_head_dim"]), costs_mla.LANES) * BF16


def latent_row_bytes(model: dict) -> int:
    """One token's row of one layer's latent cache as the device lays it out."""
    return costs_mla._round_up(costs_mla.latent_width(model), costs_mla.LANES) * BF16


def weight_stream_bytes(model: dict, experts_hit: float | None = None) -> float:
    """The weights one step reads: costs.py's (attention, the dense layers,
    routers, shared experts, `experts_hit` routed experts a layer, the head)
    and every layer's indexer."""
    return (costs.weight_stream_bytes(model, experts_hit=experts_hit)
            + int(model["n_layers"]) * indexer_params(model) * BF16)


def decode_step_bytes(model: dict, experts_hit: float, ctx_pages: float,
                      sel_tokens: float, page_size: int) -> float:
    """One decode step whose rows hold `ctx_pages` live pages between them
    and attend to `sel_tokens` selected tokens between them (what ONE layer
    sees: the flight recorder's `decode_pages_live` and `dsa_sel_tokens` a
    step): the weights once, and in every layer the index keys of the live
    pages and the selected latent rows."""
    per_layer = (ctx_pages * index_page_bytes(model, page_size)
                 + sel_tokens * latent_row_bytes(model))
    return weight_stream_bytes(model, experts_hit) + int(model["n_layers"]) * per_layer


def sparse_decode_call_bytes(model: dict, sel_tokens: float, rows: float) -> float:
    """One call of the attention kernel over the selected rows (one layer of
    one decode step): the gathered latent rows, the absorbed query in and the
    attended latent out."""
    h, dc = int(model["n_heads"]), int(model["kv_lora_rank"])
    return (sel_tokens * latent_row_bytes(model)
            + rows * h * (costs_mla.latent_width(model) + dc) * BF16)
