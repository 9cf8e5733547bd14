"""Bytes one call of the latent-attention decode kernel has to move
(`ops/mla_attention.py` `decode_mla_attention`: one call is one layer of one
decode step over the batch), from shapes alone; kept with the benchmark like
`costs.py`, which it leaves untouched.

The kernel streams each live page of each row's latent cache once (one DMA
feeds the score and the value side: the values are the latent's first
`kv_lora_rank` columns of the same page), reads the absorbed query `[rows, H,
d_c + d_rh]` and writes the attended latent `[rows, H, d_c]`. A page is
reckoned as the device lays it out, not as its shape multiplies out: the
kernel's page block is `[page_size, d_c + d_rh]` in the TPU's tiled layout,
whose minor dimension pads to whole 128-lane tiles and whose rows pad to whole
sublane groups (8 rows of 4 bytes: 16 rows of bf16, 32 of int8). At rank 256
with a rotary key of 64 a row is 320 wide and takes 384 lanes: 1.2 x the
shapes. The operations are not reckoned: at one query a row the kernel is
bound by the page stream (2 x H x (d_c + d_rh) + 2 x H x d_c operations a
cached token against 2 x (d_c + d_rh) bytes: 58 operations a byte at 32 heads,
under the chip's 240)."""

from __future__ import annotations

LANES = 128
SUBLANE_BYTES = 32  # one sublane group: 8 rows of 4 bytes
BYTES = {"bf16": 2, "int8": 1}


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def latent_width(model: dict) -> int:
    """What one token caches in one layer: the latent and the rotary key."""
    return int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])


def latent_page_bytes(model: dict, page_size: int, dtype: str = "bf16") -> int:
    """One layer's page of the latent cache as the device lays it out."""
    b = BYTES[dtype]
    rows = _round_up(int(page_size), SUBLANE_BYTES // b)
    return rows * _round_up(latent_width(model), LANES) * b


def decode_call_bytes(model: dict, live_pages: float, rows: float, page_size: int,
                      dtype: str = "bf16") -> float:
    """One call over `rows` decode rows whose contexts hold `live_pages` pages
    between them (means over calls, so not whole numbers): the pages, the
    absorbed query in and the attended latent out (both in the activations'
    bf16, whatever the cache's type)."""
    h, dc = int(model["n_heads"]), int(model["kv_lora_rank"])
    return (live_pages * latent_page_bytes(model, page_size, dtype)
            + rows * h * (latent_width(model) + dc) * BYTES["bf16"])
