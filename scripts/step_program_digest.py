"""Lower the step programs of the accepted cells' configurations (abstract
operands, the CPU backend, nothing compiled or run) and print a digest of each
program's jaxpr (the traced program, kernels' bodies included): run on the parent's tree and on the change's."""
import hashlib, json, os, sys
from functools import partial
import jax, jax.numpy as jnp
root = sys.argv[1]
sys.path.insert(0, root)
from dynamo_tpu.engine import model_runner as mr
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.ragged_paged_attention import build_ragged_metadata
f32, i32 = jnp.float32, jnp.int32
def s(d, t): return jax.ShapeDtypeStruct(d, t)
def samp(B): return SamplingParams(s((B,), f32), s((B,), i32), s((B,), f32), s((B, 2), jnp.uint32), s((B,), f32), s((B,), f32), s((B,), f32))
out = {}
for name in ("phi-3-mini-4k", "mistral-small-4-119b", "deepseek-v3.2"):
    cfg = json.load(open(os.path.join(root, "benchmark", "configs", name + ".json")))
    c = ModelConfig(**cfg["model"])
    flags = cfg["server_flags"]
    params = jax.eval_shape(lambda: llama.init_params(c, jax.random.PRNGKey(0), jnp.bfloat16))
    pools = jax.eval_shape(lambda: llama.make_kv_pool(c, flags["num-pages"], 64, dtype=jnp.bfloat16))
    B, MP = flags["max-batch"], 64
    for impl in ("jnp", "pallas"):
        def dig(fn, *a, **k):
            return hashlib.sha256(str(jax.make_jaxpr(fn)(*a, **k)).encode()).hexdigest()[:16]
        out[f"{name}/{impl}/decode_loop"] = dig(partial(mr._decode_loop, c, impl, None, 4, -1), params, s((B,), i32), s((B + B * MP + 1,), i32), None, None, None, *pools, samp(B))
        fwd = mr._forward if c.is_moe else llama.forward
        out[f"{name}/{impl}/forward"] = dig(partial(fwd, c, attn_impl=impl), params, s((1, 128), i32), s((1, 128), i32), *pools, s((1, MP), i32), s((1,), i32), s((), i32))
        N, S = 2, 128
        kw = {"prows": s((), i32)} if c.is_moe else {}
        if not c.has_indexer:  # (no fused mixed program: Runner.fuses_mixed)
            out[f"{name}/{impl}/mixed"] = dig(partial(mr._mixed_loop, c, impl, None, 4), params, s((N, S), i32), s((N, S), i32), s((N, MP), i32), s((N,), i32), s((N,), i32), None, s((B,), i32), s((B + B * MP + 1,), i32), *pools, samp(B), **kw)
        if not c.is_mla:
            T = 288
            md = build_ragged_metadata([1] * 8 + [100], [5] * 8 + [0], [6] * 8 + [100], [[1]] * 8 + [[2, 3]], T, q_block=8, max_pages=MP)
            SEG, V = md["seg_page_table"].shape[0], c.vocab_size
            out[f"{name}/{impl}/ragged"] = dig(partial(mr._ragged_step, c, impl, None), params, s((1, T), i32), s((1, T), i32), s((T, MP), i32), s((T,), i32), s(md["seg_page_table"].shape, i32), s((SEG,), i32), s(md["meta"].shape, i32), s((SEG,), i32), *pools, samp(SEG), s((SEG,), i32), s((SEG,), i32), s((), i32), s((SEG, V), jnp.bool_), s((SEG, V), f32))
# the state-holding configuration: its decode loop and ragged step, which take
# the state pool and the rows' slots by keyword
from dynamo_tpu.models import jamba
cfg = json.load(open(os.path.join(root, "benchmark", "configs", "ai21-jamba2-3b.json")))
c = ModelConfig(**cfg["model"])
flags = cfg["server_flags"]
params = jax.eval_shape(lambda: llama.init_params(c, jax.random.PRNGKey(0), jnp.bfloat16))
pools = jax.eval_shape(lambda: llama.make_kv_pool(c, flags["num-pages"], 64, dtype=jnp.bfloat16))
state = jax.eval_shape(lambda: jamba.make_state_pool(c, 9, conv_dtype=jnp.bfloat16))
B, MP, T = 8, 64, 64
dig = lambda fn, *a, **k: hashlib.sha256(str(jax.make_jaxpr(fn)(*a, **k)).encode()).hexdigest()[:16]
for impl in ("jnp", "pallas"):
    out[f"ai21-jamba2-3b/{impl}/decode_loop"] = dig(partial(mr._decode_loop, c, impl, None, 4, -1), params, s((B,), i32), s((B + B * MP + 1,), i32), None, None, None, *pools, samp(B), state=state, slots=s((B,), i32))
    md = build_ragged_metadata([1] * 4 + [40], [5] * 4 + [0], [6] * 4 + [40], [[1]] * 4 + [[2]], T, q_block=8, max_pages=MP)
    SEG, V = md["seg_page_table"].shape[0], c.vocab_size
    out[f"ai21-jamba2-3b/{impl}/ragged"] = dig(partial(mr._ragged_step, c, impl, None), params, s((1, T), i32), s((1, T), i32), s((T, MP), i32), s((T,), i32), s(md["seg_page_table"].shape, i32), s((SEG,), i32), s(md["meta"].shape, i32), s((SEG,), i32), *pools, samp(SEG), s((SEG,), i32), s((SEG,), i32), s((), i32), s((SEG, V), jnp.bool_), s((SEG, V), f32), state=state, seg_slots=s((3, SEG), i32))
# the window-pool configuration: its decode loop and ragged step, which take
# the window pool and the rows' window page tables under the same keywords
from dynamo_tpu.models import mimo
cfg = json.load(open(os.path.join(root, "benchmark", "configs", "mimo-v2-flash.json")))
c = ModelConfig(**cfg["model"])
flags = cfg["server_flags"]
PS = flags["page-size"]
params = jax.eval_shape(lambda: llama.init_params(c, jax.random.PRNGKey(0), jnp.bfloat16))
pools = jax.eval_shape(lambda: llama.make_kv_pool(c, flags["num-pages"], PS, dtype=jnp.bfloat16))
state = jax.eval_shape(lambda: mimo.make_window_pool(c, 165, PS))
B, MP, T = 16, 96, 288
for impl in ("jnp", "pallas"):
    out[f"mimo-v2-flash/{impl}/decode_loop"] = dig(partial(mr._decode_loop, c, impl, None, 4, -1), params, s((B,), i32), s((B + B * MP + 1,), i32), None, None, None, *pools, samp(B), state=state, slots=s((B, MP), i32))
    md = build_ragged_metadata([1] * 8 + [100], [5] * 8 + [0], [6] * 8 + [100], [[1]] * 8 + [[2, 3]], T, q_block=8, max_pages=MP)
    SEG, V = md["seg_page_table"].shape[0], c.vocab_size
    out[f"mimo-v2-flash/{impl}/ragged"] = dig(partial(mr._ragged_step, c, impl, None), params, s((1, T), i32), s((1, T), i32), s((T, MP), i32), s((T,), i32), s(md["seg_page_table"].shape, i32), s((SEG,), i32), s(md["meta"].shape, i32), s((SEG,), i32), *pools, samp(SEG), s((SEG,), i32), s((SEG,), i32), s((), i32), s((SEG, V), jnp.bool_), s((SEG, V), f32), state=state, seg_slots=(s((T, MP), i32), s((SEG, MP), i32)))
print(json.dumps(out, indent=1))
