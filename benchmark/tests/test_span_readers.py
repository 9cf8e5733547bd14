"""The seven readers that use the program's spans, its `other` compile
family and its spine keys (PERF.md section 3), each on a hand-made ctx:
present, absent (None: the line leaves the metric out, as with a program
that has none of them), and the edge each has."""

import importlib.util
import os

import pytest

import loadgen

LAYERS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "layers")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_" + name.replace(".", "_"), os.path.join(LAYERS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def trace_ctx(idle_gaps, window_s=4.0, modules=None):
    return {"trace": {"window_s": window_s, "busy_s": window_s - sum(v for _, v in idle_gaps),
                      "idle_gaps": idle_gaps, "modules": modules or {}},
            "percentile": loadgen.percentile}


GAPS = [["engine.schedule", 0.08], ["engine.emit", 0.06], ["engine.prep", 0.04],
        ["engine.wait", 0.04], ["engine.readback", 0.02], ["engine.stage", 0.02],
        ["engine.inbox", 0.01], ["engine.publish", 0.01], ["engine.mixed", 0.004],
        ["unattributed", 0.002]]
IDLE = ["sched.idle_plan_pct", "engine.idle_prep_pct", "engine.idle_emit_pct",
        "device.idle_unowned_pct"]


def test_idle_shares_add_up_to_the_idle_time():
    ctx = trace_ctx(GAPS)
    got = {n: reader(n)(ctx) for n in IDLE}
    assert got["sched.idle_plan_pct"] == pytest.approx(100 * 0.09 / 4)
    assert got["engine.idle_prep_pct"] == pytest.approx(100 * 0.06 / 4)  # no dispatch owner: 0
    assert got["engine.idle_emit_pct"] == pytest.approx(100 * 0.09 / 4)
    assert got["device.idle_unowned_pct"] == pytest.approx(100 * 0.006 / 4)
    wait = 100 * 0.04 / 4
    assert sum(got.values()) + wait == pytest.approx(reader("device.idle_pct")(ctx))


@pytest.mark.parametrize("name", IDLE[:3])
def test_idle_share_of_a_program_without_the_spans_is_not_reported(name):
    old = trace_ctx([["engine.decode", 0.12], ["unattributed", 0.04], ["engine.mixed", 0.03]])
    assert reader(name)(old) is None
    assert reader(name)({"trace": None}) is None and reader(name)({}) is None
    # one of the spans is enough: a listed owner counts, a missing one is 0
    assert reader(name)(trace_ctx([["engine.wait", 1.0]])) == 0.0


def test_a_bare_parent_owning_time_is_unowned():
    old = trace_ctx([["engine.decode", 0.12], ["unattributed", 0.04], ["engine.mixed", 0.03]])
    assert reader("device.idle_unowned_pct")(old) == pytest.approx(100 * 0.19 / 4)
    tiled = trace_ctx([["engine.prefill_packed", 0.01], ["engine.spec_verify", 0.01],
                       ["engine.emit", 0.5], ["engine.wait", 0.5]])
    assert reader("device.idle_unowned_pct")(tiled) == pytest.approx(100 * 0.02 / 4)
    assert reader("device.idle_unowned_pct")({"trace": None}) is None


def _snap(other, decode_loop=24):
    c = {"decode_loop": {"variants": decode_loop, "compile_s": 0.0, "calls": 9}}
    if other is not None:
        c["other"] = {"variants": other, "compile_s": 0.0, "calls": other}
    return {"compile": c}


def test_eager_compiles_over_two_replicas():
    read = reader("runner.eager_compiles_in_window")
    ctx = {"counters": {"at0": [_snap(3), _snap(0)], "at1": [_snap(5), _snap(1, 25)]}}
    assert read(ctx) == 3.0  # `other` only: the decode_loop variant is not eager
    assert reader("runner.compiles_in_window")(ctx) == 4.0  # the older reader sums all
    quiet = {"counters": {"at0": [_snap(3), _snap(0)], "at1": [_snap(3), _snap(0)]}}
    assert read(quiet) == 0.0
    old = {"counters": {"at0": [_snap(None)], "at1": [_snap(None)]}}
    assert read(old) is None and read({"counters": {"at0": [], "at1": []}}) is None


def _spine_ctx(phases):
    return {"final": {"phases": [dict(p, wall=100.0 + i, e2e_s=1.0)
                                 for i, p in enumerate(phases)]},
            "w0_wall": 99.0, "w1_wall": 150.0, "percentile": loadgen.percentile}


def test_prefill_median_and_preempted_share():
    ctx = _spine_ctx([{"prefill_s": 0.2, "preemptions": 0}, {"prefill_s": 0.4, "preemptions": 1},
                      {"prefill_s": 0.3, "preemptions": 0}, {"prefill_s": 0.9, "preemptions": 2}])
    assert reader("sched.prefill_p50_ms")(ctx) == pytest.approx(350.0)
    assert reader("sched.preempted_pct")(ctx) == pytest.approx(50.0)
    ctx["w1_wall"] = 101.5  # the window's edge cuts by arrival
    assert reader("sched.preempted_pct")(ctx) == pytest.approx(100 / 3)
    old = _spine_ctx([{"ttft_s": 0.5, "queue_wait_s": 0.1}])
    assert reader("sched.prefill_p50_ms")(old) is None
    assert reader("sched.preempted_pct")(old) is None


# -- the decode loop, found by its program's name ---------------------------


def _loop_ctx(modules, n_layers):
    return {"trace": {"modules": modules}, "model": {"n_layers": n_layers},
            "percentile": loadgen.percentile}


def test_decode_loop_labelled_by_an_expert_kernel_is_found_and_its_steps_counted():
    """27 layers, latent attention once a layer, an expert kernel twice: the
    module is labelled by the expert kernel, and a step is 27 attention
    calls whatever else ran."""
    calls = lambda steps: {"decode_mla_attention": 27 * steps, "grouped_experts": 54 * steps}
    mods = {
        "jit_decode_loop[grouped_experts]": {
            "durations_ms": [80.0, 40.0, 60.0], "kernel_calls": [216, 108, 162],
            "kernels": [calls(4), calls(2), calls(3)]},
        # the same program on an execution the capture cut: no whole number of steps
        "jit_decode_loop[-]": {"durations_ms": [3.0], "kernel_calls": [0], "kernels": [{}]},
        # another program that calls the same kernels is not the loop
        "jit_mixed_loop[grouped_experts]": {
            "durations_ms": [500.0], "kernel_calls": [54], "kernels": [calls(1)]},
    }
    assert reader("runner.decode_step_ms")(_loop_ctx(mods, 27)) == pytest.approx(20.0)
    # with the top kernel's count alone the steps would come out doubled
    assert [c // 27 for c in mods["jit_decode_loop[grouped_experts]"]["kernel_calls"]] == [8, 4, 6]


def test_decode_loop_of_the_dense_decoder_reads_as_before():
    """phi-3's shape: 32 layers, one decode_paged_attention a layer, four and
    three steps. A program of another name that calls the same kernel is not
    the loop: the finder goes by the program's name alone."""
    new = {"jit_decode_loop[decode_paged_attention]": {
               "durations_ms": [64.0, 48.0, 70.0], "kernel_calls": [128, 96, 130],
               "kernels": [{"decode_paged_attention": 128}, {"decode_paged_attention": 96},
                           {"decode_paged_attention": 130}]},
           "jit_ragged_step[ragged_paged_attention]": {
               "durations_ms": [126.0], "kernel_calls": [32],
               "kernels": [{"ragged_paged_attention": 32}]}}
    assert reader("runner.decode_step_ms")(_loop_ctx(new, 32)) == pytest.approx(16.0)
    other = {k.replace("jit_decode_loop", "jit_other"): v for k, v in new.items()}
    assert reader("runner.decode_step_ms")(_loop_ctx(other, 32)) is None
    assert reader("runner.decode_step_ms")(_loop_ctx({}, 32)) is None
    assert reader("runner.decode_step_ms")({"trace": None, "model": {"n_layers": 32}}) is None


def test_attention_busy_share_counts_the_attention_kernels_alone():
    """A routed cell's trace: two attention kernels beside an expert kernel
    that takes most of the Mosaic time. The share is the attention kernels'
    over busy time, not `kernel_s` (every Mosaic call) over it."""
    k = lambda calls, total_s: {"calls": calls, "total_s": total_s, "median_us": 1e6 * total_s / calls}
    trace = {"busy_s": 2.0, "kernel_s": 1.0, "kernels": {
        "decode_paged_attention": k(1000, 0.15), "ragged_paged_attention": k(10, 0.05),
        "routed_experts": k(2000, 0.7), "ssm_update": k(100, 0.1)}}
    read = reader("kernels.attn_busy_pct")
    assert read({"trace": trace}) == pytest.approx(10.0)  # (0.15 + 0.05) / 2.0, not 1.0 / 2.0
    latent = {"busy_s": 1.0, "kernels": {"decode_mla_attention": k(10, 0.25),
                                         "routed_experts": k(10, 0.5)}}
    assert read({"trace": latent}) == pytest.approx(25.0)
    # nothing to read: no trace, no busy time, or a window with no attention call in it
    assert read({"trace": None}) is None
    assert read({"trace": dict(trace, busy_s=0.0)}) is None
    assert read({"trace": {"busy_s": 1.0, "kernel_s": 0.5,
                           "kernels": {"routed_experts": k(10, 0.5)}}}) is None


# -- the weight stream of a decode step --------------------------------------


def _stream_ctx(model, iterations, step_ms=20.0):
    layers = model["n_layers"]
    kernel = "decode_mla_attention" if model.get("attn_type") == "mla" else "decode_paged_attention"
    return {"trace": {"modules": {f"jit_decode_loop[{kernel}]": {
                "durations_ms": [4 * step_ms], "kernel_calls": [4 * layers],
                "kernels": [{kernel: 4 * layers}]}}},
            "model": model, "percentile": loadgen.percentile,
            "here": os.path.dirname(LAYERS),
            "ready": {"device": {"kind": "TPU v5 lite"},
                      "engine": {"num_pages": 100, "page_size": 64}},
            "counters": {"iterations": iterations}}


PHI3 = {"vocab_size": 32064, "dim": 3072, "n_layers": 32, "n_heads": 32, "n_kv_heads": 32,
        "ffn_dim": 8192}
ROUTED = {"vocab_size": 1024, "dim": 2048, "n_layers": 9, "n_heads": 32, "n_kv_heads": 32,
          "ffn_dim": 6144, "attn_type": "mla", "kv_lora_rank": 512, "q_lora_rank": 0,
          "qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "v_head_dim": 128, "n_experts": 128,
          "n_experts_active": 6, "moe_ffn_dim": 768, "n_shared_experts": 2, "n_dense_layers": 1}


def test_decode_stream_share_of_a_dense_model_is_the_expression_it_was():
    import costs

    its = [{"decode_seqs": 3, "kv_usage": 0.5, "moe_experts_hit": 0.0},
           {"decode_seqs": 0, "kv_usage": 0.9, "moe_experts_hit": 0.0}]
    got = reader("model.decode_stream_pct")(_stream_ctx(PHI3, its))
    need = costs.weight_stream_bytes(PHI3) + 0.5 * 100 * 64 * costs.kv_bytes_per_token(PHI3)
    assert got == 100.0 * (need / 819e9) / 20e-3
    assert isinstance(costs.weight_stream_bytes(PHI3), int)  # no counter, no float
    assert reader("model.decode_stream_pct")(_stream_ctx(PHI3, its[1:])) is None


def test_decode_stream_share_of_a_routed_model_counts_the_experts_its_rows_hit():
    import costs

    read = reader("model.decode_stream_pct")
    its = [{"decode_seqs": 4, "kv_usage": 0.25, "moe_experts_hit": h} for h in (21.7, 22.4, 23.0)]
    live = 0.25 * 100 * 64 * costs.kv_bytes_per_token(ROUTED)
    got = read(_stream_ctx(ROUTED, its))
    assert got == pytest.approx(
        100.0 * ((costs.weight_stream_bytes(ROUTED, experts_hit=22.4) + live) / 819e9) / 20e-3)
    # 16.4 more experts than the floor, in each of 8 expert layers, 3 matrices each
    floor = read(_stream_ctx(ROUTED, [dict(i, moe_experts_hit=0.0) for i in its]))
    assert floor == pytest.approx(
        100.0 * ((costs.weight_stream_bytes(ROUTED) + live) / 819e9) / 20e-3)
    assert (got - floor) * 819e9 * 20e-3 / 100.0 == pytest.approx(
        8 * 16.4 * 3 * 2048 * 768 * 2)
    # a program that records no such counter reads the floor too
    old = [{k: v for k, v in i.items() if k != "moe_experts_hit"} for i in its]
    assert read(_stream_ctx(ROUTED, old)) == floor
