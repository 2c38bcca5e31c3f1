"""What PR 44 adds to the benchmark for its cell
``serve-xing4-longdoc-closed``: the cell, its traffic and its
configuration as the issue states them (the configuration against the
catalog row's numbers), every ``.xing`` metric found and read through
the harness's own loader from a canned run, the selectors against the
text the v5e's compiler prints at the table's three widths, the cost of
a decode step by hand. It asserts containment, never the benchmark's
size: a later PR adds to it. Nothing here is a measurement."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from cells import named, renamed  # noqa: E402

from benchmark import flops, peaks, spec, trace_reduce, xing_cost  # noqa: E402

CELL = "serve-xing4-longdoc-closed"
CONFIG = "xing4-29b-a4b-serve-1chip"
NEW_METRICS = [named(name, "xing") for name in (
    "decode_step_device_ms", "device_idle_share", "hbm_peak_share",
    "engine_host_ms_per_step", "host_calls_per_step",
    "decode_batch_occupancy", "kv_read_over_live",
    "decode_steps_ahead_share", "experts_touched_share",
    "expert_load_max_over_mean", "expert_ffn_time_share",
    "expert_ffn_roofline", "latent_attn_time_share", "latent_attn_roofline",
    "decode_step_roofline", "hyper_mix_time_share",
    # Since PR 59, as further cells on a survivor's list:
    "prefill_chunk_device_ms")]
# The catalog row Xing4.0-29B-A4B of the model-configs guide, every key
# of its `config`.
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 7, "max_position_embeddings": 8192}
# A window of 1,000 decode steps of 31 busy rows whose contexts hold
# 4,500 positions, 300 chunks of 120 tokens beside them.
COUNTERS = {
    "decode_steps": 1000, "decode_tokens": 31_000, "decode_steps_ahead": 990,
    "prefill_chunks": 300, "prefill_tokens": 36_000,
    "kv_positions_live": 31_000 * 4500, "kv_positions_read": 32 * 8192 * 1000,
    "decode_host_us": 1_500_000, "host_calls": 2_300,
    "expert_slots": 64 * 5 * 1300, "experts_touched": 56 * 5 * 1300,
    "expert_choices": (31_000 + 36_000) * 4 * 5,
    "expert_peak_choices": 2 * (31_000 + 36_000) * 4 * 5,
    "max_batch_size": 32, "max_seq_len": 8192}


def bench_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def per_layer() -> dict:
    return {m["name"]: m for m in spec.load_cell(CELL).per_layer}


def model() -> dict:
    return spec.load_cell(CELL).config


# ------------------------------------------------- the cell, as the issue


def test_the_cell_is_what_the_issue_states():
    bench = bench_json()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longdoc-closed", 1)
    assert len(cell["why"]) <= 200
    throughput = {m["name"]: m for m in bench["end_to_end"]}[
        "serve_tokens_per_s"]
    assert CELL in throughput["workloads"]
    loaded = spec.load_cell(CELL)
    assert {m["name"] for m in loaded.end_to_end} == \
        {"serve_tokens_per_s", "setup_s"}
    # At least what PR 44 promised: a later PR adds to the cell.
    assert set(NEW_METRICS) <= {m["name"] for m in loaded.per_layer}
    traffic = loaded.traffic
    assert traffic["generator"] == "closed_clients"
    assert (traffic["clients"], traffic["requests_per_client"]) == (48, 8)
    assert traffic["prompt"] == {"dist": "uniform", "min": 2048, "max": 4864}
    assert traffic["output"] == {"dist": "uniform", "min": 1024, "max": 3072}
    assert traffic["temperature"] == 0.0
    assert (traffic["ramp_timeout_s"], traffic["trace_after_share"],
            traffic["trace_seconds"]) == (90.0, 0.4, 4.0)
    # Since PR 54 ONE schedule stands for the traffic (the window sees
    # 90 of a round's 384 requests, and a deal from --seed moved the
    # prompt tokens inside it by a sixth: PERF.md section 2) and the
    # rate is the plain one over the window; --seed draws the tokens and
    # the weights (tests/benchmark/test_rate_blocks.py).
    assert traffic["schedule_seed"] == 3680000089
    assert traffic["rate_over"] == "window"
    # The longest request holds 7,936 of the table's 8,192 positions;
    # every prompt is past the quarter width, so no decode step runs
    # there.
    config = loaded.config
    assert traffic["prompt"]["max"] + traffic["output"]["max"] == 7936 \
        < config["engine"]["max_seq_len"] == 8192
    assert traffic["prompt"]["min"] >= config["engine"]["max_seq_len"] // 4


def test_the_configuration_keeps_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same key, but
    for the two in ``reduced`` (the driver checks them against the
    catalog itself)."""
    config = model()
    entry = {c["name"]: c for c in bench_json()["configs"]}[CONFIG]
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/" \
        "config.json"
    assert entry["reduced"] == config["reduced"] == list(REDUCED)
    assert {k: config[k] for k in CATALOG} == {**CATALOG, **REDUCED}
    assert set(config["reduced_why"]) == set(REDUCED)
    # The issue's third cut could not be made: the file says why.
    assert "first_k_dense_replace stays as published" \
        in config["reduced_why"]["num_hidden_layers"]
    assert config["left_out"] == ["num_nextn_predict_layers"]
    assert "adds nothing to the next token's logits" in \
        config["left_out_why"]["num_nextn_predict_layers"]
    # What the reference needs travels as numbers (spec.model_numbers):
    # the nested rope_scaling group's are repeated flat, key for key.
    numbers = spec.model_numbers(config)
    for key, value in CATALOG["rope_scaling"].items():
        if key != "type":
            assert numbers[f"rope_scaling_{key}"] == value
    for said in ("2409.19606", "2512.24880", "halves", "DeepSeek-V3",
                 "scoring_func sigmoid", "router_bias_scale",
                 "expert_init_scale"):
        assert any(said in line for line in config["assumed"]), said
    assert "33 layers would lie on further chips" in config["deployment"]
    assert config["engine"] == {"max_batch_size": 32, "max_seq_len": 8192}
    options = config["deployment_options"]["ray_actor_options"]
    assert options["max_concurrency"] == \
        spec.load_cell(CELL).traffic["clients"] + 8
    probes = config["probes"]
    # The cell's check runs the programs the window times: one context
    # past the half of the table, so prefill at all three widths and
    # decode at the whole one, and no more rows than the chip holds
    # float32 logits for. ISSUE 44's straddling lengths are the smoke's.
    widths = [config["engine"]["max_seq_len"] // 4,
              config["engine"]["max_seq_len"] // 2]
    assert any(n > widths[1] for n in probes["prompt_lengths"])
    longest = max(probes["prompt_lengths"]) + probes["max_new_tokens"]
    padded = -(-longest // 128) * 128
    assert len(probes["prompt_lengths"]) * padded * config["vocab_size"] \
        * 4 <= 2.25 * 2 ** 30
    assert probes["smoke_prompt_lengths"] == [8, 100, 130, 1030]
    rehearsal = spec.rehearsed(config, True)
    assert max(rehearsal["probes"]["prompt_lengths"]) \
        > rehearsal["engine"]["max_seq_len"] // 2
    assert max(rehearsal["probes"]["smoke_prompt_lengths"]) \
        < rehearsal["engine"]["max_seq_len"]
    assert "chip_smoke.py --paged-logits" in probes["why"]
    assert "quarter" in probes["logit_atol_why"]     # a routed expert's fault
    assert "float8" in probes["logit_atol_why"]
    built = spec.build_model_config(config)
    assert built.num_params == 4_921_067_450
    assert round(built.num_params * 2 / 2 ** 30, 2) == 9.17    # GiB in bf16
    assert (built.num_layers, built.first_k_dense, built.vocab_size,
            built.max_seq_len, built.latent_dim, built.family) == \
        (7, 2, 131072, 8192, 576, "latent")


# ------------------------------------------------------- the metric files


def event(name, start, end, hlo=""):
    return trace_reduce.Event(name, float(start), float(end), {"hlo": hlo},
                              self_ns=float(end - start))


LATENT_OP = "fusion(bf16[32,8192,640]{2,1,0} %view, bf16[32,32,640] %q)"
EXPERT_OP = "fusion(bf16[5,64,3584,1024]{3,2,1,0} %w, bf16[32,3584] %x)"
MIX_OP = "fusion(f32[32,1,4,3584]{3,2,1,0} %streams, f32[32,4] %post)"
OTHER_OP = "fusion(bf16[3584,131072] %params__lm_head__.1, bf16[32,3584] %x)"
# A prefill chunk's: one row of 128 tokens over the whole table.
CHUNK_LATENT_OP = ("fusion(bf16[1,8192,640] %view, bf16[512,32,256] %w_kvb, "
                   "bf16[1,128,32,192] %q)")
CHUNK_EXPERT_OP = "fusion(bf16[5,64,3584,1024] %w, bf16[128,3584] %x)"
CHUNK_MIX_OP = "fusion(f32[1,128,4,3584] %streams, f32[128,4] %post)"


def canned_run() -> dict:
    """Three decode steps of 23 ms and a chunk of 23 ms; in each, seven
    layers' operations on the pool of 1 ms, five layers' expert
    operations of 2 ms and fourteen mixes of 0.05 ms."""
    modules, ops = [], []
    for start, name in ((0, "jit_decode_step(7)"),
                        (25e6, "jit_decode_step(7)"),
                        (50e6, "jit_prefill_chunk(3)"),
                        (75e6, "jit_decode_step(7)")):
        modules.append(event(name, start, start + 23e6))
        latent, expert, mix = (LATENT_OP, EXPERT_OP, MIX_OP) \
            if "decode" in name \
            else (CHUNK_LATENT_OP, CHUNK_EXPERT_OP, CHUNK_MIX_OP)
        for layer in range(7):
            at = start + layer * 3.1e6
            ops.append(event("fusion.1", at, at + 1e6, latent))
            if layer >= 2:
                ops.append(event("fusion.2", at + 1e6, at + 3e6, expert))
            ops.append(event("fusion.3", at + 3e6, at + 3.05e6, mix))
            ops.append(event("fusion.4", at + 3.05e6, at + 3.1e6, mix))
        ops.append(event("fusion.5", start + 21.8e6, start + 22.8e6,
                         OTHER_OP))
    trace = trace_reduce.Trace({0: trace_reduce.Device(modules, ops)}, [])
    return {"trace": trace, "rehearse": False, "device_kind": "TPU v5 lite",
            "chips": 1, "config": model(), "counters": dict(COUNTERS),
            "memory": {"peak_bytes_in_use": 12.5e9, "bytes_limit": 16.9e9},
            "harness": {}, "traffic": {}}


CANNED = renamed({
    "decode_step_device_ms.xing": 23.0,
    "device_idle_share.xing": None,     # busy_and_window wants real lines
    "hbm_peak_share.xing": 100 * 12.5 / 16.9,
    "engine_host_ms_per_step.xing": 1.5,
    "host_calls_per_step.xing": 2.3,
    "decode_batch_occupancy.xing": 100 * 31 / 32,
    "kv_read_over_live.xing": 32 * 8192 / (31 * 4500),
    "decode_steps_ahead_share.xing": 99.0,
    "experts_touched_share.xing": 100 * 56 / 64,
    "expert_load_max_over_mean.xing": 2.0,
    # The experts' over both programs; the pool's and the mixes' are the
    # decode program's alone, over its time alone.
    "expert_ffn_time_share.xing": 100 * 4 * 5 * 2e6 / (4 * 23e6),
    "latent_attn_time_share.xing": 100 * 3 * 7 * 1e6 / (3 * 23e6),
    "hyper_mix_time_share.xing": 100 * 3 * 14 * 0.05e6 / (3 * 23e6),
})


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_file_loads_and_reads_a_canned_run(name, monkeypatch):
    loaded = spec.load_cell(CELL)
    metric = {m["name"]: m for m in loaded.per_layer}[name]
    assert CELL in metric["cells"]
    assert metric["cells"] == metric["workloads"]
    assert metric["moves"] == "serve_tokens_per_s"
    assert metric["layer"] in {m["layer"] for m in bench_json()["per_layer"]
                               if CELL not in m.get("workloads", [])}
    reader = spec.load_module(loaded.roots, "readers", metric["reader"])
    # Nothing to read (no trace, no such counter, as on the parent
    # commit): None, never an error.
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert reader.read(metric, {"trace": None, "counters": {}, "memory": {},
                                "harness": {}, "rehearse": False}) is None
    if name in CANNED and CANNED[name] is not None:
        assert reader.read(metric, canned_run()) == pytest.approx(CANNED[name])
    elif name not in CANNED:
        assert 0 < reader.read(metric, canned_run()) < 100


def test_the_roofline_reader_says_nothing_where_there_is_nothing():
    cell = per_layer()
    reader = spec.load_module(spec.load_cell(CELL).roots, "readers",
                              "latent_step_roofline")
    run = canned_run()
    for name in ("decode_step_roofline.xing", "latent_attn_roofline.xing",
                 "expert_ffn_roofline.xing"):
        assert reader.read(cell[name], {**run, "counters": {
            "decode_steps": 9, "decode_tokens": 9}}) is None
        assert reader.read(cell[name], {**run, "trace": None}) is None
        assert reader.read(cell[name], {**run, "rehearse": True}) is None


def test_the_roofline_shares_are_the_costs_over_the_traced_time():
    cell, run, config = per_layer(), canned_run(), model()
    reader = spec.load_module(spec.load_cell(CELL).roots, "readers",
                              "latent_step_roofline")
    peak = peaks.peaks("TPU v5 lite")
    rows, context, touched = 31.0, 4500.0, 56.0
    step = flops.least_seconds(xing_cost.decode_step_cost(
        config, rows, context, touched), peak)[0]
    assert reader.read(cell["decode_step_roofline.xing"], run) == \
        pytest.approx(100 * step / 23e-3)
    latent = flops.least_seconds(xing_cost.latent_attention_cost(
        config, rows, context), peak)[0]
    assert reader.read(cell["latent_attn_roofline.xing"], run) == \
        pytest.approx(100 * latent * 7 / 7e-3)
    experts = flops.least_seconds(xing_cost.expert_ffn_cost(
        config, touched, choices=(31_000 + 36_000) * 4 / 1300,
        tokens=(31_000 + 36_000) / 1300), peak)[0]
    assert reader.read(cell["expert_ffn_roofline.xing"], run) == \
        pytest.approx(100 * experts * 5 * 4 / (4 * 5 * 2e-3))
    # No share can pass 100%: the step's least time holds the parts'.
    assert 7 * latent + 5 * experts < step


# --------------------------------------------- the selectors, three widths


def decode_ops(positions: int) -> dict:
    """Operations of the decode program as the v5e's compiler prints
    them for this cell at a step of ``positions`` (a scratch compile as
    ``benchmark/sizing_family.py --hlo`` makes, at each of the table's
    three widths; operands cut short): text -> which selector owns it."""
    blocks = 32 * positions // 16
    return {
        f"%fusion.973 = bf16[7,16385,16,640]{{3,2,1,0:T(8,128)(2,1)}} "
        "fusion(bf16[7,16385,16,640] %pool, s32[32] %at, bf16[32,640] "
        "%entries)": "latent",
        f"%fusion.975 = bf16[{blocks},16,640]{{2,1,0:T(8,128)(2,1)}} "
        f"fusion(bf16[7,16385,16,640] %pool, s32[{blocks}] %tables)":
        "latent",
        f"%iota_compare_fusion.6 = pred[32,{positions}]{{1,0}} fusion("
        "s32[32] %positions)": "latent",
        f"%bitcast_reduce_fusion.5 = (f32[32,32], f32[32,{positions},1,32]) "
        f"fusion(bf16[32,{positions},640] %view, bf16[32,32,640] %q, "
        f"pred[32,{positions}] %mask)": "latent",
        f"%fusion.980 = f32[32,32]{{1,0}} fusion(f32[32,{positions},1,32] "
        "%scores, f32[32,32] %max)": "latent",
        f"%fusion.981 = bf16[32,1,32,640] fusion(bf16[32,{positions},640] "
        f"%view, f32[32,{positions},1,32] %scores, f32[32,32] %max, "
        "f32[32,32] %sum)": "latent",
        f"%convolution-base-dilated.16 = f32[32,{positions},32] convolution("
        "%fusion.870, %fusion.154)": "latent",
        "%fusion.745 = bf16[32,1,32,512] fusion(bf16[512,32,256] %w_kvb, "
        "bf16[32,1,32,192] %q)": "latent",
        "%fusion.748 = bf16[32,32,128] fusion(bf16[512,32,256] %w_kvb, "
        "bf16[32,32,512] %u)": "latent",
        "%fusion.994 = bf16[64,32,1024] fusion(bf16[5,64,3584,1024] "
        "%w_gate, s32[] %li, bf16[32,3584] %x)": "experts",
        "%fusion.996 = bf16[32,3584,1] fusion(bf16[5,64,1024,3584] %w_down, "
        "s32[] %li, f32[64,32] %combine, bf16[64,32,1024] %gate, "
        "bf16[64,32,1024] %up, pred[64,32] %chosen)": "experts",
        "%fusion.997 = bf16[32,1024] fusion(bf16[5,3584,1024] %shared_gate, "
        "s32[] %li, f32[32,1,1,3584] %h)": "experts",
        "%convert_add_fusion.2 = f32[32,3584] fusion(bf16[5,1024,3584] "
        "%shared_down, s32[] %li, bf16[32,3584,1] %routed, bf16[32,1024] "
        "%gate, bf16[32,1024] %up)": "experts",
        "%multiply_multiply_fusion.391 = f32[32,1,4,3584] fusion("
        "f32[32,1,4,3584] %streams, f32[4,3584] %scale, f32[32] %rms)":
        "mix",
        "%fusion.983 = f32[32,1,24] fusion(f32[32,14336] %flat, "
        "bf16[5,24,14336] %phi, s32[] %li)": "mix",
        "%convert_reduce_fusion.20 = f32[14336] fusion(bf16[5,14336] "
        "%scale, s32[] %li)": None,
        "%copy_bitcast_fusion.5 = bf16[512,32,256] fusion("
        "bf16[1,512,32,256] %w_kvb)": None,
        "%multiply_divide_fusion.95 = (f32[32,1], f32[32,1]) fusion("
        "f32[32,1] %m00, f32[32,1] %m01, f32[32,1] %m02)": "mix",
        "%fusion.989 = (f32[32,4], f32[32,4]) fusion(f32[32,1,24] %mixed, "
        "f32[4] %b, f32[] %a)": "mix",
        "%multiply_reduce_fusion.19 = (f32[32], f32[32,1,4,3584]) fusion("
        "f32[32,3584] %y, f32[32,4] %post, f32[32,1,1,3584] %h)": "mix",
        "%fusion.968 = bf16[32,1,576] fusion(bf16[32,3584] %x, "
        "bf16[5,3584,576] %wkv_a, s32[] %li)": None,
        "%fusion.978 = bf16[32,1,32,192] fusion(bf16[5,768,32,192] %wq_b, "
        "s32[] %li, bf16[32,768] %c_q)": None,
        "%bitcast_convert_fusion.5 = f32[32,3584] fusion(bf16[32,32,128] "
        "%o, bf16[5,32,128,3584] %wo, s32[] %li)": None,
        # 32 rows of 32 heads x 128 flattened are no pool read, whatever
        # the step's width.
        "%fusion.760 = f32[32,3584] fusion(bf16[32,4096] %o, "
        "bf16[5,4096,3584] %wo, s32[] %li)": None,
        "%fusion.758 = bf16[32,9216] fusion(bf16[2,3584,9216] %w_gate, "
        "s32[] %li, f32[32,3584] %h)": None,
        "%bitcast_add_fusion.2 = (f32[32,1,64]) fusion(f32[64] %bias, "
        "bf16[5,3584,64] %w_router, s32[] %li, f32[32,1,1,3584] %h)": None,
        "%fusion.888 = f32[32,131072] fusion(bf16[3584,131072] %lm_head, "
        "f32[32,3584] %x)": None,
    }


@pytest.mark.parametrize("positions", [2048, 4096, 8192])
def test_the_selectors_match_the_chips_operation_text(positions):
    cell = per_layer()
    owners = {"latent": cell["latent_attn_time_share.xing"]["ops"],
              "experts": cell["expert_ffn_time_share.xing"]["ops"],
              "mix": cell["hyper_mix_time_share.xing"]["ops"]}
    assert owners["latent"] == cell["latent_attn_roofline.xing"]["ops"]
    assert owners["experts"] == cell["expert_ffn_roofline.xing"]["ops"]
    for text, owner in decode_ops(positions).items():
        for name, ops in owners.items():
            assert bool(re.search(ops, text)) == (name == owner), (name, text)
    # Of a prefill chunk of 128 tokens the expert operations are found,
    # and none on the pool or the streams: those two metrics are of the
    # decode program, whose device time they are shares of.
    chunk = {
        "%fusion.12 = bf16[64,128,1024] fusion(bf16[5,64,3584,1024] %w_up, "
        "s32[] %li, bf16[128,3584] %x)": "experts",
        "%fusion.945 = bf16[7,16385,16,640] fusion(bf16[7,16385,16,640] "
        "%pool, s32[128] %at, bf16[128,640] %entries)": None,
        f"%fusion.947 = bf16[{positions // 16},16,640] fusion("
        f"bf16[7,16385,16,640] %pool, s32[{positions // 16}] %table)": None,
        f"%fusion.948 = f32[32,128,{positions}] fusion("
        f"bf16[1,{positions},640] %view, bf16[512,32,256] %w_kvb, "
        "bf16[1,128,32,192] %q)": None,
        "%fusion.3 = f32[128,1,24] fusion(f32[128,14336] %flat, "
        "bf16[5,24,14336] %phi, s32[] %li)": None,
        "%multiply_multiply_fusion.9 = f32[1,128,4,3584] fusion("
        "f32[1,128,4,3584] %streams, f32[4,3584] %scale)": None,
    }
    for text, owner in chunk.items():
        for name, ops in owners.items():
            assert bool(re.search(ops, text)) == (name == owner), (name, text)
    assert cell["latent_attn_time_share.xing"]["module"] == "^jit_decode_step"
    assert cell["expert_ffn_time_share.xing"]["module"] == \
        "^jit_(decode_step|prefill_chunk)"


# ------------------------------------------------------- the cost, by hand


def test_the_costs_are_the_hand_reckoned_bytes():
    config = model()
    assert xing_cost.layers(config) == (2, 5)
    assert xing_cost.latent_values(config) == 576
    assert xing_cost.attention_values(config) == 28_411_136
    assert xing_cost.mix_values(config) == 358_427 + 3584
    assert xing_cost.expert_matrix_values(config) == 11_010_048
    assert xing_cost.dense_ffn_values(config) == 99_090_432
    # The whole configuration, counted from the parts.
    built = spec.build_model_config(config)
    shared = xing_cost.attention_values(config) \
        + 2 * xing_cost.mix_values(config)
    assert 2 * 131_072 * 3584 + 3584 \
        + 2 * (shared + xing_cost.dense_ffn_values(config)) \
        + 5 * (shared + 3584 * 64 + 64
               + 65 * xing_cost.expert_matrix_values(config)) \
        == built.num_params
    # One decode step of 32 rows over 4,500 live positions with 56 of
    # the 64 routed experts touched a layer.
    cost = xing_cost.decode_step_cost(config, rows=32, context=4500,
                                      experts_read=56)
    moved = cost["moved"]
    assert moved["head"] == (131_072 * 3584 + 3584) * 2            # 0.94 GB
    assert moved["dense_ffn"] == 2 * 99_090_432 * 2                # 0.40 GB
    assert moved["attention_and_mixes"] == 7 * (
        28_411_136 + 2 * 362_011) * 2                              # 0.41 GB
    assert moved["experts"] == 5 * 56 * 11_010_048 * 2             # 6.17 GB
    assert moved["router_and_shared"] == 5 * (
        3584 * 64 + 64 + 11_010_048) * 2
    assert moved["latents"] == 7 * 32 * 4501 * 576 * 2             # 1.16 GB
    assert cost["bytes"] == sum(moved.values())
    assert round(cost["bytes"] / 1e9, 2) == 9.18
    least, bound = flops.least_seconds(cost, peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and round(least * 1e3, 1) == 11.2     # ms
    # The absorbed attention of one layer: each live latent once, and a
    # head's two products over it.
    latent = xing_cost.latent_attention_cost(config, rows=32, context=4500)
    assert latent["bytes"] == (32 * 4500 * 576 + 32 * 576 + 512 * 32 * 256
                               + 32 * 32 * 320) * 2
    assert latent["flops"] == 2.0 * (32 * 32 * 512 * 256
                                     + 32 * 4500 * 32 * (576 + 512))
    # 60 operations a byte of latents read, under the chip's 240.
    assert 55 < latent["flops"] / latent["bytes"] < 65
    assert flops.least_seconds(latent, peaks.peaks("TPU v5 lite"))[1] \
        == "memory"
    # The experts of a layer: the touched and the shared one's matrices,
    # the chosen and the shared one's arithmetic.
    experts = xing_cost.expert_ffn_cost(config, experts_read=56,
                                        choices=128, tokens=32)
    assert experts["bytes"] == (57 * 11_010_048 + 2 * 32 * 3584) * 2
    assert experts["flops"] == 2.0 * (128 + 32) * 11_010_048


# --------------------------------------------- the check's control, its path


def test_the_checks_control_runs_the_cell_with_the_replicas_weights_rounded():
    """``chip_smoke.py --cell <cell> --round-weights float8_e4m3fn`` is
    the committed control of ``logit_atol``: the cell through the
    harness, the replica on rounded weights and the check on the seed's.
    Rehearsed it shows that the path holds (two sets of weights, the
    first rounded, the check's numbers read back); a rehearsal's limit
    is loose, so whether it refuses float8 is the chip's to say."""
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    done = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py"), "--cell", CELL,
         "--round-weights", "float8_e4m3fn", "--rehearse", "--seed",
         str(2 ** 31 + 5)],
        cwd=root, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    said = next(line for line in done.stdout.splitlines()
                if line.startswith("smoke[cell] "))
    assert "weights_built=2" in said and "worst_gap=" in said
    assert json.loads(done.stdout.strip().splitlines()[-1])["ok"] is True
    # Without a dtype it is no control, and says so before any run.
    refused = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py"), "--cell", CELL,
         "--rehearse"], cwd=root, capture_output=True, text=True, timeout=120)
    assert refused.returncode != 0
    assert "give --round-weights" in refused.stderr + refused.stdout
