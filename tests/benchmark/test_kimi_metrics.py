"""What PR 50 adds to the benchmark for its cell
``serve-kimi-linear-reason-closed``: the cell, its traffic and its
configuration as the issue states them (the configuration against the
catalog row's numbers, the flat copies against what they repeat), every
``.kimi`` metric found and read through the harness's own loader from a
canned run, the selectors against the text the v5e prints for the two
programs' operations, the cost of a decode step by hand, and a rehearsal
of the cell on the CPU. It asserts containment, never the benchmark's
size: a later PR adds to it. Nothing here is a measurement."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from cells import named, renamed  # noqa: E402

from benchmark import flops, kimi_cost, peaks, spec, trace_reduce  # noqa: E402

CELL = "serve-kimi-linear-reason-closed"
CONFIG = "kimi-linear-48b-a3b-serve-1chip"
# Thirteen, not the issue's twenty: ``per_layer`` holds at most 128 and
# had 115 (CHANGES.md says which seven went and why).
NEW_METRICS = [named(name, "kimi") for name in (
    "decode_step_device_ms", "prefill_chunk_device_ms", "device_idle_share",
    "hbm_peak_share", "decode_batch_occupancy", "expert_ffn_time_share",
    "latent_attn_time_share", "kda_time_share", "kda_chunk_time_share",
    "expert_choices_here_share", "kda_state_roofline", "expert_ffn_roofline",
    "decode_step_roofline",
    # Since PR 59, as further cells on a survivor's list:
    "engine_host_ms_per_step", "host_calls_per_step", "kv_read_over_live",
    "decode_steps_ahead_share")]
# The catalog row Kimi-Linear-48B-A3B-Instruct of the model-configs
# guide, every key of its `config`.
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = {"num_hidden_layers": 13, "num_experts": 32, "vocab_size": 20480}
# A window of 2,000 decode steps of 63 busy rows whose contexts hold
# 1,300 positions a latent layer, 400 chunks of 120 tokens beside them;
# 15 of the 32 held experts touched a layer-step (random routers are
# uneven), an eighth of the choices landed here.
LAYER_STEPS = 12 * 2400
COUNTERS = {
    "decode_steps": 2000, "decode_tokens": 126_000, "decode_steps_ahead": 2000,
    "prefill_chunks": 400, "prefill_tokens": 48_000, "first_tokens": 80,
    "state_resets": 80,
    "kv_positions_live": 126_000 * 1300, "kv_positions_read": 126_000 * 1308,
    "decode_host_us": 7_000_000, "host_calls": 4_500,
    "expert_slots": 32 * LAYER_STEPS, "experts_touched": 15 * LAYER_STEPS,
    "expert_choices": (126_000 + 48_000) * 12,
    "expert_peak_choices": 8 * (126_000 + 48_000) * 12,
    "max_batch_size": 64, "max_seq_len": 4096, "max_waiting": 128}


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
        (CONFIG, "reason-wide-closed", 1)
    assert len(cell["why"]) <= 200 and "64 rows" in cell["why"]
    throughput = {m["name"]: m for m in bench["end_to_end"]}[
        "serve_tokens_per_s"]
    assert CELL in throughput["workloads"]
    loaded = spec.load_cell(CELL)
    assert {m["name"] for m in loaded.end_to_end} == \
        {"serve_tokens_per_s", "setup_s"}
    # At least what PR 50 brought: the seven it left out can follow.
    assert set(NEW_METRICS) <= {m["name"] for m in loaded.per_layer}
    # (The contract's limit on the table's length is held in ONE place:
    # test_per_layer_table.py.)
    traffic = loaded.traffic
    assert traffic["generator"] == "closed_clients"
    assert (traffic["clients"], traffic["requests_per_client"]) == (96, 8)
    assert traffic["prompt"] == {"dist": "uniform", "min": 192, "max": 896}
    assert traffic["output"] == {"dist": "uniform", "min": 1024, "max": 3072}
    assert traffic["temperature"] == 0.0
    assert (traffic["ramp_timeout_s"], traffic["trace_after_share"],
            traffic["trace_seconds"]) == (90.0, 0.4, 4.0)
    assert "schedule_seed" not in traffic
    assert "8k to 32k" in traffic["why"] and "1k to 3k" in traffic["why"]
    config = loaded.config
    # The longest request holds 3,968 of the table's 4,096 positions.
    assert traffic["prompt"]["max"] + traffic["output"]["max"] == 3968 \
        < config["engine"]["max_seq_len"] == 4096
    # 1.5 callers a row; every caller's first request waits inside the
    # engine at once, so its queue holds them all (a shed request is a
    # failed operation, and its caller sends no more).
    engine = config["engine"]
    assert engine["max_batch_size"] == 64
    assert traffic["clients"] == 96 <= engine["max_waiting"]
    assert "queue full" in config["engine_note"]
    options = config["deployment_options"]["ray_actor_options"]
    assert options["max_concurrency"] == traffic["clients"] + 8


def test_the_configuration_keeps_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same key, but
    for the three in ``reduced`` (the driver checks them against the
    catalog itself)."""
    config = model()
    entry = {c["name"]: c for c in bench_json()["configs"]}[CONFIG]
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/" \
        "blob/main/config.json"
    assert entry["reduced"] == config["reduced"] == list(REDUCED)
    assert {k: config[k] for k in CATALOG} == {**CATALOG, **REDUCED}
    assert set(config["reduced_why"]) == set(REDUCED)
    assert config["published"] == {"num_experts": 256, "vocab_size": 163840}
    for said in ("eight chips share each layer", "two pipeline stages",
                 "experts 0 to 31", "layers 1 to 13"):
        assert said in config["deployment"], said
    # What the reference needs travels as numbers (spec.model_numbers):
    # the nested group's are repeated flat, key for key; its two lists
    # stay as published, all 27 layers of them.
    numbers, group = spec.model_numbers(config), CATALOG["linear_attn_config"]
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert numbers[f"linear_attn_{key}"] == group[key]
    assert numbers["num_experts_routed_over"] == \
        config["published"]["num_experts"]
    assert numbers["first_expert_held"] == 0
    assert "head_dim" in numbers and numbers["head_dim"] == 72  # unused
    assert len(group["kda_layers"]) + len(group["full_attn_layers"]) == 27
    rehearsal = spec.rehearsed(config, True)
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert rehearsal[f"linear_attn_{key}"] == \
            rehearsal["linear_attn_config"][key]
    assert rehearsal["linear_attn_config"]["kda_layers"] == \
        group["kda_layers"]
    for said in ("2510.26692", "KimiDeltaAttention", "l2-normalised",
                 "WITHOUT bias", "mla_use_nope", "A_log is log U(1, 16)",
                 "router_bias_scale", "expert_init_scale", "head_dim 72"):
        assert any(said in line for line in config["assumed"]), said
    probes = config["probes"]
    # The check runs what the window runs: a prompt of several chunks
    # and sub-chunks with a padded last one, a paged block straddled,
    # 64 tokens through the decode program at the 64-row engine; its
    # float32 logits fit beside the rebuilt weights.
    chunk, sub, block = 128, 64, 16
    longest = max(probes["prompt_lengths"])
    assert longest > 4 * chunk and longest % chunk % sub and longest % block
    padded = -(-(longest + probes["max_new_tokens"]) // 128) * 128
    assert len(probes["prompt_lengths"]) * padded * config["vocab_size"] \
        * 4 <= 0.25 * 2 ** 30
    assert "float8" in probes["logit_atol_why"]
    built = spec.build_model_config(config)
    assert built.num_params == 3_450_547_008
    assert round(built.num_params * 2 / 2 ** 30, 2) == 6.43     # GiB in bf16
    assert (built.num_layers, built.vocab_size, built.max_seq_len,
            built.latent_dim, built.family, built.held, built.num_experts) \
        == (13, 20480, 4096, 576, "linear", (0, 32), 256)
    assert (built.kda_layers, built.latent_layers, built.periods) == \
        (10, 3, 3)


# ------------------------------------------------------- the metric files


def event(name, start, end, hlo=""):
    return trace_reduce.Event(name, float(start), float(end), {"hlo": hlo},
                              self_ns=float(end - start))


STATE_OP = ("%select_dynamic-update-slice_fusion.6 = f32[10,64,32,128,128] "
            "fusion(f32[10,64,32,128,128] %state, s32[] %si, "
            "f32[64,32,128] %k, f32[64,32,128] %update)")
EXPERT_OP = ("%fusion.1112 = bf16[32,64,1024] fusion(bf16[3,32,2304,1024] "
             "%w_gate, s32[] %p, bf16[64,2304] %x)")
LATENT_OP = ("%paged_latent_attention.13 = bf16[64,32,512] custom-call("
             "s32[16384] %tables, s32[64] %lengths, s32[1] %layer, "
             "bf16[64,32,640] %q, bf16[64,640] %entries, "
             "bf16[3,16385,16,640] %pool)")
OTHER_OP = "%fusion.731 = f32[64,20480] fusion(bf16[2304,20480] %head)"
CHUNK_STATE_OP = ("%custom-call.76 = f32[2,32,1,64,64] custom-call("
                  "f32[2,32,1,64,64] %system), custom_call_target="
                  "\"InvertDiagBlocksLowerTriangular\"")
CHUNK_EXPERT_OP = ("%fusion.1493 = bf16[32,128,1024] fusion("
                   "bf16[3,32,2304,1024] %w_up, s32[] %p, bf16[128,2304] %x)")


def canned_run() -> dict:
    """Three decode steps of 18 ms and a chunk of 15 ms; in a step ten
    KDA layers' state operations of 0.6 ms, twelve layers' expert
    operations of 0.6 ms and three reads of the pool of 0.25 ms; in the
    chunk ten triangular systems of 0.5 ms and twelve expert operations
    of 0.6 ms."""
    modules, ops = [], []
    for start, name, length in ((0, "jit_decode_step(7)", 18e6),
                                (20e6, "jit_decode_step(7)", 18e6),
                                (40e6, "jit_prefill_chunk(3)", 15e6),
                                (60e6, "jit_decode_step(7)", 18e6)):
        modules.append(event(name, start, start + length))
        decode = "decode" in name
        at = start
        for layer in range(13):
            if layer % 4 != 3:
                took = 0.6e6 if decode else 0.5e6
                ops.append(event("fusion.1", at, at + took,
                                 STATE_OP if decode else CHUNK_STATE_OP))
                at += took
            elif decode:
                ops.append(event("custom-call.1", at, at + 0.25e6, LATENT_OP))
                at += 0.25e6
            if layer:
                ops.append(event("fusion.2", at, at + 0.6e6,
                                 EXPERT_OP if decode else CHUNK_EXPERT_OP))
                at += 0.6e6
        ops.append(event("fusion.5", at, at + 0.1e6, OTHER_OP))
    trace = trace_reduce.Trace({0: trace_reduce.Device(modules, ops)}, [])
    return {"trace": trace, "rehearse": False, "device_kind": "TPU v5 lite",
            "chips": 1, "config": model(), "counters": dict(COUNTERS),
            "memory": {"peak_bytes_in_use": 9.35e9, "bytes_limit": 16.9e9},
            "harness": {}, "traffic": {}}


CANNED = renamed({
    "decode_step_device_ms.kimi": 18.0,
    "prefill_chunk_device_ms.kimi": 15.0,
    "device_idle_share.kimi": None,     # busy_and_window wants real lines
    "hbm_peak_share.kimi": 100 * 9.35 / 16.9,
    "decode_batch_occupancy.kimi": 100 * 63 / 64,
    "expert_choices_here_share.kimi": 12.5,
    # The experts' over both programs; the state's and the pool's are
    # the decode program's alone, the systems' the prefill program's.
    "expert_ffn_time_share.kimi": 100 * 4 * 12 * 0.6e6 / (3 * 18e6 + 15e6),
    "kda_time_share.kimi": 100 * 3 * 10 * 0.6e6 / (3 * 18e6),
    "latent_attn_time_share.kimi": 100 * 3 * 3 * 0.25e6 / (3 * 18e6),
    "kda_chunk_time_share.kimi": 100 * 10 * 0.5e6 / 15e6,
    "engine_host_ms_per_step.kimi": 3.5,
    "host_calls_per_step.kimi": 2.25,
    "kv_read_over_live.kimi": 1308 / 1300,
    "decode_steps_ahead_share.kimi": 100.0,
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
                              "kimi_step_roofline")
    run = canned_run()
    for name in ("decode_step_roofline.kimi", "kda_state_roofline.kimi",
                 "expert_ffn_roofline.kimi"):
        assert reader.read(cell[name], {**run, "counters": {
            "decode_steps": 9, "decode_tokens": 9}}) is None
        assert reader.read(cell[name], {**run, "trace": None}) is None
        assert reader.read(cell[name], {**run, "rehearse": True}) is None


def test_the_roofline_shares_are_the_costs_over_the_traced_time():
    cell, run, config = per_layer(), canned_run(), model()
    reader = spec.load_module(spec.load_cell(CELL).roots, "readers",
                              "kimi_step_roofline")
    peak = peaks.peaks("TPU v5 lite")
    rows, context, touched = 63.0, 1300.0, 15.0
    landed = (126_000 + 48_000) * 12 / LAYER_STEPS      # a layer-step
    step_choices = landed * 2400 * 126_000 / (174_000 * 2000)
    assert step_choices == pytest.approx(63.0)    # an eighth of 63 x 8
    step = flops.least_seconds(kimi_cost.decode_step_cost(
        config, rows, context, touched, step_choices), peak)[0]
    assert reader.read(cell["decode_step_roofline.kimi"], run) == \
        pytest.approx(100 * step / 18e-3)
    state = flops.least_seconds(kimi_cost.kda_cost(config, rows), peak)[0]
    assert reader.read(cell["kda_state_roofline.kimi"], run) == \
        pytest.approx(100 * state * 10 / 6e-3)
    experts = flops.least_seconds(kimi_cost.expert_ffn_cost(
        config, touched, choices=landed, tokens=174_000 / 2400), peak)[0]
    assert reader.read(cell["expert_ffn_roofline.kimi"], run) == \
        pytest.approx(100 * experts * 12 * 4 / (4 * 12 * 0.6e-3))
    # No share can pass 100%: the step's least time holds the parts'.
    assert 10 * state + 12 * experts < step


# ------------------------------------------------------- the cost, by hand


def test_the_costs_are_the_hand_reckoned_bytes():
    config = model()
    assert kimi_cost.layers(config) == {"kda": 10, "latent": 3, "dense": 1,
                                        "sparse": 12}
    assert kimi_cost.latent_values(config) == 576
    assert kimi_cost.kda_matrix_values(config) == 39_514_272
    assert kimi_cost.latent_matrix_values(config) == 29_114_880
    assert kimi_cost.expert_matrix_values(config) == 7_077_888
    assert kimi_cost.dense_ffn_values(config) == 63_700_992
    assert kimi_cost.state_bytes(config) == 2 * 2 ** 20          # 2 MiB
    # The whole configuration, counted from the parts.
    built = spec.build_model_config(config)
    assert 2 * 20480 * 2304 + 2304 + 13 * 2 * 2304 \
        + 10 * 39_514_272 + 3 * 29_114_880 + 63_700_992 \
        + 12 * (2304 * 256 + 256 + 33 * 7_077_888) == built.num_params
    # One decode step of 64 rows over 2,000 live positions with every
    # held expert touched, an eighth of 64 x 8 choices landed a layer.
    cost = kimi_cost.decode_step_cost(config, rows=64, context=2000,
                                      experts_read=32, choices=64)
    moved = cost["moved"]
    state_traffic = 10 * 64 * 2 * 2 * 2 ** 20
    assert state_traffic == 2.5 * 2 ** 30          # the issue's 2.5 GiB
    assert moved["kda"] == state_traffic + 10 * (
        39_514_272 * 2 + 64 * 4 * 12288 * 2 + 2 * 64 * 2304 * 2)
    assert moved["experts"] == 12 * 32 * 7_077_888 * 2          # 5.44 GB
    assert round(moved["experts"] / 1e9, 1) == 5.4
    assert moved["head"] == (20480 * 2304 + 2304) * 2
    assert moved["dense_ffn"] == 63_700_992 * 2
    assert moved["latent_matrices"] == 3 * 29_114_880 * 2
    assert moved["latents"] == 3 * 64 * 2001 * 576 * 2          # 0.44 GB
    assert moved["router_and_shared"] == 12 * (
        2304 * 256 + 256 + 7_077_888) * 2
    assert cost["bytes"] == sum(moved.values())
    assert round(cost["bytes"] / 1e9, 1) == 10.0
    least, bound = flops.least_seconds(cost, peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and round(least * 1e3, 1) == 12.2      # ms
    # The KDA mixer of one layer: memory-bound by far (7 operations a
    # state value read and written).
    kda = kimi_cost.kda_cost(config, rows=64)
    assert kda["bytes"] == moved["kda"] / 10
    assert kda["flops"] == 2.0 * 64 * 39_514_272 + 7.0 * 64 * 32 * 128 * 128
    assert flops.least_seconds(kda, peaks.peaks("TPU v5 lite"))[1] == "memory"
    # The experts of a layer: the touched held ones and the shared
    # one's matrices, the landed choices' and the shared one's arithmetic.
    experts = kimi_cost.expert_ffn_cost(config, experts_read=15,
                                        choices=64, tokens=64)
    assert experts["bytes"] == (16 * 7_077_888 + 2 * 64 * 2304) * 2
    assert experts["flops"] == 2.0 * (64 + 64) * 7_077_888


# ------------------------------------------------- the cell, rehearsed


def test_a_rehearsal_of_the_cell_ends_correct():
    """The cell through ``benchmark/run.py`` on the CPU at the file's
    rehearsal size: the deployment, the probes through both programs,
    the closed loop, the check against the reference. It shows that the
    path holds; what it prints is no speed."""
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 7), "--seconds", "3",
         "--trace", "0", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"


# ------------------------------------ the selectors, the chip's own text


def op_texts() -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "kimi_op_texts.json")) as f:
        return json.load(f)


# Where a printed operation has the run's 64 ROWS (never the sub-chunk's
# 64 of [2,32,64,..] or [32,64,128]): the shape with the rows left open.
ROWS_IN = ("[10,{},32,128,128]", "[10,3,{},12288]", "f32[{},32,128]",
           "f32[{},32]", "[{},2304]", "[{},1,2304]", "[3,{},12288]",
           "[1,{},12288]", "[12288,{}]", "[{},640]", "[{},32,512]",
           "[{},32,640]", "[{},32,192]", "s32[{}]", "pred[{}]", "[32,{}]",
           "[{},2304,1]", "[32,{},1024]", "[{},1024]", "[{},9216]",
           "[{},20480]", "[{},1,256]", "[{},256]", "f32[{}]", "bf16[{}]")


def at_rows(text: str, rows: int, program: str) -> str:
    shapes = ROWS_IN if program == "decode_step" else ROWS_IN[:2]
    for shape in shapes:
        text = text.replace(shape.format(64), shape.format(rows))
    return text


@pytest.mark.parametrize("rows", [64, 48, 32])
def test_the_selectors_match_the_chips_operation_text(rows):
    """Each operation the v5e printed for the two programs is owned by
    the selector of its layer's part and by no other, at the cell's 64
    rows and at the 48 and 32 the issue's rule on rows could have left
    it with (the patterns name the role, with the rows as a group). A
    decode selector owns no operation of the prefill program and the
    other way round: ``trace_op_share`` sums matching operations
    wherever they ran."""
    cell = per_layer()
    owners = {"experts": cell["expert_ffn_time_share.kimi"]["ops"],
              "latent": cell["latent_attn_time_share.kimi"]["ops"],
              "kda": cell["kda_time_share.kimi"]["ops"],
              "kda_chunk": cell["kda_chunk_time_share.kimi"]["ops"]}
    assert owners["experts"] == cell["expert_ffn_roofline.kimi"]["ops"]
    assert owners["kda"] == cell["kda_state_roofline.kimi"]["ops"]
    texts = op_texts()
    seen = set()
    for program in ("decode_step", "prefill_chunk"):
        for op in texts[program]:
            text = at_rows(op["text"], rows, program)
            seen.add(op["owner"])
            for name, ops in owners.items():
                assert bool(re.search(ops, text)) == (name == op["owner"]), \
                    (name, text)
    assert seen == {None, "experts", "latent", "kda", "kda_chunk"}
    assert cell["kda_time_share.kimi"]["module"] == "^jit_decode_step"
    assert cell["latent_attn_time_share.kimi"]["module"] == "^jit_decode_step"
    assert cell["kda_chunk_time_share.kimi"]["module"] == "^jit_prefill_chunk"
    assert cell["expert_ffn_time_share.kimi"]["module"] == \
        "^jit_(decode_step|prefill_chunk)"
