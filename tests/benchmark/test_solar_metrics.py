"""What PR 55 adds to the benchmark for its cell
``serve-solar-open2-reason-closed``: the cell, its traffic and its
configuration as the issue states them (the configuration against the
catalog row's numbers, the flat copies against what they repeat), every
``.solar`` metric found and read through the harness's own loader from a
canned run, the selectors against the text the v5e prints for the two
programs' operations at the three table widths, the cost of a decode
step by hand, and a rehearsal of the cell on the CPU. It asserts
containment, never the benchmark's size: a later PR adds to it. Nothing
here is a measurement."""

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

from benchmark import (  # noqa: E402
    flops, peaks, solar_cost, spec, trace_reduce)

CELL = "serve-solar-open2-reason-closed"
CONFIG = "solar-open2-250b-serve-1chip"
# Thirteen when PR 55 wrote them, not the issue's fourteen (at 127 of 128
# entries ``test_per_layer_table.py``'s last test failed, so
# ``decode_batch_occupancy`` stayed out); PR 59's fold gave it its place,
# on the survivor's list with the host-side three.
NEW_METRICS = [named(name, "solar") for name in (
    "decode_step_device_ms", "prefill_chunk_device_ms", "device_idle_share",
    "hbm_peak_share", "kv_read_over_live", "expert_choices_here_share",
    "expert_ffn_time_share", "kda_time_share", "kda_chunk_time_share",
    "gated_attn_time_share", "kda_state_roofline", "expert_ffn_roofline",
    "decode_step_roofline",
    # Since PR 59, as further cells on a survivor's list:
    "engine_host_ms_per_step", "host_calls_per_step",
    "decode_batch_occupancy", "decode_steps_ahead_share")]
# The catalog row Solar-Open2-250B of the model-configs guide, every key
# of its `config`.
CATALOG = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
REDUCED = {"num_hidden_layers": 4, "n_routed_experts": 40,
           "vocab_size": 24576}
# A window of 2,000 decode steps of 63 busy rows whose contexts hold
# 1,300 positions in the full layer, gathered at the half table, 400
# chunks of 120 tokens beside them; 30 of the 40 held experts touched a
# layer-step, an eighth of the choices landed here.
LAYER_STEPS = 4 * 2400
COUNTERS = {
    "decode_steps": 2000, "decode_tokens": 126_000, "decode_steps_ahead": 2000,
    "decode_steps_narrow": 2000, "prefill_chunks": 400,
    "prefill_tokens": 48_000, "first_tokens": 80, "state_resets": 80,
    "kv_positions_live": 126_000 * 1300,
    "kv_positions_read": 2000 * 64 * 2048,
    "decode_host_us": 7_000_000, "host_calls": 4_500,
    "expert_slots": 40 * LAYER_STEPS, "experts_touched": 30 * LAYER_STEPS,
    "expert_choices": (126_000 + 48_000) * 4,
    "expert_peak_choices": 8 * (126_000 + 48_000) * 4,
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
    assert "no prefix reuse" in cell["why"]
    throughput = {m["name"]: m for m in bench["end_to_end"]}[
        "serve_tokens_per_s"]
    assert CELL in throughput["workloads"]
    loaded = spec.load_cell(CELL)
    assert {m["name"] for m in loaded.end_to_end} == \
        {"serve_tokens_per_s", "setup_s"}
    assert set(NEW_METRICS) <= {m["name"] for m in loaded.per_layer}
    # The traffic is the Kimi cell's file, as it was.
    traffic = loaded.traffic
    kimi = spec.load_cell("serve-kimi-linear-reason-closed")
    assert traffic == kimi.traffic
    assert traffic["generator"] == "closed_clients"
    assert (traffic["clients"], traffic["requests_per_client"]) == (96, 8)
    assert traffic["prompt"] == {"dist": "uniform", "min": 192, "max": 896}
    assert traffic["output"] == {"dist": "uniform", "min": 1024, "max": 3072}
    assert traffic["temperature"] == 0.0
    assert (traffic["ramp_timeout_s"], traffic["trace_after_share"],
            traffic["trace_seconds"]) == (90.0, 0.4, 4.0)
    config = loaded.config
    # The longest request holds 3,968 of the table's 4,096 positions.
    assert traffic["prompt"]["max"] + traffic["output"]["max"] == 3968 \
        < config["engine"]["max_seq_len"] == 4096
    engine = config["engine"]
    assert engine == {"max_batch_size": 64, "max_seq_len": 4096,
                      "max_waiting": 128}
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
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
    assert entry["reduced"] == config["reduced"] == list(REDUCED)
    assert len(entry["why"]) <= 200
    assert {k: config[k] for k in CATALOG} == {**CATALOG, **REDUCED}
    assert set(config["reduced_why"]) == set(REDUCED)
    assert config["published"] == {"n_routed_experts": 320,
                                   "vocab_size": 196608}
    for said in ("eight chips share each layer", "twelve pipeline stages",
                 "experts 0 to 39", "layers 0 to 3"):
        assert said in config["deployment"], said
    # What the reference needs travels as numbers (spec.model_numbers,
    # which hands over no group, no list and no boolean): each flat copy
    # equals what it repeats.
    numbers, group = spec.model_numbers(config), CATALOG["linear_attn_config"]
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert numbers[f"linear_attn_{key}"] == group[key]
    assert numbers["n_routed_experts_routed_over"] == \
        config["published"]["n_routed_experts"] == 320
    assert numbers["first_expert_held"] == 0
    assert numbers["kda_beta_scale"] == \
        (2 if config["kda_allow_neg_eigval"] else 1) == 2
    for boolean in ("use_rope", "use_gqa_gate", "norm_topk_prob",
                    "kda_allow_neg_eigval", "kda_use_full_proj"):
        assert boolean not in numbers and boolean in config
    assert "gqa_layers" not in numbers and "_flat_why" in config
    assert len(config["gqa_layers"]) == 12          # as published
    rehearsal = spec.rehearsed(config, True)
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert rehearsal[f"linear_attn_{key}"] == \
            rehearsal["linear_attn_config"][key]
    assert rehearsal["gqa_layers"] == CATALOG["gqa_layers"]
    assert rehearsal["kda_beta_scale"] == 2
    for said in ("2505.06708", "every head AND channel", "no QK-norm",
                 "selection bias", "KimiDeltaAttention", "2411.12537",
                 "beta = 2 sigmoid", "A_log log U(1, 16)",
                 "router_bias_scale", "expert_init_scale",
                 "max_position_embeddings"):
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
    assert built.num_params == 3_308_353_344
    assert round(built.num_params * 2 / 2 ** 30, 2) == 6.16     # GiB in bf16
    assert (built.num_layers, built.vocab_size, built.max_seq_len,
            built.family, built.full_kind, built.held, built.num_experts) \
        == (4, 24576, 4096, "linear", "gqa", (0, 40), 320)
    assert (built.kda_layers, built.full_layers, built.periods,
            built.first_k_dense) == (3, 1, 1, 0)
    assert built.kinds == ("gqa", "kda", "kda", "kda")
    assert (built.kda_beta_scale, built.rotary, built.use_gqa_gate) == \
        (2.0, False, True)


# ------------------------------------------------------- the metric files


def event(name, start, end, hlo=""):
    return trace_reduce.Event(name, float(start), float(end), {"hlo": hlo},
                              self_ns=float(end - start))


STATE_OP = ("%kda_state_update.7 = (f32[64,64,128], f32[3,64,64,128,128]) "
            "custom-call(s32[1] %constant.342, f32[4096] %reshape.162, "
            "f32[64,64,128] %q, f32[64,64,128] %k, f32[64,64,128] %v, "
            "f32[64,64,128] %g, f32[3,64,64,128,128] %state)")
EXPERT_OP = ("%grouped_expert_ffn.8 = bf16[64,4096] custom-call(s32[1] %p, "
             "s32[40] %touched, s32[1] %n, bf16[64,4096] %x, f32[64,40] "
             "%combine, bf16[1,40,4096,1280] %w_gate, bf16[1,40,4096,1280] "
             "%w_up, bf16[1,40,1280,4096] %w_down)")
GATHER_OP = ("%fusion.5 = bf16[8192,16,8,128]{3,2,1,0:T(8,128)(2,1)} fusion("
             "bf16[1,16385,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} %bitcast.21, "
             "s32[8192]{0:T(1024)S(1)} %bitcast.657)")
OTHER_OP = "%fusion.273 = f32[64,24576] fusion(bf16[4096,24576] %head)"
CHUNK_STATE_OP = ("%custom-call.25 = f32[2,64,1,64,64] custom-call("
                  "f32[2,64,1,64,64] %system), custom_call_target="
                  "\"InvertDiagBlocksLowerTriangular\"")
CHUNK_EXPERT_OP = ("%grouped_expert_ffn.10 = bf16[128,4096] custom-call("
                   "s32[1] %p, s32[40] %touched, s32[1] %n, bf16[128,4096] "
                   "%x, f32[128,40] %combine, bf16[1,40,4096,1280] %w_gate, "
                   "bf16[1,40,4096,1280] %w_up, bf16[1,40,1280,4096] %w_down)")


def canned_run() -> dict:
    """Three decode steps of 16 ms and a chunk of 9 ms; in a step the
    full layer's two gathers of 1.5 ms, three KDA layers' state
    operations of 1.2 ms and four layers' expert operations of 1.4 ms;
    in the chunk three triangular systems of 0.5 ms and four expert
    operations of 1.4 ms."""
    modules, ops = [], []
    for start, name, length in ((0, "jit_decode_step(7)", 16e6),
                                (20e6, "jit_decode_step(7)", 16e6),
                                (40e6, "jit_prefill_chunk(3)", 9e6),
                                (60e6, "jit_decode_step(7)", 16e6)):
        modules.append(event(name, start, start + length))
        decode = "decode" in name
        at = start
        for layer in range(4):
            if layer:
                took = 1.2e6 if decode else 0.5e6
                ops.append(event("custom-call.1", at, at + took,
                                 STATE_OP if decode else CHUNK_STATE_OP))
                at += took
            elif decode:
                for _ in range(2):
                    ops.append(event("fusion.5", at, at + 1.5e6, GATHER_OP))
                    at += 1.5e6
            ops.append(event("custom-call.2", at, at + 1.4e6,
                             EXPERT_OP if decode else CHUNK_EXPERT_OP))
            at += 1.4e6
        ops.append(event("fusion.273", at, at + 0.1e6, OTHER_OP))
    trace = trace_reduce.Trace({0: trace_reduce.Device(modules, ops)}, [])
    return {"trace": trace, "rehearse": False, "device_kind": "TPU v5 lite",
            "chips": 1, "config": model(), "counters": dict(COUNTERS),
            "memory": {"peak_bytes_in_use": 8.62e9, "bytes_limit": 16.9e9},
            "harness": {}, "traffic": {}}


CANNED = renamed({
    "decode_step_device_ms.solar": 16.0,
    "prefill_chunk_device_ms.solar": 9.0,
    "device_idle_share.solar": None,    # busy_and_window wants real lines
    "hbm_peak_share.solar": 100 * 8.62 / 16.9,
    "kv_read_over_live.solar": 2000 * 64 * 2048 / (126_000 * 1300),
    "expert_choices_here_share.solar": 12.5,
    # The experts' over both programs; the state's and the gathers' are
    # the decode program's alone, the systems' the prefill program's.
    "expert_ffn_time_share.solar": 100 * 4 * 4 * 1.4e6 / (3 * 16e6 + 9e6),
    "kda_time_share.solar": 100 * 3 * 3 * 1.2e6 / (3 * 16e6),
    "gated_attn_time_share.solar": 100 * 3 * 2 * 1.5e6 / (3 * 16e6),
    "kda_chunk_time_share.solar": 100 * 3 * 0.5e6 / 9e6,
    "engine_host_ms_per_step.solar": 3.5,
    "host_calls_per_step.solar": 2.25,
    "decode_batch_occupancy.solar": 100 * 63 / 64,
    "decode_steps_ahead_share.solar": 100.0,
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


def test_the_three_time_shares_cannot_pass_the_whole_step():
    cell, run = per_layer(), canned_run()
    reader = spec.load_module(spec.load_cell(CELL).roots, "readers",
                              "trace_op_share")
    decode_only = {**cell["expert_ffn_time_share.solar"],
                   "module": "^jit_decode_step"}
    # (the experts' selector spans both programs: over the decode
    # program's time alone it would count the chunk's too, so compare
    # the decode step's own operations)
    shares = [reader.read(cell[name], run) for name in
              ("kda_time_share.solar", "gated_attn_time_share.solar")]
    experts = 100 * 3 * 4 * 1.4e6 / (3 * 16e6)
    assert reader.read(decode_only, run) > experts      # the chunk's too
    assert sum(shares) + experts < 100


def test_the_roofline_reader_says_nothing_where_there_is_nothing():
    cell = per_layer()
    reader = spec.load_module(spec.load_cell(CELL).roots, "readers",
                              "solar_step_roofline")
    run = canned_run()
    for name in ("decode_step_roofline.solar", "kda_state_roofline.solar",
                 "expert_ffn_roofline.solar"):
        assert reader.read(cell[name], {**run, "counters": {
            "decode_steps": 9, "decode_tokens": 9}}) is None
        assert reader.read(cell[name], {**run, "trace": None}) is None
        assert reader.read(cell[name], {**run, "rehearse": True}) is None


def test_the_roofline_shares_are_the_costs_over_the_traced_time():
    cell, run, config = per_layer(), canned_run(), model()
    reader = spec.load_module(spec.load_cell(CELL).roots, "readers",
                              "solar_step_roofline")
    peak = peaks.peaks("TPU v5 lite")
    rows, context, touched = 63.0, 1300.0, 30.0
    landed = (126_000 + 48_000) * 4 / LAYER_STEPS       # a layer-step
    step_choices = landed * 2400 * 126_000 / (174_000 * 2000)
    assert step_choices == pytest.approx(63.0)    # an eighth of 63 x 8
    step = flops.least_seconds(solar_cost.decode_step_cost(
        config, rows, context, touched, step_choices), peak)[0]
    assert reader.read(cell["decode_step_roofline.solar"], run) == \
        pytest.approx(100 * step / 16e-3)
    state = flops.least_seconds(solar_cost.kda_cost(config, rows), peak)[0]
    assert reader.read(cell["kda_state_roofline.solar"], run) == \
        pytest.approx(100 * state * 3 / 3.6e-3)
    experts = flops.least_seconds(solar_cost.expert_ffn_cost(
        config, touched, choices=landed, tokens=174_000 / 2400), peak)[0]
    assert reader.read(cell["expert_ffn_roofline.solar"], run) == \
        pytest.approx(100 * experts * 4 * 4 / (4 * 4 * 1.4e-3))
    # No share can pass 100%: the step's least time holds the parts'.
    assert 3 * state + 4 * experts < step


# ------------------------------------------------------- the cost, by hand


def test_the_costs_are_the_hand_reckoned_bytes():
    config = model()
    assert solar_cost.layers(config) == {"kda": 3, "full": 1, "dense": 0,
                                         "sparse": 4}
    assert solar_cost.kv_values(config) == 2048     # 4 KiB a live position
    assert solar_cost.kv_values(config) * 2 == 4 * 2 ** 10
    assert solar_cost.kda_matrix_values(config) == 137_732_288
    assert solar_cost.full_matrix_values(config) == 109_051_904
    assert solar_cost.expert_matrix_values(config) == 15_728_640
    assert solar_cost.state_bytes(config) == 4 * 2 ** 20          # 4 MiB
    # The whole configuration, counted from the parts.
    built = spec.build_model_config(config)
    assert 2 * 24576 * 4096 + 4096 + 4 * 2 * 4096 \
        + 3 * 137_732_288 + 109_051_904 \
        + 4 * (4096 * 320 + 320 + 41 * 15_728_640) == built.num_params
    # One decode step of 64 rows over 2,300 live positions with 80% of
    # the held experts touched, an eighth of 64 x 8 choices landed a
    # layer.
    cost = solar_cost.decode_step_cost(config, rows=64, context=2300,
                                       experts_read=32, choices=64)
    moved = cost["moved"]
    state_traffic = 3 * 64 * 2 * 4 * 2 ** 20
    assert state_traffic == 1.5 * 2 ** 30          # the issue's 1.5 GiB
    assert moved["kda"] == state_traffic + 3 * (
        137_732_288 * 2 + 64 * 4 * 24576 * 2 + 2 * 64 * 4096 * 2)
    assert moved["experts"] == 4 * 32 * 15_728_640 * 2
    assert round(moved["experts"] / 1e9, 1) == 4.0    # the issue's 4.0 GB
    assert moved["head"] == (24576 * 4096 + 4096) * 2
    assert moved["full_matrices"] == 109_051_904 * 2
    assert moved["keys_and_values"] == 64 * 2301 * 4 * 2 ** 10   # 0.6 GB
    assert round(moved["keys_and_values"] / 1e9, 1) == 0.6
    assert moved["router_and_shared"] == 4 * (
        4096 * 320 + 320 + 15_728_640) * 2
    assert cost["bytes"] == sum(moved.values())
    assert round(cost["bytes"] / 1e9, 1) == 7.7
    least, bound = flops.least_seconds(cost, peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and round(least * 1e3, 1) == 9.4      # ms
    # The KDA mixer of one layer: Kimi's count at these widths.
    kda = solar_cost.kda_cost(config, rows=64)
    assert kda["bytes"] == moved["kda"] / 3
    assert kda["flops"] == 2.0 * 64 * 137_732_288 + 7.0 * 64 * 64 * 128 * 128
    assert flops.least_seconds(kda, peaks.peaks("TPU v5 lite"))[1] == "memory"
    # The full mixer: the live positions, never the gathered table.
    full = solar_cost.attention_cost(config, rows=64, context=2300)
    assert full["bytes"] == moved["full_matrices"] \
        + moved["keys_and_values"] + 2 * 64 * 4096 * 2
    assert full["flops"] == 2.0 * 64 * 109_051_904 \
        + 4.0 * 64 * 2301 * 64 * 128
    # The experts of a layer: the touched held ones and the shared
    # one's matrices, the landed choices' and the shared one's arithmetic.
    experts = solar_cost.expert_ffn_cost(config, experts_read=32,
                                         choices=64, tokens=64)
    assert experts["bytes"] == (33 * 15_728_640 + 2 * 64 * 4096) * 2
    assert experts["flops"] == 2.0 * (64 + 64) * 15_728_640


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
                           "solar_op_texts.json")) as f:
        return json.load(f)


# Where a printed operation has the table's width: the decode step's
# gathered blocks (64 rows x 256, 128 or 64 blocks) and positions, the
# prefill chunk's row view (the traced chunks ran at the quarter).
WIDTH_IN = {
    "decode_step": (("[16384,16,8,128]", "[{n},16,8,128]"),
                    ("s32[16384]", "s32[{n}]"),
                    ("[64,8,8,1,4096]", "[64,8,8,1,{s}]"),
                    ("[64,4096,8,128]", "[64,{s},8,128]"),
                    ("pred[64,4096]", "pred[64,{s}]")),
    "prefill_chunk": (("[1,8,8,128,1024]", "[1,8,8,128,{s}]"),
                      ("[1024,8,128,1]", "[{s},8,128,1]"),
                      ("pred[128,1024]", "pred[128,{s}]")),
}


def at_width(text: str, blocks: int, program: str) -> str:
    for printed, shape in WIDTH_IN[program]:
        text = text.replace(printed, shape.format(n=64 * blocks,
                                                  s=16 * blocks))
    return text


@pytest.mark.parametrize("blocks", [256, 128, 64])
def test_the_selectors_match_the_chips_operation_text(blocks):
    """Each operation the v5e printed for the two programs is owned by
    the selector of its layer's part and by no other, at the table's
    three widths (PR 53's lesson: a pattern that spells one width reads
    0.0 at the others; at the quarter the gathered blocks are 4,096, the
    hidden size's own number). A decode selector owns no operation of
    the prefill program and the other way round: ``trace_op_share`` sums
    matching operations wherever they ran."""
    cell = per_layer()
    owners = {"experts": cell["expert_ffn_time_share.solar"]["ops"],
              "gated": cell["gated_attn_time_share.solar"]["ops"],
              "kda": cell["kda_time_share.solar"]["ops"],
              "kda_chunk": cell["kda_chunk_time_share.solar"]["ops"]}
    assert owners["experts"] == cell["expert_ffn_roofline.solar"]["ops"]
    assert owners["kda"] == cell["kda_state_roofline.solar"]["ops"]
    texts = op_texts()
    seen, widths_spelled = set(), 0
    for program in ("decode_step", "prefill_chunk"):
        assert len(texts[program]) >= 34
        for op in texts[program]:
            text = at_width(op["text"], blocks, program)
            widths_spelled += text != op["text"]
            seen.add(op["owner"])
            for name, ops in owners.items():
                assert bool(re.search(ops, text)) == (name == op["owner"]), \
                    (name, text)
    assert seen == {None, "experts", "gated", "kda", "kda_chunk"}
    assert widths_spelled > 0      # (the traced chunks ran at the quarter)
    # The canned run's own texts are the chip's kind.
    assert re.search(owners["gated"], GATHER_OP)
    assert re.search(owners["kda"], STATE_OP)
    assert cell["kda_time_share.solar"]["module"] == "^jit_decode_step"
    assert cell["gated_attn_time_share.solar"]["module"] == "^jit_decode_step"
    assert cell["kda_chunk_time_share.solar"]["module"] == "^jit_prefill_chunk"
    assert cell["expert_ffn_time_share.solar"]["module"] == \
        "^jit_(decode_step|prefill_chunk)"
