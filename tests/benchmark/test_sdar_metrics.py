"""What PR 35 adds to the benchmark for its cell
``serve-sdar-blockgen-closed``: every new metric file found and read
through the harness's own loader and read from a canned run, the cost of
a pass by hand at a tiny shape and at the published widths, the new
reader's arithmetic on a made-up trace, the expert selector against the
text the v5e's compiler prints, the configuration and the traffic as the
issue states them. Nothing here is a measurement."""

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

from benchmark import flops, peaks, sdar_cost, spec, trace_reduce  # noqa: E402

CELL = "serve-sdar-blockgen-closed"
NEW_METRICS = [named(name, "sdar") for name in (
    "decode_step_device_ms", "device_idle_share", "hbm_peak_share",
    "engine_host_ms_per_step", "host_calls_per_step", "kv_read_over_live",
    "decode_batch_occupancy", "tokens_per_row_pass", "commit_pass_share",
    "experts_touched_share", "expert_load_max_over_mean",
    "expert_ffn_time_share", "block_step_roofline", "expert_ffn_roofline",
    # Since PR 59, as further cells on a survivor's list:
    "decode_steps_ahead_share")]
# The published widths (catalog row SDAR-30B-A3B-Chat), cut to 7 layers.
SDAR = {"hidden_size": 2048, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 7,
        "vocab_size": 151936, "block_length": 4}
TINY = {"hidden_size": 8, "intermediate_size": 99, "moe_intermediate_size": 4,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
        "num_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 3,
        "vocab_size": 16, "block_length": 4}
# A window of 1,000 passes of 31 busy rows whose contexts hold 500
# positions, 300 chunks of 30 tokens beside them, as the engine counts.
COUNTERS = {
    "decode_steps": 1000, "decode_steps_ahead": 990, "block_rows": 31_000,
    "commit_rows": 10_300,
    "decode_tokens": 41_400, "prefill_chunks": 300, "prefill_tokens": 9_000,
    "kv_positions_live": 31_000 * 500, "kv_positions_read": 32 * 1024 * 1000,
    "decode_host_us": 2_500_000, "host_calls": 2_300,
    "expert_slots": 128 * 7 * 1300, "experts_touched": 120 * 7 * 1300,
    "expert_choices": (31_000 * 4 + 9_000) * 8 * 7,
    "expert_peak_choices": 2 * (31_000 * 4 + 9_000) * 8 * 7,
    "max_batch_size": 32, "max_seq_len": 2048}


def bench_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def per_layer() -> dict:
    return {m["name"]: m for m in spec.load_cell(CELL).per_layer}


def event(name, start, end, hlo=""):
    return trace_reduce.Event(name, float(start), float(end), {"hlo": hlo},
                              self_ns=float(end - start))


def canned_run() -> dict:
    """Three passes of 20 ms and a chunk of 12 ms; in each, seven expert
    operations of 2 ms (1 ms in the chunk)."""
    text = "fusion(bf16[7,128,2048,768]{3,2,1,0} %w, bf16[128,128,2048] %x)"
    modules, ops = [], []
    for start, name, length, expert_ns in (
            (0, "jit_decode_step(7)", 20e6, 2e6),
            (25e6, "jit_decode_step(7)", 20e6, 2e6),
            (50e6, "jit_prefill_chunk(3)", 12e6, 1e6),
            (65e6, "jit_decode_step(7)", 20e6, 2e6)):
        modules.append(event(name, start, start + length))
        for layer in range(7):
            at = start + layer * 2.5e6
            ops.append(event("fusion.9", at, at + expert_ns, text))
            ops.append(event("fusion.1", at + expert_ns, at + 2.5e6 - 1e5,
                             "fusion(bf16[2048,4096] %wq)"))
    trace = trace_reduce.Trace({0: trace_reduce.Device(modules, ops)}, [])
    return {"trace": trace, "rehearse": False, "device_kind": "TPU v5 lite",
            "chips": 1, "config": SDAR, "counters": dict(COUNTERS),
            "memory": {"peak_bytes_in_use": 11e9, "bytes_limit": 16.9e9},
            "harness": {}, "traffic": {}}


CANNED = renamed({
    "decode_step_device_ms.sdar": 20.0,
    "device_idle_share.sdar": None,     # busy_and_window wants real lines
    "hbm_peak_share.sdar": 100 * 11 / 16.9,
    "engine_host_ms_per_step.sdar": 2.5,
    "host_calls_per_step.sdar": 2.3,
    "kv_read_over_live.sdar": 32 * 1024 / (31 * 500),
    "decode_batch_occupancy.sdar": 100 * 31 / 32,
    "tokens_per_row_pass.sdar": 41_400 / 31_000,
    "commit_pass_share.sdar": 100 * 10_300 / 31_000,
    "experts_touched_share.sdar": 100 * 120 / 128,
    "expert_load_max_over_mean.sdar": 2.0,
    "expert_ffn_time_share.sdar": 100 * (3 * 14e6 + 7e6) / 72e6,
    "decode_steps_ahead_share.sdar": 99.0,
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


def test_the_cell_is_what_the_issue_states():
    bench = bench_json()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("sdar-30b-a3b-serve-1chip", "blockgen-closed", 1)
    assert len(cell["why"]) <= 200
    # (No count of the benchmark's cells or metrics is pinned here: a
    # later PR adds to both.)
    loaded = spec.load_cell(CELL)
    assert {m["name"] for m in loaded.end_to_end} == \
        {"serve_tokens_per_s", "setup_s"}
    # At least what PR 35 promised: a later PR adds to the cell.
    assert set(NEW_METRICS) <= {m["name"] for m in loaded.per_layer}
    traffic = loaded.traffic
    assert traffic["generator"] == "closed_clients"
    assert (traffic["clients"], traffic["requests_per_client"]) == (48, 8)
    assert traffic["prompt"] == {"dist": "uniform", "min": 64, "max": 384}
    assert traffic["output"] == {"dist": "uniform", "min": 256, "max": 576}
    assert traffic["temperature"] == 0.0
    assert (traffic["ramp_timeout_s"], traffic["trace_after_share"],
            traffic["trace_seconds"]) == (90.0, 0.4, 4.0)
    # The longest request (960 positions) runs at the 512- or the
    # 1,024-position rung of the table, none at the whole width.
    config = loaded.config
    assert traffic["prompt"]["max"] + traffic["output"]["max"] == 960 \
        <= config["engine"]["max_seq_len"] // 2
    assert config["engine"]["max_seq_len"] == \
        config["max_position_embeddings"] == 2048


def test_the_configuration_keeps_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same key, but
    for the two in ``reduced`` (the driver checks them against the
    catalog itself)."""
    config = spec.load_cell(CELL).config
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    assert (config["num_hidden_layers"],
            config["max_position_embeddings"]) == (7, 2048)
    assert config["engine"] == {"max_batch_size": 32, "max_seq_len": 2048}
    options = config["deployment_options"]["ray_actor_options"]
    assert options == {"max_concurrency": 56, "resources": {"TPU": 1}}
    assert options["max_concurrency"] >= \
        spec.load_cell(CELL).traffic["clients"] + 2
    probes = config["probes"]
    assert probes["prompt_lengths"] == [8, 36, 152, 700]
    assert probes["max_new_tokens"] == 16
    # Every generated block of a probe starts fully masked.
    assert all(n % config["block_length"] == 0
               for n in probes["prompt_lengths"])
    # What the reference needs travels as numbers (spec.model_numbers).
    numbers = spec.model_numbers(config)
    assert (numbers["block_length"], numbers["denoising_steps"]) == (4, 2)
    assert 0 < numbers["mask_token_id"] < config["vocab_size"]
    assert config["remasking"] == "sequential"
    for said in ("block_length 4", "mask_token_id", "q_norm", "norm_topk_prob",
                 "no shift by one", "intermediate_size 6144"):
        assert any(said in line for line in config["assumed"]), said
    built = spec.build_model_config(config)
    assert built.num_params == 4_984_176_384
    assert round(built.num_params * 2 / 2 ** 30, 2) == 9.28    # GiB in bf16
    assert (built.num_layers, built.vocab_size, built.max_seq_len,
            built.intermediate_size, built.qk_norm, built.block_length) == \
        (7, 151936, 2048, 768, "head", 4)
    # A random router as decided as the file says, in the rehearsal too.
    assert built.router_init_scale == 4.0
    assert any("router_init_scale 4" in line for line in config["assumed"])
    tiny = spec.build_model_config(spec.rehearsed(config, True))
    assert (tiny.block_length, tiny.mask_token_id, tiny.vocab_size,
            tiny.router_init_scale) == (4, 255, 256, 4.0)


def test_costs_by_hand_at_a_tiny_shape():
    """8 wide, experts of 4, 2 + 1 heads of 4, 4 experts of which 2 a
    token, 3 layers, 16 words, blocks of 4: 2 rows over 10 positions."""
    assert sdar_cost.expert_matrix_values(TINY) == 3 * 8 * 4 == 96
    assert sdar_cost.attention_values(TINY) == 2 * 8 * 3 * 4 == 192
    assert sdar_cost.kv_bytes_per_position(TINY) == 2 * 1 * 4 * 2 == 16
    experts = sdar_cost.expert_ffn_cost(TINY, experts_read=3, choices=16,
                                        tokens=8)
    assert experts == {"bytes": (3 * 96 + 2 * 8 * 8) * 2.0,
                       "flops": 2.0 * 16 * 96}
    cost = sdar_cost.block_pass_cost(TINY, rows=2, context=10,
                                     experts_read=3)
    assert cost["moved"] == {
        "attention": 3 * (192 + 8 * 4 + 2 * 8 + 2 * 4) * 2,
        "experts": 3 * 3 * 96 * 2,
        "head": (8 * 16 + 8) * 2,
        "kv_read": 3 * 2 * 10 * 16,
        "kv_written": 3 * 8 * 16,
        "tokens": 8 * 8 * 2}
    assert cost["bytes"] == 1488 + 1728 + 272 + 960 + 384 + 128
    # 8 tokens: the projections, router and 2 experts a layer, the head;
    # then scores and weighted sum over 10 positions, 2 heads of 4.
    assert cost["flops"] == 2.0 * 8 * (3 * (192 + 32 + 2 * 96) + 128) \
        + 2.0 * 2 * 2 * 4 * 8 * 10 * 3


def test_a_pass_at_the_published_widths_is_bound_by_its_weights():
    """The issue's arithmetic: 603,979,776 values in a layer's experts,
    9.3 GB a pass, memory-bound at 32 rows, over the ridge by 64."""
    assert 128 * sdar_cost.expert_matrix_values(SDAR) == 603_979_776
    cost = sdar_cost.block_pass_cost(SDAR, rows=32, context=500,
                                     experts_read=128)
    moved = cost["moved"]
    assert moved["experts"] == 7 * 603_979_776 * 2
    assert moved["attention"] == 7 * (18_874_368 + 262_144 + 4_352) * 2
    assert moved["head"] == (311_164_928 + 2048) * 2
    assert moved["kv_read"] == 7 * 32 * 500 * 2048
    seconds, bound = flops.least_seconds(cost, peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and seconds == pytest.approx(11.69e-3, rel=1e-3)
    # What the algorithm needs of arithmetic is a tenth of that time...
    assert cost["flops"] / 197e12 < 0.15 * seconds
    # ...and the chosen experts' part is memory-bound at 32 rows, at 64
    # too (the all-experts product, 16 times the arithmetic, is not).
    for rows in (32, 64):
        experts = sdar_cost.expert_ffn_cost(
            SDAR, experts_read=128, choices=rows * 4 * 8, tokens=rows * 4)
        assert flops.least_seconds(
            experts, peaks.peaks("TPU v5 lite"))[1] == "memory"
    assert 16 * experts["flops"] / 197e12 > experts["bytes"] / 819e9


def test_the_new_reader_is_least_time_over_traced_time():
    reader = spec.load_module([os.path.join(REPO, "benchmark")], "readers",
                              "block_pass_roofline")
    run, cell = canned_run(), per_layer()
    whole = sdar_cost.block_pass_cost(SDAR, rows=31.0, context=500.0,
                                      experts_read=120.0)
    assert reader.read(cell["block_step_roofline.sdar"], run) == \
        pytest.approx(100.0 * (whole["bytes"] / 819e9) / 20e-3)
    steps = 1300
    experts = sdar_cost.expert_ffn_cost(
        SDAR, experts_read=120.0,
        choices=(31_000 * 4 + 9_000) * 8 / steps,
        tokens=(31_000 * 4 + 9_000) / steps)
    # Four runs of seven layers over the traced expert time.
    assert reader.read(cell["expert_ffn_roofline.sdar"], run) == \
        pytest.approx(100.0 * (experts["bytes"] / 819e9) * 7 * 4
                      / ((3 * 14e6 + 7e6) / 1e9))
    for name in ("block_step_roofline.sdar", "expert_ffn_roofline.sdar"):
        assert 0 < reader.read(cell[name], run) < 100.0
        # Without the counters (the parent), a trace, or a chip: nothing.
        assert reader.read(cell[name], {**run, "counters": {
            "decode_steps": 9, "decode_tokens": 9}}) is None
        assert reader.read(cell[name], {**run, "trace": None}) is None
        assert reader.read(cell[name], {**run, "rehearse": True}) is None


def test_the_expert_selector_matches_the_chips_operation_text():
    """Operations as the v5e's compiler prints them for this cell
    (``benchmark/sizing_family.py --hlo``, operands cut short): the
    selector finds the three expert products and none of the rest."""
    ops = per_layer()["expert_ffn_time_share.sdar"]["ops"]
    assert ops == per_layer()["expert_ffn_roofline.sdar"]["ops"]
    texts = {
        "gate or up": (
            "%fusion.30 = bf16[128,128,768]{2,0,1:T(8,128)(2,1)S(1)} fusion("
            "bf16[7,128,2048,768]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element."
            "301, bf16[128,2048]{1,0:T(8,128)(2,1)S(1)} %reshape.5)", True),
        "down": (
            "%fusion.41 = bf16[32,4,2048]{2,0,1:T(8,128)(2,1)S(1)} fusion("
            "bf16[7,128,768,2048]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element."
            "305, bf16[32,4,128,768]{3,1,0,2:T(4,128)(2,1)S(1)} %reshape.9)",
            True),
        "router": (
            "%fusion.22 = f32[32,4,128]{2,0,1:T(8,128)S(1)} fusion(bf16[7,2048,"
            "128]{2,1,0:T(8,128)(2,1)} %w_router, bf16[32,4,2048] %m)", False),
        "query projection": (
            "%fusion.12 = bf16[32,4,32,128]{3,2,1,0:T(8,128)(2,1)S(1)} fusion("
            "bf16[7,2048,32,128]{3,2,1,0:T(8,128)(2,1)} %wq, bf16[32,4,2048]"
            " %n)", False),
        "scores": (
            "%fusion.17 = f32[32,4,8,4,2048]{4,2,3,1,0:T(8,128)S(1)} fusion("
            "bf16[4096,16,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} %keys, bf16[32,4,"
            "4,8,128] %q)", False),
        "head": (
            "%fusion.161 = f32[32,4,151936]{2,0,1:T(8,128)} fusion(bf16[2048,"
            "151936]{1,0:T(8,128)(2,1)} %params__lm_head__.1, bf16[32,4,2048]"
            " %x)", False),
    }
    for what, (text, wanted) in texts.items():
        assert bool(re.search(ops, text)) == wanted, what
