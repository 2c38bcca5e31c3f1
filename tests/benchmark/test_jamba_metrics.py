"""What PR 60 adds to the benchmark for its cell
``serve-jamba2-reason-closed``: the cell, its traffic and its
configuration as the issue states them (the configuration against the
catalog row's numbers), every ``.jamba`` metric found and read through
the harness's own loader from a canned run, the selectors against the
text the v5e prints for the two programs' operations, the cost of a
decode step by hand, the ``step_roofline`` reader on a cost module of
its own, and a rehearsal of the cell on the CPU. It asserts containment,
never the benchmark's size: a later PR adds to it. Nothing here is a
measurement."""

import json
import os
import re
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import (  # noqa: E402
    flops, jamba_cost, peaks, spec, trace_reduce)

CELL = "serve-jamba2-reason-closed"
CONFIG = "jamba2-3b-serve-1chip"
# ``kv_read_over_live_jamba`` and not the issue's ``.jamba``:
# ``test_kv_read_metrics.py`` (PR 59's, a benchmark file that this PR
# may not edit) refuses every ``kv_read_over_live.<suffix>`` but its two.
NEW_METRICS = [name + ".jamba" for name in (
    "decode_step_device_ms", "device_idle_share", "hbm_peak_share",
    "engine_host_ms_per_step", "host_calls_per_step",
    "decode_batch_occupancy", "device_idle_gc_share",
    "launch_starved_share", "ssm_time_share", "ssm_state_roofline",
    "ssm_chunk_time_share", "mqa_attn_time_share",
    "prefill_chunk_device_ms", "decode_step_roofline",
    "decode_steps_ahead_share")] \
    + ["kv_read_over_live_jamba"]
# The catalog row AI21-Jamba2-3B of the model-configs guide, every key
# of its `config`.
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
REDUCED = {"max_position_embeddings": 4096}
# A window of 2,000 decode steps of 63 busy rows whose contexts hold
# 2,300 positions in an attention layer, read by row (under a page a row
# over what is live), 400 chunks of 120 tokens beside them.
COUNTERS = {
    "decode_steps": 2000, "decode_tokens": 126_000,
    "decode_steps_ahead": 2000, "decode_steps_narrow": 0,
    "prefill_chunks": 400, "prefill_tokens": 48_000, "first_tokens": 80,
    "state_resets": 80, "kv_positions_live": 126_000 * 2300,
    "kv_positions_read": 126_000 * 2308, "decode_host_us": 7_000_000,
    "host_calls": 4_500, "launches_starved": 24,
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
    assert throughput["workloads"][-1] == CELL
    loaded = spec.load_cell(CELL)
    assert {m["name"] for m in loaded.end_to_end} == \
        {"serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in loaded.per_layer} == set(NEW_METRICS)
    # The issue's fifteen and ``decode_steps_ahead_share.jamba`` (the
    # review's: the layer runs in this cell too) stand at the table's
    # end, in the order they were added.
    assert [m["name"] for m in bench["per_layer"]][-16:] == [
        m["name"] for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    # The traffic is the Kimi and Solar cells' file, as it was.
    traffic = loaded.traffic
    for other in ("serve-kimi-linear-reason-closed",
                  "serve-solar-open2-reason-closed"):
        assert traffic == spec.load_cell(other).traffic
    assert (traffic["clients"], traffic["requests_per_client"]) == (96, 8)
    assert traffic["prompt"] == {"dist": "uniform", "min": 192, "max": 896}
    assert traffic["output"] == {"dist": "uniform", "min": 1024, "max": 3072}
    config = loaded.config
    assert traffic["prompt"]["max"] + traffic["output"]["max"] == 3968 \
        < config["engine"]["max_seq_len"] == 4096
    assert config["engine"] == {"max_batch_size": 64, "max_seq_len": 4096,
                                "max_waiting": 128}
    options = config["deployment_options"]["ray_actor_options"]
    assert options["max_concurrency"] == traffic["clients"] + 8 == 104


def test_the_configuration_keeps_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same key, but
    for the one in ``reduced`` (the driver checks them against the
    catalog itself): nothing is cut but the table of positions."""
    config = model()
    entry = {c["name"]: c for c in bench_json()["configs"]}[CONFIG]
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"
    assert entry["reduced"] == config["reduced"] == list(REDUCED)
    assert len(entry["why"]) <= 200
    assert {k: config[k] for k in CATALOG} == {**CATALOG, **REDUCED}
    assert set(config["reduced_why"]) == set(REDUCED)
    for said in ("order of the layer types", "layers 7 and 21",
                 "head_dim 128", "no rotary", "num_experts 1",
                 "dt_layernorm", "rms_norm_eps 1e-6", "A_log",
                 "variance 1 / fan-in", "tie_word_embeddings"):
        assert any(said in line for line in config["assumed"]), said
    assert "whole" in config["deployment"]
    numbers = spec.model_numbers(config)
    for key in ("attn_layer_period", "attn_layer_offset", "rms_norm_eps",
                "mamba_d_state", "mamba_dt_rank", "mamba_expand",
                "mamba_d_conv"):
        assert numbers[key] == CATALOG[key]      # what the reference reads
    probes = config["probes"]
    chunk, block = 128, 16
    longest = max(probes["prompt_lengths"])
    assert longest > 4 * chunk and longest % chunk and longest % block
    padded = -(-(longest + probes["max_new_tokens"]) // 128) * 128
    assert len(probes["prompt_lengths"]) * padded * config["vocab_size"] \
        * 4 <= 0.32 * 2 ** 30
    for said in ("float8", "bfloat16", "SOUND"):
        assert said in probes["logit_atol_why"], said
    built = spec.build_model_config(config)
    assert built.num_params == 3_029_337_472
    assert round(built.num_params * 2 / 2 ** 30, 2) == 5.64     # GiB in bf16
    assert (built.num_layers, built.vocab_size, built.max_seq_len,
            built.family, built.num_experts, built.rotary) == \
        (28, 65536, 4096, "mamba", 0, False)
    assert (built.mamba_layers, built.attn_layers, built.periods,
            built.head_dim, built.d_inner) == (26, 2, 2, 128, 5120)
    assert [i for i, kind in enumerate(built.kinds)
            if kind == "attention"] == [7, 21]
    rehearsal = spec.build_model_config(spec.rehearsed(config, True))
    assert rehearsal.kinds.count("attention") == 2 \
        and rehearsal.num_heads == 5


# ------------------------------------------------------- the metric files


def event(name, start, end, hlo=""):
    return trace_reduce.Event(name, float(start), float(end), {"hlo": hlo},
                              self_ns=float(end - start))


STATE_OP = ("%fusion.370 = f32[26,64,5120,16] fusion(f32[26,64,5120,16] "
            "%state, s32[] %layer, f32[64,16] %b, f32[64,5120] %dt, "
            "f32[5120,16] %a, f32[64,5120] %u, pred[] %real)")
ATTENTION_OP = ("%paged_kv_attention.11 = bf16[64,20,128] custom-call("
                "s32[16384] %tables, s32[64] %lengths, s32[1] %entry, "
                "bf16[64,20,128] %q, bf16[64,1,128] %k, bf16[64,1,128] %v, "
                "bf16[2,16385,16,128] %pool_k, bf16[2,16385,16,128] %pool_v)")
OTHER_OP = ("%fusion.368 = bf16[64,8192] fusion(bf16[26,2560,8192] %w_gate, "
            "s32[] %layer, f32[64,1,2560] %x, f32[2560] %scale, f32[64] %r)")
SCAN_OP = ("%multiply_reduce_fusion.48 = (f32[5120], f32[5120,16]) fusion("
           "f32[16] %b, f32[16] %c, f32[5120] %dtu, f32[5120,16] %s, "
           "f32[5120,16] %a, f32[5120] %dt)")
CHUNK_OTHER_OP = ("%fusion.371 = bf16[128,10240] fusion(bf16[26,2560,10240] "
                  "%in_proj, s32[] %layer, f32[1,128,2560] %x)")


def canned_run() -> dict:
    """Three decode steps of 13 ms and a chunk of 30 ms; in a step the
    26 Mamba layers' state operations of 0.24 ms, the two attention
    layers' reads of 0.3 ms and an MLP's product; in the chunk 26 scans
    of 0.8 ms and a product."""
    modules, ops = [], []
    for start, name, length in ((0, "jit_decode_step(7)", 13e6),
                                (20e6, "jit_decode_step(7)", 13e6),
                                (40e6, "jit_prefill_chunk(3)", 30e6),
                                (80e6, "jit_decode_step(7)", 13e6)):
        modules.append(event(name, start, start + length))
        decode, at = "decode" in name, start
        for _ in range(26):
            took = 0.24e6 if decode else 0.8e6
            ops.append(event("fusion.1", at, at + took,
                             STATE_OP if decode else SCAN_OP))
            at += took
        for _ in range(2 * decode):
            ops.append(event("custom-call.1", at, at + 0.3e6, ATTENTION_OP))
            at += 0.3e6
        ops.append(event("fusion.2", at, at + 0.1e6,
                         OTHER_OP if decode else CHUNK_OTHER_OP))
    trace = trace_reduce.Trace({0: trace_reduce.Device(modules, ops)}, [])
    return {"trace": trace, "rehearse": False, "device_kind": "TPU v5 lite",
            "chips": 1, "config": model(), "counters": dict(COUNTERS),
            "memory": {"peak_bytes_in_use": 7.1e9, "bytes_limit": 16.9e9},
            "harness": {}, "traffic": {}}


CANNED = {
    "decode_step_device_ms.jamba": 13.0,
    "prefill_chunk_device_ms.jamba": 30.0,
    "device_idle_share.jamba": None,    # busy_and_window wants real lines
    "device_idle_gc_share.jamba": None,  # and the collector's spans
    "hbm_peak_share.jamba": 100 * 7.1 / 16.9,
    "kv_read_over_live_jamba": 2308 / 2300,
    "engine_host_ms_per_step.jamba": 3.5,
    "host_calls_per_step.jamba": 2.25,
    "decode_batch_occupancy.jamba": 100 * 63 / 64,
    "decode_steps_ahead_share.jamba": 100.0,
    "launch_starved_share.jamba": 1.0,
    "ssm_time_share.jamba": 100 * 26 * 0.24e6 / 13e6,
    "mqa_attn_time_share.jamba": 100 * 2 * 0.3e6 / 13e6,
    "ssm_chunk_time_share.jamba": 100 * 26 * 0.8e6 / 30e6,
}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_file_loads_and_reads_a_canned_run(name, monkeypatch):
    loaded = spec.load_cell(CELL)
    metric = {m["name"]: m for m in loaded.per_layer}[name]
    assert metric["cells"] == metric["workloads"] == [CELL]
    assert metric["moves"] == "serve_tokens_per_s"
    assert metric["layer"] in {m["layer"] for m in bench_json()["per_layer"]
                               if CELL not in m.get("workloads", [])}
    reader = spec.load_module(loaded.roots, "readers", metric["reader"])
    # Nothing to read (no trace, no such counter, as on the parent
    # commit): None, never an error.
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert reader.read(metric, {"trace": None, "counters": {}, "memory": {},
                                "harness": {}, "rehearse": False}) is None
    if CANNED.get(name) is not None:
        assert reader.read(metric, canned_run()) == pytest.approx(CANNED[name])
    elif name not in CANNED:
        assert 0 < reader.read(metric, canned_run()) < 100


def test_a_copy_says_what_its_survivor_says_but_for_the_cell():
    """The ten common quantities and the prefill program's time are
    COPIES of the files the older cells read them through (a
    ``model_config`` PR may extend no list): the same reader, selector
    and formula, the same unit, layer, direction and source, so the next
    ``benchmark`` PR folds each onto its survivor's list and loses
    nothing."""
    bench = bench_json()
    entries = {m["name"]: m for m in bench["per_layer"]}
    survivors = {
        "decode_step_device_ms": ".closed", "device_idle_share": ".closed",
        "hbm_peak_share": ".closed", "engine_host_ms_per_step": ".closed",
        "host_calls_per_step": ".closed", "kv_read_over_live": ".closed",
        "decode_batch_occupancy": "", "decode_steps_ahead_share": "",
        "device_idle_gc_share": "",
        "launch_starved_share": "", "prefill_chunk_device_ms": ".closed"}
    said_of_a_cell = ("name", "cells", "what", "what_by_cell")
    for quantity, suffix in survivors.items():
        files = []
        ours = quantity + ("_jamba" if quantity == "kv_read_over_live"
                           else ".jamba")
        for name in (ours, quantity + suffix):
            with open(os.path.join(REPO, "benchmark", "metrics",
                                   name + ".json")) as f:
                files.append({k: v for k, v in json.load(f).items()
                              if k not in said_of_a_cell})
        assert files[0] == files[1], quantity
        ours, theirs = entries[ours], entries[quantity + suffix]
        assert {k: v for k, v in ours.items()
                if k not in ("name", "workloads")} == \
            {k: v for k, v in theirs.items() if k not in ("name", "workloads")}
        assert CELL not in theirs["workloads"]


def test_the_time_shares_cannot_pass_the_whole_program():
    cell, run = per_layer(), canned_run()
    reader = spec.load_module(spec.load_cell(CELL).roots, "readers",
                              "trace_op_share")
    step = [reader.read(cell[name], run) for name in
            ("ssm_time_share.jamba", "mqa_attn_time_share.jamba")]
    assert 0 < sum(step) < 100
    assert 0 < reader.read(cell["ssm_chunk_time_share.jamba"], run) < 100


def test_the_roofline_reader_says_nothing_where_there_is_nothing():
    cell = per_layer()
    reader = spec.load_module(spec.load_cell(CELL).roots, "readers",
                              "step_roofline")
    run = canned_run()
    for name in ("decode_step_roofline.jamba", "ssm_state_roofline.jamba"):
        assert reader.read(cell[name], {**run, "counters": {
            "decode_steps": 9, "decode_tokens": 9}}) is None
        assert reader.read(cell[name], {**run, "trace": None}) is None
        assert reader.read(cell[name], {**run, "rehearse": True}) is None
        # A tree whose benchmark lacks the cost module, as a parent's.
        assert reader.read({**cell[name], "cost": "no_such_cost"}, run) is None


def test_the_roofline_shares_are_the_costs_over_the_traced_time():
    cell, run, config = per_layer(), canned_run(), model()
    reader = spec.load_module(spec.load_cell(CELL).roots, "readers",
                              "step_roofline")
    peak = peaks.peaks("TPU v5 lite")
    rows, context = 63.0, 2300.0
    step = flops.least_seconds(
        jamba_cost.decode_step_cost(config, rows, context), peak)[0]
    assert reader.read(cell["decode_step_roofline.jamba"], run) == \
        pytest.approx(100 * step / 13e-3)
    state = flops.least_seconds(jamba_cost.mamba_cost(config, rows), peak)[0]
    assert reader.read(cell["ssm_state_roofline.jamba"], run) == \
        pytest.approx(100 * state * 26 / (26 * 0.24e-3))
    # No share can pass 100%: the step's least time holds the parts'.
    assert 26 * state < step


def test_the_step_roofline_reader_takes_any_cost_module(monkeypatch):
    """The reader written ONCE (``ROADMAP.md`` B2 (12)): the cost module
    by the name in the metric's file, the part by ``part``; the step's
    cost is handed the window's mean rows and context, a part's the
    rows."""
    reader = spec.load_module(spec.load_cell(CELL).roots, "readers",
                              "step_roofline")
    seen = {}

    def decode_step_cost(model, rows, context):
        seen["step"] = (rows, context)
        return {"flops": 0.0, "bytes": 819e9 * 6.5e-3}

    def experts_cost(model, rows):
        seen["experts"] = rows
        return {"flops": 197e12 * 0.06e-3, "bytes": 0.0}

    made_up = types.ModuleType("benchmark.made_up_cost")
    made_up.decode_step_cost, made_up.experts_cost = \
        decode_step_cost, experts_cost
    made_up.layers = lambda model: {"experts": 4}
    monkeypatch.setitem(sys.modules, "benchmark.made_up_cost", made_up)
    run = canned_run()
    metric = {"cost": "made_up_cost", "module": "^jit_decode_step"}
    assert reader.read(metric, run) == pytest.approx(50.0)    # 6.5 of 13 ms
    assert seen["step"] == (63.0, 2300.0)
    part = {**metric, "part": "experts", "ops": r"\[64,8192\]"}
    # Four layers of 0.06 ms a run over the one matching 0.1 ms a run.
    assert reader.read(part, run) == pytest.approx(100 * 4 * 0.06 / 0.1)
    assert seen["experts"] == 63.0


# ------------------------------------------------------- the cost, by hand


def test_the_costs_are_the_hand_reckoned_bytes():
    config = model()
    assert jamba_cost.layers(config) == {"mamba": 26, "attention": 2}
    values = jamba_cost.mamba_matrix_values(config)
    assert values == {"products": 2560 * 10240 + 5120 * 192 + 160 * 5120
                      + 5120 * 2560,
                      "beside": 4 * 5120 + 5120 + 5120 + 5120 * 16 + 5120
                      + 160 + 16 + 16}
    assert sum(values.values()) == 41_241_792         # the issue's count
    assert jamba_cost.attention_matrix_values(config) == 13_762_560
    assert jamba_cost.kv_values(config) == 256      # 0.5 KiB a live position
    # A row's state of one layer: [5120, 16] float32 and three inputs.
    assert jamba_cost.state_bytes(config) == 5120 * 16 * 4 + 3 * 5120 * 2 \
        == 358_400
    assert 26 * jamba_cost.state_bytes(config) == 9_318_400   # 8.89 MiB a row
    # The whole configuration, counted from the parts.
    built = spec.build_model_config(config)
    assert 65536 * 2560 + 2560 + 28 * (2 * 2560 + 3 * 2560 * 8192) \
        + 26 * 41_241_792 + 2 * 13_762_560 == built.num_params
    cost = jamba_cost.decode_step_cost(config, rows=64, context=2300)
    moved = cost["moved"]
    assert moved["head"] == (65536 * 2560 + 2560) * 2
    assert moved["mamba"] == 26 * (41_241_792 * 2 + 2 * 64 * 358_400
                                   + 2 * 64 * 2560 * 2)
    # The issue's figures: 2.14 GB of mixer matrices and 1.09 of state
    # (64 rows read once and written once), 3.52 of MLPs, 0.34 of head.
    assert round(26 * 41_241_792 * 2 / 1e9, 2) == 2.14
    assert round((26 * 2 * 64 * 5120 * 16 * 4) / 1e9, 2) == 1.09
    assert round(moved["mlp_and_norms"] / 1e9, 2) == 3.52
    assert round(moved["head"] / 1e9, 2) == 0.34
    assert moved["attention"] == 2 * (13_762_560 + 64 * 2301 * 256
                                      + 2 * 64 * 2560) * 2
    assert round(2 * 64 * 2301 * 256 * 2 / 1e9, 2) == 0.15   # live k and v
    assert cost["bytes"] == sum(moved.values()) == 7_420_906_240
    least, bound = flops.least_seconds(cost, peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and round(least * 1e3, 2) == 9.06     # ms
    assert round(moved["mamba"] / cost["bytes"], 2) == 0.45  # the issue's 45%
    mamba = jamba_cost.mamba_cost(config, rows=64)
    assert mamba["bytes"] == moved["mamba"] / 26
    assert mamba["flops"] == 64 * (2.0 * values["products"] + 2.0 * 4 * 5120
                                   + 6.0 * 5120 * 16)
    assert flops.least_seconds(mamba, peaks.peaks("TPU v5 lite"))[1] \
        == "memory"
    attention = jamba_cost.attention_cost(config, rows=64, context=2300)
    assert attention["bytes"] == moved["attention"] / 2
    assert attention["flops"] == 2.0 * 64 * 13_762_560 \
        + 4.0 * 64 * 2301 * 20 * 128


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
                           "jamba_op_texts.json")) as f:
        return json.load(f)


def test_the_selectors_match_the_chips_operation_text():
    """Each operation the v5e printed for the two programs is owned by
    the selector of its layer's part and by no other. A decode selector
    owns no operation of the prefill program and the other way round:
    ``trace_op_share`` sums matching operations wherever they ran. The
    decode step runs at ONE width (it reads by row); the three prefill
    programs differ in the gathered view alone, which no selector
    spells."""
    cell = per_layer()
    owners = {"ssm": cell["ssm_time_share.jamba"]["ops"],
              "mqa": cell["mqa_attn_time_share.jamba"]["ops"],
              "ssm_chunk": cell["ssm_chunk_time_share.jamba"]["ops"]}
    assert owners["ssm"] == cell["ssm_state_roofline.jamba"]["ops"]
    texts, seen = op_texts(), set()
    for program in ("decode_step", "prefill_chunk"):
        assert len(texts[program]) >= 30
        for op in texts[program]:
            seen.add(op["owner"])
            assert op["owner"] in (None, *owners)
            for name, ops in owners.items():
                assert bool(re.search(ops, op["text"])) == \
                    (name == op["owner"]), (name, op["text"])
    assert seen == {None, "ssm", "mqa", "ssm_chunk"}
    # What the selectors leave unowned in a step is the MLPs, the head,
    # the norms and the weight-only slices: the owned parts and they
    # make the program's time, so no share can pass 100%.
    for program, parts in (("decode_step", ("ssm", "mqa")),
                           ("prefill_chunk", ("ssm_chunk",))):
        owned = sum(op["self_ms"] for op in texts[program]
                    if op["owner"] in parts)
        assert 0 < owned < texts[program + "_device_ms"]
