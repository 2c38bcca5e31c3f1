"""The per-layer metrics PR 25 adds for its two cells: every file found
and read through the harness's own loader, the new reader's arithmetic
on a made-up trace, the counts from shapes, and the operation selectors
against the HLO text the v5e's compiler prints. Nothing here is a
measurement."""

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
from cells import named  # noqa: E402

from benchmark import flops, moe_cost, peaks, spec, trace_reduce  # noqa: E402

MOE, SHORT = "serve-olmoe-longgen-closed", "train-512-1chip"
NEW_METRICS = {
    **{named(name, "moe"): MOE for name in (
        "decode_step_device_ms", "decode_batch_occupancy",
        "device_idle_share", "hbm_peak_share", "engine_host_ms_per_step",
        # (PR 53 retired idle_ms_per_step_launch.moe and _fetch.moe: 0.35
        # and 0.0026 ms of a 14 ms step on the ledger's PR 52 line, the
        # device busy under both spans since PR 36; device_idle_share
        # and breakdown.idle_gaps carry what they were for. PR 59 retired
        # kv_gather_time_share.moe: the gathers it selected left the step
        # with PR 58, 7.5874 -> 0.0495% on the ledger's PR 58 line.)
        "expert_ffn_time_share",
        "experts_touched_share", "expert_load_max_over_mean",
        "expert_ffn_roofline")},
    **{named(name, "512"): SHORT for name in (
        "train_step_device_ms", "train_step_mfu", "flash_time_share",
        # (PR 53 retired hbm_peak_share.512: 66.684, the very number of
        # hbm_peak_share.train in train-4k-1chip: one configuration,
        # 8,192 tokens a step in both.)
        "device_idle_share")},
}
# The published widths (OLMoE-1B-7B) at the cell's depth.
OLMOE = {"hidden_size": 2048, "intermediate_size": 1024, "num_experts": 64,
         "num_experts_per_tok": 8, "num_hidden_layers": 12}


def bench_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, cell", sorted(NEW_METRICS.items()))
def test_new_metric_file_loads_through_the_cell(name, cell, monkeypatch):
    loaded = spec.load_cell(cell)
    metric = {m["name"]: m for m in loaded.per_layer}[name]
    assert cell in metric["cells"]
    assert metric["cells"] == metric["workloads"]
    assert metric["moves"] in {m["name"] for m in loaded.end_to_end}
    reader = spec.load_module(loaded.roots, "readers", metric["reader"])
    # Nothing to read (no trace, no such counter, as on the parent
    # commit): None, never an error.
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda directory: None)
    assert reader.read(metric, {"trace": None, "counters": {}, "memory": {},
                                "harness": {}, "rehearse": False}) is None


def test_the_new_cells_report_what_the_contract_asks():
    bench = bench_json()
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[MOE]["chips"] == cells[SHORT]["chips"] == 1
    assert cells[MOE]["traffic"] == "longgen-closed"  # the existing file
    for cell, main in ((MOE, "serve_tokens_per_s"),
                       (SHORT, "train_tokens_per_s")):
        reported = {m["name"] for m in spec.load_cell(cell).end_to_end}
        assert reported == {main, "setup_s"}
    per_layer = {m["name"] for m in bench["per_layer"]}
    assert set(NEW_METRICS) <= per_layer


def test_the_configuration_keeps_the_published_widths():
    """The catalog row's keys unchanged but for the two in ``reduced``
    (the driver checks them against the catalog itself)."""
    config = spec.load_cell(MOE).config
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "model_type": "olmoe", "norm_topk_prob": False,
        "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    assert (config["num_hidden_layers"],
            config["max_position_embeddings"]) == (12, 2048)
    built = spec.build_model_config(config)
    assert built.qk_norm and built.experts_per_token == 8 \
        and not built.norm_topk_prob
    assert round(built.num_params * 2 / 2 ** 30, 2) == 9.76  # GiB in bf16
    rehearsal = spec.build_model_config(spec.rehearsed(config, True))
    assert rehearsal.num_experts >= 8 and rehearsal.experts_per_token >= 2


def test_expert_cost_counts_what_the_algorithm_needs():
    one_expert = 3 * 2048 * 1024
    assert moe_cost.expert_matrix_values(OLMOE) == one_expert
    # 16 tokens x 8 choices that touched 56 of the 64 experts.
    cost = moe_cost.expert_ffn_cost(OLMOE, experts_read=56, choices=128,
                                    tokens=16)
    assert cost["flops"] == 2.0 * 128 * one_expert
    assert cost["bytes"] == (56 * one_expert + 2 * 16 * 2048) * 2
    # Memory-bound by far: 0.86 ms of reading against 8 us of arithmetic.
    seconds, bound = flops.least_seconds(cost, peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and seconds == pytest.approx(8.6e-4, rel=0.01)


def event(name, start, end):
    return trace_reduce.Event(name, float(start), float(end), {})


def test_expert_roofline_is_least_time_over_traced_time():
    reader = spec.load_module([os.path.join(REPO, "benchmark")], "readers",
                              "expert_ffn_roofline")
    metric = {m["name"]: m for m in spec.load_cell(MOE).per_layer}[
        "expert_ffn_roofline.moe"]
    expert_op = ("%fusion.9 = bf16[64,16,1024]{2,1,0} fusion(bf16[64,16,2048]"
                 "{2,1,0} %x, bf16[12,64,2048,1024]{3,2,1,0} %w), kind=kOutput")
    other_op = "%fusion.3 = bf16[16,1,2048]{2,1,0} fusion(%p0)"
    # Two decode steps and one chunk; the expert operations take 24 ms.
    ops = [event(expert_op, 0, 8e6), event(other_op, 8e6, 9e6),
           event(expert_op, 20e6, 28e6), event(expert_op, 40e6, 48e6)]
    for op in ops:
        op.self_ns = op.duration_ns
    modules = [event("jit_decode_step(7)", 0, 9e6),
               event("jit_decode_step(7)", 20e6, 28e6),
               event("jit_prefill_chunk(3)", 40e6, 48e6),
               event("jit__threefry_split(1)", 50e6, 51e6)]
    trace = trace_reduce.Trace({0: trace_reduce.Device(modules, ops)}, [])
    layer_steps = 1000 * 12
    run = {"trace": trace, "rehearse": False, "device_kind": "TPU v5 lite",
           "config": OLMOE,
           "counters": {"decode_steps": 900, "prefill_chunks": 100,
                        "decode_tokens": 14400, "prefill_tokens": 1600,
                        "expert_slots": 64 * layer_steps,
                        "experts_touched": 56 * layer_steps,
                        "expert_choices": 128 * layer_steps}}
    cost = moe_cost.expert_ffn_cost(OLMOE, experts_read=56, choices=128,
                                    tokens=16)
    least = cost["bytes"] / 819e9
    assert reader.read(metric, run) == pytest.approx(
        100.0 * least * 12 * 3 / 24e-3)
    # Without the counters (the parent), a trace, or a chip: nothing.
    assert reader.read(metric, {**run, "counters": {"decode_steps": 9}}) \
        is None
    assert reader.read(metric, {**run, "trace": None}) is None
    assert reader.read(metric, {**run, "rehearse": True}) is None


def test_the_selectors_match_the_chips_operation_text():
    """The shapes as the v5e's compiler prints them in the decode
    program of this configuration (deviceless compile and the traced
    runs of PR 25): the expert products by an expert tensor among their
    operands, whole or as a layer of the stacked weights. The two
    table-wide gathers of the step before PR 58 are operations that no
    selector owns (their entry went with them, PR 59)."""
    cell = {m["name"]: m for m in spec.load_cell(MOE).per_layer}
    experts = cell["expert_ffn_time_share.moe"]["ops"]
    assert experts == cell["expert_ffn_roofline.moe"]["ops"]
    texts = {
        "gate": "%fusion.181 = bf16[64,16,1024]{2,1,0:T(8,128)(2,1)S(1)} "
                "fusion(bf16[12,64,2048,1024]{3,2,1,0:T(8,128)(2,1)} %p.1, "
                "s32[]{:T(128)} %p.2), kind=kOutput",
        "down": "%fusion.185 = bf16[16,2048,1]{1,0,2:T(8,128)(2,1)} "
                "fusion(bf16[64,1024,2048]{2,1,0:T(8,128)(2,1)} %w)",
        "gather": "%fusion.190 = bf16[2048,16,16,128]{3,2,1,0:T(8,128)(2,1)}"
                  " fusion(%fusion.188, %fusion.189), kind=kCustom",
        "attention projection": "%fusion.4 = bf16[16,1,16,128]{3,2,1,0} "
                                "fusion(bf16[12,2048,16,128]{3,2,1,0} %wq)",
        "router": "%fusion.7 = f32[16,1,64]{2,1,0} fusion(bf16[12,2048,64]"
                  "{2,1,0} %w_router)",
    }
    assert [k for k, t in texts.items() if re.search(experts, t)] == \
        ["gate", "down"]
    others = [m["ops"] for m in cell.values()
              if "ops" in m and m["ops"] != experts]
    assert not [ops for ops in others if re.search(ops, texts["gather"])]


def op_texts() -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "olmoe_op_texts.json")) as f:
        return json.load(f)


def at_width(text: str, blocks: int) -> str:
    """The printed operation with the decode step's gather at another
    width of the table: 16 rows x 32, 64 or 128 blocks of 16 positions
    (a quarter, a half, the whole of 2,048; the chip's run had 512)."""
    for shape in ("[{},16,16,128]", "s32[{}]", "[16,16,{},1,2]",
                  "[16,{},16,128]", "pred[16,{}]"):
        text = text.replace(shape.format(512), shape.format(blocks))
    return text


@pytest.mark.parametrize("blocks", [512, 1024, 2048])
def test_the_selectors_match_the_v5es_own_text_at_every_width(blocks):
    """PR 53: each operation the v5e printed for the two programs in a
    traced run of this cell is owned by the selector of its part and by
    no other, at the quarter width the cell's steps ran at and at the
    two wider ones. The saved gathers are the step's before PR 58 (it
    reads by row since): operations that no selector owns, as the rest
    of the step; ``kv_gather_time_share.moe`` went with them (PR 59),
    and a selector for the by-row kernel's share is the next
    configuration's to bring."""
    cell = {m["name"]: m for m in spec.load_cell(MOE).per_layer}
    owners = {"experts": cell["expert_ffn_time_share.moe"]["ops"]}
    assert not [name for name in cell if name.startswith("kv_gather")]
    texts = op_texts()
    seen = []
    for program in ("decode_step", "prefill_chunk"):
        for op in texts[program]:
            text = at_width(op["text"], blocks) \
                if program == "decode_step" else op["text"]
            seen.append((program, op["owner"]))
            for name, ops in owners.items():
                assert bool(re.search(ops, text)) == (name == op["owner"]), \
                    (name, text)
    # Two gathers a step (keys, values), one expert call, and the rest.
    assert seen.count(("decode_step", "kv_gather")) == 2
    assert seen.count(("decode_step", "experts")) == 1
    assert ("decode_step", None) in seen
    if blocks != 512:
        assert f"bf16[{blocks},16,16,128]" in at_width(
            texts["decode_step"][1]["text"], blocks)
