"""What PR 33 adds to the benchmark for its cell
``serve-phi4flash-reason-closed``: every new metric file found and read
through the harness's own loader, the cost of a decode step by hand at
the published widths, the new reader's arithmetic on a made-up trace,
the operation selectors against the text the v5e's compiler prints, the
configuration and the traffic as the issue states them. Nothing here is
a measurement."""

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

from benchmark import flops, peaks, phi_cost, spec, trace_reduce  # noqa: E402

CELL = "serve-phi4flash-reason-closed"
NEW_METRICS = [named(name, "phi") for name in (
    "decode_step_device_ms", "decode_batch_occupancy", "device_idle_share",
    "hbm_peak_share", "engine_host_ms_per_step", "host_calls_per_step",
    # (PR 53 retired stream_backlog_rows.phi: it read a negative count
    # of rows, -0.0075 / -0.0119 on the ledger's PR 52 line;
    # stream_take_age_ms_p95 and stream_get_age_ms_p95 measure the same
    # backlog in ms.)
    "kv_read_over_live", "shared_kv_time_share",
    "window_kv_time_share", "ssm_time_share", "decode_step_roofline")]
# The published widths (catalog row Phi-4-mini-flash-reasoning).
PHI = {"hidden_size": 2560, "intermediate_size": 10240,
       "num_attention_heads": 40, "num_key_value_heads": 20,
       "num_hidden_layers": 32, "vocab_size": 200064, "sliding_window": 512}


def bench_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def per_layer() -> dict:
    return {m["name"]: m for m in spec.load_cell(CELL).per_layer}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_file_loads_through_the_cell(name, monkeypatch):
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


def test_the_cell_is_what_the_issue_states():
    bench = bench_json()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("phi4-mini-flash-serve-1chip", "reason-closed", 1)
    assert len(cell["why"]) <= 200
    # At most a quarter of the cells, rounded down, take four chips, and
    # at least the one (no count of cells is pinned: later PRs add).
    assert 1 <= sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(bench["workloads"]) // 4)
    loaded = spec.load_cell(CELL)
    assert {m["name"] for m in loaded.end_to_end} == \
        {"serve_tokens_per_s", "setup_s"}
    # At least what PR 33 promised: a later PR adds to the cell.
    assert set(NEW_METRICS) <= {m["name"] for m in loaded.per_layer}
    traffic = loaded.traffic
    assert traffic["generator"] == "closed_clients"
    assert (traffic["clients"], traffic["requests_per_client"]) == (48, 8)
    assert traffic["prompt"] == {"dist": "uniform", "min": 64, "max": 512}
    assert traffic["output"] == {"dist": "uniform", "min": 1024, "max": 3072}
    assert traffic["temperature"] == 0.0
    assert (traffic["ramp_timeout_s"], traffic["trace_after_share"],
            traffic["trace_seconds"]) == (90.0, 0.4, 4.0)
    tiny = traffic["rehearsal"]
    assert (tiny["clients"], tiny["prompt"]["min"], tiny["prompt"]["max"],
            tiny["output"]["min"], tiny["output"]["max"]) == (6, 4, 12, 16, 40)
    # The longest request fits the table.
    config = loaded.config
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= config["engine"]["max_seq_len"] == config["max_position_embeddings"]


def test_the_configuration_keeps_the_catalog_rows_numbers():
    """Every number of the catalog row's ``config`` under the same key,
    but for the one in ``reduced`` (the driver checks them against the
    catalog itself)."""
    config = spec.load_cell(CELL).config
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "mb_per_layer": 2, "model_type": "phi4flash",
        "num_attention_heads": 40, "num_hidden_layers": 32,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["max_position_embeddings"]
    assert config["max_position_embeddings"] == 4096
    assert config["engine"] == {"max_batch_size": 32, "max_seq_len": 4096}
    options = config["deployment_options"]["ray_actor_options"]
    # Every caller's stream admitted, and room for the control calls.
    assert options == {"max_concurrency": 56, "resources": {"TPU": 1}}
    assert options["max_concurrency"] >= \
        spec.load_cell(CELL).traffic["clients"] + 2
    assert config["probes"]["prompt_lengths"] == [5, 33, 150, 700]
    assert config["probes"]["max_new_tokens"] == 16
    for said in ("d_state 16", "d_conv 4", "expand 2", "dt_rank",
                 "ifferential attention", "lambda_init", "LayerNorm",
                 "no rotary", "mb_per_layer"):
        assert any(said in line for line in config["assumed"]), said
    built = spec.build_model_config(config)
    assert built.num_params == 3_852_562_944
    assert round(built.num_params * 2 / 2 ** 30, 2) == 7.18    # GiB in bf16
    assert (built.num_layers, built.vocab_size, built.max_seq_len) == \
        (32, 200064, 4096)
    tiny = spec.build_model_config(spec.rehearsed(config, True))
    rehearsed = spec.rehearsed(config, True)["engine"]
    # The rehearsal's contexts (up to 12 + 40) cross its window (8) and
    # wrap its ring (8 + 8 + 4).
    assert tiny.sliding_window + rehearsed["prefill_chunk"] \
        + rehearsed["block_size"] == 20 < 52


def test_decode_step_cost_by_hand_at_the_published_widths():
    parts = phi_cost.parameters(PHI)
    assert parts["table"] == 200064 * 2560
    assert parts["blocks"] == 32 * (3 * 2560 * 10240 + 4 * 2560) + 2 * 2560
    assert parts["gmu"] == 7 * 2 * 2560 * 5120
    # A state-space mixer 41.24M, an attention mixer 19.67M, a cross
    # mixer 13.11M (the issue's arithmetic).
    assert round(parts["ssm"] / 9 / 1e6, 2) == 41.24
    assert round(parts["attention"] / 9 / 1e6, 2) == 19.67
    assert round(parts["cross"] / 7 / 1e6, 2) == 13.11
    assert sum(parts.values()) == 3_852_562_944   # the program's own count
    assert phi_cost.kv_bytes_per_position(PHI) == 5120
    assert phi_cost.state_bytes_per_row(PHI) == \
        9 * (5120 * 16 * 4 + 3 * 5120 * 2)
    # 32 rows whose contexts hold 1,300 positions.
    cost = phi_cost.decode_step_cost(PHI, rows=32, context=1300)
    assert cost["moved"] == {
        "weights": 7_705_125_888,
        "shared_kv": 32 * 1300 * 5120 * 8,
        "window_kv": 32 * 512 * 5120 * 8,
        "state": 2 * 32 * 9 * 358_400,
        "kv_written": 32 * 5120 * 9}
    assert cost["bytes"] == 10_288_063_488
    seconds, bound = flops.least_seconds(cost, peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and seconds == pytest.approx(12.56e-3, rel=1e-3)
    # A context inside the window reads only what it holds.
    short = phi_cost.decode_step_cost(PHI, rows=32, context=100)
    assert short["moved"]["window_kv"] == 32 * 100 * 5120 * 8
    # Arithmetic: two a parameter and row, and the attention's products.
    assert cost["flops"] == pytest.approx(
        2 * 32 * 3_852_562_944 + 4 * 2560 * 32 * (1300 * 8 + 512 * 8))
    assert cost["flops"] / 197e12 < 0.2 * seconds


def event(name, start, end):
    return trace_reduce.Event(name, float(start), float(end), {})


def test_decode_step_roofline_is_least_time_over_traced_time():
    reader = spec.load_module([os.path.join(REPO, "benchmark")], "readers",
                              "decode_step_roofline")
    metric = per_layer()["decode_step_roofline.phi"]
    # Three decode steps of 25 ms and a chunk, which is not counted.
    modules = [event("jit_decode_step(7)", 0, 25e6),
               event("jit_decode_step(7)", 30e6, 55e6),
               event("jit_prefill_chunk(3)", 56e6, 70e6),
               event("jit_decode_step(7)", 71e6, 96e6)]
    trace = trace_reduce.Trace({0: trace_reduce.Device(modules, [])}, [])
    run = {"trace": trace, "rehearse": False, "device_kind": "TPU v5 lite",
           "config": PHI,
           "counters": {"decode_steps": 1000, "decode_tokens": 31_000,
                        "kv_positions_live": 31_000 * 1300}}
    cost = phi_cost.decode_step_cost(PHI, rows=31.0, context=1300.0)
    assert reader.read(metric, run) == pytest.approx(
        100.0 * (cost["bytes"] / 819e9) / 25e-3)
    assert reader.read(metric, run) < 100.0
    # Without the counters (the parent), a trace, or a chip: nothing.
    assert reader.read(metric, {**run, "counters": {"decode_steps": 9,
                                                    "decode_tokens": 9}}) \
        is None
    assert reader.read(metric, {**run, "trace": None}) is None
    assert reader.read(metric, {**run, "rehearse": True}) is None


def test_kv_read_over_live_is_a_ratio_of_the_two_counters():
    reader = spec.load_module([os.path.join(REPO, "benchmark")], "readers",
                              "counters")
    metric = per_layer()[named("kv_read_over_live", "phi")]
    counters = {"kv_positions_read": 32 * 4096 * 10,
                "kv_positions_live": 32 * 1024 * 10, "max_batch_size": 32}
    assert reader.read(metric, {"counters": counters}) == 4.0
    assert reader.read(metric, {"counters": {"max_batch_size": 32}}) is None
    assert reader.read(metric, {"counters": {
        "kv_positions_read": 0, "kv_positions_live": 0}}) is None


def test_the_selectors_match_the_chips_operation_text():
    """Operations of the decode program as the v5e's compiler printed
    them in this cell's traced run (my chip run, PR 33; the ``hlo`` stat
    of the trace's events, operands cut short): each selector finds its
    own and none of another's, and none finds the MLP or the head. These
    are the WHOLE-width step's, with PR 33's ring of 560 positions (the
    chunk was 32): the patterns, which since PR 53 leave the ring's
    length open and name the table's three widths, own them as before;
    the next test holds them to the text of PR 53's run."""
    cell = per_layer()
    selectors = {name: cell[name + "_time_share.phi"]["ops"]
                 for name in ("shared_kv", "window_kv", "ssm")}
    texts = {
        "pool write": (
            "%fusion.7 = bf16[8193,16,1280]{2,1,0:T(8,128)(2,1)} fusion("
            "bf16[8193,16,1280]{2,1,0:T(8,128)(2,1)} %bitcast.36, "
            "bf16[32,1280]{1,0:T(8,128)(2,1)S(1)} %copy-done.9), kind=kCustom",
            "shared_kv"),
        "pool gather": (
            "%fusion.2 = bf16[8192,16,1280]{2,1,0:T(8,128)(2,1)} fusion("
            "bf16[1,8193,16,1280]{3,2,1,0:T(8,128)(2,1)} %bitcast.40, "
            "s32[8192]{0:T(1024)S(1)} %bitcast.763), kind=kCustom",
            "shared_kv"),
        "cross scores": (
            "%bitcast_reduce_fusion.3 = (f32[32,40]{1,0:T(8,128)S(1)}, "
            "f32[32,4096,1,40]{1,3,0,2:T(8,128)S(1)}) fusion(bf16[32,4096,1280]"
            "{2,1,0:T(8,128)(2,1)} %get-tuple-element.1318, bf16[32,40,1280]"
            "{2,1,0:T(8,128)(2,1)S(1)} %reshape.919, pred[32,4096]{1,0:T(8,128)"
            "(4,1)S(1)} %fusion.761), kind=kOutput", "shared_kv"),
        "cross softmax halves": (
            "%fusion.764 = (f32[32,10,2,1,1,4096]{5,3,2,1,0,4:T(1,128)S(1)}, "
            "f32[32,10,2,1,1,4096]{5,3,2,1,0,4:T(1,128)S(1)}) fusion("
            "f32[32,10,2,2,1,4096]{5,3,2,1,0,4:T(2,128)S(1)} %reshape.920), "
            "kind=kLoop", "shared_kv"),
        "cross weighted sum": (
            "%fusion.767 = f32[32,1,10,2,1280]{4,2,3,0,1:T(8,128)S(1)} fusion("
            "f32[32,10,2,1,1,4096]{5,3,2,1,0,4:T(1,128)S(1)} %get-tuple-"
            "element.1057, f32[]{:T(128)S(6)} %add.2282, bf16[32,4096,1280]"
            "{2,1,0:T(8,128)(2,1)} %get-tuple-element.1319), kind=kOutput",
            "shared_kv"),
        "ring write": (
            "%fusion.722 = bf16[8,32,560,1280]{3,2,1,0:T(8,128)(2,1)} fusion("
            "bf16[8,32,560,1280]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element."
            "1117, s32[32]{0:T(128)S(1)} %fusion.718, bf16[32,1280]{1,0:T(8,"
            "128)(2,1)S(1)} %get-tuple-element.1014), kind=kCustom",
            "window_kv"),
        "window scores": (
            "%fusion.724 = (f32[32,40]{1,0:T(8,128)S(1)}, f32[32,40,1,560]"
            "{3,1,0,2:T(8,128)S(1)}) fusion(bf16[32,40,1280]{2,1,0:T(8,128)"
            "(2,1)S(1)} %reshape.914, bf16[8,32,560,1280]{3,2,1,0:T(8,128)"
            "(2,1)} %fusion.722, s32[]{:T(128)} %select_n.182, pred[32,560]"
            "{1,0:T(8,128)(4,1)S(1)} %fusion.723), kind=kOutput", "window_kv"),
        "window weighted sum": (
            "%fusion.730 = f32[32,1,10,2,1280]{4,2,3,0,1:T(8,128)S(1)} fusion("
            "bf16[8,32,560,1280]{3,2,1,0:T(8,128)(2,1)} %fusion.720, s32[]"
            "{:T(128)} %select_n.182, f32[32,10,2,1,1,560]{5,3,2,1,0,4:T(1,"
            "128)S(1)} %get-tuple-element.1017), kind=kOutput", "window_kv"),
        "state update": (
            "%fusion.737 = f32[9,32,5120,16]{2,3,1,0:T(8,128)} fusion("
            "f32[9,32,5120,16]{2,3,1,0:T(8,128)} %get-tuple-element.1115, "
            "s32[]{:T(128)} %select_n.181, f32[32,16]{1,0:T(8,128)S(1)} "
            "%get-tuple-element.1005), kind=kLoop", "ssm"),
        "state read out": (
            "%fusion.715 = f32[32,5120]{1,0:T(8,128)S(1)} fusion(f32[9,32,5120"
            ",16]{2,3,1,0:T(8,128)} %get-tuple-element.1115, f32[5120,16]{0,1:"
            "T(8,128)S(1)} %negate_bitcast_fusion.2), kind=kLoop", "ssm"),
        "out_proj": (
            "%convert_reduce_fusion.56 = (f32[32]{0:T(128)S(1)}, bf16[32,1,"
            "2560]{2,0,1:T(8,128)(2,1)S(1)}) fusion(bf16[32,1,2560]{2,0,1:T(8,"
            "128)(2,1)S(1)} %get-tuple-element.1114, bf16[8,5120,2560]{2,1,0:"
            "T(8,128)(2,1)} %get-tuple-element.1199, f32[32,5120]{1,0:T(8,128)"
            "S(1)} %fusion.715), kind=kOutput", "ssm"),
        "convolution state": (
            "%fusion.738 = bf16[9,32,3,5120]{3,1,2,0:T(8,128)(2,1)} fusion("
            "bf16[9,32,3,5120]{3,1,2,0:T(8,128)(2,1)} %get-tuple-element.1116)",
            "ssm"),
        "memory unit gate": (
            "%fusion.755 = bf16[32,5120]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[7,"
            "2560,5120]{2,1,0:T(8,128)(2,1)} %get-tuple-element.1314, s32[]{:T"
            "(128)} %get-tuple-element.1254, bf16[32,1,2560]{2,0,1:T(8,128)(2,"
            "1)} %get-tuple-element.1255), kind=kOutput", None),
        "mlp": (
            "%fusion.736 = bf16[32,1,20480]{2,0,1:T(8,128)(2,1)S(1)} fusion("
            "bf16[8,2560,20480]{2,1,0:T(8,128)(2,1)} %get-tuple-element.1180, "
            "bf16[32,1,2560]{2,0,1:T(8,128)(2,1)S(1)} %x), kind=kOutput", None),
        "window q projection": (
            "%bitcast_add_fusion.16 = bf16[32,1,5120]{2,0,1:T(8,128)(2,1)S(1)}"
            " fusion(bf16[8,2560,5120]{2,1,0:T(8,128)(2,1)} %get-tuple-"
            "element.1179, bf16[32,1,2560]{2,0,1:T(8,128)(2,1)S(1)} %h, "
            "bf16[5120]{0:T(1024)(128)(2,1)S(1)} %bias), kind=kOutput", None),
        "head": (
            "%fusion.508 = f32[200064,32]{0,1:T(8,128)} fusion(bf16[200064,"
            "2560]{1,0:T(8,128)(2,1)} %params__embed____tokens__.1, bf16[32,1,"
            "2560]{2,0,1:T(8,128)(2,1)} %get-tuple-element.1338), kind=kOutput",
            None),
    }
    for what, (text, owner) in texts.items():
        found = [name for name, ops in selectors.items()
                 if re.search(ops, text)]
        assert found == ([owner] if owner else []), (what, found)


def op_texts() -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "phi_op_texts.json")) as f:
        return json.load(f)


def at_width(text: str, width: int) -> str:
    """The printed operation at another width of the step's table: the
    chip's run had 2,048 positions a row, gathered as 32 x 128 = 4,096
    blocks; no other number of the decode program's text is either."""
    return re.sub(r"\b(2048|4096)\b", lambda m: str(
        width * int(m.group(1)) // 2048), text)


@pytest.mark.parametrize("width", [1024, 2048, 4096])
def test_the_selectors_match_the_v5es_own_text_at_every_width(width):
    """PR 53: each operation the v5e printed for the two programs in a
    traced run of this cell is owned by the selector of its part and by
    no other, at the half width the traced window's steps ran at and at
    the quarter and the whole (`shared_kv_time_share.phi` spelt 4096
    until PR 53 and saw the whole-width steps alone: 5.3; the ring is
    656 positions and `window_kv_time_share.phi` spelt 560: 0.0; ledger,
    PR 52)."""
    cell = per_layer()
    owners = {name: cell[name + "_time_share.phi"]["ops"]
              for name in ("shared_kv", "window_kv", "ssm")}
    texts = op_texts()
    seen = []
    for program in ("decode_step", "prefill_chunk"):
        for op in texts[program]:
            text = at_width(op["text"], width) \
                if program == "decode_step" else op["text"]
            seen.append((program, op["owner"]))
            for name, ops in owners.items():
                assert bool(re.search(ops, text)) == (name == op["owner"]), \
                    (name, text)
    for owner in ("shared_kv", "window_kv", "ssm", None):
        assert ("decode_step", owner) in seen
    # The chunk's view of the pool is one row's ([1,1024,1280]) and is
    # nobody's; its writes into the rings are the window's.
    assert ("prefill_chunk", "shared_kv") not in seen
    assert ("prefill_chunk", "window_kv") in seen
    assert f"bf16[32,{width},1280]" in at_width(
        texts["decode_step"][0]["text"], width)
    # A ring of another length is still a ring, and never the pool.
    longer = texts["decode_step"][7]["text"].replace("656", "784")
    assert "bf16[8,32,784,1280]" in longer
    assert re.search(owners["window_kv"], longer)
    assert not re.search(owners["shared_kv"], longer)
