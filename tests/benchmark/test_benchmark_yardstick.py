"""The benchmark's yardstick on the CPU: arithmetic, generators, counts
from shapes, the trace reduction, the plain reference, the contract of
``BENCHMARK.json``. Nothing here is a measurement."""

import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import (  # noqa: E402
    flops,
    peaks,
    serve_cell,
    spec,
    stats,
    trace_reduce,
    traffic_gen,
)

HERE = os.path.dirname(os.path.abspath(__file__))
MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "head_dim": 128, "vocab_size": 32768, "num_hidden_layers": 2}


def bench_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ arithmetic


@pytest.mark.parametrize("values, q, want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 90, 90.1),
    (list(range(1, 101)), 95, 95.05),
    ([7], 99, 7.0),
    ([10, 20], 25, 12.5),
])
def test_percentile_interpolates_between_ranks(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_quartile_distance_over_median():
    assert stats.spread([90, 100, 100, 100, 110]) == pytest.approx(0.0)
    assert stats.spread([80, 90, 100, 110, 120]) == pytest.approx(0.2)


# ------------------------------------------------------------ generators


def chat() -> dict:
    with open(os.path.join(REPO, "benchmark/traffic/chat-steady.json")) as f:
        return json.load(f)


def test_open_loop_reproduces_schedule_and_lengths_exactly():
    params = dict(chat(), rate_per_s=2.0)
    a = traffic_gen.open_poisson(params, 45.0, 7, 32768)
    b = traffic_gen.open_poisson(params, 45.0, 7, 32768)
    assert [(r.due_s, r.tokens, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.tokens, r.max_new_tokens) for r in b]
    assert len(a) == 90  # rate x seconds: every seed offers the same load
    assert all(0 <= r.due_s < 45.0 for r in a)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)


def test_a_fixed_schedule_is_replayed_whatever_the_seed():
    params = chat()
    assert "schedule_seed" in params
    a = traffic_gen.open_poisson(params, 50.0, 1, 32768)
    b = traffic_gen.open_poisson(params, 50.0, 2, 32768)
    assert [(r.due_s, len(r.tokens), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.tokens), r.max_new_tokens) for r in b]
    assert [r.tokens for r in a] != [r.tokens for r in b]


def test_another_seed_keeps_the_length_histogram():
    params = dict(chat(), rate_per_s=2.0)
    del params["schedule_seed"]  # the schedule follows --seed
    a = traffic_gen.open_poisson(params, 45.0, 1, 32768)
    b = traffic_gen.open_poisson(params, 45.0, 2, 32768)
    assert [r.due_s for r in a] != [r.due_s for r in b]
    for field in (lambda r: len(r.tokens), lambda r: r.max_new_tokens):
        assert sorted(map(field, a)) == sorted(map(field, b))
    prompts = sorted(len(r.tokens) for r in a)
    assert 16 <= prompts[0] and prompts[-1] <= 1024
    assert 110 <= prompts[len(prompts) // 2] <= 146  # median 128
    outputs = sorted(r.max_new_tokens for r in a)
    assert 8 <= outputs[0] and outputs[-1] <= 256
    assert 56 <= outputs[len(outputs) // 2] <= 72   # median 64


def test_quantiles_of_the_length_distributions():
    lognormal = {"dist": "lognormal", "median": 128, "sigma": 0.8,
                 "min": 16, "max": 1024}
    assert traffic_gen.quantile(lognormal, 0.5) == 128
    assert traffic_gen.quantile(lognormal, 0.0001) == 16
    assert traffic_gen.quantile(lognormal, 0.9999) == 1024
    # One sigma above the median: 128 * e^0.8 = 284.87
    assert traffic_gen.quantile(lognormal, 0.841344746) == 285
    uniform = {"dist": "uniform", "min": 32, "max": 128}
    assert [traffic_gen.quantile(uniform, u) for u in (0, 0.5, 1)] == \
        [32, 80, 128]
    with pytest.raises(ValueError):
        traffic_gen.quantile({"dist": "zipf"}, 0.5)


def test_closed_loop_clients_and_train_batches_are_seeded():
    params = {"clients": 3, "requests_per_client": 4,
              "prompt": {"dist": "uniform", "min": 4, "max": 12},
              "output": {"dist": "uniform", "min": 8, "max": 24}}
    a = traffic_gen.closed_clients(params, 2.0, 5, 256)
    b = traffic_gen.closed_clients(params, 2.0, 5, 256)
    assert [[r.tokens for r in c] for c in a] == \
        [[r.tokens for r in c] for c in b]
    assert [len(c) for c in a] == [4, 4, 4]
    # The first request of each client is cut to a different share.
    firsts = [c[0].max_new_tokens for c in a]
    assert len(set(firsts)) == 3 or max(firsts) <= 24
    train = {"batch": 2, "seq_len": 16}
    x = next(traffic_gen.train_batches(train, 3, 256))
    y = next(traffic_gen.train_batches(train, 3, 256))
    assert np.array_equal(x["tokens"], y["tokens"])
    assert x["tokens"].shape == (2, 16)
    assert np.array_equal(x["tokens"][:, 1:], x["targets"][:, :-1])
    gen = traffic_gen.train_batches(train, 3, 256)
    assert not np.array_equal(next(gen)["tokens"], next(gen)["tokens"])


def record(due, sent, arrivals, error=None, finished=True):
    request = traffic_gen.Request(0, 0.0, [1, 2], 4)
    return serve_cell.Record(request, due=due, sent=sent,
                             arrivals=list(arrivals), error=error,
                             finished=finished)


def test_ttft_counts_from_the_due_instant_and_gaps_stay_in_the_window():
    records = [
        # Due at 1.0, sent late at 1.2, first token at 1.5: 500 ms, not 300.
        record(1.0, 1.2, [1.5, 1.6, 1.8]),
        # Shed: as bad as the window is long.
        record(2.0, 2.0, [], error=RuntimeError("shed"), finished=False),
        # Due inside, last gap ends after the window closes.
        record(9.0, 9.0, [9.5, 10.5], finished=True),
        # Due before the window opened: no TTFT, but its gap counts.
        record(-1.0, -1.0, [-0.5, 0.25]),
    ]
    seen = serve_cell.reduce_window(records, 0.0, 10.0)
    assert seen["due"] == 3 and seen["failed_due"] == 1
    assert seen["ttft_ms"] == pytest.approx([500.0, 10000.0, 500.0])
    assert sorted(seen["gaps_ms"]) == pytest.approx([100.0, 200.0, 750.0])
    assert seen["lateness_ms"] == pytest.approx([200.0, 0.0, 0.0])
    assert seen["tokens"] == 5  # 1.5 1.6 1.8 9.5 0.25
    assert seen["tokens_per_s_mean"] == pytest.approx(0.5)
    assert seen["tokens_per_s"] == pytest.approx(0.5)  # under 3 blocks


def test_tokens_per_second_is_the_median_over_blocks_of_tokens():
    # 16 tokens every 0.1 s, with one stall of 5 s after the 40th step.
    times = [0.1 * step + (5.0 if step >= 40 else 0.0)
             for step in range(100) for _ in range(16)]
    assert serve_cell.block_rates(times[:64], 32) == \
        pytest.approx([160.0])  # one whole block after the first token
    seen = serve_cell.reduce_window([record(0.0, 0.0, times)], 0.0, 20.0, 256)
    assert seen["tokens"] == 1600
    assert seen["tokens_per_s_mean"] == pytest.approx(80.0)
    assert seen["tokens_per_s"] == pytest.approx(160.0)


# ------------------------------------------------- operations and bytes


def test_matmul_parameters_of_mistral_7b_by_hand():
    p = flops.matmul_params(MISTRAL)
    # wq 4096*4096 + wk, wv 2*4096*1024 + wo 4096*4096 + 3*4096*14336
    assert p["layer"] == 16777216 + 8388608 + 16777216 + 176160768
    assert round(p["layer"] / 1e6, 1) == 218.1
    assert p["head"] == 4096 * 32768 and round(p["head"] / 1e6, 1) == 134.2


def test_train_flops_per_token_by_hand():
    got = flops.train_flops_per_token(MISTRAL, 4096)
    dense = 6 * (2 * 218103808 + 134217728)
    attention = 6 * 2 * 32 * 128 * 4096  # causal: half of 12*L*h*d*seq
    assert got == dense + attention
    # The head is a large share at 2 layers and a small one at 32.
    assert flops.head_share(MISTRAL, 4096) == pytest.approx(0.2222, abs=1e-3)
    whole = dict(MISTRAL, num_hidden_layers=32)
    assert flops.head_share(whole, 4096) == pytest.approx(0.0175, abs=1e-3)


def test_flash_kernel_operations_and_bytes_by_hand():
    fwd = flops.flash_kernel_cost("fwd", 2, 4096, 32, 8, 128)
    # Two causal products of L*L*d each, per batch and head.
    assert fwd["flops"] == 2 * 2 * 32 * 4096 * 4096 * 128
    q, kv, lse = 2 * 4096 * 32 * 128 * 2, 2 * 4096 * 8 * 128 * 2, \
        2 * 4096 * 32 * 4
    assert fwd["bytes"] == q + 2 * kv + q + lse
    assert flops.flash_kernel_cost("dq", 2, 4096, 32, 8, 128)["flops"] \
        == 1.5 * fwd["flops"]
    dkv = flops.flash_kernel_cost("dkv", 2, 4096, 32, 8, 128)
    assert dkv["flops"] == 2 * fwd["flops"]
    assert dkv["bytes"] == 3 * q + 4 * kv + lse
    seconds, bound = flops.least_seconds(fwd, peaks.peaks("TPU v5 lite"))
    assert bound == "compute"
    assert seconds == pytest.approx(fwd["flops"] / 197e12)
    tiny = {"flops": 1.0, "bytes": 819e9}
    assert flops.least_seconds(tiny, peaks.peaks("TPU v5 lite")) == \
        (pytest.approx(1.0), "memory")


def test_an_unknown_device_has_no_peak():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v9 imaginary"):
        with pytest.raises(ValueError, match="no peaks known"):
            peaks.peaks(kind)


# ------------------------------------------------------- trace reduction


def events(*spans):
    out = [trace_reduce.Event(n, float(s), float(e), {}) for n, s, e in spans]
    out.sort(key=lambda e: (e.start_ns, -e.end_ns))
    return out


def test_union_self_time_and_breakdown_on_a_known_trace():
    ops = events(("while.1", 0, 100), ("fusion.1", 0, 30),
                 ("custom-call.2", 40, 70), ("all-reduce.3", 70, 100),
                 ("fusion.1", 150, 180), ("copy.4", 400, 410))
    trace_reduce.set_self_times(ops)
    by_name = {(e.name, e.start_ns): e.self_ns for e in ops}
    assert by_name[("while.1", 0.0)] == 10.0   # 100 - 30 - 30 - 30
    assert by_name[("custom-call.2", 40.0)] == 30.0
    modules = events(("jit_step(123)", 0, 100), ("jit_step(123)", 150, 180),
                     ("jit_other(9)", 400, 410))
    device = trace_reduce.Device(modules, ops)
    spans = events(("bench.fence", 190, 390))
    trace = trace_reduce.Trace({0: device}, spans)
    assert trace_reduce.busy_intervals(device) == \
        [[0.0, 100.0], [150.0, 180.0], [400.0, 410.0]]
    busy_s, window_s = trace_reduce.busy_and_window(trace)
    assert busy_s == pytest.approx(140e-9)
    assert window_s == pytest.approx(410e-9)
    assert trace_reduce.module_runs(device, "^jit_step") == [100.0, 30.0]
    assert trace_reduce.op_self_seconds(device, "custom-call") == \
        pytest.approx(30e-9)
    assert trace_reduce.op_self_seconds(device, "^(all-reduce|fusion)") == \
        pytest.approx(90e-9)
    shown = trace_reduce.breakdown(trace)
    assert shown["device_ops"][0] == ["fusion.1", pytest.approx(60e-9)]
    assert shown["idle_gaps"] == [
        ["bench.fence", pytest.approx(220e-9)],
        ["unattributed (after jit_step)", pytest.approx(50e-9)]]
    assert trace_reduce.busy_and_window(trace_reduce.Trace({}, [])) is None
    assert trace_reduce.breakdown(trace_reduce.Trace({}, [])) is None


def test_reduction_of_a_trace_recorded_on_the_v5e():
    """Eight steps of ``train-4k-1chip`` traced on one TPU v5e chip (my
    chip run, PR 22), kept beside this file: the names the reduction
    looks for are the ones the chip's profiler writes."""
    trace = trace_reduce.load(os.path.join(
        HERE, "data", "train-4k-1chip.v5e.xplane.pb"))
    assert sorted(trace.devices) == [0]
    device = trace_reduce.first_device(trace)
    runs = trace_reduce.module_runs(device, "^jit_step")
    assert len(runs) == 8
    assert stats.median(runs) / 1e6 == pytest.approx(277.285673)
    assert trace_reduce.module_runs(device, "^jit_decode_step") == []
    busy_s, window_s = trace_reduce.busy_and_window(trace)
    assert (busy_s, window_s) == pytest.approx((2.218207312, 2.218349518))
    # Self times add up to the busy time: nothing is counted twice.
    assert sum(e.self_ns for e in device.ops) / 1e9 == pytest.approx(busy_s)
    flash = trace_reduce.op_self_seconds(device, r" custom-call\(")
    assert flash == pytest.approx(0.214001176)
    assert trace_reduce.op_in_flight_seconds(
        device, r" (all-gather|all-reduce)(-start|-done)?\(") == 0.0
    assert {s.name for s in trace.host_spans} == \
        {"bench.fence", "bench.make_batch"}
    shown = trace_reduce.breakdown(trace)
    assert len(shown["device_ops"]) == 10
    assert shown["device_ops"][0][0].startswith("fusion.217 [fusion ")
    assert shown["idle_gaps"][0][0] == "bench.fence"
    run = {"trace": trace, "rehearse": False, "device_kind": "TPU v5 lite",
           "chips": 1, "traffic": {"batch": 2, "seq_len": 4096},
           "config": MISTRAL, "harness": {"train_tokens_per_s": 29500.0,
                                          "seq_len": 4096}}
    roots = [os.path.join(REPO, "benchmark")]

    def read(reader, **metric):
        return spec.load_module(roots, "readers", reader).read(metric, run)

    assert read("trace_program_ms", module="^jit_step") == \
        pytest.approx(277.285673)
    assert read("trace_op_share", module="^jit_step",
                ops=r" custom-call\(") == pytest.approx(9.647, abs=1e-3)
    assert read("trace_idle") == pytest.approx(0.00641, abs=1e-4)
    # Least time of fwd + dq + dk/dv in 2 layers x 8 steps over 0.214 s.
    least = 16 * (2 + 3 + 4) * 2 * 32 * 4096 * 4096 * 128 / 197e12
    assert read("flash_roofline", module="^jit_step",
                ops=r" custom-call\(") == pytest.approx(
        100 * least / 0.214001176)
    assert read("train_mfu") == pytest.approx(
        100 * 29500.0 * flops.train_flops_per_token(MISTRAL, 4096) / 197e12)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    run = {"trace": None, "memory": {}, "counters": {"decode_steps": 0},
           "harness": {}, "rehearse": True}
    roots = [os.path.join(REPO, "benchmark")]
    for name in ("trace_idle", "trace_program_ms", "trace_op_share",
                 "memory_peak", "train_mfu", "flash_roofline"):
        reader = spec.load_module(roots, "readers", name)
        assert reader.read({"module": "x", "ops": "y"}, run) is None
    counters = spec.load_module(roots, "readers", "counters")
    assert counters.read({"formula": "1 / decode_steps"}, run) is None
    assert counters.read({"formula": "missing + 1"}, run) is None
    run["counters"] = {"decode_tokens": 30, "decode_steps": 2,
                       "max_batch_size": 16}
    assert counters.read(
        {"formula": "100 * decode_tokens / (decode_steps * max_batch_size)"},
        run) == pytest.approx(93.75)
    lateness = spec.load_module(roots, "readers", "harness_percentile")
    run["harness"] = {"generator_lateness_ms": [1.0, 2.0, 3.0]}
    assert lateness.read({"series": "generator_lateness_ms",
                          "percentile": 50}, run) == 2.0


# ------------------------------------------------------------- reference


def test_plain_reference_agrees_with_llama_forward_at_tiny():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmark.reference import dense_decoder
    from ray_tpu.models import llama

    config = dataclasses.replace(
        llama.LlamaConfig.tiny(), num_kv_heads=2, dtype=jnp.float32,
        rope_theta=1e6)
    model = {"rms_norm_eps": config.rms_norm_eps,
             "rope_theta": config.rope_theta}
    params = llama.init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                config.vocab_size)
    got = dense_decoder.forward(params, tokens[:, :-1], model)
    want = llama.forward(params, tokens[:, :-1], config)
    # float32 on both sides: only the order of sums differs.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    loss = dense_decoder.loss(params, tokens[:, :-1], tokens[:, 1:], model)
    want_loss = llama.loss_fn(params, tokens[:, :-1], tokens[:, 1:], config)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)


# ---------------------------------------------------------- the contract

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_the_contract():
    bench = bench_json()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    assert 1 <= bench["run_seconds"] <= 51
    cells = {w["name"]: w for w in bench["workloads"]}
    assert sum(w["chips"] == 4 for w in cells.values()) <= \
        max(1, len(cells) // 4)
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) \
        == len(cells)
    configs = {c["name"] for c in bench["configs"]}
    assert configs == {w["config"] for w in cells.values()}
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert end_to_end["setup_s"]["bound"] == 0.1
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in end_to_end
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # Reported only where the metric it moves is.
        moved = end_to_end[m["moves"]].get("workloads", list(cells))
        assert set(m["workloads"]) <= set(moved)
        assert os.path.exists(os.path.join(
            REPO, "benchmark/metrics", m["name"] + ".json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for entry in bench["workloads"] + bench["configs"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for name, w in cells.items():
        reported = [m for m in bench["end_to_end"]
                    if name in m.get("workloads", [name])]
        assert len(reported) >= 2  # setup_s and at least one other
        assert any(name in m["workloads"] for m in bench["per_layer"])
        assert NAME.match(w["traffic"])


def test_configurations_keep_the_published_widths():
    bench = bench_json()
    for entry in bench["configs"]:
        assert entry["file"].startswith("benchmark/configs/")
        assert 1 <= len(entry["source"]) <= 200
        with open(os.path.join(REPO, entry["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == entry["reduced"]
        assert config["source"] == entry["source"]
        for key in entry["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank", "_size"))
        for key in ("hidden_size", "intermediate_size",
                    "num_attention_heads", "num_key_value_heads",
                    "head_dim", "vocab_size"):
            assert config[key] == MISTRAL[key]
        assert config["rope_theta"] == 1e6 and config["rms_norm_eps"] == 1e-5
        assert config["sliding_window"] is None
        assert config["tie_word_embeddings"] is False
        built = spec.build_model_config(config)
        assert (built.hidden_size, built.intermediate_size, built.num_heads,
                built.num_kv_heads, built.head_dim, built.vocab_size,
                built.num_layers) == (
            4096, 14336, 32, 8, 128, 32768, config["num_hidden_layers"])
        assert built.rope_theta == 1e6
        tiny = spec.rehearsed(config, True)
        assert tiny["hidden_size"] == 64 and "rehearsal" not in tiny


def test_every_cell_finds_its_files_by_name():
    for w in bench_json()["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["generator"] in traffic_gen.GENERATORS
        assert cell.chips == w["chips"]
        for metric in cell.per_layer:
            reader = spec.load_module(cell.roots, "readers",
                                      metric["reader"])
            assert callable(reader.read)
    with pytest.raises(SystemExit, match="no workload"):
        spec.load_cell("no-such-cell")


def test_the_rate_of_the_open_loop_is_a_number_in_its_file():
    assert isinstance(chat()["rate_per_s"], float)
    assert 0.1 < chat()["rate_per_s"] < 100
