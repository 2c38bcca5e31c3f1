"""What the files that compile for a DESCRIBED ``v5e:2x2`` share
(``test_chip_compile.py`` and its siblings by cell family, ``_paged``,
``_hybrid``, ``_block`` and ``_latent``: a file each, so that ``--dist
loadfile`` spreads 67 compiles that share nothing over the workers): the
fixtures that describe the topology, imported by name into each file
(module-scoped there, never ``autouse`` and not in ``conftest.py``:
guide on-chip-measurement, 2.3), and the helpers more than one file
uses. The TPU compiler is installed here and refuses what the chip
would refuse (a slice off the tiling, too much VMEM), which interpret
mode never notices. Nothing runs, so these say nothing about results or
times. Skipped where the topology cannot be described."""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="module")
def v5e_devices():
    """The four chips of a described v5e:2x2. The persistent
    compilation cache is off around these compiles: an entry written
    for a described device cannot be read back without the chip, and
    the next run would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e_chip(v5e_devices):
    """Sharding on one chip of the described v5e:2x2."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_devices[0])


# A line of a compiled program's text that only hands a buffer on: the
# entry's parameter, a loop's tuple and its elements, a computation's head.
HANDS_ON = re.compile(
    r" (parameter|get-tuple-element|tuple|while)\(|^ENTRY |^%|^HloModule")


@pytest.fixture
def compiled_kernels(monkeypatch):
    """``default_backend()`` is the CPU during a deviceless compile, and
    a pallas kernel asks it whether to interpret: steered here, in the
    test, to the kernel the chip's compiler takes."""
    from ray_tpu._private import jax_compat

    monkeypatch.setattr(jax_compat, "interpret_kernels", lambda: False)


def assert_experts_reach_the_kernel_whole(text, stack, calls):
    """``text``: a compiled serving program of a model whose expert
    layers' ``w_gate`` is stacked ``stack`` = (layers, E, H, M). It
    holds ``calls`` calls of ``ops/grouped_expert_ffn.py`` (one an
    expert layer of a scan's body), each handed the three stacked
    tensors WHOLE; and nothing else takes an expert tensor, a layer of
    it or a copy of it in any dtype: what else names one only hands it
    on (the entry's parameter, a loop's tuple and its elements)."""
    layers, e, h, m = stack
    lines = text.splitlines()
    kernel = [line for line in lines
              if "custom-call(" in line and "grouped_expert_ffn" in line]
    assert len(kernel) == calls
    for line in kernel:
        operands = line.split("operand_layout_constraints={")[1]
        assert operands.count(f"bf16[{layers},{e},{h},{m}]{{") == 2
        assert operands.count(f"bf16[{layers},{e},{m},{h}]{{") == 1
    tensor = re.compile(rf"\[(\d+,)?{e},({h},{m}|{m},{h})\]")
    assert [line[:200] for line in lines
            if tensor.search(line) and line not in kernel
            and not HANDS_ON.search(line)] == []


def projection_layers_made(text, config) -> list:
    """The instructions of a compiled serving program that MAKE a layer
    of the stacked query, key and value weights, in any layout: outside
    every fused computation (the entry, the layers' loop: what the
    chip runs as an operation of its own), a result in bfloat16 with the
    hidden size among its dimensions and as many elements as a layer of
    ``wq``, of ``wk`` or of the three side by side. A layer of ``wo``
    has ``wq``'s count and is told by its order ([H, D, E]: its
    asynchronous slice is the one such line these programs hold); what
    only hands a buffer on, or names it anew (``bitcast``), makes
    nothing. A layer sliced INSIDE its product's fusion, as the
    feed-forward's weights are, has no line here."""
    e, d = config.hidden_size, config.head_dim
    h, kv = config.num_heads, config.num_kv_heads
    sizes = {e * heads * d for heads in (h, kv, h + 2 * kv)}
    fused = set(re.findall(r"calls=%([\w\-.]+)", text))
    made, inside = [], None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w\-.]+) \(", line)
        if head:
            inside = head.group(2)
        result = re.match(r"\s*(ROOT )?%[\w\-.]+ = bf16\[([\d,]+)\]\S* "
                          r"([\w\-]+)\(", line)
        if inside in fused or not result or result.group(3) == "bitcast" \
                or HANDS_ON.search(line):
            continue
        dims = [int(n) for n in result.group(2).split(",") if n != "1"]
        if e in dims and math.prod(dims) in sizes and dims != [h, d, e]:
            made.append(line.strip()[:200])
    return made


def kernel_calls(text, kernel: str) -> list:
    """The calls of one of ``ops/``'s kernels in a compiled program's
    text, by the call's line: a program's table of source files may name
    the kernel's own file or ``tests/test_paged_kv_attention.py`` where
    another file ran first on this worker and a cached trace carries its
    frames (under ``--dist loadfile`` which files share a worker changes
    with every file a PR adds: PR 60 met Mistral's programs naming
    ``grouped_expert_ffn.py`` so)."""
    return [line for line in text.splitlines()
            if "custom-call(" in line and kernel in line]


def pallas_calls(text) -> list:
    """Every call of a pallas kernel in a compiled program's text,
    whatever the kernel is called: the chip's compiler spells each
    ``custom_call_target="tpu_custom_call"``."""
    return [line for line in text.splitlines()
            if "custom-call(" in line
            and 'custom_call_target="tpu_custom_call"' in line]


def kv_attention_calls(text) -> list:
    return kernel_calls(text, "paged_kv_attention")


def _sdar(num_layers=2):
    """``benchmark/configs/sdar-30b-a3b-serve-1chip.json`` as the
    harness builds it: SDAR-30B-A3B's widths, 2 of the cell's 7 layers."""
    from ray_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=151936, hidden_size=2048, intermediate_size=768,
        num_layers=num_layers, num_heads=32, num_kv_heads=4, head_dim=128,
        max_seq_len=2048, rope_theta=1e6, rms_norm_eps=1e-6,
        num_experts=128, experts_per_token=8, norm_topk_prob=True,
        qk_norm="head", block_length=4, denoising_steps=2,
        mask_token_id=151669)


def _memory_of(compiled) -> tuple:
    memory = compiled.memory_analysis()
    return (memory.alias_size_in_bytes, memory.temp_size_in_bytes,
            memory.argument_size_in_bytes)
