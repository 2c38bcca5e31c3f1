"""What the chip's compiler says, asked without the chip; and the rules
that keep one process on each chip.

The first half compiles the train cells' main path (the flash kernels,
the fused norm, the whole train step) at real widths for a DESCRIBED
``v5e:2x2`` (``v5e_compile.py``, guide on-chip-measurement, 2.3); the
serve cells' programs have a file a family beside this one
(``test_chip_compile_paged.py``, ``_hybrid``, ``_block``, ``_latent``).
The second half are CPU tests of the rules of PR 21: who may see a
chip, where the compile cache lives, no CPU fallback on the measuring
path.
"""

import contextlib
import dataclasses
import importlib
import io
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from v5e_compile import v5e_chip, v5e_devices  # noqa: F401 — the fixtures

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ray_tpu.ops re-exports the function under the module's name.
fa = importlib.import_module("ray_tpu.ops.flash_attention")


def _flash_grad(seq, heads, kv_heads, head_dim, chip):
    """(jitted fwd+bwd of the compiled kernel, its argument shapes)."""
    q = jax.ShapeDtypeStruct((1, seq, heads, head_dim), jnp.bfloat16,
                             sharding=chip)
    kv = jax.ShapeDtypeStruct((1, seq, kv_heads, head_dim), jnp.bfloat16,
                              sharding=chip)

    def loss(q, k, v):
        # interpret=False to the kernel itself: default_backend() is
        # the CPU during a deviceless compile.
        out = fa.flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))), (q, kv, kv)


@pytest.mark.parametrize("seq,heads,kv_heads,head_dim", [
    (2048, 16, 8, 64),     # short heads of 64: half a lane tile
    (2048, 32, 32, 128),   # Llama-2-7B widths: chip_smoke.py's train phase
    (4096, 32, 8, 128),    # Llama-3-8B widths at the train cells' 4k
    (8192, 32, 8, 128),    # and at the llama3_8b preset's own max_seq_len
    (12288, 32, 8, 128),   # the longest the rule admits in tiles of 512
])
def test_flash_kernels_compile_for_v5e(v5e_chip, seq, heads, kv_heads,
                                       head_dim):
    fn, shapes = _flash_grad(seq, heads, kv_heads, head_dim, v5e_chip)
    compiled = fn.lower(*shapes).compile()
    # Forward, dq and dk/dv kernels all reached Mosaic.
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("side", ["rule", "compiler"])
def test_flash_backward_refused_beyond_12k(v5e_chip, monkeypatch, side):
    """L=12800 at 32/8 heads of 128, one tile of 512 past the longest
    admitted length (12288, which compiles above): the whole-sequence k
    and v blocks of the forward and dq kernels, and the q and dO blocks
    of dk/dv, no longer fit beside the score tiles. The rule refuses it
    at trace time, by name; and with the rule out of the way the chip's
    compiler refuses it too, so the rule is not refusing something that
    would have worked. (8192 was the bound while dk/dv kept o and a
    lane-padded lse resident too: PR 64.)"""
    if side == "rule":
        fn, shapes = _flash_grad(12800, 32, 8, 128, v5e_chip)
        with pytest.raises(ValueError) as info:
            fn.lower(*shapes)
        message = str(info.value)
        assert "12800" in message and "128" in message
        assert "16 MiB" in message and "12736" in message
        with pytest.raises(ValueError, match="dk/dv kernel.*12540"):
            fa.check_vmem_fit(12800, 128, jnp.bfloat16, backward=True)
        return
    monkeypatch.setattr(fa, "check_vmem_fit", lambda *a, **k: None)
    fn, shapes = _flash_grad(12800, 32, 8, 128, v5e_chip)
    with pytest.raises(Exception, match="(?i)vmem"):
        fn.lower(*shapes).compile()


def test_fused_rms_norm_compiles_for_v5e(v5e_chip):
    from ray_tpu.ops.fused import rms_norm

    x = jax.ShapeDtypeStruct((2048, 4096), jnp.bfloat16, sharding=v5e_chip)
    scale = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=v5e_chip)

    def loss(x, scale):
        return rms_norm(x, scale, interpret=False).astype(jnp.float32).sum()

    # The value too: the backward is plain JAX, so gradients alone
    # would leave the kernel dead code.
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        x, scale).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def sized_step(v5e_devices):
    """cell -> (the lines benchmark/sizing.py prints for a train cell,
    the text of its compiled step), each compiled once a module for the
    described chips. The kernels see the CPU backend during such a
    compile, so ``interpret_kernels`` is steered here."""
    from benchmark import sizing, spec
    from ray_tpu._private import jax_compat

    sized = {}

    def size(cell):
        if cell in sized:
            return sized[cell]
        texts, printed = {}, io.StringIO()
        report = sizing.report

        def keep_text(name, program, compiled):
            texts[program] = compiled.as_text()
            report(name, program, compiled)

        with pytest.MonkeyPatch.context() as patch, \
                contextlib.redirect_stdout(printed):
            patch.setattr(jax_compat, "interpret_kernels", lambda: False)
            patch.setattr(sizing, "report", keep_text)
            sizing.size_train(spec.load_cell(cell), v5e_devices)
        sized[cell] = ([json.loads(line)
                        for line in printed.getvalue().splitlines()],
                       texts["step"])
        return sized[cell]

    return size


@pytest.mark.parametrize("cell, ceiling_gib", [
    ("train-4k-1chip", 14.0), ("train-4k-fsdp2tp2", 14.85)])
def test_train_step_fits_a_v5e_with_the_attention_kept(
        sized_step, cell, ceiling_gib):
    """The two train configurations' whole steps, at the program's
    default remat_policy, for the described chips: arguments plus
    temporaries 13.79 GiB on one chip and 14.64 a chip on four (PR 64,
    whose lane-dense lse took 0.22 and 0.07 off PR 41's 14.00 and
    14.71; 13.17 and 13.07 under "full") of the 15.75 a chip gives. A
    PR that adds to what the layer scan keeps sees the memory here
    before the chip does; the ceilings are those figures and a margin of
    0.2."""
    lines, _ = sized_step(cell)
    step = next(x for x in lines if x.get("program") == "step")
    assert step["arguments_plus_temporaries_gib"] < ceiling_gib, step
    assert step["arguments_plus_temporaries_gib"] > 13.5, (
        "under what \"full\" takes: is the default policy in force?", step)
    assert lines[-1]["has_tpu_custom_call"], lines[-1]


OPERAND = re.compile(r"%([\w.\-]+)")


def test_train_step_keeps_the_mlp_input_in_fast_memory(sized_step):
    """What a change to the layer scan must not lose (PERF.md 7 (I),
    ROADMAP S2 (d')): in the one-chip step the backward's two
    weight-gradient products of gate and up (the fusions that write the
    stacked ``f32[layers, hidden, intermediate]`` gradient) read the
    MLP's 64 MiB input from fast memory, ``S(1)`` on the operand in the
    compiled text; on the chip its loss cost 3 to 6 ms a step (PR 41).
    And the kernels' lse crosses the step as lane-dense rows: no float32
    buffer of four or more dimensions ends ``,4096,1]`` (the column the
    chip padded to 128 lanes, 128 MiB a scan body, until PR 64)."""
    _, text = sized_step("train-4k-1chip")
    shapes = {}
    for line in text.splitlines():
        made = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ", line)
        if made:
            shapes.setdefault(made.group(1), made.group(2))
    products = [line for line in text.splitlines()
                if re.match(r"\s*%[\w.\-]+ = f32\[2,4096,14336\]\S* fusion\(",
                            line) and "kind=kOutput" in line]
    assert len(products) == 2, products
    for line in products:
        operands = OPERAND.findall(line.split(" fusion(", 1)[1].split(")")[0])
        inputs = [shapes[name] for name in operands
                  if shapes.get(name, "").startswith("bf16[2,4096,4096]")]
        assert len(inputs) == 1 and "S(1)" in inputs[0], (inputs, line[:300])
    assert not re.search(r"f32\[(\d+,){2,}4096,1\]", text)
    assert re.search(r"f32\[4,16,1,4096\]\{3,2,1,0:T\(1,128\)", text)


def test_vmem_rule_admits_the_main_path():
    for seq, head_dim in ((2048, 64), (2048, 128), (4096, 128),
                          (8192, 128), (12288, 64), (12288, 128)):
        fa.check_vmem_fit(seq, head_dim, jnp.bfloat16)
        fa.check_vmem_fit(seq, head_dim, jnp.bfloat16, backward=True)
    # A head of 64 pads to a lane tile: the same blocks, a little less
    # beside them (the compiler takes 12800 there and refuses 13312).
    fa.check_vmem_fit(12800, 64, jnp.bfloat16, backward=True)
    with pytest.raises(ValueError, match="sequence length 13312"):
        fa.check_vmem_fit(13312, 64, jnp.bfloat16)
    with pytest.raises(ValueError, match="sequence length 16384"):
        fa.check_vmem_fit(16384, 128, jnp.bfloat16)


def test_kernels_interpret_on_the_cpu_only(monkeypatch):
    """interpret=None means interpret on the CPU platform and compile on
    every other one, known or not."""
    from ray_tpu._private import jax_compat

    assert jax_compat.interpret_kernels() is True  # tests run on the CPU
    for backend in ("tpu", "gpu", "a-backend-nobody-heard-of"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert jax_compat.interpret_kernels() is False


# ---------------------------------------------------- one process per chip


def _fake_host(root, n_functions, vfio_groups=(), accel=0):
    """A sysfs/dev tree like a v5e host's: PCI functions of Google's
    vendor id, an IOMMU group each, and the device nodes given."""
    sys_root, dev_root = root / "sys", root / "dev"
    for i in range(n_functions):
        function = sys_root / "bus/pci/devices" / f"0000:00:{8 + i:02x}.0"
        function.mkdir(parents=True)
        (function / "vendor").write_text("0x1ae0\n")
        (function / "device").write_text("0x0063\n")
        group = sys_root / "kernel/iommu_groups" / str(i)
        group.mkdir(parents=True)
        (function / "iommu_group").symlink_to(group)
    # Another vendor's function with a VFIO group of its own.
    other = sys_root / "bus/pci/devices/0000:00:03.0"
    other.mkdir(parents=True)
    (other / "vendor").write_text("0x8086\n")
    (other / "device").write_text("0x0063\n")
    (dev_root / "vfio").mkdir(parents=True)
    (dev_root / "vfio/vfio").write_text("")
    for g in vfio_groups:
        (dev_root / "vfio" / str(g)).write_text("")
    for i in range(accel):
        (dev_root / f"accel{i}").write_text("")
    return str(sys_root), str(dev_root)


def test_chips_are_counted_from_device_nodes(tmp_path):
    from ray_tpu._private import accelerators

    # One chip of a four-chip host: four PCI functions, one VFIO group.
    roots = _fake_host(tmp_path / "one", 4, vfio_groups=(2,))
    assert accelerators.local_chips(*roots) == (1, "v5e")
    roots = _fake_host(tmp_path / "four", 4, vfio_groups=(0, 1, 2, 3))
    assert accelerators.local_chips(*roots) == (4, "v5e")
    roots = _fake_host(tmp_path / "accel", 4, accel=4)  # older driver
    assert accelerators.local_chips(*roots) == (4, "v5e")
    roots = _fake_host(tmp_path / "none", 0, vfio_groups=(7,))
    assert accelerators.local_chips(*roots) == (0, None)


def test_environment_names_the_slice_but_does_not_count(monkeypatch):
    """The one-chip machine's environment describes the four-chip host
    it was cut from; the runtime advertises what is really there, and a
    host without chips advertises none whatever the environment says."""
    from ray_tpu._private import accelerators

    monkeypatch.delenv("RAY_TPU_SKIP_TPU_DETECTION", raising=False)
    monkeypatch.delenv("RAY_TPU_NUM_TPU_CHIPS", raising=False)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setattr(accelerators, "local_chips", lambda: (1, "v5e"))
    assert accelerators.detect_resources() == {
        "TPU": 1.0, "TPU-v5litepod-4-head": 1.0}
    monkeypatch.setattr(accelerators, "local_chips", lambda: (0, None))
    monkeypatch.setattr(
        accelerators, "_gce_metadata",
        lambda *a, **k: pytest.fail("metadata asked on a chipless host"))
    assert accelerators.detect_resources() == {}


def test_detection_does_not_import_jax():
    """The detecting process is often not the computing one, and a
    process that initialised the TPU backend would hold the chip."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_SKIP_TPU_DETECTION",
                        "RAY_TPU_NUM_TPU_CHIPS")}
    script = ("import sys; from ray_tpu._private import accelerators; "
              "print(accelerators.detect_resources()); "
              "assert 'jax' not in sys.modules, 'detection imported jax'")
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_chip_leases_keep_one_owner():
    from ray_tpu._private.accelerators import ChipLeases, tpu_chip_demand
    from ray_tpu.exceptions import ChipOwnershipError

    assert tpu_chip_demand({"CPU": 1.0}, 4) == 0
    assert tpu_chip_demand({"TPU": 0.5}, 4) == 1  # a chip is not shared
    assert tpu_chip_demand({"TPU-v5litepod-4-head": 1.0}, 4) == 4

    leases = ChipLeases(4)
    first = leases.lease("a", 1, "actor a")
    second = leases.lease("b", 2, "actor b")
    assert not set(first) & set(second)
    with pytest.raises(ChipOwnershipError, match="1 of 4 are free"):
        leases.lease("c", 2, "actor c")
    with pytest.raises(ChipOwnershipError, match="leased to child"):
        leases.claim_in_process("thread actor t")
    leases.release("a")
    leases.release("b")
    leases.claim_in_process("thread actor t")  # now the host's, for life
    with pytest.raises(ChipOwnershipError, match="already holds"):
        leases.lease("d", 1, "actor d")


def test_worker_env_never_pins_a_tpu_worker_to_the_cpu():
    from ray_tpu._private import compile_cache
    from ray_tpu._private.worker_pool import _worker_env

    base = {"PATH": "/bin", "JAX_PLATFORMS": "tpu,cpu"}
    cpu_worker = _worker_env(base, None)
    assert cpu_worker["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in cpu_worker
    tpu_worker = _worker_env(base, [2])
    assert tpu_worker["JAX_PLATFORMS"] == "tpu,cpu"  # inherited, untouched
    assert tpu_worker["TPU_VISIBLE_CHIPS"] == "2"
    assert tpu_worker["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert tpu_worker["JAX_COMPILATION_CACHE_DIR"] == compile_cache.DEFAULT_DIR
    assert "JAX_PLATFORMS" not in _worker_env({"PATH": "/bin"}, [0, 1])
    kept = _worker_env({"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}, [0])
    assert kept["JAX_COMPILATION_CACHE_DIR"] == "/elsewhere"


def test_tpu_process_actors_get_disjoint_chips(monkeypatch):
    """End to end through the runtime: process actors that demand a TPU
    get a child that may see a chip — one each, never the same — and
    are not handed a CPU; a plain process actor stays pinned to it."""
    import ray_tpu

    monkeypatch.delenv("JAX_PLATFORMS")  # what the runtime itself sets
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=2)
    try:
        @ray_tpu.remote
        class Env:
            def read(self):
                return (os.environ.get("JAX_PLATFORMS"),
                        os.environ.get("TPU_VISIBLE_CHIPS"))

        on_tpu = [Env.options(process=True, resources={"TPU": 1}).remote()
                  for _ in range(2)]
        on_cpu = Env.options(process=True).remote()
        seen = ray_tpu.get([a.read.remote() for a in on_tpu], timeout=120)
        assert [platforms for platforms, _ in seen] == [None, None]
        assert sorted(chips for _, chips in seen) == ["0", "1"]
        assert ray_tpu.get(on_cpu.read.remote(), timeout=120) == ("cpu", None)
    finally:
        ray_tpu.shutdown()


def test_a_stalled_process_does_not_kill_its_own_node(monkeypatch):
    """A sibling process initialising a chip freezes the whole machine
    for seconds (seen on the v5e host: 4.8 s and 8.9 s). The in-process
    liveness check stood still with the beater, so heartbeats it finds
    stale right after prove nothing; a node whose beats really stopped
    is still found."""
    import threading
    import time

    from ray_tpu._private import recovery

    class Node:
        alive = True

        def __init__(self, node_id):
            self.node_id = node_id
            self.last_heartbeat = time.monotonic()

    class Gcs:
        def __init__(self):
            self.nodes = [Node("stalled-with-us"), Node("really-dead")]

        def list_nodes(self):
            return self.nodes

        def heartbeat(self, node_id):
            for node in self.nodes:
                if node.node_id == node_id:
                    node.last_heartbeat = time.monotonic()

    dead, real, jump = [], time.monotonic, [0.0]
    monkeypatch.setattr(time, "monotonic", lambda: real() + jump[0])
    monitor = recovery.NodeHealthMonitor(
        Gcs(), period_s=0.05, failure_threshold=3, on_node_dead=dead.append)
    try:
        time.sleep(0.2)
        jump[0] = 10.0  # every thread of the process lost ten seconds
        time.sleep(0.4)
        assert dead == []
        monitor.suppress("really-dead")
        deadline = real() + 5.0
        while not dead and real() < deadline:
            time.sleep(0.02)
        assert dead == ["really-dead"]
    finally:
        monitor.shutdown()
    assert threading.active_count() >= 1


# ------------------------------------------------ cache, bench, rehearsal


def test_compile_cache_has_one_home(monkeypatch, tmp_path):
    from ray_tpu._private import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable() == "/somewhere/else"
    assert updates == []  # JAX reads the variable; nothing set in code

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.chdir(tmp_path)
    first = compile_cache.enable()
    monkeypatch.chdir("/")
    assert compile_cache.enable() == first == os.path.join(REPO, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", first)] * 2


def test_native_library_is_named_by_its_sources():
    from ray_tpu import _native

    if _native.load() is None:
        pytest.skip("no toolchain: callers are on their Python paths")
    assert _native.status() in ("built", "reused")
    digest = os.path.basename(_native._lib_path())
    assert digest.startswith("libray_tpu_native-") and len(digest) == 37
    built = [f for f in os.listdir(os.path.dirname(_native._lib_path()))
             if f.endswith(".so")]
    assert built == [digest]  # nothing left over from another tree


@pytest.fixture(scope="module")
def smoke():
    """chip_smoke.py's phases as functions at its rehearsal size. The
    whole rehearsal (`python chip_smoke.py --rehearse`, about 20 s, with
    the kernels phase that tests/test_ops.py covers here) is a manual
    step of the verify skill: tier-1 has no time to spare for it."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    stop = chip_smoke.watch_compiles()
    yield chip_smoke
    stop()


CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_chip_smoke_accepts_a_cpu_only_to_rehearse(smoke):
    """Without --rehearse a CPU ends the run before any phase."""
    with pytest.raises(SystemExit, match="only with --rehearse"):
        smoke.phase_device(smoke.ARGS.parse_args([]))
    found = smoke.phase_device(smoke.ARGS.parse_args(["--rehearse"]))
    assert found["platform"] == "cpu"
    with pytest.raises(SystemExit):  # the driver never gives --chips
        smoke.ARGS.parse_args(["--chips", "2"])


def test_chip_smoke_train_and_serve_phases(smoke, capsys):
    """JaxTrainer and LLMEngineServer through the entry points the real
    run uses, with the CPU declared as the one chip."""
    import ray_tpu

    # The virtual mesh has 8 devices here; the batch must split 8 ways.
    sz = dataclasses.replace(smoke.sizes(True), batch=8)
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        smoke.phase_train(sz, 0, CPU_DEVICE)
        smoke.phase_serve(sz, 0, CPU_DEVICE)
    finally:
        ray_tpu.shutdown()
    out = capsys.readouterr().out
    assert "step_programs=1 compiles_after_first_step=0" in out
    assert "greedy continuation equals the reference argmax" in out


@pytest.mark.parametrize("config", ["olmoe-1b-7b-serve-1chip",
                                    "mistral7b-serve-1chip"])
def test_chip_smoke_paged_logits_mode(smoke, capsys, config):
    """``--paged-logits <configuration>`` at the file's rehearsal size:
    the engine's two programs against the configuration's own plain
    reference, a sparse one with its expert choices compared and its
    long context, a dense one without."""
    smoke.phase_paged_logits(
        os.path.join(REPO, "benchmark", "configs", config + ".json"), 7,
        True, CPU_DEVICE)
    out = capsys.readouterr().out
    assert "logits of the paged programs against the float32 reference" in out
    sparse = config.startswith("olmoe")
    assert ("expert_choices=0 " not in out) == sparse
    assert ("sequences=[9, 15, 23, 64]" in out) == sparse
