"""Pass ``disarm-gates``: one module attribute per disarmed plane.

Every optional plane (tracing, perf, spill, fused execution, raw
framing, locality scheduling, speculation, chaos, lock witness) pays
for its disarmed state with exactly ONE module-attribute branch per
site — ``if perf_plane.PERF_ON:`` — never a per-hit config lookup
(``GLOBAL_CONFIG.x`` takes a lock and a dict probe per read). The
rules this pass enforces:

- a module-level ALL-CAPS ``*_ON`` assignment declares a gate; a gate
  name must be declared in exactly one module (two modules sharing
  ``PERF_ON`` would make ``from x import PERF_ON`` sites ambiguous);
- every declared gate is branched on somewhere in the tree (an
  unreferenced gate is a plane nothing can disarm);
- the plane's config knob is read ONLY in the gate's home module or
  inside init/boot/arming functions elsewhere — a knob read on a
  gated site means the site pays the config lock per hit;
- no single ``if`` test branches on two different gates (a site
  belongs to one plane; compound gating hides which knob disarms it).

``chaos.ACTIVE`` is grandfathered as the chaos plane's gate (the
``is not None`` idiom predates the ``*_ON`` convention).
"""

from __future__ import annotations

import ast

from ray_tpu._private.analysis import Finding

# knob in config._DEFAULTS -> (home module rel path, gate attribute).
KNOB_GATES: "dict[str, tuple[str, str]]" = {
    "tracing_enabled": ("ray_tpu/util/tracing.py", "TRACE_ON"),
    "perf_plane": ("ray_tpu/_private/perf_plane.py", "PERF_ON"),
    "spill_enabled": ("ray_tpu/_private/spill_manager.py", "SPILL_ON"),
    "fused_execution": ("ray_tpu/_private/node_executor.py",
                        "FUSED_ON"),
    "raw_framing": ("ray_tpu/_private/serialization.py", "RAW_ON"),
    "locality_aware_scheduling": ("ray_tpu/_private/scheduler.py",
                                  "LOCALITY_ON"),
    "speculation_enabled": ("ray_tpu/_private/speculation.py",
                            "SPEC_ON"),
    "lock_witness": ("ray_tpu/_private/lock_witness.py", "WITNESS_ON"),
    "driver_sharded_dispatch": ("ray_tpu/_private/dispatch_lanes.py",
                                "SHARD_ON"),
    "gcs_shards": ("ray_tpu/_private/gcs_shard.py", "SHARDS_ON"),
    "metrics_history": ("ray_tpu/_private/metrics_history.py",
                        "HISTORY_ON"),
    "chaos": ("ray_tpu/_private/chaos.py", "ACTIVE"),
}

# Functions allowed to read plane knobs outside the home module: the
# one-time arming/boot paths (Runtime init, daemon boot, module
# init_from_config hooks).
_ARMING_NAMES = ("init", "boot", "start", "enable", "arm",
                 "configure", "main", "_apply", "daemon", "run_")


def _gate_names() -> "set[str]":
    return {gate for _, gate in KNOB_GATES.values()}


def _declared_gates(sources) -> "dict[str, list[tuple[str, int]]]":
    """{gate name -> [(module rel, line)]} for module-level *_ON
    assignments."""
    out: dict[str, list[tuple[str, int]]] = {}
    for src in sources:
        for node in src.tree.body:
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign):
                targets = [node.target]
            for target in targets:
                if isinstance(target, ast.Name) \
                        and target.id.isupper() \
                        and target.id.endswith("_ON"):
                    out.setdefault(target.id, []).append(
                        (src.rel, node.lineno))
    return out


def _enclosing_funcs(tree) -> "list[tuple[int, int, str]]":
    spans = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            spans.append((node.lineno, node.end_lineno or node.lineno,
                          node.name))
    return spans


def _in_arming_function(spans, lineno: int) -> bool:
    for start, end, name in spans:
        if start <= lineno <= end \
                and any(tag in name.lower() for tag in _ARMING_NAMES):
            return True
    return False


def run(sources) -> "list[Finding]":
    findings: list[Finding] = []
    declared = _declared_gates(sources)
    known_gates = _gate_names()

    # Duplicate declarations (one gate name, several modules).
    for gate, where in sorted(declared.items()):
        if len(where) > 1:
            paths = ", ".join(f"{p}:{ln}" for p, ln in where)
            for path, line in where:
                findings.append(Finding(
                    "disarm-gates", path, line, f"dup.{gate}",
                    f"disarm gate {gate!r} declared in multiple "
                    f"modules ({paths}) — one plane, one gate, one "
                    f"home"))

    # Gate references: any Name/Attribute read of a gate name outside
    # its declaring assignment.
    referenced: set[str] = set()
    multi_gate: list[tuple[str, int, frozenset]] = []
    for src in sources:
        for node in ast.walk(src.tree):
            test = None
            if isinstance(node, (ast.If, ast.IfExp)):
                test = node.test
            elif isinstance(node, ast.While):
                test = node.test
            if test is None:
                continue
            gates_here = set()
            for sub in ast.walk(test):
                if isinstance(sub, ast.Attribute) \
                        and sub.attr in declared:
                    gates_here.add(sub.attr)
                elif isinstance(sub, ast.Name) and sub.id in declared:
                    gates_here.add(sub.id)
                # chaos.ACTIVE is the chaos gate.
                if isinstance(sub, ast.Attribute) \
                        and sub.attr == "ACTIVE":
                    gates_here.add("ACTIVE")
            referenced |= gates_here
            if len(gates_here - {"ACTIVE"}) > 1:
                multi_gate.append((src.rel, node.lineno,
                                   frozenset(gates_here)))

    for gate, where in sorted(declared.items()):
        if gate not in referenced and len(where) == 1:
            path, line = where[0]
            findings.append(Finding(
                "disarm-gates", path, line, f"unused.{gate}",
                f"disarm gate {gate!r} is never branched on — a plane "
                f"nothing can disarm (or a stale gate)"))

    for path, line, gates in multi_gate:
        ident = "multi." + "-".join(sorted(g for g in gates))
        findings.append(Finding(
            "disarm-gates", path, line, ident,
            f"one branch tests {len(gates)} disarm gates "
            f"({', '.join(sorted(gates))}) — a gated site belongs to "
            f"exactly one plane"))

    # Config-knob reads outside the home module / arming functions.
    for src in sources:
        if src.rel.startswith("ray_tpu/_private/analysis/"):
            continue
        spans = None
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Attribute)
                    and node.attr in KNOB_GATES):
                continue
            value = node.value
            is_config = (isinstance(value, ast.Name)
                         and "CONFIG" in value.id.upper()) \
                or (isinstance(value, ast.Attribute)
                    and "CONFIG" in value.attr.upper())
            if not is_config:
                continue
            home, gate = KNOB_GATES[node.attr]
            if src.rel == home or src.rel.endswith("/config.py"):
                continue
            if spans is None:
                spans = _enclosing_funcs(src.tree)
            if _in_arming_function(spans, node.lineno):
                continue
            findings.append(Finding(
                "disarm-gates", src.rel, node.lineno,
                f"knob.{node.attr}",
                f"config knob {node.attr!r} read outside its plane's "
                f"home module and outside an init/arming function — "
                f"gate the site on {gate} instead (one attribute "
                f"load, no config lock)"))
    return findings
