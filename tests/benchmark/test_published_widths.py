"""Every configuration keeps what its source publishes. What a source
publishes is one data file, ``<path>/published/<model>.json`` under one
of ``BENCHMARK.json``'s ``paths``: a PR that adds a model of another
family adds such a file beside its configuration and edits nothing (the
last test here does exactly that, in a temporary directory, for a
family with none of a dense GQA decoder's keys). Nothing here is a
measurement."""

import glob
import json
import math
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# What the contract never lets a configuration cut, by the key's name;
# no published file may offer such a key under `share` or `cut`.
WIDTH_LIKE = re.compile(r"(_dim|_rank|_size|_width|_factor|_per_tok"
                        r"|_per_token)$")
# `cut` is for depth, positions and dtype only; a count of leading
# dense layers (`first_k_dense_replace`) is depth too (PR 54).
MAY_BE_CUT = re.compile(r"layer|depth|position|context|dtype|dense_replace")
FLOOR_EXPERTS, FLOOR_VOCABULARY_SHARE = 8, 8  # model-configs guide, 4


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def published_for(source: str, roots: list) -> dict:
    """The one published file whose URL the configuration's ``source``
    starts with."""
    found = [path for root in roots
             for path in sorted(glob.glob(os.path.join(root, "published",
                                                       "*.json")))
             if source.startswith(load(path)["source"])]
    assert found, (
        f"no file under {[os.path.join(r, 'published') for r in roots]} has "
        f"a `source` that {source!r} starts with: the PR that adds this "
        "configuration adds benchmark/published/<model>.json beside it, "
        "with `source` (the URL), `widths`, `share`, `cut` and `built` as "
        "the files there have them")
    assert len(found) == 1, f"{source!r} matches more than one of {found}"
    return load(found[0])


def same(a, b) -> bool:
    """Equal, and not by ``False == 0`` or ``None``'s grace."""
    if a is None or b is None or isinstance(a, bool) or isinstance(b, bool):
        return a is b
    return a == b


def check_configuration(entry: dict, benchmark_json: str) -> None:
    """One entry of ``configs`` against its source's published file."""
    base = os.path.dirname(os.path.abspath(benchmark_json))
    bench = load(benchmark_json)
    assert any(entry["file"].startswith(p + "/") for p in bench["paths"])
    assert 1 <= len(entry["source"]) <= 200
    config = load(os.path.join(base, entry["file"]))
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    reduced = set(entry["reduced"])
    published = published_for(entry["source"], spec.roots_of(benchmark_json))
    widths, share, cut, built = (published[k] for k in (
        "widths", "share", "cut", "built"))

    # The published file itself offers no width for cutting.
    for key in list(share) + list(cut):
        assert key == "vocab_size" or not WIDTH_LIKE.search(key), key
        assert key not in widths
    assert all(MAY_BE_CUT.search(key) for key in cut), sorted(cut)

    for key, value in widths.items():
        assert key in config, f"{entry['file']} leaves out the width {key}"
        assert same(config[key], value), \
            f"{key}: {config[key]!r}, published {value!r}"
        assert key not in reduced, f"`reduced` names the width {key}"
    for key in reduced:
        assert NAME.match(key)
        assert key in share or key in cut, (
            f"`reduced` names {key}: only depth, positions, dtype "
            f"({sorted(cut)}) and a chip's share of {sorted(share)} "
            "may be cut")
        assert key in config

    for key, count in share.items():
        if key not in reduced:
            assert same(config[key], count), key
            continue
        # One chip's share of a stated deployment, no smaller than the
        # guide's floors, with the published count beside it.
        floor = math.ceil(count / FLOOR_VOCABULARY_SHARE) \
            if key == "vocab_size" else min(FLOOR_EXPERTS, count)
        assert floor <= config[key] <= count, \
            f"{key}: {config[key]} held here, floor {floor} of {count}"
        assert config["published"][key] == count
        assert isinstance(config["deployment"], str) and config["deployment"]
    for key, value in cut.items():
        if key in config and key not in reduced:
            assert same(config[key], value), key

    def build(block: dict):
        model = spec.build_model_config(block)
        for attribute, key in built.items():
            assert getattr(model, attribute) == block[key], (attribute, key)
        return model

    build(config)
    # The rehearsal block is laid over cleanly, and is small.
    tiny = spec.rehearsed(config, True)
    assert "rehearsal" not in tiny and tiny["hidden_size"] <= 128
    build(tiny)


BENCHMARK_JSON = os.path.join(REPO, "BENCHMARK.json")
CONFIGS = load(BENCHMARK_JSON)["configs"]


@pytest.mark.parametrize("entry", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_configuration_keeps_what_its_source_publishes(entry):
    check_configuration(entry, BENCHMARK_JSON)


def test_the_two_published_files_say_what_the_first_tables_said():
    """``MISTRAL`` and ``PUBLISHED``, the tables of PR 22's and PR 25's
    tests of this purpose, which went with them."""
    roots = spec.roots_of(BENCHMARK_JSON)
    mistral = published_for(
        "https://huggingface.co/mistralai/Mistral-7B-v0.3/blob/main/"
        "config.json", roots)
    assert {**mistral["widths"], **mistral["share"]} == {
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "head_dim": 128, "vocab_size": 32768, "rope_theta": 1e6,
        "rms_norm_eps": 1e-5, "sliding_window": None,
        "tie_word_embeddings": False}
    olmoe = published_for(
        "https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/"
        "main/config.json", roots)
    assert {**olmoe["widths"], **olmoe["share"]} == {
        "hidden_size": 2048, "intermediate_size": 1024,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "head_dim": 128, "vocab_size": 50304, "rope_theta": 10000,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
        "num_experts": 64, "num_experts_per_tok": 8}
    assert set(olmoe["share"]) == {"vocab_size", "num_experts"}
    assert set(mistral["share"]) == {"vocab_size"}
    for file in (mistral, olmoe):
        assert set(file["built"]) >= {"hidden_size", "num_heads",
                                      "num_kv_heads", "head_dim",
                                      "rope_theta", "vocab_size"}


# A made-up third family: state-space and windowed layers, no rotary
# base, no `head_dim`, no `num_key_value_heads`; tied embeddings, a
# window that is a number, and a vocabulary of which one chip of eight
# holds its eighth.
THIRD_SOURCE = "https://example.org/made-up/hybrid-3b/blob/main/config.json"
THIRD_PUBLISHED = {
    "name": "made-up-hybrid-3b",
    "source": "https://example.org/made-up/hybrid-3b",
    "widths": {"hidden_size": 2560, "intermediate_size": 10240,
               "num_attention_heads": 40, "state_size": 16,
               "sliding_window": 512, "layer_norm_eps": 1e-5,
               "tie_word_embeddings": True},
    "share": {"vocab_size": 200064},
    "cut": {"num_hidden_layers": 32, "max_position_embeddings": 262144,
            "torch_dtype": "bfloat16"},
    "built": {"hidden_size": "hidden_size", "mlp": "intermediate_size",
              "heads": "num_attention_heads", "window": "sliding_window",
              "vocab_size": "vocab_size", "depth": "num_hidden_layers"},
}
THIRD_CONFIG = {
    "name": "hybrid-3b-serve-1chip", "kind": "serve", "source": THIRD_SOURCE,
    "hidden_size": 2560, "intermediate_size": 10240,
    "num_attention_heads": 40, "state_size": 16, "sliding_window": 512,
    "layer_norm_eps": 1e-5, "tie_word_embeddings": True,
    "num_hidden_layers": 8, "max_position_embeddings": 4096,
    "torch_dtype": "bfloat16", "vocab_size": 25008,
    "reduced": ["num_hidden_layers", "max_position_embeddings",
                "vocab_size"],
    "published": {"vocab_size": 200064},
    "deployment": "each layer shared by eight chips: this one holds an "
                  "eighth of the vocabulary's rows",
    "builder": {"path": "types.SimpleNamespace",
                "from_keys": {"hidden_size": "hidden_size",
                              "mlp": "intermediate_size",
                              "heads": "num_attention_heads",
                              "window": "sliding_window",
                              "vocab_size": "vocab_size",
                              "depth": "num_hidden_layers"}},
    "rehearsal": {"hidden_size": 128, "intermediate_size": 256,
                  "num_attention_heads": 4, "num_hidden_layers": 2,
                  "vocab_size": 256, "sliding_window": 8},
}


def third_family(tmp_path, config=None, published=THIRD_PUBLISHED):
    """BENCHMARK.json with a third configuration and a cell for it, in
    a directory that holds only the new files."""
    config = config or THIRD_CONFIG

    def dump(obj, *parts):
        path = tmp_path.joinpath(*parts)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(obj))

    if published is not None:
        dump(published, "benchmark", "published", "made-up-hybrid-3b.json")
    dump(config, "benchmark", "configs", config["name"] + ".json")
    bench = load(BENCHMARK_JSON)
    bench["paths"] = ["benchmark"]
    entry = {"name": config["name"], "source": config["source"],
             "file": f"benchmark/configs/{config['name']}.json",
             "reduced": config["reduced"], "why": "a test's"}
    bench["configs"].append(entry)
    cell = config["name"] + ".longgen-closed"
    bench["workloads"].append({
        "name": cell, "config": config["name"], "traffic": "longgen-closed",
        "chips": 1, "why": "a test's"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "serve-longgen-closed" in metric.get("workloads", []):
            metric["workloads"].append(cell)
    dump(bench, "BENCHMARK.json")
    return entry, str(tmp_path / "BENCHMARK.json"), cell


def test_a_third_family_is_added_as_new_files_only(tmp_path):
    before = {p: os.path.getmtime(p) for p in glob.glob(
        os.path.join(REPO, "benchmark", "**", "*.json"), recursive=True)}
    entry, benchmark_json, cell = third_family(tmp_path)
    assert not {"rope_theta", "head_dim", "num_key_value_heads"} \
        & set(THIRD_CONFIG)
    assert THIRD_CONFIG["vocab_size"] * 8 == 200064
    check_configuration(entry, benchmark_json)
    # The harness finds the new cell's files: the new ones there, the
    # traffic mix and the readers here.
    loaded = spec.load_cell(cell, benchmark_json)
    assert loaded.config["name"] == entry["name"]
    assert loaded.traffic["generator"] == "closed_clients"
    assert {p: os.path.getmtime(p) for p in before} == before


@pytest.mark.parametrize("change, message", [
    (lambda c, p: c.update(intermediate_size=8192),
     "intermediate_size: 8192, published 10240"),
    (lambda c, p: c.update(reduced=c["reduced"] + ["sliding_window"]),
     "`reduced` names the width sliding_window"),
    (lambda c, p: c.update(vocab_size=25007),
     "vocab_size: 25007 held here, floor 25008 of 200064"),
    (lambda c, p: c.update(reduced=c["reduced"] + ["state_size"]),
     "`reduced` names the width state_size"),
    (lambda c, p: c.pop("deployment"), "deployment"),
    (lambda c, p: p["cut"].update(hidden_size=2560), "hidden_size"),
    (None, "adds benchmark/published/<model>.json"),
], ids=["a-width-changed", "a-width-in-reduced", "vocabulary-under-an-eighth",
        "another-width-in-reduced", "no-deployment-stated",
        "a-width-offered-for-cutting", "no-published-file"])
def test_the_third_family_fails_where_it_should(tmp_path, change, message):
    config = json.loads(json.dumps(THIRD_CONFIG))
    published = json.loads(json.dumps(THIRD_PUBLISHED))
    if change is None:
        published = None
    else:
        change(config, published)
    entry, benchmark_json, _ = third_family(tmp_path, config, published)
    with pytest.raises((AssertionError, KeyError)) as failed:
        check_configuration(entry, benchmark_json)
    assert message in str(failed.value)
