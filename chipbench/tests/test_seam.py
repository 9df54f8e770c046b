"""The seam between the harness and a configuration's tower: every
configuration's reference has a tower adapter, both export the contract
(``harness`` module docstring), a reference without an adapter fails in
set-up, and weights drawn from a seed are those drawn before the LIST
head moved out of the BERT reference."""
import hashlib
import importlib
import json
import pathlib
import sys

import numpy as np
import pytest

import jax

from chipbench import check, harness, system

BENCH = pathlib.Path(__file__).resolve().parents[1]
TINY = pathlib.Path(__file__).resolve().parent / "fixtures" / "tiny"
CONFIGS = sorted([*(BENCH / "configs").glob("*.json"),
                  *(TINY / "configs").glob("*.json")])
REFERENCE_EXPORTS = ("init_weights", "calibrate_router", "encode",
                     "mixing_weights", "router_logits", "step_table",
                     "score", "query_flops")
TOWER_EXPORTS = ("program_config", "program_params")
HARNESS_KEYS = ("vocab_size", "max_len", "n_clusters")


def load(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_every_configuration_brings_a_reference_and_a_tower(path):
    config = load(path)
    name = config["reference"]
    assert (BENCH / "references" / f"{name}.py").is_file()
    assert (BENCH / "towers" / f"{name}.py").is_file()
    ref = harness.reference_module(config)
    tower = system.tower_adapter(config)
    assert all(callable(getattr(ref, f, None)) for f in REFERENCE_EXPORTS)
    assert all(callable(getattr(tower, f, None)) for f in TOWER_EXPORTS)
    assert all(k in config["model"] for k in HARNESS_KEYS)


def test_the_reference_imports_nothing_of_the_program():
    for path in (BENCH / "references").glob("*.py"):
        text = path.read_text()
        assert "import repro" not in text and "from repro" not in text, path


def test_a_reference_without_a_tower_adapter_fails_in_setup(monkeypatch):
    # a reference module that exists, under a name with no adapter
    real = importlib.import_module("chipbench.references.list_dual_encoder")
    monkeypatch.setitem(sys.modules, "chipbench.references.headless", real)
    _, _, config, mix, _, _ = harness.cell_spec(TINY, "tiny.serve",
                                                TINY / "traffic")
    config = {**config, "reference": "headless"}
    with pytest.raises(LookupError, match="chipbench/towers/headless.py"):
        harness.Setup(jax, config, mix, seed=3, seconds=1.0)


def leaves_hash(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_weights_from_a_fixed_key_are_bit_identical():
    # hashes recorded from the reference before the head moved to
    # references/list_head.py
    config = load(TINY / "configs" / "tiny.json")
    ref = harness.reference_module(config)
    model = check.Frozen(config["model"])
    w = jax.jit(ref.init_weights, static_argnums=1)(
        jax.random.PRNGKey(20261018), model)
    assert len(jax.tree_util.tree_leaves(w)) == 31
    assert leaves_hash(w) == (
        "ae0da01afc865d8f57311cca54f5c3dfe41dc6713477487aa3875d0e8ebaee76")
    rng = np.random.default_rng(5)
    tok = rng.integers(0, model["vocab_size"],
                       (32, model["max_len"])).astype(np.int32)
    msk = np.arange(model["max_len"])[None] < rng.integers(
        2, model["max_len"], (32, 1))
    loc = rng.random((32, 2)).astype(np.float32)
    c = jax.jit(ref.calibrate_router, static_argnums=4)(w, tok, msk, loc,
                                                        model)
    assert leaves_hash(c) == (
        "d3c0858c6bb8bfad0a8667602fe0f97bb865c9d24f9b8b7abf606a80915270d7")
