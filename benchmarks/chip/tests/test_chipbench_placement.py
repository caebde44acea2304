"""Where the reference's leaves sit in the program's tree.  The two
benchmark adapters get the tree and leaf keys of the layout they were
written for (every layer a repeat of the one pattern block, the
top-level leaves as they are); a pattern of two layer kinds fills and
reads back one stacked block each, and nested top-level leaves their
paths; a leaf that nothing fills or reads fails.  A tiny decoder of two
layer kinds with LayerNorm runs through the harness end to end and is
correct against its own plain reference."""

from __future__ import annotations

import json
import shutil
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import calibrate, check, model, run, spec
from benchmarks.chip.reference import common

import chipbench_tiny as tiny
from test_chipbench_reference import tiny_config

HERE = Path(__file__).resolve().parent


def old_to_program(ref, adapter):
    """The layout before adapters could place leaves: every layer stacked,
    keyed by layer 0's leaf names, into ``blocks[0]``."""
    block: dict = {}
    for name in ref["layers"][0]:
        path = adapter.LAYER_PATHS[name]
        node = block
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = jnp.stack([lp[name] for lp in ref["layers"]])
    out = {k: ref[k] for k in adapter.TOP_LEAVES}
    out["blocks"] = (block,)
    return out


def old_program_leaves(tree, n_layers, adapter):
    out = {k: tree[k] for k in adapter.TOP_LEAVES}
    block = tree["blocks"][0]
    for name, path in adapter.LAYER_PATHS.items():
        node = block
        for key in path:
            node = node.get(key) if isinstance(node, dict) else None
        if node is None:
            continue
        for i in range(n_layers):
            out[f"layers.{i}.{name}"] = node[i]
    return out


def same_tree(a, b) -> bool:
    return (jax.tree.structure(a) == jax.tree.structure(b)
            and all(x.dtype == y.dtype and np.array_equal(x, y)
                    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))))


@pytest.mark.parametrize("name", ["olmoe-1b-7b", "chatglm3-6b-32k"])
def test_default_placement_is_the_old_layout(name):
    bench = spec.Benchmark()
    cfg, adapter = tiny_config(name), bench.adapter(name)
    reference = bench.reference(name)
    ref = reference.init_params(common.seed_key(3), cfg)
    n = len(ref["layers"])
    period = len(model.arch_config(cfg, reference, adapter).pattern)
    tree = model.to_program(ref, adapter, period)
    assert same_tree(tree, old_to_program(ref, adapter))
    got = model.program_leaves(tree, n, adapter)
    old = old_program_leaves(tree, n, adapter)
    assert list(got) == list(old)
    assert all(np.array_equal(got[k], old[k]) for k in old)
    assert sorted(got) == sorted(common.leaf_names(ref))
    sq = model.program_sq_norms(tree, n, adapter)
    assert all(float(sq[k]) == float(jnp.sum(jnp.square(old[k])))
               for k in old)


def layer(seed: int, names) -> dict:
    return {k: jnp.full((2, 3), seed + j, jnp.float32)
            for j, k in enumerate(names)}


#: a LayerNorm at the top, and two pattern positions (the second with a
#: router) repeated twice
HAND = types.SimpleNamespace(
    TOP_PATHS={"embed": ("embed",), "final_norm_w": ("final_norm", "w"),
               "final_norm_b": ("final_norm", "b")},
    LAYER_PATHS={"norm": ("norm1",), "wq": ("mixer", "wq"),
                 "w1": ("ffn", "w1"), "router": ("ffn", "router")})
DENSE, MOE = ("norm", "wq", "w1"), ("norm", "wq", "w1", "router")


def hand_reference() -> dict:
    kinds = [DENSE, MOE, DENSE, MOE]
    return {"embed": jnp.arange(6.0).reshape(3, 2),
            "final_norm_w": jnp.ones(2), "final_norm_b": jnp.zeros(2),
            "layers": [layer(10 * i, k) for i, k in enumerate(kinds)]}


def hand_target(ref) -> dict:
    def block(lp):
        out = {"norm1": lp["norm"], "mixer": {"wq": lp["wq"]},
               "ffn": {"w1": lp["w1"]}}
        if "router" in lp:
            out["ffn"]["router"] = lp["router"]
        return out

    def stack(*layers):
        return jax.tree.map(lambda *x: jnp.stack(x), *map(block, layers))

    L = ref["layers"]
    return {"embed": ref["embed"],
            "final_norm": {"w": ref["final_norm_w"],
                           "b": ref["final_norm_b"]},
            "blocks": (stack(L[0], L[2]), stack(L[1], L[3]))}


def test_placement_round_trip():
    ref = hand_reference()
    tree = model.to_program(ref, HAND, 2)
    assert same_tree(tree, hand_target(ref))
    got = model.program_leaves(tree, len(ref["layers"]), HAND)
    names = common.leaf_names(ref)
    assert sorted(got) == sorted(names)
    for name, leaf in zip(names, jax.tree.leaves(ref)):
        assert np.array_equal(got[name], leaf), name
    sq = jax.jit(lambda t: model.program_sq_norms(t, 4, HAND))(tree)
    assert float(sq["layers.3.router"]) == float(
        jnp.sum(jnp.square(ref["layers"][3]["router"])))


def _with(**changes):
    return types.SimpleNamespace(**{**vars(HAND), **changes})


@pytest.mark.parametrize("adapter, period, ref_change, error", [
    # a reference leaf with no place in the program's tree
    (_with(LAYER_PATHS={k: v for k, v in HAND.LAYER_PATHS.items()
                        if k != "router"}), 2, None, "router"),
    (_with(TOP_PATHS={"embed": ("embed",)}), 2, None, "final_norm"),
    # layers that do not repeat the pattern
    (HAND, 3, None, "pattern of 3"),
    # the layers of one pattern position differ in their leaves
    (HAND, 1, None, "differ"),
    (HAND, 2, lambda ref: ref["layers"][2].pop("w1"), "differ"),
], ids=["layer_leaf", "top_leaf", "not_a_repeat", "kinds_in_one_block",
        "leaf_missing"])
def test_unplaced_leaves_fail(adapter, period, ref_change, error):
    ref = hand_reference()
    if ref_change:
        ref_change(ref)
    with pytest.raises(ValueError, match=error):
        model.to_program(ref, adapter, period)


@pytest.mark.parametrize("change, n_layers", [
    # a program leaf that nothing reads
    (lambda t: t["final_norm"].update(extra=jnp.ones(2)), 4),
    (lambda t: t["blocks"][1]["ffn"].update(extra=jnp.ones(2)), 4),
    # a stacked row that no layer reads
    (None, 2),
    # a place the tree does not have
    (lambda t: t.pop("embed"), 4),
], ids=["top_leaf", "layer_leaf", "stacked_row", "missing_top"])
def test_unread_leaves_fail(change, n_layers):
    tree = model.to_program(hand_reference(), HAND, 2)
    if change:
        change(tree)
    with pytest.raises(ValueError, match="program leaf"):
        model.program_leaves(tree, n_layers, HAND)


SEED = 2**31 + 12345
HYBRID = "tiny-hybrid.smoke"
HYBRID_CONFIG = {
    "name": "tiny-hybrid", "source": "test", "hidden_size": 128,
    "intermediate_size": 96, "moe_intermediate_size": 64,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 64,
    "num_hidden_layers": 4, "num_experts": 4, "num_experts_per_tok": 2,
    "vocab_size": 200, "vocab_rows": 256, "layer_norm_eps": 1e-5,
    "rope_theta": 10000.0, "moe_capacity_factor": 1.25,
    "router_aux_loss_coef": 0.01,
}
#: limits set from CPU readings at this size, on SEED and seeds 1-3:
#: sound runs read loss 3.9e-4 to 1.1e-3, grad 4.4e-3 to 4.3e-2, delta
#: 1.4e-3 to 4.6e-3; the float8 control reads loss 3.9e-3 to 1.2e-2, grad
#: 0.092 to 0.12, delta 8.1e-3 to 1.4e-2; half a batch reads loss 0.022
#: to 0.027
HYBRID_LIMITS = {"loss_gap": {"limit": 0.0025}, "grad_gap": {"limit": 0.07},
                 "delta_gap": {"limit": 0.007}}


@pytest.fixture
def hybrid_root(tmp_path, monkeypatch):
    """The tiny checkout with a cell of the two-kind decoder added as
    files: its configuration, reference, adapter and limits."""
    root = tiny.make_root(tmp_path, HYBRID_LIMITS)
    home = root / "benchmarks" / "chip"
    cfg = json.loads((home / "configs" / "olmoe-1b-7b.json").read_text())
    (home / "configs" / "tiny-hybrid.json").write_text(json.dumps(
        {**HYBRID_CONFIG, "assumed": cfg["assumed"]}))
    shutil.copy(HERE / "tiny_hybrid_reference.py",
                home / "reference" / "tiny-hybrid.py")
    shutil.copy(HERE / "tiny_hybrid_adapter.py",
                home / "adapters" / "tiny-hybrid.py")
    (home / "limits" / f"{HYBRID}.json").write_text(
        json.dumps(HYBRID_LIMITS))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-hybrid", "source": "test",
                             "file": "benchmarks/chip/configs/tiny-hybrid.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": HYBRID, "config": "tiny-hybrid",
                               "traffic": "smoke", "chips": 1,
                               "why": "tiny"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    return root


def test_two_kinds_of_layer_run_correct(hybrid_root, capsys):
    bench = spec.Benchmark(hybrid_root)
    program = model.Program(bench.config("tiny-hybrid"),
                            bench.traffic("smoke"),
                            bench.reference("tiny-hybrid"),
                            bench.adapter("tiny-hybrid"))
    assert [s.ffn for s in program.arch.pattern] == ["dense", "moe"]
    assert program.arch.norm == "ln" and program.n_layers == 4
    assert run.main(["--workload", HYBRID, "--seed", str(SEED),
                     "--seconds", "1", "--trace", "0"],
                    root=hybrid_root) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"] == HYBRID_LIMITS[name]["limit"]


def test_two_kinds_of_layer_control_is_not_correct(hybrid_root, tmp_path):
    out = tmp_path / "cal.json"
    assert calibrate.main(["--workload", HYBRID, "--control-seeds",
                           str(SEED), "--out", str(out)],
                          root=hybrid_root) == 0
    (row,) = json.loads(out.read_text())
    assert row["kind"] == "control" and row["correct"] is False
    assert set(row["grad_leaves"]) >= {"final_norm_b", "layers.0.w1",
                                       "layers.1.router"}
    assert not check.verdict(row, HYBRID_LIMITS)
