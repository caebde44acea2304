"""The plain references against the program on the CPU, at a tiny size:
the loss, every gradient leaf and one AdamW update, all in float32."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import model, spec
from benchmarks.chip.reference import common

TINY = {
    "olmoe-1b-7b": (
        {"hidden_size": 128, "intermediate_size": 64, "num_attention_heads": 2,
         "num_key_value_heads": 2, "num_hidden_layers": 2, "num_experts": 4,
         "num_experts_per_tok": 2, "vocab_size": 200},
        {"head_dim": 64}),
    "chatglm3-6b-32k": (
        {"hidden_size": 128, "ffn_hidden_size": 192, "kv_channels": 32,
         "num_attention_heads": 4, "multi_query_group_num": 2,
         "num_layers": 2, "padded_vocab_size": 200},
        {}),
}
SEQ, ROWS = 64, 2


def tiny_config(name: str) -> dict:
    cfg = spec.Benchmark().config(name)
    published, assumed = TINY[name]
    cfg.update(published)
    for k, v in assumed.items():
        cfg["assumed"][k]["value"] = v
    cfg["departures"]["vocab_rows"]["as_run"] = 256
    return cfg


def batch(seed: int):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, 200, (ROWS, SEQ + 1), dtype=np.int32)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


@pytest.fixture(params=sorted(TINY))
def case(request):
    name = request.param
    cfg = tiny_config(name)
    bench = spec.Benchmark()
    ref, adapter = bench.reference(name), bench.adapter(name)
    params = ref.init_params(common.seed_key(7), cfg)
    return cfg, ref, adapter, params


def test_loss_and_grads_match(case):
    from repro.models import decoder

    cfg, ref, adapter, params = case
    arch = model.arch_config(cfg, ref, adapter)
    b = batch(3)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: decoder.loss_fn(
            p, arch, {k: jnp.asarray(v) for k, v in b.items()},
            compute_dtype=jnp.float32))(model.to_program(params, adapter))
        lr, gr = jax.value_and_grad(lambda p: ref.loss(
            p, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]), cfg,
            common.f32_matmul))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    names = common.leaf_names(gr)
    got = model.program_leaves(gp, arch.num_layers, adapter)
    assert sorted(got) == sorted(names)
    for name, g in zip(names, jax.tree.leaves(gr)):
        scale = float(jnp.max(jnp.abs(g))) or 1.0
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(g),
                                   atol=1e-4 * scale, err_msg=name)


def test_one_adamw_update_matches(case):
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init

    cfg, ref, adapter, params = case
    arch = model.arch_config(cfg, ref, adapter)
    opt = cfg["assumed"]["optimizer"]["value"]
    b = batch(4)
    with jax.default_matmul_precision("highest"):
        step = jax.jit(make_train_step(
            arch, lr=opt["lr"], weight_decay=opt["weight_decay"],
            clip=opt["clip_norm"], compute_dtype=jnp.float32, microbatch=1))
        p_prog = model.to_program(params, adapter)
        new_prog, _, _ = step(p_prog, adamw_init(p_prog), b)
        grads = [jax.grad(lambda p: ref.loss(
            p, jnp.asarray(b["tokens"][i:i + 1]),
            jnp.asarray(b["labels"][i:i + 1]), cfg, common.f32_matmul))(params)
            for i in range(ROWS)]
        g = jax.tree.map(lambda a, c: (a + c) / 2, *grads)
        zeros = jax.tree.map(jnp.zeros_like, params)
        new_ref, _, _ = common.adamw_step(params, zeros, zeros, g, 1.0, opt)
    got = model.program_leaves(new_prog, arch.num_layers, adapter)
    for name, p in zip(common.leaf_names(new_ref), jax.tree.leaves(new_ref)):
        # one step moves each weight by about lr; allow a tenth of that
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(p),
                                   atol=0.1 * opt["lr"], err_msg=name)


def test_program_widths_are_the_published_ones():
    """The adapter of each configuration runs the widths that its
    published keys state."""
    bench = spec.Benchmark()
    for c in bench.spec["configs"]:
        cfg = json.loads((bench.root / c["file"]).read_text())
        ref = bench.reference(c["name"])
        n = ref.dims(cfg)
        arch = model.arch_config(cfg, ref, bench.adapter(c["name"]))
        assert (arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim,
                arch.num_layers, arch.vocab) == (
            n["d"], n["heads"], n["kv_heads"], n["head_dim"], n["layers"],
            n["vocab"])
        assert arch.padded_vocab == n["vocab_rows"]
        assert (arch.moe_d_ff or arch.d_ff) == n["ffn"]
        if "experts" in n:
            assert (arch.moe_experts, arch.moe_top_k) == (n["experts"],
                                                           n["top_k"])
