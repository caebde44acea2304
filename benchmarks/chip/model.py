"""The benchmark's hold on the program under test: its ArchConfig for a
configuration file, its train step, and its parameter tree filled with the
weights the benchmark makes from the seed.  What differs by model family
(the ArchConfig, where each leaf sits) is the configuration's adapter,
``adapters/<config>.py``.

The weights are the reference's own initial weights (made by
``reference/<config>.py`` from the seed), restacked into the program's
layout in the same jitted call that makes them.  Readings taken from the
program's state are keyed by the reference's leaf names, so the two can
be compared leaf by leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import common


def arch_config(cfg: dict, reference, adapter):
    """The program's ArchConfig for a configuration file, from the widths
    its reference reads (``adapters/<config>.py``)."""
    arch = adapter.arch_config(cfg, reference.dims(cfg))
    arch.validate()
    return arch


def to_program(ref: dict, adapter) -> dict:
    """Reference layout → the program's: top-level leaves as they are, the
    layers stacked on a leading axis into the one pattern block."""
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


def program_leaves(tree: dict, n_layers: int, adapter) -> dict:
    """Leaves of a program-layout tree keyed by the reference's leaf names
    (``layers.<i>.<name>`` for each layer)."""
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


def program_sq_norms(tree: dict, n_layers: int, adapter) -> dict:
    """Per-leaf sums of squares of a program-layout tree, keyed by the
    reference's leaf names."""
    return {k: jnp.sum(jnp.square(v))
            for k, v in program_leaves(tree, n_layers, adapter).items()}


class Program:
    """The program's train step over the benchmark's weights for one
    configuration and mix."""

    def __init__(self, cfg: dict, mix: dict, reference, adapter):
        from repro.launch.steps import make_train_step

        self.cfg, self.mix = cfg, mix
        self.reference, self.adapter = reference, adapter
        self.arch = arch_config(cfg, reference, adapter)
        self.opt = cfg["assumed"]["optimizer"]["value"]
        self.n_layers = self.arch.num_layers
        self.step = jax.jit(
            make_train_step(self.arch, lr=self.opt["lr"],
                            weight_decay=self.opt["weight_decay"],
                            clip=self.opt["clip_norm"],
                            compute_dtype=jnp.bfloat16, remat=True,
                            microbatch=mix["microbatch"]),
            donate_argnums=(0, 1))
        self._sq = jax.jit(lambda t: program_sq_norms(t, self.n_layers,
                                                      adapter))
        self._delta = jax.jit(lambda p, k: program_sq_norms(
            jax.tree.map(jnp.subtract, p, self.initial_params(k)),
            self.n_layers, adapter))

    def initial_params(self, key):
        return to_program(self.reference.init_params(key, self.cfg),
                          self.adapter)

    def init_state(self, key):
        """Parameters and AdamW state on the device, in one jitted call."""
        from repro.models import decoder
        from repro.optim import adamw_init

        def make(k):
            p = self.initial_params(k)
            return p, adamw_init(p)

        want = jax.eval_shape(
            lambda k: decoder.init_model(self.arch, k), key)
        got = jax.eval_shape(make, key)[0]
        if (jax.tree.structure(want) != jax.tree.structure(got)
                or any(a.shape != b.shape for a, b in
                       zip(jax.tree.leaves(want), jax.tree.leaves(got)))):
            raise ValueError("the benchmark's weights do not fill the "
                             "program's parameter tree")
        return jax.jit(make)(key)

    def grad_norms(self, opt_state) -> dict:
        """Per-leaf norms of the first step's gradient as AdamW took it,
        from the first moment after one step: m₁ = (1 − b1)·g."""
        return {k: float(v) ** 0.5 / (1 - self.opt["b1"])
                for k, v in self._sq(opt_state.mu).items()}

    def delta_norms(self, params, key) -> dict:
        """Per-leaf norms of the parameters' change since initialization."""
        return {k: float(v) ** 0.5 for k, v in self._delta(params, key).items()}


def reference_readings(cfg: dict, mix: dict, reference, key, batches, *,
                       matmul: str = "float32") -> dict:
    """The plain reference's readings of the first three steps, keyed by
    leaf name like the program's."""
    opt = cfg["assumed"]["optimizer"]["value"]
    init = lambda k: reference.init_params(k, cfg)  # noqa: E731
    out = common.train_readings(
        lambda p, t, l, mm: reference.loss(p, t, l, cfg, mm), init, key,
        batches, opt, rows_per_block=mix["microbatch"],
        mm=common.MATMULS[matmul])
    names = common.leaf_names(jax.eval_shape(init, key))
    return {"loss": out["loss"], "grad": dict(zip(names, out["grad"])),
            "delta": dict(zip(names, out["delta"]))}
