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


def _top_paths(adapter) -> dict:
    """Top-level reference leaf name → its path in the program's tree
    (``TOP_PATHS`` of the adapter; by default ``TOP_LEAVES`` at the top,
    as they are)."""
    return getattr(adapter, "TOP_PATHS",
                   None) or {k: (k,) for k in adapter.TOP_LEAVES}


def _put(tree: dict, path, value) -> None:
    node = tree
    for key in path[:-1]:
        node = node.setdefault(key, {})
    if path[-1] in node:
        raise ValueError(f"two reference leaves fill program path {path}")
    node[path[-1]] = value


def _block(layers: list[dict], adapter) -> dict:
    """One pattern block: its layers' leaves, stacked on a leading axis
    in repeat order, at the paths ``LAYER_PATHS`` gives."""
    names = list(layers[0])
    if any(set(lp) != set(names) for lp in layers):
        raise ValueError("the layers of one pattern position differ in "
                         "their leaves")
    block: dict = {}
    for name in names:
        if name not in adapter.LAYER_PATHS:
            raise ValueError(f"reference layer leaf {name!r} has no place "
                             "in the program's tree")
        _put(block, adapter.LAYER_PATHS[name],
             jnp.stack([lp[name] for lp in layers]))
    return block


def to_program(ref: dict, adapter, period: int) -> dict:
    """Reference layout → the program's, as ``decoder.init_model`` lays it
    out: each top-level leaf at its path, and reference layer ``i`` at
    repeat ``i // period`` of pattern block ``i % period``, where
    ``period`` is the length of the ArchConfig's pattern.  Each layer is
    built from its own leaf names."""
    tops = _top_paths(adapter)
    unplaced = set(ref) - {"layers"} - set(tops)
    if unplaced:
        raise ValueError(f"reference leaves {sorted(unplaced)} have no place "
                         "in the program's tree")
    layers = ref["layers"]
    if len(layers) % period:
        raise ValueError(f"{len(layers)} layers do not repeat a pattern of "
                         f"{period}")
    out: dict = {}
    for name, path in tops.items():
        _put(out, path, ref[name])
    out["blocks"] = tuple(_block(layers[j::period], adapter)
                          for j in range(period))
    return out


def _at(tree, path):
    """The node at ``path``, or None where the tree has none."""
    node = tree
    for key in path:
        try:
            node = node[key]
        except (KeyError, IndexError, TypeError):
            return None
    return node


def _plain(path) -> tuple:
    return tuple(getattr(k, "key", getattr(k, "idx", k)) for k in path)


def program_leaves(tree: dict, n_layers: int, adapter) -> dict:
    """Leaves of a program-layout tree keyed by the reference's leaf names
    (``layers.<i>.<name>`` for each layer), read back through the layout
    that ``to_program`` fills, with the period that the tree's pattern
    blocks give.  Every leaf of the tree, and every row of a stacked one,
    is read, or this fails."""
    period = len(tree["blocks"])
    repeats = n_layers // period
    out, read = {}, set()
    for name, path in _top_paths(adapter).items():
        out[name] = _at(tree, path)
        read.add(tuple(path))
    for name, path in adapter.LAYER_PATHS.items():
        for j in range(period):
            node = _at(tree, ("blocks", j, *path))
            if node is None:
                continue
            read.add(("blocks", j, *path))
            if repeats * period != n_layers or node.shape[0] != repeats:
                raise ValueError(f"program leaf {('blocks', j, *path)} "
                                 f"stacks {node.shape[0]} layers, not "
                                 f"{n_layers} / {period}")
            for r in range(repeats):
                out[f"layers.{r * period + j}.{name}"] = node[r]
    held = {_plain(p) for p, _ in jax.tree_util.tree_leaves_with_path(tree)}
    if held != read:
        raise ValueError(
            f"program leaf not read: {sorted(held - read, key=str)}; "
            f"not in the tree: {sorted(read - held, key=str)}")
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
        self.n_layers = reference.dims(cfg)["layers"]
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
                          self.adapter, len(self.arch.pattern))

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
