"""Pallas kernel validation: interpret-mode sweep vs the jnp oracles."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import moe as moe_k
from repro.kernels import ref
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.flash_attention import Blocks, flash_attention
from repro.nn import moe as moe_mod

KEY = jax.random.PRNGKey(0)
#: 128 x 128 blocks in every kernel: several blocks at small lengths
B128 = Blocks((128, 128), (128, 128), (128, 128))


def _qkv(B, H, Sq, Sk, hd, dtype):
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (B, H, Sq, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (B, H, Sk, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (B, H, Sk, hd), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("B,H,S,hd", [
        (1, 1, 128, 64), (2, 2, 256, 64), (1, 2, 384, 128), (1, 1, 128, 256),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shape_dtype_sweep_causal(self, B, H, S, hd, dtype):
        q, k, v = _qkv(B, H, S, S, hd, dtype)
        out = flash_attention(q, k, v, causal=True, blocks=B128,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), atol=tol)

    @pytest.mark.parametrize("window", [32, 100, 128])
    def test_sliding_window(self, window):
        q, k, v = _qkv(1, 2, 256, 256, 64, jnp.float32)
        out = flash_attention(q, k, v, causal=True, window=window,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)

    def test_logit_softcap(self):
        q, k, v = _qkv(1, 1, 128, 128, 64, jnp.float32)
        out = flash_attention(q, k, v, causal=True, softcap=50.0, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, softcap=50.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)

    def test_non_causal_encoder(self):
        q, k, v = _qkv(2, 1, 128, 256, 64, jnp.float32)
        out = flash_attention(q, k, v, causal=False, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)

    def test_cross_lengths(self):
        q, k, v = _qkv(1, 2, 128, 384, 64, jnp.float32)
        out = flash_attention(q, k, v, causal=False, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)

    def test_matches_model_blockwise_path(self):
        """The XLA blockwise fallback and the Pallas kernel agree."""
        from repro.nn.attention import AttnSpec, _sdpa_blockwise

        B, H, S, hd = 1, 2, 4096, 64
        q, k, v = _qkv(B, H, S, S, hd, jnp.float32)
        spec = AttnSpec(n_heads=H, n_kv_heads=H, head_dim=hd, causal=True,
                        rope=False)
        qb = jnp.moveaxis(q, 1, 2)  # (B,S,H,hd)
        kb = jnp.moveaxis(k, 1, 2)
        vb = jnp.moveaxis(v, 1, 2)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        out_xla = jnp.moveaxis(_sdpa_blockwise(qb, kb, vb, pos, pos, spec), 2, 1)
        out_pl = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out_xla), np.asarray(out_pl),
                                   atol=3e-5)


def _gqa(H, KV, S, hd, dtype):
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (1, H, S, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (1, KV, S, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (1, KV, S, hd), dtype)
    g = jax.random.normal(jax.random.fold_in(KEY, 4), (1, H, S, hd), dtype)
    return q, k, v, g


def _rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


class TestFlashAttentionGrad:
    """Forward and ``jax.grad`` (dq, dk, dv) of the kernel against the
    direct oracle, over GQA ratios, window, dtype and ragged lengths."""

    def _check(self, H, KV, S, dtype, *, causal=True, window=None, hd=64):
        q, k, v, g = _gqa(H, KV, S, hd, dtype)
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

        def loss(fn, q, k, v):
            o = fn(q, k, v, causal=causal, window=window)
            return jnp.sum(f32(o) * f32(g))

        kern = functools.partial(flash_attention, blocks=B128,
                                 interpret=True)
        want_o = ref.flash_attention_ref(f32(q), f32(k), f32(v),
                                         causal=causal, window=window)
        got_o = kern(q, k, v, causal=causal, window=window)
        got = jax.grad(functools.partial(loss, kern), (0, 1, 2))(q, k, v)
        want = jax.grad(functools.partial(loss, ref.flash_attention_ref),
                        (0, 1, 2))(f32(q), f32(k), f32(v))
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        assert got_o.shape == q.shape and got_o.dtype == dtype
        assert _rel_err(got_o, want_o) < tol
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            assert a.shape == b.shape, name
            assert _rel_err(a, b) < tol, name

    @pytest.mark.parametrize("H,KV", [(2, 2), (4, 1), (16, 1)],
                             ids=["mha", "gqa4", "gqa16"])
    @pytest.mark.parametrize("window", [None, 96])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_reference(self, H, KV, window, dtype):
        self._check(H, KV, 256, dtype, window=window)

    @pytest.mark.parametrize("S,causal", [(200, True), (300, True),
                                          (200, False)])
    def test_ragged_length_is_padded(self, S, causal):
        self._check(4, 2, S, jnp.float32, causal=causal)

    def test_per_kernel_blocks(self):
        """Forward, dQ and dK/dV may tile differently."""
        q, k, v, g = _gqa(4, 2, 512, 128, jnp.float32)
        blocks = Blocks(fwd=(256, 128), dq=(128, 256), dkv=(256, 128))

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v, causal=True, window=130) * g)

        kern = functools.partial(flash_attention, blocks=blocks,
                                 interpret=True)
        got = jax.grad(functools.partial(loss, kern), (0, 1, 2))(q, k, v)
        want = jax.grad(functools.partial(loss, ref.flash_attention_ref),
                        (0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            assert _rel_err(a, b) < 2e-5

    def test_softcap_has_no_backward(self):
        q, k, v, _ = _gqa(2, 2, 128, 64, jnp.float32)
        with pytest.raises(NotImplementedError):
            jax.grad(lambda q: flash_attention(
                q, k, v, softcap=30.0, interpret=True).sum())(q)


@pytest.fixture
def attn_calls():
    """Turns the obs registry on; yields a reader of the
    ``attention.calls`` counts added since the test began."""
    from repro import obs

    reg = obs.REGISTRY
    was = reg.enabled
    reg.enabled = True

    def counts():
        return {tuple(sorted(lab.items())): m.value
                for lab, m in reg.find("attention.calls")}

    before = counts()

    def added():
        return {k: v - before.get(k, 0.0) for k, v in counts().items()
                if v != before.get(k, 0.0)}

    yield added
    reg.enabled = was


FLASH = (("path", "flash"),)


def _xla(reason):
    return (("path", "xla"), ("reason", reason))


class TestAttentionRouting:
    """``nn.attention.attention`` takes the kernel only for causal
    self-attention over ``arange`` positions above the threshold."""

    D, S = 64, 256

    @pytest.fixture(autouse=True)
    def low_threshold(self, monkeypatch):
        from repro.nn import attention as attn_mod

        monkeypatch.setattr(attn_mod, "BLOCKWISE_THRESHOLD", 128)

    def _layer(self, **kw):
        from repro.nn import attention as attn_mod

        d = dict(n_heads=4, n_kv_heads=2, head_dim=16, causal=True, rope=True,
                 qk_norm=True)
        d.update(kw)
        spec = attn_mod.AttnSpec(**d)
        p = attn_mod.init_attention(KEY, self.D, spec)
        x = jax.random.normal(jax.random.fold_in(KEY, 5), (2, self.S, self.D))
        pos = jnp.broadcast_to(jnp.arange(self.S, dtype=jnp.int32),
                               (2, self.S))
        return attn_mod, spec, p, x, pos

    @pytest.mark.parametrize("window", [None, 100])
    def test_kernel_matches_blockwise(self, attn_calls, window):
        """Value and gradient (params and input) of the kernel path equal
        the XLA blockwise path's."""
        attn_mod, spec, p, x, pos = self._layer(window=window)

        def loss(impl, p, x):
            y = attn_mod.attention(p, x, spec, positions=pos, impl=impl,
                                   arange_positions=True)
            return jnp.sum(jnp.sin(y))

        lk, gk = jax.value_and_grad(functools.partial(loss, "interpret"),
                                    (0, 1))(p, x)
        lx, gx = jax.value_and_grad(functools.partial(loss, "xla"),
                                    (0, 1))(p, x)
        assert attn_calls() == {FLASH: 1, _xla("backend"): 1}
        np.testing.assert_allclose(float(lk), float(lx), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gx)):
            assert _rel_err(a, b) < 1e-4

    @pytest.mark.parametrize("reason", ["cross", "non_causal", "softcap",
                                        "positions", "short"])
    def test_fallback_keeps_xla_path(self, attn_calls, reason):
        """A call the kernel does not cover runs the XLA path bit for bit
        and is counted with its reason."""
        attn_mod, spec, p, x, pos = self._layer(
            causal=reason != "non_causal",
            logit_softcap=30.0 if reason == "softcap" else None)
        if reason == "short":
            x, pos = x[:, :100], pos[:, :100]
        kw = dict(positions=pos, arange_positions=reason != "positions")
        if reason == "cross":
            kw.update(kv_x=x[:, ::-1], kv_positions=pos)
        got = attn_mod.attention(p, x, spec, impl="interpret", **kw)
        want = attn_mod.attention(p, x, spec, impl="xla", **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        want_calls = {_xla(reason): 2}
        if reason != "short":
            want_calls = {_xla(reason): 1, _xla("backend"): 1}
        assert attn_calls() == want_calls

    @pytest.mark.parametrize("cell", ["chatglm3-6b-32k.train-8k",
                                      "olmoe-1b-7b.train-4k"])
    def test_benchmark_train_steps_take_kernel(self, attn_calls, cell,
                                               monkeypatch):
        """Every attention call of the benchmark configurations' train
        steps traces onto the kernel at the cells' own shapes."""
        import dataclasses

        from benchmarks.chip import model, spec
        from repro.launch.steps import make_train_step
        from repro.models import decoder
        from repro.nn import attention as attn_mod
        from repro.optim import adamw_init

        monkeypatch.setattr(attn_mod, "BLOCKWISE_THRESHOLD", 2048)
        bench = spec.Benchmark()
        c = bench.cell(cell)
        mix = bench.traffic(c["traffic"])
        arch = dataclasses.replace(model.arch_config(
            bench.config(c["config"]), bench.reference(c["config"]),
            bench.adapter(c["config"])), attn_impl="interpret")
        p = jax.eval_shape(lambda k: decoder.init_model(arch, k), KEY)
        rows = (mix["sequences_per_step"], mix["sequence_length"])
        batch = {"tokens": jax.ShapeDtypeStruct(rows, jnp.int32),
                 "labels": jax.ShapeDtypeStruct(rows, jnp.int32)}
        jax.eval_shape(make_train_step(arch, microbatch=mix["microbatch"]),
                       p, jax.eval_shape(adamw_init, p), batch)
        calls = attn_calls()
        assert set(calls) == {FLASH} and calls[FLASH] >= arch.num_layers


class TestEmbeddingBag:
    @pytest.mark.parametrize("N,bag,V,dim", [
        (8, 4, 100, 128), (16, 1, 50, 128), (4, 16, 1000, 256),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, N, bag, V, dim, dtype):
        ids = jax.random.randint(KEY, (N, bag), 0, V)
        table = jax.random.normal(KEY, (V, dim), dtype)
        out = embedding_bag(ids, table, interpret=True)
        want = ref.embedding_bag_ref(ids, table)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), atol=tol)

    def test_duplicate_ids(self):
        ids = jnp.zeros((4, 8), jnp.int32)  # all the same row
        table = jax.random.normal(KEY, (10, 128))
        out = embedding_bag(ids, table, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(8 * table[0])[None]
                                   .repeat(4, 0), rtol=1e-5)


def _moe_setup(G, S, D, E, K, cf, *, dtype=jnp.float32, fold=0):
    p = moe_mod.init_moe(jax.random.fold_in(KEY, fold), D, 2 * D, E)
    p = jax.tree.map(lambda a: a.astype(dtype), p)
    x = jax.random.normal(jax.random.fold_in(KEY, fold + 1), (G, S, D), dtype)
    C = moe_mod.moe_capacity(S, E, K, cf)
    return p, x, C


def _routing(p, x, K, C):
    _, gate, eid_f, pos, keep = moe_mod.moe_route(p["router"], x, top_k=K,
                                                  capacity=C)
    return gate, eid_f, pos, keep


class TestMoeDispatchCombine:
    """Fused MoE dispatch/combine vs the nn/moe.py scatter/gather oracle."""

    @pytest.mark.parametrize("impl", ["slot", "interpret"])
    @pytest.mark.parametrize("G,S,D,E,K,cf", [
        (2, 24, 16, 4, 2, 1.25),
        (1, 64, 32, 8, 2, 1.0),
        (2, 32, 16, 4, 1, 0.25),   # heavy overflow / dropped tokens
        (1, 8, 16, 4, 4, 8.0),     # full capacity, top_k = E
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_forward_equivalence(self, impl, G, S, D, E, K, cf, dtype):
        p, x, _ = _moe_setup(G, S, D, E, K, cf, dtype=dtype)
        y_ref, aux_ref = moe_mod.moe_ffn(p, x, top_k=K, capacity_factor=cf,
                                         impl="ref")
        y, aux = moe_mod.moe_ffn(p, x, top_k=K, capacity_factor=cf, impl=impl)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32), atol=tol)
        assert float(aux["dropped"]) == pytest.approx(
            float(aux_ref["dropped"]), abs=1e-6)

    @pytest.mark.parametrize("impl", ["slot", "interpret"])
    @pytest.mark.parametrize("cf", [1.25, 0.25])  # incl. dropped tokens
    def test_grad_equivalence(self, impl, cf):
        """jax.grad through the kernelized moe_ffn == reference path,
        for every parameter and the input, incl. capacity overflow."""
        G, S, D, E, K = 2, 24, 16, 4, 2
        p, x, _ = _moe_setup(G, S, D, E, K, cf)

        def loss(p, x, impl):
            y, aux = moe_mod.moe_ffn(p, x, top_k=K, capacity_factor=cf,
                                     impl=impl)
            return (y ** 2).sum() + aux["aux_loss"]

        (g_ref, gx_ref) = jax.grad(loss, argnums=(0, 1))(p, x, "ref")
        (g, gx) = jax.grad(loss, argnums=(0, 1))(p, x, impl)
        for k in g_ref:
            np.testing.assert_allclose(np.asarray(g[k]), np.asarray(g_ref[k]),
                                       atol=2e-5, err_msg=k)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                                   atol=2e-5)

    def test_dispatch_combine_roundtrip_identity(self):
        """With no drops, combine(dispatch(x)) with gate weights must
        reconstruct x exactly: gates renormalize to Σ_k w = 1."""
        G, S, D, E, K, cf = 2, 16, 16, 4, 2, 8.0
        p, x, C = _moe_setup(G, S, D, E, K, cf)
        gate, eid_f, pos, keep = _routing(p, x, K, C)
        assert bool(jnp.all(keep))
        buf = moe_k.moe_dispatch(x, eid_f, pos, keep.astype(jnp.float32),
                                 E, C, K, "slot")
        w = (gate.reshape(G, S, K) * keep.reshape(G, S, K))
        y = moe_k.moe_combine(buf, eid_f.reshape(G, S, K),
                              jnp.where(keep, pos, 0).reshape(G, S, K),
                              w, "slot")
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-5)

    @given(st.integers(4, 48), st.integers(1, 3), st.sampled_from(
        [0.25, 0.5, 1.0, 1.25, 2.0]))
    @settings(max_examples=12, deadline=None)
    def test_slot_map_invariants(self, S, K, cf):
        """Kernel-path routing invariants, randomized over (S, K, cf):
        every kept (token, k) claims exactly one slot of its expert's
        slab, occupancy ≤ capacity, drops match moe_capacity arithmetic."""
        G, D, E = 2, 8, 4
        K = min(K, E)
        p, x, C = _moe_setup(G, S, D, E, K, cf, fold=S * 8 + K)
        _, eid_f, pos, keep = _routing(p, x, K, C)
        slot_nk = moe_k.slot_maps(eid_f, pos, keep, num_experts=E, capacity=C)
        nk, snk = np.asarray(eid_f), np.asarray(slot_nk)
        keep_np, pos_np = np.asarray(keep), np.asarray(pos)
        for g in range(G):
            filled = snk[g][snk[g] >= 0]
            # each kept (token,k) appears in exactly one slot, drops in none
            assert sorted(filled.tolist()) == sorted(
                np.nonzero(keep_np[g])[0].tolist())
            # a claimed slot sits in the slab of the expert that routed it
            for e in range(E):
                owners = snk[g, e][snk[g, e] >= 0]
                assert (nk[g][owners] == e).all()
                # occupancy ≤ capacity and == min(routed, C)
                routed = int((nk[g] == e).sum())
                assert len(owners) == min(routed, C) <= C
            # drop accounting: overflow per expert == dropped (token,k)s
            overflow = sum(max(0, int((nk[g] == e).sum()) - C)
                           for e in range(E))
            assert int((~keep_np[g]).sum()) == overflow
            # position-in-expert is the exclusive running count
            assert (pos_np[g] >= 0).all()

    @given(st.integers(0, 5))
    @settings(max_examples=6, deadline=None)
    def test_property_kernel_matches_ref(self, fold):
        """Randomized fwd equivalence of the full kernelized moe_ffn."""
        G, S, D, E, K, cf = 2, 20, 16, 4, 2, 1.0
        p, x, _ = _moe_setup(G, S, D, E, K, cf, fold=10 + fold)
        y_ref, _ = moe_mod.moe_ffn(p, x, top_k=K, capacity_factor=cf,
                                   impl="ref")
        y, _ = moe_mod.moe_ffn(p, x, top_k=K, capacity_factor=cf, impl="slot")
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)

    def test_grad_through_interpret_kernels(self):
        """custom_vjp backward runs through the Pallas interpreter too."""
        G, S, D, E, K, cf = 1, 12, 16, 4, 2, 0.5  # with drops
        p, x, C = _moe_setup(G, S, D, E, K, cf)
        gate, eid_f, pos, keep = _routing(p, x, K, C)
        w = (gate.reshape(G, S, K) * keep.reshape(G, S, K))
        safe_pos = jnp.where(keep, pos, 0)

        def f(x, w, impl):
            buf = moe_k.moe_dispatch(x, eid_f, pos, keep.astype(jnp.float32),
                                     E, C, K, impl)
            y = moe_k.moe_combine(buf, eid_f.reshape(G, S, K),
                                  safe_pos.reshape(G, S, K), w, impl)
            return (y ** 2).sum()

        gx_s, gw_s = jax.grad(f, argnums=(0, 1))(x, w, "slot")
        gx_i, gw_i = jax.grad(f, argnums=(0, 1))(x, w, "interpret")
        np.testing.assert_allclose(np.asarray(gx_i), np.asarray(gx_s),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw_i), np.asarray(gw_s),
                                   atol=1e-5)


# --------------------------------------------------------------------------
# paged KV-cache decode attention (kernels/paged_attention.py)
# --------------------------------------------------------------------------

from repro.kernels import ops as kernel_ops  # noqa: E402
from repro.kernels import paged_attention as paged_k  # noqa: E402


def _paged_setup(B, KV, G, hd, ps, P, fold=0, dtype=jnp.float32):
    """Identity-allocated pool (slot b owns pages [1+bP, 1+(b+1)P))."""
    key = jax.random.fold_in(KEY, 100 + fold)
    N = 1 + B * P
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, KV, G, hd), dtype)
    kp = jax.random.normal(jax.random.fold_in(key, 2), (N, ps, KV, hd), dtype)
    vp = jax.random.normal(jax.random.fold_in(key, 3), (N, ps, KV, hd), dtype)
    table = (1 + jnp.arange(B * P, dtype=jnp.int32)).reshape(B, P)
    return q, kp, vp, table


def _paged_dense_ref(q, kp, vp, table, q_pos, *, window, softcap):
    """Straight-line oracle: densify the pages, masked grouped softmax."""
    B, KV, G, hd = q.shape
    ps, P = kp.shape[1], table.shape[1]
    k = np.asarray(kp, np.float32)[np.asarray(table)].reshape(B, P * ps, KV, hd)
    v = np.asarray(vp, np.float32)[np.asarray(table)].reshape(B, P * ps, KV, hd)
    qn = np.asarray(q, np.float32)
    pos = np.arange(P * ps)
    out = np.zeros_like(qn)
    for b in range(B):
        valid = pos <= int(q_pos[b])
        if window is not None:
            valid &= pos > int(q_pos[b]) - window
        s = np.einsum("kgd,skd->kgs", qn[b], k[b]) / np.sqrt(hd)
        if softcap:
            s = softcap * np.tanh(s / softcap)
        s = np.where(valid[None, None, :], s, -1e30)
        s -= s.max(-1, keepdims=True)
        w = np.exp(s)
        w /= w.sum(-1, keepdims=True)
        out[b] = np.einsum("kgs,skd->kgd", w, v[b])
    return out


class TestPagedDecodeAttention:
    CASES = [
        # B, KV, G, hd, ps, P, window, softcap — incl. multi-page spans
        (2, 2, 2, 64, 4, 4, None, None),
        (2, 1, 4, 32, 8, 3, 5, 30.0),
        (1, 4, 1, 16, 4, 3, None, 50.0),
        (3, 2, 4, 32, 4, 5, 7, None),
    ]

    @pytest.mark.parametrize("B,KV,G,hd,ps,P,window,sc", CASES)
    def test_gather_matches_dense_oracle(self, B, KV, G, hd, ps, P, window, sc):
        q, kp, vp, table = _paged_setup(B, KV, G, hd, ps, P)
        # positions spanning >1 page and mid-page, ragged across the batch
        q_pos = jnp.asarray([(ps * P - 1), ps + 1, 0][:B], jnp.int32)
        got = kernel_ops.paged_attention_decode(
            q, kp, vp, table, q_pos, window=window, softcap=sc, impl="gather")
        want = _paged_dense_ref(q, kp, vp, table, q_pos, window=window,
                                softcap=sc)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)

    @pytest.mark.parametrize("B,KV,G,hd,ps,P,window,sc", CASES)
    def test_interpret_matches_gather(self, B, KV, G, hd, ps, P, window, sc):
        """The Pallas kernel body (online softmax over scalar-prefetched
        pages) against the jnp gather formulation."""
        q, kp, vp, table = _paged_setup(B, KV, G, hd, ps, P)
        q_pos = jnp.asarray([(ps * P - 1), ps + 1, 0][:B], jnp.int32)
        got = kernel_ops.paged_attention_decode(
            q, kp, vp, table, q_pos, window=window, softcap=sc,
            impl="interpret")
        want = kernel_ops.paged_attention_decode(
            q, kp, vp, table, q_pos, window=window, softcap=sc, impl="gather")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    @given(st.integers(0, 63), st.integers(0, 4))
    @settings(max_examples=12, deadline=None)
    def test_property_any_position(self, q_pos, fold):
        """Randomized positions (incl. page boundaries) stay equivalent."""
        B, KV, G, hd, ps, P = 1, 2, 2, 16, 8, 8
        q, kp, vp, table = _paged_setup(B, KV, G, hd, ps, P, fold=fold)
        qp = jnp.asarray([q_pos], jnp.int32)
        got = kernel_ops.paged_attention_decode(
            q, kp, vp, table, qp, window=11, impl="interpret")
        want = _paged_dense_ref(q, kp, vp, table, qp, window=11, softcap=None)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)

    def test_write_then_read_roundtrip(self):
        """paged_write lands the row where the gather path reads it; an
        inactive slot's write is steered to the scratch page."""
        B, KV, G, hd, ps, P = 2, 2, 2, 16, 4, 3
        q, kp, vp, table = _paged_setup(B, KV, G, hd, ps, P)
        k_new = jax.random.normal(KEY, (B, KV, hd))
        v_new = jax.random.normal(jax.random.fold_in(KEY, 7), (B, KV, hd))
        q_pos = jnp.asarray([5, 2], jnp.int32)
        active = jnp.asarray([True, False])
        kp2, vp2 = paged_k.paged_write(kp, vp, k_new, v_new, table, q_pos,
                                       active)
        # active slot 0: row at (table[0, 5//ps], 5%ps)
        pid = int(table[0, 5 // ps])
        np.testing.assert_allclose(np.asarray(kp2[pid, 5 % ps]),
                                   np.asarray(k_new[0]))
        # inactive slot 1: its own pages untouched, scratch page got the row
        pid1 = int(table[1, 2 // ps])
        np.testing.assert_allclose(np.asarray(kp2[pid1, 2 % ps]),
                                   np.asarray(kp[pid1, 2 % ps]))
        np.testing.assert_allclose(np.asarray(kp2[0, 2 % ps]),
                                   np.asarray(k_new[1]))
