"""Pallas kernel validation: interpret-mode sweep vs the jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import moe as moe_k
from repro.kernels import ref
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.flash_attention import flash_attention
from repro.nn import moe as moe_mod

KEY = jax.random.PRNGKey(0)


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
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
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
