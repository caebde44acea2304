"""Layer-level unit & equivalence tests for the nn library."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st


from repro.nn import attention as attn_mod
from repro.nn import mamba as mamba_mod
from repro.nn import moe as moe_mod
from repro.nn import rwkv as rwkv_mod
from repro.nn.attention import AttnSpec
from repro.nn.base import apply_rope, cross_entropy_loss, rmsnorm, softcap

KEY = jax.random.PRNGKey(0)


class TestAttention:
    def _spec(self, **kw):
        d = dict(n_heads=4, n_kv_heads=2, head_dim=32, causal=True, rope=True)
        d.update(kw)
        return AttnSpec(**d)

    def test_blockwise_equals_direct(self):
        """The flash-style scan path must equal direct attention exactly."""
        spec = self._spec()
        B, S, D = 2, 2304, 128  # > BLOCKWISE_THRESHOLD with padding ragged
        p = attn_mod.init_attention(KEY, D, spec)
        x = jax.random.normal(KEY, (B, S, D)) * 0.3
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        out_block = attn_mod.attention(p, x, spec, positions=pos)
        # force the direct path by raising the threshold
        old = attn_mod.BLOCKWISE_THRESHOLD
        try:
            attn_mod.BLOCKWISE_THRESHOLD = 10**9
            out_direct = attn_mod.attention(p, x, spec, positions=pos)
        finally:
            attn_mod.BLOCKWISE_THRESHOLD = old
        np.testing.assert_allclose(np.asarray(out_block),
                                   np.asarray(out_direct), atol=3e-5)

    def test_causality(self):
        """Future tokens must not influence earlier outputs."""
        spec = self._spec(rope=False)
        D = 128
        p = attn_mod.init_attention(KEY, D, spec)
        x1 = jax.random.normal(KEY, (1, 16, D))
        x2 = x1.at[:, -1].set(99.0)  # perturb only the last token
        pos = jnp.arange(16, dtype=jnp.int32)[None]
        o1 = attn_mod.attention(p, x1, spec, positions=pos)
        o2 = attn_mod.attention(p, x2, spec, positions=pos)
        np.testing.assert_allclose(np.asarray(o1[:, :-1]),
                                   np.asarray(o2[:, :-1]), atol=1e-6)

    def test_sliding_window_limits_receptive_field(self):
        spec = self._spec(window=4, rope=False)
        D = 128
        p = attn_mod.init_attention(KEY, D, spec)
        x1 = jax.random.normal(KEY, (1, 32, D))
        x2 = x1.at[:, 0].set(50.0)  # token 0 outside window of token 31
        pos = jnp.arange(32, dtype=jnp.int32)[None]
        o1 = attn_mod.attention(p, x1, spec, positions=pos)
        o2 = attn_mod.attention(p, x2, spec, positions=pos)
        np.testing.assert_allclose(np.asarray(o1[:, -1]), np.asarray(o2[:, -1]),
                                   atol=1e-6)

    def test_ring_buffer_decode_window(self):
        """Windowed decode with L = window must match full-cache decode."""
        spec = self._spec(window=8, rope=True)
        D = 128
        p = attn_mod.init_attention(KEY, D, spec)
        B, T = 1, 20
        xs = jax.random.normal(KEY, (B, T, 1, D)) * 0.5
        big = attn_mod.init_kv_cache(B, T, spec, dtype=jnp.float32)
        ring = attn_mod.init_kv_cache(B, 8, spec, dtype=jnp.float32)
        for i in range(T):
            o_big, big = attn_mod.decode_attention(p, xs[:, i], big, jnp.int32(i), spec)
            o_ring, ring = attn_mod.decode_attention(p, xs[:, i], ring, jnp.int32(i), spec)
            np.testing.assert_allclose(np.asarray(o_big), np.asarray(o_ring),
                                       atol=1e-5)


class TestRope:
    def test_rope_preserves_norm(self):
        x = jax.random.normal(KEY, (2, 8, 4, 64))
        pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
        y = apply_rope(x, pos)
        np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                                   np.linalg.norm(np.asarray(y), axis=-1),
                                   rtol=1e-5)

    def test_partial_rope_leaves_tail_untouched(self):
        x = jax.random.normal(KEY, (1, 4, 2, 64))
        pos = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32), (1, 4))
        y = apply_rope(x, pos, fraction=0.5)
        np.testing.assert_allclose(np.asarray(x[..., 32:]),
                                   np.asarray(y[..., 32:]))

    def test_relative_phase(self):
        """RoPE scores depend only on relative distance."""
        q = jax.random.normal(KEY, (1, 1, 1, 64))
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 1, 1, 64))
        def score(pq, pk):
            qq = apply_rope(q, jnp.full((1, 1), pq, jnp.int32))
            kk = apply_rope(k, jnp.full((1, 1), pk, jnp.int32))
            return float(jnp.sum(qq * kk))
        assert score(3, 1) == pytest.approx(score(10, 8), rel=1e-4)


class TestMamba:
    def test_decode_matches_sequence(self):
        D = 64
        p = mamba_mod.init_mamba(KEY, D)
        B, S = 2, 24
        x = jax.random.normal(KEY, (B, S, D)) * 0.5
        y_seq = mamba_mod.mamba(p, x)
        cache = mamba_mod.init_mamba_cache(B, D)
        outs = []
        for i in range(S):
            y, cache = mamba_mod.decode_mamba(p, x[:, i : i + 1], cache)
            outs.append(y[:, 0])
        y_dec = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_seq),
                                   atol=1e-4)

    def test_state_carries_information(self):
        D = 32
        p = mamba_mod.init_mamba(KEY, D)
        cache = mamba_mod.init_mamba_cache(1, D)
        x = jax.random.normal(KEY, (1, 1, D))
        _, c1 = mamba_mod.decode_mamba(p, x, cache)
        assert float(jnp.abs(c1["h"]).max()) > 0


class TestRwkv:
    def test_decode_matches_sequence(self):
        D = 128
        p = rwkv_mod.init_time_mix(KEY, D, head_size=64)
        B, S = 1, 16
        x = jax.random.normal(KEY, (B, S, D)) * 0.5
        y_seq = rwkv_mod.time_mix(p, x, head_size=64)
        cache = rwkv_mod.init_rwkv_cache(B, D, head_size=64)
        outs = []
        for i in range(S):
            y, upd = rwkv_mod.decode_time_mix(p, x[:, i : i + 1], cache,
                                              head_size=64)
            cache = {**cache, **upd}
            outs.append(y[:, 0])
        np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                                   np.asarray(y_seq), atol=1e-4)

    def test_decay_in_unit_interval(self):
        D = 128
        p = rwkv_mod.init_time_mix(KEY, D, head_size=64)
        x = jax.random.normal(KEY, (4, D))
        from repro.nn.rwkv import _lora
        w = jnp.exp(-jnp.exp(p["decay_base"] + _lora(p["decay_lora"], x)))
        assert float(w.min()) > 0.0 and float(w.max()) < 1.0


class TestMoe:
    def test_full_capacity_equals_dense_expert_mix(self):
        """With capacity ≥ all tokens and top_k=E, MoE = gate-weighted sum
        of every expert — check against an explicit loop."""
        D, F, E = 16, 32, 4
        p = moe_mod.init_moe(KEY, D, F, E)
        x = jax.random.normal(KEY, (2, 8, D))
        y, aux = moe_mod.moe_ffn(p, x, top_k=E, capacity_factor=8.0)
        logits = (x @ p["router"]).astype(jnp.float32)
        gates = jax.nn.softmax(logits, -1)
        want = jnp.zeros_like(x)
        for e in range(E):
            pe = {"w1": p["w1"][e], "w3": p["w3"][e], "w2": p["w2"][e]}
            want += gates[..., e : e + 1] * moe_mod.dense_ffn(pe, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)
        assert float(aux["dropped"]) == 0.0

    def test_capacity_drops_tokens(self):
        D, F, E = 8, 16, 2
        p = moe_mod.init_moe(KEY, D, F, E)
        x = jax.random.normal(KEY, (1, 64, D))
        _, aux = moe_mod.moe_ffn(p, x, top_k=1, capacity_factor=0.25)
        assert float(aux["dropped"]) > 0.0

    @given(st.integers(1, 4))
    @settings(max_examples=6, deadline=None)
    def test_aux_loss_finite(self, top_k):
        D, F, E = 8, 16, 4
        p = moe_mod.init_moe(KEY, D, F, E)
        x = jax.random.normal(KEY, (2, 16, D))
        y, aux = moe_mod.moe_ffn(p, x, top_k=top_k)
        assert np.isfinite(float(aux["aux_loss"]))
        assert np.isfinite(np.asarray(y)).all()

    def test_impl_paths_agree(self):
        """Default (auto→slot on CPU) and ref oracle produce one answer."""
        D, F, E = 16, 32, 4
        p = moe_mod.init_moe(KEY, D, F, E)
        x = jax.random.normal(KEY, (2, 24, D))
        y_auto, _ = moe_mod.moe_ffn(p, x, top_k=2)
        y_ref, _ = moe_mod.moe_ffn(p, x, top_k=2, impl="ref")
        np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_ref),
                                   atol=1e-5)


class TestMoeRouting:
    """Property tests for the routing invariants of moe_route (the
    pure-JAX reference shared by oracle and kernel paths)."""

    def _route(self, S, K, cf, seed=0):
        D, E = 8, 4
        key = jax.random.fold_in(KEY, seed)
        p = moe_mod.init_moe(key, D, 16, E)
        x = jax.random.normal(key, (2, S, D))
        C = moe_mod.moe_capacity(S, E, K, cf)
        probs, gate, eid_f, pos, keep = moe_mod.moe_route(
            p["router"], x, top_k=K, capacity=C)
        return E, C, probs, gate, eid_f, pos, keep

    @given(st.integers(4, 40), st.integers(1, 4), st.sampled_from(
        [0.25, 0.5, 1.0, 1.25, 4.0]))
    @settings(max_examples=15, deadline=None)
    def test_routing_invariants(self, S, K, cf):
        E, C, probs, gate, eid_f, pos, keep = self._route(S, K, cf,
                                                          seed=S * 16 + K)
        eid_np = np.asarray(eid_f)
        pos_np = np.asarray(pos)
        keep_np = np.asarray(keep)
        G, NK = eid_np.shape
        assert NK == S * K
        # gates: renormalized over k, each in (0, 1]
        g_np = np.asarray(gate)
        np.testing.assert_allclose(g_np.sum(-1), 1.0, atol=1e-5)
        assert (g_np > 0).all()
        for g in range(G):
            for e in range(E):
                sel = eid_np[g] == e
                # kept slots of expert e occupy distinct positions 0..<C
                kept_pos = pos_np[g][sel & keep_np[g]]
                assert len(set(kept_pos.tolist())) == len(kept_pos)
                assert (kept_pos < C).all() and (kept_pos >= 0).all()
                # occupancy == min(routed, C): first-come-first-kept
                assert len(kept_pos) == min(int(sel.sum()), C)
            # per-token: the K expert choices are distinct (top-k)
            per_tok = eid_np[g].reshape(S, K)
            for s in range(S):
                assert len(set(per_tok[s].tolist())) == K
        # drop accounting matches moe_capacity arithmetic exactly
        overflow = sum(
            max(0, int((eid_np[g] == e).sum()) - C)
            for g in range(G) for e in range(E)
        )
        assert int((~keep_np).sum()) == overflow

    @given(st.integers(1, 4))
    @settings(max_examples=4, deadline=None)
    def test_reconstruction_when_undropped(self, K):
        """combine∘dispatch on an un-dropped batch reconstructs the
        top-k gate-weighted mix: with identity experts, y == x."""
        from repro.kernels import moe as moe_k

        D, E, S = 8, 4, 12
        key = jax.random.fold_in(KEY, 7 + K)
        p = moe_mod.init_moe(key, D, 16, E)
        x = jax.random.normal(key, (2, S, D))
        C = moe_mod.moe_capacity(S, E, K, 8.0)   # capacity ≥ all tokens
        _, gate, eid_f, pos, keep = moe_mod.moe_route(p["router"], x,
                                                      top_k=K, capacity=C)
        assert bool(jnp.all(keep))
        buf = moe_k.moe_dispatch(x, eid_f, pos, keep.astype(jnp.float32),
                                 E, C, K, "slot")
        y = moe_k.moe_combine(buf, eid_f.reshape(2, S, K),
                              pos.reshape(2, S, K),
                              gate.reshape(2, S, K), "slot")
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-5)


class TestBase:
    def test_softcap_bounds(self):
        x = jnp.linspace(-1e4, 1e4, 101)
        y = softcap(x, 30.0)
        assert float(jnp.abs(y).max()) <= 30.0

    def test_rmsnorm_unit_rms(self):
        x = jax.random.normal(KEY, (4, 64)) * 7
        y = rmsnorm(x, jnp.ones((64,)))
        rms = np.sqrt((np.asarray(y) ** 2).mean(-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)

    def test_cross_entropy_ignores_masked(self):
        logits = jax.random.normal(KEY, (2, 4, 10))
        labels = jnp.array([[1, 2, -1, -1], [0, -1, -1, -1]])
        l1 = cross_entropy_loss(logits, labels, vocab=10)
        labels2 = jnp.array([[1, 2, -1, -1], [0, -1, -1, -1]])
        assert np.isfinite(float(l1))
        # uniform logits → loss = log(10) on unmasked positions
        lu = cross_entropy_loss(jnp.zeros((1, 3, 10)),
                                jnp.array([[0, 1, -1]]), vocab=10)
        assert float(lu) == pytest.approx(np.log(10), rel=1e-5)
