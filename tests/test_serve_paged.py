"""Paged KV-cache decode + batched prefill + continuous-batching serve.

Parity pins (the acceptance gates of the paged subsystem):
  * paged decode == dense ring-buffer decode (the oracle) per step,
    across GQA / sliding-window / softcap / rope / qk-norm / partial-rope
    arch configs, with sequences spanning multiple pages;
  * batched prefill logits == full-attention forward logits, and decode
    continued from a prefilled cache == decode continued from a stepped
    cache (dense AND paged);
  * PagePool invariants under random admit/grow/evict traffic
    (hypothesis): no page owned by two live slots, free list conserved.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.kernels.paged_attention import PagePool
from repro.models import decoder as dec
from repro.models.profile import kv_read_bytes_per_token, profile_arch

KEY = jax.random.PRNGKey(0)
#: GQA+rope (llama), window+softcap+post-norm (gemma2), qk-norm+MoE
#: (qwen3), partial rotary (chatglm)
PARITY_ARCHS = ["llama3.2-1b", "gemma2-2b", "qwen3-moe-30b-a3b",
                "chatglm3-6b"]


def _cfg(arch):
    cfg = get_config(arch, reduced=True)
    if cfg.has_moe:
        # full capacity: routing drops would differ between runs only via
        # float noise; parity should not depend on drop order
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    return cfg


class TestPagedDecodeParity:
    @pytest.mark.parametrize("arch", PARITY_ARCHS)
    def test_paged_matches_dense_decode(self, arch):
        """Per-step logits of the paged path vs the dense oracle, over a
        sequence spanning 3 pages (page_size=4, S=12)."""
        cfg = _cfg(arch)
        params = dec.init_model(cfg, KEY)
        B, S, ps = 2, 12, 4
        toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
        cache_d = dec.init_cache(cfg, B, 32, dtype=jnp.float32)
        pcfg = dataclasses.replace(cfg, kv_impl="paged")
        cache_p = dec.init_cache(pcfg, B, 32, dtype=jnp.float32, page_size=ps)
        for i in range(S):
            ld, cache_d = dec.decode_step(params, cfg, toks[:, i:i + 1],
                                          cache_d, jnp.int32(i),
                                          compute_dtype=jnp.float32)
            lp, cache_p = dec.decode_step(params, pcfg, toks[:, i:i + 1],
                                          cache_p, 0,
                                          compute_dtype=jnp.float32)
            np.testing.assert_allclose(np.asarray(lp), np.asarray(ld),
                                       atol=1e-4, rtol=1e-4)
        assert int(cache_p["length"][0]) == S

    @pytest.mark.parametrize("arch", PARITY_ARCHS + ["rwkv6-7b",
                                                     "jamba-v0.1-52b"])
    def test_prefill_matches_forward(self, arch):
        """ONE-forward prefill logits == the training forward's."""
        cfg = _cfg(arch)
        params = dec.init_model(cfg, KEY)
        B, S = 2, 10
        toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
        full, _ = dec.forward(params, cfg, toks, compute_dtype=jnp.float32,
                              remat=False)
        cache = dec.init_cache(cfg, B, 32, dtype=jnp.float32)
        lg, _ = dec.prefill(params, cfg, toks, cache,
                            compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(full),
                                   atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("arch", ["llama3.2-1b", "gemma2-2b",
                                      "rwkv6-7b", "jamba-v0.1-52b"])
    def test_prefill_cache_continues_like_stepping(self, arch):
        """Decode from a prefilled cache == decode from a stepped cache —
        the cache contents (KV rings / pools / recurrent state) agree."""
        cfg = _cfg(arch)
        params = dec.init_model(cfg, KEY)
        B, S = 2, 9
        toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
        stepped = dec.init_cache(cfg, B, 32, dtype=jnp.float32)
        for i in range(S):
            lg_s, stepped = dec.decode_step(params, cfg, toks[:, i:i + 1],
                                            stepped, jnp.int32(i),
                                            compute_dtype=jnp.float32)
        prefilled = dec.init_cache(cfg, B, 32, dtype=jnp.float32)
        lg_p, prefilled = dec.prefill(params, cfg, toks, prefilled,
                                      compute_dtype=jnp.float32)
        nt = jnp.argmax(lg_p[:, -1:, :cfg.vocab], -1).astype(jnp.int32)
        a, _ = dec.decode_step(params, cfg, nt, prefilled, jnp.int32(S),
                               compute_dtype=jnp.float32)
        b, _ = dec.decode_step(params, cfg, nt, stepped, jnp.int32(S),
                               compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)

    def test_paged_prefill_then_decode(self):
        """Paged prefill fills the pool exactly like paged stepping."""
        cfg = _cfg("gemma2-2b")
        pcfg = dataclasses.replace(cfg, kv_impl="paged")
        params = dec.init_model(cfg, KEY)
        B, S, ps = 2, 11, 4
        toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
        stepped = dec.init_cache(pcfg, B, 32, dtype=jnp.float32, page_size=ps)
        for i in range(S):
            lg_s, stepped = dec.decode_step(params, pcfg, toks[:, i:i + 1],
                                            stepped, 0,
                                            compute_dtype=jnp.float32)
        prefilled = dec.init_cache(pcfg, B, 32, dtype=jnp.float32,
                                   page_size=ps)
        lg_p, prefilled = dec.prefill(params, pcfg, toks, prefilled,
                                      compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(lg_p[:, -1:]),
                                   np.asarray(lg_s), atol=1e-4, rtol=1e-4)
        nt = jnp.argmax(lg_p[:, -1:, :cfg.vocab], -1).astype(jnp.int32)
        a, _ = dec.decode_step(params, pcfg, nt, prefilled, 0,
                               compute_dtype=jnp.float32)
        b, _ = dec.decode_step(params, pcfg, nt, stepped, 0,
                               compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)

    def test_ragged_prefill_masks_padding(self):
        """Right-padded batched prefill == per-sequence exact prefill at
        each sequence's own last position (attention-family arch)."""
        cfg = _cfg("llama3.2-1b")
        params = dec.init_model(cfg, KEY)
        lens = [5, 9]
        S = max(lens)
        toks = jax.random.randint(KEY, (2, S), 0, cfg.vocab)
        cache = dec.init_cache(cfg, 2, 32, dtype=jnp.float32)
        lg, cache = dec.prefill(params, cfg, toks, cache,
                                lengths=jnp.asarray(lens),
                                compute_dtype=jnp.float32)
        for b, ln in enumerate(lens):
            solo = dec.init_cache(cfg, 1, 32, dtype=jnp.float32)
            lg_solo, _ = dec.prefill(params, cfg, toks[b:b + 1, :ln], solo,
                                     compute_dtype=jnp.float32)
            np.testing.assert_allclose(
                np.asarray(lg[b, ln - 1]), np.asarray(lg_solo[0, -1]),
                atol=1e-4, rtol=1e-4)


class TestDecodeLoop:
    def test_loop_matches_stepping(self):
        """The fused lax.scan loop emits exactly the tokens the per-token
        host loop would."""
        cfg = _cfg("llama3.2-1b")
        params = dec.init_model(cfg, KEY)
        B, S, gen = 2, 6, 5
        toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
        cache = dec.init_cache(cfg, B, 32, dtype=jnp.float32)
        lg, cache = dec.prefill(params, cfg, toks, cache,
                                compute_dtype=jnp.float32)
        tok = jnp.argmax(lg[:, -1:, :cfg.vocab], -1).astype(jnp.int32)

        # reference: python loop
        ref, rtok, rcache = [], tok, cache
        for i in range(gen):
            ref.append(np.asarray(rtok[:, 0]))
            lgs, rcache = dec.decode_step(params, cfg, rtok, rcache,
                                          jnp.int32(S + i),
                                          compute_dtype=jnp.float32)
            rtok = jnp.argmax(lgs[:, :, :cfg.vocab], -1).astype(jnp.int32)
        want = np.stack(ref, 1)
        got, _, _ = dec.decode_loop(params, cfg, tok, cache, jnp.int32(S),
                                    gen, compute_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(got), want)


class TestSampling:
    def _logits(self, key, B=3, V=50):
        return jax.random.normal(key, (B, V)) * 4.0

    def test_fixed_key_is_deterministic(self):
        lg = self._logits(KEY)
        k = jax.random.PRNGKey(42)
        a = dec.sample_logits(lg, k, temperature=0.8, top_k=10, top_p=0.9)
        b = dec.sample_logits(lg, k, temperature=0.8, top_k=10, top_p=0.9)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == jnp.int32
        assert np.all((np.asarray(a) >= 0) & (np.asarray(a) < 50))

    def test_truncation_limits_collapse_to_argmax(self):
        """top_k=1, tiny top_p, and tiny temperature each pin the draw to
        the argmax token regardless of the key."""
        lg = self._logits(KEY)
        want = np.asarray(jnp.argmax(lg, -1))
        for kw in (dict(top_k=1), dict(top_p=1e-6),
                   dict(temperature=1e-7)):
            for s in range(3):
                got = dec.sample_logits(lg, jax.random.PRNGKey(s), **kw)
                np.testing.assert_array_equal(np.asarray(got), want)

    def test_top_k_restricts_support(self):
        lg = self._logits(KEY, B=64)
        allowed = np.asarray(jax.lax.top_k(lg, 5)[1])
        got = np.asarray(dec.sample_logits(lg, jax.random.PRNGKey(3),
                                           temperature=2.0, top_k=5))
        assert all(got[i] in allowed[i] for i in range(got.shape[0]))

    def test_decode_loop_sampled_is_reproducible_and_greedy_unchanged(self):
        cfg = _cfg("llama3.2-1b")
        params = dec.init_model(cfg, KEY)
        B, S, gen = 2, 6, 5
        toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab)

        def run(key):
            cache = dec.init_cache(cfg, B, 32, dtype=jnp.float32)
            lg, cache = dec.prefill(params, cfg, toks, cache,
                                    compute_dtype=jnp.float32)
            tok = jnp.argmax(lg[:, -1:, :cfg.vocab], -1).astype(jnp.int32)
            got, _, _ = dec.decode_loop(
                params, cfg, tok, cache, jnp.int32(S), gen,
                compute_dtype=jnp.float32, key=key,
                temperature=0.9, top_k=20, top_p=0.95)
            return np.asarray(got)

        k = jax.random.PRNGKey(11)
        a, b = run(k), run(k)
        np.testing.assert_array_equal(a, b)       # fixed key → same tokens
        assert np.all((a >= 0) & (a < cfg.vocab))
        c = run(jax.random.PRNGKey(12))
        # greedy path (key=None) is byte-identical to the pre-sampling
        # loop: covered by test_loop_matches_stepping; here just pin that
        # sampling actually depends on the key (vanishing odds otherwise)
        assert not np.array_equal(a[:, 1:], c[:, 1:]) or gen == 1

    def test_serve_sampling_reproducible_across_kv_impls(self):
        from repro.launch.serve import serve

        kw = dict(reduced=True, batch=2, prompt_len=8, gen=6, cache_len=32,
                  temperature=0.8, top_k=12, top_p=0.9, sample_seed=5)
        a = serve("llama3.2-1b", **kw)
        b = serve("llama3.2-1b", **kw)
        assert a["sampling"] and a["tokens"] == b["tokens"]
        assert a["tokens_in_vocab"]
        p = serve("llama3.2-1b", **kw, kv_impl="paged", page_size=4)
        assert a["tokens"] == p["tokens"]   # sampling is kv-layout-blind


class TestServeEndToEnd:
    def test_serve_paged_equals_dense_tokens(self):
        from repro.launch.serve import serve

        a = serve("llama3.2-1b", reduced=True, batch=2, prompt_len=8, gen=6,
                  cache_len=32)
        b = serve("llama3.2-1b", reduced=True, batch=2, prompt_len=8, gen=6,
                  cache_len=32, kv_impl="paged", page_size=4)
        assert a["tokens"] == b["tokens"]
        assert a["tokens_in_vocab"] and b["tokens_in_vocab"]
        assert b["kv_bytes_per_token"] < a["kv_bytes_per_token"]

    def test_serve_paged_rejects_capacity_overflow(self):
        """The pool does not ring-wrap: generating past cache_len must be
        an error, not silently dropped KV."""
        from repro.launch.serve import serve

        with pytest.raises(ValueError, match="paged serve"):
            serve("llama3.2-1b", reduced=True, batch=2, prompt_len=8,
                  gen=32, cache_len=32, kv_impl="paged", page_size=4)

    def test_serve_continuous_recycles_pages(self):
        from repro.launch.serve import serve_continuous

        out = serve_continuous(
            "llama3.2-1b", slots=3, page_size=4, decode_chunk=4,
            requests=[(5, 4), (9, 6), (3, 5), (12, 4), (7, 3)],
            num_pages=12,  # oversubscribed: forces admit to wait on evict
        )
        assert out["generated"] == [4, 6, 5, 4, 3]
        assert out["tokens_in_vocab"]
        assert out["pool_conserved"]
        assert out["kv_bytes_per_token_paged"] < out["kv_bytes_per_token_dense"]
        # pin every request's tokens against a solo dense-cache reference
        # (same prompt construction as serve_continuous) — a page-recycle
        # or length-mirroring bug would corrupt these, not just counts
        cfg = get_config("llama3.2-1b", reduced=True)
        key = jax.random.PRNGKey(0)
        params = dec.init_model(cfg, key)
        for rid, (plen, g) in enumerate([(5, 4), (9, 6), (3, 5), (12, 4),
                                         (7, 3)]):
            prompt = jax.random.randint(jax.random.fold_in(key, 1000 + rid),
                                        (1, plen), 0, cfg.vocab)
            cache = dec.init_cache(cfg, 1, 64, dtype=jnp.float32)
            lg, cache = dec.prefill(params, cfg, prompt, cache,
                                    compute_dtype=jnp.float32)
            tok = jnp.argmax(lg[:, -1:, :cfg.vocab], -1).astype(jnp.int32)
            want, _, _ = dec.decode_loop(params, cfg, tok, cache,
                                         jnp.int32(plen), g,
                                         compute_dtype=jnp.float32)
            assert out["tokens"][rid] == np.asarray(want)[0].tolist()

    def test_serve_continuous_rejects_oversize_request(self):
        """A request the pool cannot hold even when empty must terminate
        with a typed ``rejected`` outcome (PR 10) — not hang waiting for
        an eviction that cannot help, and not crash the serve loop."""
        from repro.launch.serve import serve_continuous

        out = serve_continuous("llama3.2-1b", slots=2, page_size=8,
                               decode_chunk=4, requests=[(40, 10), (5, 4)],
                               max_seq_len=32)
        assert out["outcomes"] == ["rejected", "completed"]
        assert "pages_per_seq" in out["outcome_detail"][0]
        assert out["outcome_counts"]["rejected"] == 1
        assert out["pool_conserved"]

    def test_serve_continuous_rejects_decreasing_arrivals(self):
        from repro.launch.serve import serve_continuous

        with pytest.raises(ValueError, match="non-decreasing"):
            serve_continuous("llama3.2-1b", slots=2,
                             requests=[(5, 4), (5, 4)],
                             arrival_s=[1.0, 0.5])


class TestPagePoolInvariants:
    def _check(self, pool: PagePool):
        owned = [list(pool.owned_pages(s)) for s in range(pool.slots)]
        flat = [p for o in owned for p in o]
        # no page shared by two live sequences; scratch page never owned
        assert len(flat) == len(set(flat))
        assert 0 not in flat
        # free list conserved across admit/evict
        assert pool.free_pages + len(flat) == pool.num_pages - 1
        # live table rows point at the owned pages, in logical order
        for s, o in enumerate(owned):
            assert list(pool.table[s, :len(o)]) == o

    @given(st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_random_admit_grow_evict(self, seed):
        import random

        rng = random.Random(seed)
        slots, ps, pps = rng.randint(1, 4), rng.choice([2, 4, 8]), 8
        pool = PagePool(rng.randint(4, 40), ps, slots, pps)
        live: dict[int, int] = {}
        for _ in range(30):
            op = rng.random()
            s = rng.randrange(slots)
            if op < 0.45 and s not in live:
                want = rng.randint(1, ps * pps)
                if pool.can_admit(want):
                    pool.admit(s, want)
                    live[s] = want
            elif op < 0.7 and s in live:
                want = min(ps * pps, live[s] + rng.randint(0, 2 * ps))
                try:
                    pool.grow(s, want)
                    live[s] = max(live[s], want)
                except MemoryError:
                    pass  # exhausted pool keeps prior state — still valid
            elif s in live:
                pool.evict(s)
                del live[s]
            self._check(pool)
        for s in list(live):
            pool.evict(s)
        self._check(pool)
        assert pool.free_pages == pool.num_pages - 1

    @given(st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_random_preempt_reserve_interleavings(self, seed):
        """Any admit/preempt/resume(admit-from-reservation)/evict/reserve/
        cancel interleaving conserves the free list AND the reservation
        watermark: pages withheld by ``reserve`` are invisible to other
        admissions and to ``grow``, and every page comes back on evict."""
        import random

        rng = random.Random(seed)
        slots, ps, pps = rng.randint(1, 4), rng.choice([2, 4, 8]), 8
        pool = PagePool(rng.randint(4, 40), ps, slots, pps)
        live: dict[int, int] = {}
        reservations: list[int] = []   # outstanding reserve() token counts

        def check():
            self._check(pool)
            want_res = sum(pool.pages_for(t) for t in reservations)
            assert pool.reserved_pages == want_res
            assert 0 <= pool.reserved_pages <= pool.free_pages
            assert pool.available_pages == \
                pool.free_pages - pool.reserved_pages

        for _ in range(40):
            op = rng.random()
            s = rng.randrange(slots)
            if op < 0.30 and s not in live:
                want = rng.randint(1, ps * pps)
                if reservations and rng.random() < 0.5:
                    # resume path: consume an outstanding reservation
                    want = reservations.pop()
                    if pool.can_admit(want, from_reservation=True):
                        pool.admit(s, want, from_reservation=True)
                        live[s] = want
                    else:  # shouldn't happen: reserve() guaranteed pages
                        raise AssertionError("reservation not honoured")
                elif pool.can_admit(want):
                    pool.admit(s, want)
                    live[s] = want
            elif op < 0.45 and s in live:
                want = min(ps * pps, live[s] + rng.randint(0, 2 * ps))
                try:
                    pool.grow(s, want)
                    live[s] = max(live[s], want)
                except MemoryError:
                    pass  # exhausted/withheld pool keeps prior state
            elif op < 0.60 and s in live:
                freed = pool.preempt(s)
                assert freed == pool.pages_for(live[s])
                del live[s]
            elif op < 0.75:
                want = rng.randint(1, ps * pps)
                if pool.reserve(want):
                    reservations.append(want)
            elif op < 0.85 and reservations:
                pool.cancel_reservation(reservations.pop())
            elif s in live:
                pool.evict(s)
                del live[s]
            check()
        for t in reservations:
            pool.cancel_reservation(t)
        reservations.clear()
        for s in list(live):
            pool.evict(s)
        check()
        assert pool.free_pages == pool.num_pages - 1
        assert pool.reserved_pages == 0

    def test_reserve_withholds_pages_from_admission_and_grow(self):
        pool = PagePool(8, 4, 2, 4)   # 7 allocatable
        assert pool.reserve(16)       # 4 pages withheld
        assert pool.available_pages == 3
        assert not pool.can_admit(16)             # 4 > 3 available
        assert pool.can_admit(16, from_reservation=True)
        pool.admit(0, 12)                         # 3 pages: exactly fits
        with pytest.raises(MemoryError):
            pool.grow(0, 16)          # 4th page exists but is withheld
        pool.admit(1, 16, from_reservation=True)  # consumes the hold
        assert pool.reserved_pages == 0
        pool.evict(1)                 # pages return unreserved
        pool.grow(0, 16)              # no watermark left: grow succeeds

    def test_cancel_more_than_reserved_raises(self):
        pool = PagePool(8, 4, 2, 4)
        assert pool.reserve(4)
        with pytest.raises(ValueError):
            pool.cancel_reservation(8)
        pool.cancel_reservation(4)
        assert pool.reserved_pages == 0

    def test_preempt_returns_pages_and_counts(self):
        pool = PagePool(8, 4, 2, 4)
        pool.admit(0, 10)             # 3 pages
        assert pool.preempt(0) == 3
        assert pool.free_pages == 7 and pool.preempt_count == 1
        with pytest.raises(ValueError):
            pool.preempt(0)           # not live any more

    def test_double_admit_rejected(self):
        pool = PagePool(8, 4, 2, 4)
        pool.admit(0, 6)
        with pytest.raises(ValueError):
            pool.admit(0, 4)

    def test_exhaustion_raises(self):
        pool = PagePool(4, 4, 2, 4)  # 3 allocatable pages
        pool.admit(0, 12)
        with pytest.raises(MemoryError):
            pool.admit(1, 8)


class TestKVBytesAccounting:
    def test_paged_charges_used_pages_not_max_len(self):
        cfg = get_config("llama3.2-1b", reduced=True)
        dense = kv_read_bytes_per_token(cfg, 8, cache_len=4096)
        paged = kv_read_bytes_per_token(cfg, 8, cache_len=4096, page_size=16)
        assert paged < dense
        # one page of 16 positions vs the 4096-slot ring
        assert paged == pytest.approx(dense * 16 / 4096)

    def test_window_caps_both_layouts(self):
        cfg = get_config("gemma2-2b", reduced=True)  # window=32 + global
        near_full = kv_read_bytes_per_token(cfg, 4000, cache_len=4096,
                                            page_size=16)
        dense = kv_read_bytes_per_token(cfg, 4000, cache_len=4096)
        # the window layer reads ~32 positions in both; the global layer
        # dominates and pages≈ring at full occupancy
        assert near_full <= dense * 1.1

    def test_profile_arch_decode_mode(self):
        from repro.core import default_fleet

        fleet = default_fleet()
        base = profile_arch("llama3.2-1b", fleet)
        dense = profile_arch("llama3.2-1b", fleet, decode_kv_len=8,
                             kv_cache_len=4096)
        paged = profile_arch("llama3.2-1b", fleet, decode_kv_len=8,
                             kv_cache_len=4096, kv_page_size=16)
        # decode mode adds KV read traffic to the attention rows, and the
        # paged accounting charges (far) less of it at short lengths
        att = next(i for i, p in enumerate(base) if p.kind == "attention")
        assert dense[att].input_bytes > paged[att].input_bytes
        assert paged[att].input_bytes > base[att].input_bytes
