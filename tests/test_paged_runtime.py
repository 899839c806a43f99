"""Paged KV runtime: the kernel-level view of Continuum's mechanism —
pinned physical pages survive the tool-call gap and the next turn decodes
against them exactly as a contiguous float32 forward would."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model
from repro.models.reference import compare_logits, reference_logits
from repro.serving.paged_runtime import PagedKVRuntime

#: float32 on both sides, so only summation order differs (<1e-6 of the
#: largest |logit|); the same path in bf16 misses by ~6e-3
REL_TOL = 1e-4


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("glm4-9b", smoke=True),
                              compute_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def assert_matches_reference(cfg, params, rt, pid, tokens, n_steps):
    """Decode ``n_steps`` greedy tokens of ``pid`` through the pages and
    compare every step's logits with the float32 contiguous forward of
    ``tokens`` plus the tokens the paged path fed itself."""
    fed, outs = [], []
    for _ in range(n_steps):
        fed.append(int(rt._last_token(params, pid)))
        outs.append(np.asarray(rt.decode(params, pid)))
    seq = jnp.concatenate([jnp.asarray(tokens, jnp.int32),
                           jnp.asarray(fed, jnp.int32)])
    ref = reference_logits(cfg, params, seq, n_steps)
    for out, r in zip(outs, ref):
        check = compare_logits(out, r, REL_TOL)
        assert check["ok"], check


class TestPagedRuntime:
    def test_decode_matches_contiguous(self, setup):
        cfg, model, params = setup
        tokens = jax.random.randint(jax.random.PRNGKey(1), (24,), 0,
                                    cfg.vocab_size)
        rt = PagedKVRuntime(cfg, n_pages=16, page_size=8)
        logits = rt.prefill(params, "prog", tokens)
        check = compare_logits(logits, reference_logits(cfg, params,
                                                        tokens, 1)[0],
                               REL_TOL)
        assert check["ok"], check
        assert_matches_reference(cfg, params, rt, "prog", tokens, 3)

    def test_ttl_pin_survives_other_program_eviction(self, setup):
        """The Continuum mechanism at page level: program A's pages are
        pinned through its tool call while program B churns pages; A's next
        turn decodes identically to an uninterrupted run."""
        cfg, model, params = setup
        tok_a = jax.random.randint(jax.random.PRNGKey(2), (16,), 0,
                                   cfg.vocab_size)
        tok_b = jax.random.randint(jax.random.PRNGKey(3), (24,), 0,
                                   cfg.vocab_size)
        rt = PagedKVRuntime(cfg, n_pages=12, page_size=8)
        rt.prefill(params, "A", tok_a)
        pages_a = rt.pages_of("A")
        rt.pin("A")                                 # tool call starts; TTL pin
        # program B arrives, allocates, finishes, evicted (pages recycled)
        rt.prefill(params, "B", tok_b)
        rt.evict("B")
        # A returns within TTL: same physical pages, no recompute
        assert rt.pages_of("A") == pages_a
        assert_matches_reference(cfg, params, rt, "A", tok_a, 2)

    def test_eviction_frees_pages(self, setup):
        cfg, model, params = setup
        rt = PagedKVRuntime(cfg, n_pages=8, page_size=8)
        free0 = len(rt.free)
        tokens = jax.random.randint(jax.random.PRNGKey(4), (20,), 0,
                                    cfg.vocab_size)
        rt.prefill(params, "p", tokens)
        assert len(rt.free) < free0
        rt.evict("p")
        assert len(rt.free) == free0

    def test_oom_raises(self, setup):
        cfg, model, params = setup
        rt = PagedKVRuntime(cfg, n_pages=2, page_size=8)
        tokens = jax.random.randint(jax.random.PRNGKey(5), (40,), 0,
                                    cfg.vocab_size)
        with pytest.raises(MemoryError):
            rt.prefill(params, "p", tokens)


class TestPrefillShapes:
    """Prefill compiles one forward per (chunk, scratch) shape, so both
    must come from a small fixed set however chunks and offsets fall."""

    def test_chunked_programs_compile_pow2_shapes(self, setup):
        """Many programs, chunked at assorted offsets (radix adoption
        starts mid-page): every forward is a pair of powers of two with
        the scratch at most max_len's bucket. Page moves are stubbed; only
        the shapes handed to the forward are counted."""
        from repro.serving.backend import JaxModelBackend
        from repro.serving.paged_runtime import ProgramEntry
        cfg, model, params = setup
        max_len, page = 1000, 16
        rt = PagedKVRuntime(cfg, n_pages=max_len // page + 1, page_size=page)
        shapes = set()

        def forward(params, *, tokens, cache, mode, **_):
            shapes.add((tokens.shape[1], cache["k"].shape[2], mode))
            return jnp.zeros((1, 1, cfg.vocab_size)), cache
        rt._forward = forward
        rt._gather_into = lambda cache, e: cache
        rt._scatter_from = lambda cache, e, start, count: None
        rs = np.random.default_rng(0)
        for p in range(300):
            n = int(rs.integers(2, max_len + 1))
            pos = int(rs.integers(0, n)) if p % 2 else 0
            rt.programs["p"] = ProgramEntry([], pos)
            while pos < n:
                chunk = min(int(rs.integers(1, 129)), n - pos)
                rt.prefill(params, "p", np.zeros(chunk, np.int32),
                           pad_to=JaxModelBackend._bucket(chunk),
                           max_len=max_len)
                pos += chunk
                assert rt.programs["p"].length == pos
            rt.evict("p")
        pow2 = lambda x: x & (x - 1) == 0
        assert all(pow2(s) and pow2(t) and s <= t <= 1024
                   for s, t, _ in shapes), sorted(shapes)
        # chunks 1..128 -> 8 widths, scratch 16..1024 -> 7 lengths
        assert len(shapes) <= 8 * 7 * 2, len(shapes)
        assert len(rt.free) == rt.n_pages

    def test_chunk_at_max_len_cap_runs_as_pieces(self, setup):
        """A padded chunk past max_len's bucket runs as exact
        power-of-two pieces and gives the logits and the KV of one
        unpadded prefill."""
        cfg, model, params = setup
        tokens = np.asarray(jax.random.randint(
            jax.random.PRNGKey(9), (31,), 0, cfg.vocab_size))
        rt = PagedKVRuntime(cfg, n_pages=8, page_size=8)
        shapes = []
        fwd = rt._forward

        def record(params, **kw):
            shapes.append((kw["tokens"].shape[1], kw["cache"]["k"].shape[2]))
            return fwd(params, **kw)
        rt._forward = record
        rt.prefill(params, "p", tokens[:20], pad_to=32, max_len=32)
        logits = rt.prefill(params, "p", tokens[20:], pad_to=16, max_len=32)
        # 11 tokens at 20: a 16-wide chunk would end at 36 > 32
        assert shapes == [(32, 32), (8, 32), (2, 32), (1, 32)], shapes
        check = compare_logits(logits, reference_logits(cfg, params,
                                                        tokens, 1)[0],
                               REL_TOL)
        assert check["ok"], check
        assert_matches_reference(cfg, params, rt, "p", tokens, 1)


class TestPinnedEvict:
    """Regression: evict() must refuse a pinned program (the TTL mechanism
    depends on pinned pages surviving) unless force=True."""

    def test_evict_refuses_pinned(self, setup):
        cfg, model, params = setup
        from repro.serving.paged_runtime import ProgramEntry
        rt = PagedKVRuntime(cfg, n_pages=8, page_size=8)
        rt.programs["p"] = ProgramEntry([rt._alloc_page()], 8)
        rt.pin("p")
        assert rt.evict("p") is False          # refused: pages intact
        assert "p" in rt.programs and len(rt.free) == 7
        assert rt.evict("p", force=True) is True
        assert "p" not in rt.programs and len(rt.free) == 8
        assert rt.evict("p") is True           # absent: trivially evicted

    def test_unpin_then_evict(self, setup):
        cfg, model, params = setup
        from repro.serving.paged_runtime import ProgramEntry
        rt = PagedKVRuntime(cfg, n_pages=8, page_size=8)
        rt.programs["p"] = ProgramEntry([rt._alloc_page()], 8)
        rt.pin("p")
        rt.unpin("p")
        assert rt.evict("p") is True and len(rt.free) == 8


class TestPhysicalPrefixSharing:
    """Acceptance: two sequences sharing a radix prefix reference the SAME
    physical HBM page ids, and a divergent append COW-splits — both then
    decode bit-identically to uninterrupted runs."""

    def test_radix_hit_shares_pages_and_cow_splits(self, setup):
        from repro.serving.prefix import PrefixConfig, RadixPrefixIndex
        cfg, model, params = setup
        tokens = jax.random.randint(jax.random.PRNGKey(8), (16,), 0,
                                    cfg.vocab_size)
        rt = PagedKVRuntime(cfg, n_pages=16, page_size=8)
        idx = RadixPrefixIndex(PrefixConfig())
        rt.attach_index(idx)
        rt.prefill(params, "A", tokens)                  # 2 full pages
        hashes = (101, 202)                              # per-block hashes
        assert rt.publish_prefix(idx, "A", hashes) == 0  # fresh publish
        pages_a = rt.pages_of("A")
        # tree + A hold the pages now
        assert all(rt.page_ref(p) == 2 for p in pages_a)

        # B's prompt is identical; the scheduler charges prompt_len-1, so
        # B adopts 15 tokens and recomputes the last one into the page
        adopted = rt.adopt_prefix(idx, "B", hashes, max_tokens=15)
        assert adopted == 15
        assert rt.pages_of("B") == pages_a               # SAME physical ids
        assert all(rt.page_ref(p) == 3 for p in pages_a)

        # divergent append: B writes token 15 into the shared second page
        rt.prefill(params, "B", tokens[15:16])
        assert rt.cow_splits == 1
        pages_b = rt.pages_of("B")
        assert pages_b[0] == pages_a[0]                  # still shared
        assert pages_b[1] != pages_a[1]                  # COW-split copy
        assert rt.page_ref(pages_a[1]) == 2              # A + tree
        assert rt.page_ref(pages_b[1]) == 1              # B exclusive

        # both programs decode exactly like uninterrupted runs
        for name in ("A", "B"):
            assert_matches_reference(cfg, params, rt, name, tokens, 2)

    def test_evicted_sharer_releases_only_its_refs(self, setup):
        from repro.serving.paged_runtime import ProgramEntry
        from repro.serving.prefix import PrefixConfig, RadixPrefixIndex
        cfg, model, params = setup
        rt = PagedKVRuntime(cfg, n_pages=8, page_size=8)
        idx = RadixPrefixIndex(PrefixConfig())
        rt.attach_index(idx)
        rt.programs["A"] = ProgramEntry([rt._alloc_page(), rt._alloc_page()],
                                        16)
        rt.publish_prefix(idx, "A", (1, 2))
        rt.adopt_prefix(idx, "B", (1, 2))
        pages = rt.pages_of("A")
        rt.evict("B")
        assert all(rt.page_ref(p) == 2 for p in pages)   # A + tree remain
        rt.evict("A")
        assert all(rt.page_ref(p) == 1 for p in pages)   # tree only
        assert not rt.free or set(rt.free).isdisjoint(pages)
        # LRU-evicting the tree node releases the physical pages too
        idx.evict(2)
        assert all(rt.page_ref(p) == 0 for p in pages)
        assert set(pages) <= set(rt.free)
