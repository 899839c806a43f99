"""Batched paged decode: the whole decode batch through one fused kernel
step must be bit-identical to the per-program loop — in any batch order,
across table-padding widths, through a COW split mid-batch, and the
token-append primitive must conserve page refcounts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.page_copy import append_tokens, append_tokens_ref
from repro.serving.paged_runtime import PagedKVRuntime


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("glm4-9b", smoke=True)
    rt0 = PagedKVRuntime(cfg, n_pages=4, page_size=8)
    params = rt0.model.init(jax.random.PRNGKey(0))
    return cfg, params


def make_runtime(cfg, params, lengths, n_pages=64, seed_base=100):
    """Fresh runtime with one prefilled program per entry of ``lengths``
    (distinct prompts, ragged contexts)."""
    rt = PagedKVRuntime(cfg, n_pages=n_pages, page_size=8)
    pids = []
    for i, n in enumerate(lengths):
        pid = f"p{i}"
        toks = jax.random.randint(jax.random.PRNGKey(seed_base + i),
                                  (n,), 0, cfg.vocab_size)
        rt.prefill(params, pid, toks)
        pids.append(pid)
    return rt, pids


class TestDecodeBatchBitExact:
    def test_batched_equals_sequential(self, setup):
        cfg, params = setup
        lengths = [5, 24, 13, 8]          # ragged: 1..3 pages each
        rt_a, pids = make_runtime(cfg, params, lengths)
        rt_b, _ = make_runtime(cfg, params, lengths)
        batched = rt_a.decode_batch(params, pids)
        seq = [rt_b.decode(params, pid) for pid in pids]
        for b, s in zip(batched, seq):
            assert np.array_equal(np.asarray(b), np.asarray(s))
        # pools end bit-identical too (same pages written, same values)
        assert np.array_equal(np.asarray(rt_a.k_pages),
                              np.asarray(rt_b.k_pages))
        assert np.array_equal(np.asarray(rt_a.v_pages),
                              np.asarray(rt_b.v_pages))

    def test_shuffled_batch_order(self, setup):
        cfg, params = setup
        lengths = [5, 24, 13, 8]
        rt_a, pids = make_runtime(cfg, params, lengths)
        rt_b, _ = make_runtime(cfg, params, lengths)
        perm = [2, 0, 3, 1]
        out_a = rt_a.decode_batch(params, pids)
        out_b = rt_b.decode_batch(params, [pids[i] for i in perm])
        for j, i in enumerate(perm):
            assert np.array_equal(np.asarray(out_a[i]), np.asarray(out_b[j]))

    def test_padding_width_invariance(self, setup):
        """A short program batched with a long one gets a wider sentinel-
        padded table than when batched alone — per-row results must not
        change (dead slots never reach the accumulators)."""
        cfg, params = setup
        rt_a, _ = make_runtime(cfg, params, [5, 60])   # table width 8
        rt_b, _ = make_runtime(cfg, params, [5, 60])
        wide = rt_a.decode_batch(params, ["p0", "p1"])[0]
        narrow = rt_b.decode_batch(params, ["p0"])[0]
        assert np.array_equal(np.asarray(wide), np.asarray(narrow))

    def test_multi_step_continuation(self, setup):
        cfg, params = setup
        lengths = [5, 13]
        rt_a, pids = make_runtime(cfg, params, lengths)
        rt_b, _ = make_runtime(cfg, params, lengths)
        for _ in range(3):
            batched = rt_a.decode_batch(params, pids)
            seq = [rt_b.decode(params, pid) for pid in pids]
            for b, s in zip(batched, seq):
                assert np.array_equal(np.asarray(b), np.asarray(s))

    def test_cow_split_mid_batch(self, setup):
        """Two programs sharing a partially-filled page (radix-style
        adoption) decode in ONE batch: the shared append page must be
        COW-split before the tables are built, both rows must match their
        sequential counterparts, and refcount conservation must hold."""
        cfg, params = setup

        def build():
            rt = PagedKVRuntime(cfg, n_pages=64, page_size=8)
            toks = jax.random.randint(jax.random.PRNGKey(7), (12,), 0,
                                      cfg.vocab_size)
            rt.prefill(params, "a", toks)
            ea = rt.programs["a"]
            # program b adopts a's pages (refcount bump, zero copy), with
            # the last page only partially filled -> the next decode's
            # append page is SHARED between a and b
            from repro.serving.paged_runtime import ProgramEntry
            for pi in ea.pages:
                rt.refs[pi] += 1
            rt.programs["b"] = ProgramEntry(list(ea.pages), ea.length)
            rt.seed_token("b", 11)
            return rt

        rt_a, rt_b = build(), build()
        splits_before = rt_a.cow_splits
        out = rt_a.decode_batch(params, ["a", "b"])
        assert rt_a.cow_splits > splits_before       # the split happened
        rt_a.check()                                  # refcounts conserved
        seq = [rt_b.decode(params, "a"), rt_b.decode(params, "b")]
        for b, s in zip(out, seq):
            assert np.array_equal(np.asarray(b), np.asarray(s))

    def test_zero_length_program_in_batch(self, setup):
        """A zero-context program (nothing prefilled, seeded first token)
        decodes purely against its own new token: the kernel row is all
        dead pages (m=-inf, l=0) and the residual merge degenerates to
        the new token's self-attention — batched alongside a long program
        it must still match its own sequential run."""
        cfg, params = setup
        from repro.serving.paged_runtime import ProgramEntry

        def build():
            rt, pids = make_runtime(cfg, params, [24])
            rt.programs["z"] = ProgramEntry([rt._alloc_page()], 0)
            rt.seed_token("z", 5)
            return rt, pids

        rt_a, pids = build()
        rt_b, _ = build()
        out = rt_a.decode_batch(params, pids + ["z"])
        assert np.isfinite(np.asarray(out[-1])).all()
        seq = [rt_b.decode(params, pid) for pid in pids + ["z"]]
        for b, s in zip(out, seq):
            assert np.array_equal(np.asarray(b), np.asarray(s))
        assert rt_a.programs["z"].length == 1

    def test_empty_and_duplicate_batches(self, setup):
        cfg, params = setup
        rt, pids = make_runtime(cfg, params, [5])
        assert rt.decode_batch(params, []) == []
        with pytest.raises(AssertionError):
            rt.decode_batch(params, [pids[0], pids[0]])


class TestAppendTokensRefcounts:
    def test_refcount_conservation_fuzz(self, setup):
        """Randomized decode batches over programs with shared prefixes:
        after every batch, page refcounts must exactly equal the holders
        (``PagedKVRuntime.check``), and every append must land in an
        exclusively-owned page."""
        cfg, params = setup
        rng = np.random.default_rng(0)
        rt, pids = make_runtime(cfg, params, [5, 9, 17, 24])
        for step in range(4):
            k = int(rng.integers(1, len(pids) + 1))
            batch = list(rng.choice(pids, size=k, replace=False))
            rt.decode_batch(params, batch)
            rt.check()
            for pid in batch:
                e = rt.programs[pid]
                # the page holding the last written token is exclusive
                last_page = e.pages[(e.length - 1) // rt.page_size]
                assert rt.refs[last_page] == 1

    def test_append_tokens_matches_ref(self):
        rng = np.random.default_rng(3)
        L, P, page, KV, Dh, B = 2, 9, 8, 2, 16, 4
        k_pages = jnp.asarray(rng.normal(size=(L, P, page, KV, Dh)),
                              jnp.float32)
        v_pages = jnp.asarray(rng.normal(size=(L, P, page, KV, Dh)),
                              jnp.float32)
        k_tok = jnp.asarray(rng.normal(size=(L, B, KV, Dh)), jnp.float32)
        v_tok = jnp.asarray(rng.normal(size=(L, B, KV, Dh)), jnp.float32)
        page_ids = jnp.asarray([3, 0, 7, 5], jnp.int32)
        offsets = jnp.asarray([0, 7, 3, 3], jnp.int32)
        k2, v2 = append_tokens((k_pages, v_pages), (k_tok, v_tok),
                               page_ids, offsets)
        kr, vr = append_tokens_ref((k_pages, v_pages), (k_tok, v_tok),
                                   page_ids, offsets)
        assert np.array_equal(np.asarray(k2), np.asarray(kr))
        assert np.array_equal(np.asarray(v2), np.asarray(vr))
        # untouched pages stay bit-identical
        untouched = np.ones(P, bool)
        untouched[np.asarray(page_ids)] = False
        assert np.array_equal(np.asarray(k2)[:, untouched],
                              np.asarray(k_pages)[:, untouched])
        assert np.array_equal(np.asarray(v2)[:, untouched],
                              np.asarray(v_pages)[:, untouched])
