"""Host-tier restore from the staged power-of-two buffers.

``PagedKVRuntime.stage_out`` hands back the gather's whole (L, W, page,
KV, Dh) host buffers, W = pow2(pages), the padded slots repeating the
last page; ``restore`` copies them to the device as they are and pads on
the device. Checked here: the pools match the host-padding reference bit
for bit; a truncated restore keeps exactly the kept prefix and touches no
other page; the stored array itself goes to the device copy and no host
array of its size is allocated; warm-up at the power-of-two widths
covers every restore shape; ``restores_truncated`` counts the truncated
restores.
"""
import math
import pathlib
import sys
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.page_copy import scatter_pages
from repro.serving import paged_runtime
from repro.serving.backend import JaxModelBackend
from repro.serving.paged_runtime import PagedKVRuntime, ProgramEntry

PAGE = 16


def runtime(n_pages: int = 48) -> PagedKVRuntime:
    """A smoke-size runtime whose pools hold distinct random bytes."""
    rt = PagedKVRuntime(get_config("qwen2-1.5b", smoke=True),
                        n_pages=n_pages, page_size=PAGE)
    rng = np.random.default_rng(7)
    shape, dt = rt.k_pages.shape, rt.k_pages.dtype
    rt.k_pages = jnp.asarray(rng.standard_normal(shape), dt)
    rt.v_pages = jnp.asarray(rng.standard_normal(shape), dt)
    return rt


def staged_program(rt, pid: str, pages: int):
    """A program over ``pages`` pages, staged out and evicted."""
    ids = [rt._alloc_page() for _ in range(pages)]
    rt.programs[pid] = ProgramEntry(ids, pages * PAGE - 3)
    k, v, length = rt.stage_out(pid)
    rt.evict(pid, force=True)
    return k, v, length


def host_padding_reference(pool, staging, pages: int, ids: list[int]):
    """The restore as the host padded it: the staged real pages taken at
    min(i, n - 1) on the host, scattered at the ids padded by repeating
    the last."""
    W = 1 << (pages - 1).bit_length()
    take = np.minimum(np.arange(W), pages - 1)
    padded = np.take(np.asarray(staging)[:, :pages], take, axis=1)
    pad_ids = np.asarray(ids + ids[-1:] * (W - pages), np.int32)
    return np.asarray(scatter_pages(pool, jnp.asarray(padded),
                                    jnp.asarray(pad_ids)))


@pytest.mark.parametrize("pages", [1, 3, 4, 5, 8])
def test_untruncated_restore_matches_host_padding(pages):
    rt = runtime()
    k, v, length = staged_program(rt, "p", pages)
    assert k.shape[1] == v.shape[1] == 1 << (pages - 1).bit_length()
    before = rt.k_pages, rt.v_pages
    ids = rt.restore("p", k, v, length)
    assert len(ids) == pages
    np.testing.assert_array_equal(
        np.asarray(rt.k_pages),
        host_padding_reference(before[0], k, pages, ids))
    np.testing.assert_array_equal(
        np.asarray(rt.v_pages),
        host_padding_reference(before[1], v, pages, ids))
    rt.check()


@pytest.mark.parametrize("staged,kept", [
    (5, 3),      # width drop: staged at 8, kept pages pad to 4
    (7, 5),      # no width drop: both at 8, slots 5..7 held dropped pages
    (8, 1),      # one page kept of a full width
    (6, 6),      # nothing dropped
])
def test_truncated_restore_keeps_the_prefix_only(staged, kept):
    rt = runtime()
    k, v, length = staged_program(rt, "p", staged)
    before_k, before_v = np.asarray(rt.k_pages), np.asarray(rt.v_pages)
    usable = min(length, kept * PAGE)
    ids = rt.restore("p", k, v, usable)
    assert len(ids) == kept and rt.programs["p"].length == usable
    after_k, after_v = np.asarray(rt.k_pages), np.asarray(rt.v_pages)
    np.testing.assert_array_equal(after_k[:, ids], k[:, :kept])
    np.testing.assert_array_equal(after_v[:, ids], v[:, :kept])
    others = np.setdiff1d(np.arange(rt.n_pages), ids)
    np.testing.assert_array_equal(after_k[:, others], before_k[:, others])
    np.testing.assert_array_equal(after_v[:, others], before_v[:, others])
    rt.check()


def backend_with_staged(pages: int = 5, n_pages: int = 48):
    rt = runtime(n_pages)
    be = JaxModelBackend(rt.cfg, runtime=rt, max_len=256, page_size=PAGE)
    ids = [rt._alloc_page() for _ in range(pages)]
    rt.programs["p"] = ProgramEntry(ids, pages * PAGE)
    be.offload_program("p")
    return be


def test_restore_hands_the_stored_buffers_to_the_device(monkeypatch):
    be = backend_with_staged()
    k, v, _ = be.host_caches["p"]
    sent = []
    put = jax.device_put

    def record(x, *a, **kw):
        sent.append(x)
        return put(x, *a, **kw)
    monkeypatch.setattr(paged_runtime.jax, "device_put", record)
    be.restore_program("p")
    assert be.restores == 1
    hosts = [x for x in sent if isinstance(x, np.ndarray)]
    assert len(hosts) == 2
    assert np.shares_memory(hosts[0], k) and hosts[0].shape == k.shape
    assert np.shares_memory(hosts[1], v) and hosts[1].shape == v.shape


def test_restore_allocates_no_host_copy():
    be = backend_with_staged(pages=100, n_pages=128)   # staged 128 wide
    be.restore_program("p")                       # compile outside
    be.offload_program("p")
    k, _, _ = be.host_caches["p"]
    tracemalloc.start()
    try:
        be.restore_program("p")
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert be.restores == 2
    # a host copy of the staged pages would peak at k.nbytes at least
    assert peak < k.nbytes // 4, (peak, k.nbytes)


def test_warm_tiers_covers_every_restore_shape():
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "benchmark"))
    import warmup
    rt = runtime(n_pages=40)
    warmup.warm_tiers(rt, max_pages=16)
    assert not rt.programs and len(rt.free) == rt.n_pages
    warm = paged_runtime._restore_pages._cache_size()
    for staged, kept in [(1, 1), (3, 2), (5, 5), (7, 3), (9, 9), (13, 4),
                         (16, 16), (11, 1)]:
        k, v, length = staged_program(rt, "p", staged)
        rt.restore("p", k, v, min(length, kept * PAGE))
        rt.evict("p", force=True)
    assert paged_runtime._restore_pages._cache_size() == warm


def test_restores_truncated_counts_the_truncated_restores():
    be = backend_with_staged(pages=5)            # 80 tokens staged
    want = 0
    for tokens, truncated in [(None, False), (80, False), (65, False),
                              (64, True), (17, True), (1, True),
                              (200, False)]:
        if "p" not in be.host_caches:
            be.offload_program("p")
        k, _, staged = be.host_caches["p"]
        be.restore_program("p", tokens=tokens)
        want += truncated
        assert be.restores_truncated == want, tokens
        kept = math.ceil(min(staged, tokens or staged) / PAGE)
        assert len(be.runtime.programs["p"].pages) == kept
