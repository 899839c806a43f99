"""Latent attention and held experts on the served path (Moonlight-style
smoke config, CPU): the paged runtime against the benchmark's float32
reference module, the expert layer's shares against the uncut layer,
dropless decode rows alone and in any batch, the latent pool through the
tier moves, a copy-on-write split and prefix adoption, and the latent
kernel against its oracle."""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attention import (paged_mla_decode,
                                            paged_mla_decode_ref)
from repro.kernels.page_copy import append_tokens, append_tokens_ref
from repro.models.common import init_params
from repro.models.mlp import mlp_apply
from repro.models.moe import moe_dropless, moe_specs
from repro.serving.paged_runtime import PagedKVRuntime

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                       / "benchmark"))
import reference_mla  # noqa: E402

SMOKE = get_config("moonshot-v1-16b-a3b", smoke=True)
F32 = dataclasses.replace(SMOKE, compute_dtype="float32")
PAGE = 8

#: float32 on both sides, the reference at the highest matmul precision:
#: only summation order differs (measured under 1e-6 of the largest
#: |logit|); bf16 serving misses by ~2e-2, so 1e-4 tells the two apart
REL_TOL = 1e-4


@pytest.fixture(scope="module")
def weights():
    """The benchmark's weights for the smoke config, in the served tree."""
    return reference_mla.make_weights(reference_mla.served_dims(SMOKE), 3,
                                      0.02)


def tokens(seed, n):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         SMOKE.vocab_size), np.int32)


def prefilled(cfg, params, lengths, n_pages=64):
    """A runtime with one prefilled program per length."""
    rt = PagedKVRuntime(cfg, n_pages=n_pages, page_size=PAGE)
    for i, n in enumerate(lengths):
        rt.prefill(params, f"p{i}", tokens(100 + i, n), max_len=256)
    return rt


def test_runtime_holds_one_latent_pool():
    rt = PagedKVRuntime(SMOKE, n_pages=4, page_size=PAGE)
    pools = {k for k, v in vars(rt).items()
             if k.endswith("_pages") and isinstance(v, jax.Array)}
    assert pools == {"c_pages"}
    assert rt.c_pages.shape == (3, 4, PAGE, SMOKE.mla.cache_width)
    assert SMOKE.kv_bytes_per_token(2) == 3 * SMOKE.mla.cache_width * 2


def test_chunked_prefill_then_decode_matches_reference(weights):
    """Prefill in chunks of 13 (ragged against the 8-token pages), then
    decode through the latent kernel; every position's logits against
    the reference module's full forward of the same tokens."""
    d = reference_mla.served_dims(F32)
    rt = PagedKVRuntime(F32, n_pages=32, page_size=PAGE)
    prompt = tokens(1, 37)
    outs = []
    for s in range(0, len(prompt), 13):
        outs.append(rt.prefill(weights, "a", prompt[s:s + 13], max_len=256))
    fed = []
    for _ in range(6):
        fed.append(int(rt._last["a"]))
        outs.append(rt.decode(weights, "a"))
    seq = np.concatenate([prompt, fed]).astype(np.int32)
    h = reference_mla.hidden(d, False, weights, jnp.asarray(seq))
    ref = np.asarray(h @ weights["lm_head"])
    at = [12, 25, 36] + list(range(37, 43))
    for got, p in zip(outs, at):
        err = np.abs(np.asarray(got) - ref[p]).max()
        assert err <= REL_TOL * np.abs(ref[p]).max(), (p, err)


def test_expert_shares_add_up_to_the_whole_layer():
    """Eight chips of eight experts each: the held parts, with the shared
    experts counted once, add up to the layer that holds all 64, each
    token's weights normalised over all six chosen on every chip."""
    cfg = F32
    m = cfg.moe
    p = init_params(moe_specs(cfg, "float32"), jax.random.PRNGKey(4))
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(5),
                                               (m.num_experts,))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, cfg.d_model))
    whole, n_all = moe_dropless(p, x, cfg)
    zero = {**p, "shared": jax.tree.map(jnp.zeros_like, p["shared"])}
    parts, held = jnp.zeros_like(x), 0
    for chip in range(8):
        sl = slice(8 * chip, 8 * chip + 8)
        share = dataclasses.replace(cfg, moe=dataclasses.replace(
            m, held=8, held_start=8 * chip))
        pc = {**zero, "w1": p["w1"][sl], "w3": p["w3"][sl],
              "w2": p["w2"][sl]}
        y, n = moe_dropless(pc, x, share)
        parts, held = parts + y, held + int(n)
    shared = mlp_apply(p["shared"], x, cfg.activation)
    assert held == int(n_all) == x.shape[0] * x.shape[1] * m.top_k
    # float32 sums in a different order: ~1e-7 of the output's scale
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_decode_rows_are_bit_identical_alone_and_in_any_batch(weights):
    """Dropless: with the router biased so every token picks the same
    experts (a capacity layer would drop some), each row is the same in a
    batch of four, in another order, and alone, in the served bf16."""
    skewed = jax.tree.map(lambda a: a, weights)
    bias = np.zeros(SMOKE.moe.num_experts, np.float32)
    bias[:SMOKE.moe.top_k] = 5.0
    skewed["blocks"]["mlp"]["router_bias"] = jnp.broadcast_to(
        bias, skewed["blocks"]["mlp"]["router_bias"].shape)
    lengths = [5, 24, 13, 17]
    rt_a = prefilled(SMOKE, skewed, lengths)
    rt_b = prefilled(SMOKE, skewed, lengths)
    rt_c = prefilled(SMOKE, skewed, lengths)
    pids = [f"p{i}" for i in range(len(lengths))]
    batched = dict(zip(pids, rt_a.decode_batch(skewed, pids)))
    order = [pids[i] for i in (2, 0, 3, 1)]
    shuffled = dict(zip(order, rt_b.decode_batch(skewed, order)))
    alone = {p: rt_c.decode(skewed, p) for p in pids}
    held, copies = (int(v) for v in rt_a.moe_copies)
    assert held == copies == 4 * SMOKE.moe.top_k * (SMOKE.num_layers - 1)
    for p in pids:
        assert np.array_equal(np.asarray(batched[p]), np.asarray(alone[p]))
        assert np.array_equal(np.asarray(shuffled[p]), np.asarray(alone[p]))
    assert np.array_equal(np.asarray(rt_a.c_pages), np.asarray(rt_c.c_pages))


def test_latent_pool_through_tier_moves_cow_and_adoption(weights):
    """stage_out -> restore, a copy-on-write split and prefix adoption
    move the latent pool's pages bit-exactly."""
    from repro.serving.prefix import PrefixConfig, RadixPrefixIndex
    rt = prefilled(SMOKE, weights, [29], n_pages=32)
    before = np.asarray(rt.c_pages)[:, rt.pages_of("p0")]
    staged = rt.stage_out("p0")
    assert len(staged) == 2 and staged[-1] == 29
    rt.evict("p0", force=True)
    ids = rt.restore("p0", *staged)
    assert np.array_equal(np.asarray(rt.c_pages)[:, ids], before)

    rt.verify_copies = True
    idx = RadixPrefixIndex(PrefixConfig(block_size=PAGE))
    rt.attach_index(idx)
    hashes = (11, 22, 33)
    rt.publish_prefix(idx, "p0", hashes)
    assert rt.adopt_prefix(idx, "q", hashes, max_tokens=23) == 23
    assert rt.pages_of("q") == rt.pages_of("p0")[:3]
    shared = rt.pages_of("q")[2]
    rt.prefill(weights, "q", tokens(100, 29)[23:24], max_len=256)
    assert rt.cow_splits == 1 and rt.copy_checks == [True]
    new = rt.pages_of("q")[2]
    assert new != shared
    pool = np.asarray(rt.c_pages)
    # the split copied the page, then the recomputed row landed on the
    # copy at the same value: the two pages agree
    assert np.array_equal(pool[:, new], pool[:, shared])
    rt.check(idx)


@pytest.mark.parametrize("lens", [[1, 8, 9, 30], [16, 3, 25, 7]],
                         ids=["short-first", "page-boundaries"])
def test_latent_kernel_matches_oracle(lens):
    """Interpret mode, ragged rows over scattered pages of a stacked
    pool: float32 accumulator and statistics to 1e-5 (summation order)."""
    rng = np.random.default_rng(len(lens) + lens[0])
    L, P, H, W, Dv = 2, 24, 4, 128, 32
    c = jnp.asarray(rng.normal(size=(L, P, PAGE, W)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(len(lens), H, W)), jnp.float32)
    tab = jnp.asarray(rng.permutation(P)[:4 * len(lens)]
                      .reshape(len(lens), 4), jnp.int32)
    got = paged_mla_decode(q, c, tab, jnp.asarray(lens), layer=1, scale=0.2,
                           value_dim=Dv, interpret=True)
    want = paged_mla_decode_ref(q, c, tab, jnp.asarray(lens), layer=1,
                                scale=0.2, value_dim=Dv)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)


def test_latent_append_matches_oracle():
    rng = np.random.default_rng(9)
    L, P, W, B = 3, 10, 128, 4
    pool = jnp.asarray(rng.normal(size=(L, P, PAGE, W)), jnp.float32)
    tok = jnp.asarray(rng.normal(size=(L, B, W)), jnp.float32)
    ids = jnp.asarray([6, 1, 9, 3], jnp.int32)
    offs = jnp.asarray([0, 7, 2, 2], jnp.int32)
    (got,) = append_tokens((pool,), (tok,), ids, offs)
    (want,) = append_tokens_ref((pool,), (tok,), ids, offs)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_decode_steps_report_their_expert_copies(tmp_path):
    """An engine serving the smoke model under a profiler trace, with
    telemetry attached: every ``model.sync`` after a decode carries the
    step's copies (held here, all, MoE layers), and the registry's
    counters sum them."""
    import glob

    from jax.profiler import ProfileData

    from repro.core.types import Request
    from repro.obs import Telemetry
    from repro.serving.backend import JaxModelBackend
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.profiler import HardwareProfile
    cfg = dataclasses.replace(SMOKE, moe=dataclasses.replace(
        SMOKE.moe, held=8, held_start=8))
    be = JaxModelBackend(cfg, rng=jax.random.PRNGKey(0), max_len=128,
                         page_size=16)
    eng = Engine(cfg, EngineConfig(max_batch=2, chunk_size=64, block_size=16,
                                   kv_budget_bytes=32 * 16
                                   * cfg.kv_bytes_per_token(2)),
                 HardwareProfile(), backend=be)
    tel = Telemetry()
    eng.attach_telemetry(tel)
    for pid in ("a", "b"):
        eng.submit(Request(pid, 0, 20, 4, 0.0, 0.0, is_last_turn=True), 0.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        now = 0.0
        while eng.has_work:
            now += max(eng.step(now).duration, 1e-3)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    syncs = [dict(e.stats) for p in ProfileData.from_file(path).planes
             for line in p.lines for e in line.events
             if e.name == "model.sync" and "held_copies" in dict(e.stats)]
    moe_layers = cfg.num_layers - cfg.moe.first_k_dense
    assert syncs and all(s["moe_layers"] == moe_layers for s in syncs)
    assert sum(s["copies"] for s in syncs) == \
        be.decode_tokens_computed * cfg.moe.top_k * moe_layers
    held = sum(s["held_copies"] for s in syncs)
    assert 0 < held < sum(s["copies"] for s in syncs)
    text = tel.metrics.exposition()
    assert f'continuum_moe_held_copies_total{{replica="engine0"}} {held}' \
        in text
