"""The latent/expert reference reads each served token through the
router's ties: where a chosen and an unchosen expert lie closer than
``ROUTE_TIE``, either routing of the token's own forward is the
reference's. Without ties it is the strict read; a forward that took
the other side of a tie reads 0; at the published widths a forward
rounded to bfloat16 reads far under the configuration's limit, the
float8 control above it (CPU)."""
from __future__ import annotations

import dataclasses
import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
import reference_mla as M  # noqa: E402

CFG = json.loads((BENCH / "configs" / "moonlight-16b-a3b-ep8pp2.json")
                 .read_text())
LIMIT = CFG["check"]["max_gap_sd"]
#: the smoke model's widths with the configuration's routing and share
SMALL = M.Dims(layers=3, d_model=64, heads=4, kv_lora=32, nope=16, rope=8,
               v_head=16, dense_ff=192, expert_ff=32, router_experts=64,
               top_k=6, held=8, held_start=0, shared=2, vocab=256,
               theta=50000.0, eps=1e-5, scaling=2.446, first_dense=1,
               norm_topk=True)
S = 128


def small():
    params = M.make_weights(SMALL, 5, 0.02)
    seq = np.random.default_rng(5).integers(0, SMALL.vocab, S)
    return params, seq.astype(np.int32)


def strict(d, params, seq, at, tok):
    h = M.hidden(d, False, params, jnp.asarray(seq))
    g, _ = M.pair_gaps(d, False, params, h, jnp.asarray(at),
                       jnp.asarray(tok))
    return np.asarray(g)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def forced(d, params, toks, t, swap_at):
    """The forward with, at the expert layer and rank pair ``swap_at``
    = (layer, chosen rank, unchosen rank), position ``t``'s chosen
    expert swapped for the unchosen one; its final-norm hidden states
    and each expert layer's selection values and ranking."""
    x = params["embed"][toks]
    x, _ = jax.lax.scan(functools.partial(M._layer, d, False, False), x,
                        params["dense_layers"])
    sels = []
    for i in range(d.layers - d.first_dense):
        p = jax.tree.map(lambda w: w[i], params["blocks"])
        o, _, _ = M._attention(d, False, M._rms(x, p["ln1"], d.eps),
                               p["attn"])
        x = x + o
        h = M._rms(x, p["ln2"], d.eps)
        scores, sel = M._route(d, False, h, p["mlp"])
        vals, top = jax.lax.top_k(sel, d.top_k + 2)
        ids = top[:, :d.top_k]
        if swap_at is not None and swap_at[0] == i:
            ids = ids.at[t, swap_at[1]].set(top[t, swap_at[2]])
        sels.append((vals, top))
        x = x + M._experts(d, False, h, p["mlp"], ids, scores)
    return M._rms(x, params["final_norm"], d.eps), sels


def test_without_a_tie_band_the_read_is_the_strict_one():
    params, seq = small()
    at = np.arange(S // 2, S, dtype=np.int32)
    tok = np.random.default_rng(6).integers(0, SMALL.vocab, len(at))
    tok = tok.astype(np.int32)
    _, kv_dense, kv_moe = M.forward(SMALL, False, params, jnp.asarray(seq))
    got = M.branch_gaps(SMALL, params, jnp.asarray(seq), kv_dense, kv_moe,
                        jnp.asarray(at), jnp.asarray(tok), 0.0)
    want = strict(SMALL, params, seq, at, tok)
    assert np.median(want) > 0.5                # the read has work to do
    # one position's forward against the whole sequence's: float32 sums
    # in another order, ~1e-6 of a gap
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    # with the band the read only ever finds a routing as close or closer
    tol = M.gaps(SMALL, params, seq, at, tok, S)
    assert np.all(tol <= want + 1e-4)


def test_the_other_side_of_a_tie_reads_zero():
    """Search the expert layers for a held expert at a tie whose swap
    changes the position's first choice: the strict read puts that
    token below the best, the read through the ties at 0."""
    params, seq = small()
    toks = jnp.asarray(seq)
    _, sels = forced(SMALL, params, toks, 0, None)
    k = SMALL.top_k
    held = lambda e: SMALL.held_start <= e < SMALL.held_start + SMALL.held
    found = 0
    for layer, (vals, top) in enumerate(sels):
        vals, top = np.asarray(vals), np.asarray(top)
        for t in range(S // 2, S):
            if not (vals[t, k - 1] - vals[t, k] < M.ROUTE_TIE
                    and (held(top[t, k - 1]) or held(top[t, k]))):
                continue
            h, _ = forced(SMALL, params, toks, t, (layer, k - 1, k))
            tok = np.asarray([jnp.argmax(h[t] @ params["lm_head"])],
                             np.int32)
            before = strict(SMALL, params, seq, np.asarray([t], np.int32),
                            tok)[0]
            if before < 0.05:
                continue            # the swap left the first choice
            got = M.gaps(SMALL, params, seq, np.asarray([t], np.int32), tok,
                         S)[0]
            assert got < 1e-4, (layer, t, before, got)
            found += 1
            if found == 3:
                return
    assert found, "no tie of a held expert moved a first choice"


def test_bfloat16_reads_under_the_limit_and_float8_above():
    """The published widths, one dense and two expert layers: the tokens
    a forward with every matmul input rounded to bfloat16 puts first
    read under a fifth of the configuration's limit; the float8
    control's (``reference.py``'s rounding) read above the limit."""
    d = M.shape_of(CFG["model"])
    d = dataclasses.replace(d, layers=3)
    params = M.make_weights(d, 12345, CFG["model"]["initializer_range"])
    seq = np.random.default_rng(0).integers(0, d.vocab, 512)
    seq = seq.astype(np.int32)
    at = np.arange(256, 512, dtype=np.int32)

    def bf16(x, axis=None):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def mm(x, w, rounded):
        if rounded:
            x, w = bf16(x), bf16(w)
        return jnp.dot(x, w, precision=reference.HIGHEST)

    saved = M._mm, M._fp8
    M._mm, M._fp8 = mm, bf16
    try:                                  # traced afresh, outside the cache
        h = jax.jit(lambda p, t: M.forward.__wrapped__(d, True, p, t)[0])(
            params, jnp.asarray(seq))
    finally:
        M._mm, M._fp8 = saved
    tok = np.asarray(jnp.argmax(h[at] @ params["lm_head"], -1), np.int32)
    assert float(M.gaps(d, params, seq, at, tok, 512).max()) < LIMIT / 5
    control = M.gaps(d, params, seq, at, tok, 512, control=True)
    assert float(control.max()) > LIMIT
