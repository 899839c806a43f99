"""Per-arch model correctness: forward/loss finiteness, prefill+decode
parity against the full forward (smoke configs, CPU)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import Model


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch, rng):
    """Assignment requirement: reduced config, one forward/train step on
    CPU, output shapes + no NaNs."""
    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.fold_in(rng, 1))
    B, S = 2, 32
    tokens = jax.random.randint(jax.random.fold_in(rng, 2), (B, S), 0,
                                cfg.vocab_size)
    labels = jax.random.randint(jax.random.fold_in(rng, 3), (B, S), 0,
                                cfg.vocab_size)
    logits, _ = model.forward(params, tokens=tokens, mode="train")
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    loss, grads = jax.value_and_grad(model.loss)(params, tokens, labels)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


#: archs whose smoke config routes through a capacity-bounded MoE
MOE_ARCHS = [a for a in ARCH_IDS if get_config(a, smoke=True).moe is not None]

#: configured capacity: the full forward dispatches all B*(S+extra)
#: tokens at once and may drop a token the one-token decode serves (GShard
#: semantics), removing that expert's share from the token. The affected
#: rows moved by up to 0.44 of their largest |logit| (measured, seed 0:
#: 0.207 moonshot, 0.440 qwen3-moe, both at the prefill position; the
#: others stayed under 0.08); greedy tokens still agree everywhere
CAPACITY_DROP_REL_TOL = 0.5


@pytest.mark.parametrize(
    "arch,capacity", [pytest.param(a, "no_drop", id=a) for a in ARCH_IDS]
    + [pytest.param(a, "configured", id=f"{a}-configured")
       for a in MOE_ARCHS])
def test_prefill_decode_parity(arch, capacity, rng):
    """Greedy serving path == full forward at every position. With
    ``no_drop`` the MoE archs get a capacity no routing can overflow, so
    the two paths must agree to float rounding (1e-3): static-capacity
    dispatch drops different tokens for different token counts, which is
    a dispatch property, not a serving-path one. ``configured`` keeps
    the arch's own capacity factor and holds it to
    CAPACITY_DROP_REL_TOL of each position's largest |logit|."""
    cfg = get_config(arch, smoke=True)
    if cfg.moe is not None and capacity == "no_drop":
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=cfg.moe.num_experts / cfg.moe.top_k))
    model = Model(cfg)
    params = model.init(jax.random.fold_in(rng, 10))
    B, S, extra = 2, 25, 4                      # odd S exercises chunk padding
    tokens = jax.random.randint(jax.random.fold_in(rng, 11), (B, S + extra), 0,
                                cfg.vocab_size)
    full_logits, _ = model.forward(params, tokens=tokens, mode="train")

    def check(got, want):
        got, want = np.asarray(got), np.asarray(want)
        if capacity == "no_drop":
            np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
            return
        err = np.abs(got - want).max(-1) / np.abs(want).max(-1)
        assert (err <= CAPACITY_DROP_REL_TOL).all(), err
        assert (got.argmax(-1) == want.argmax(-1)).all()

    cache = model.init_cache(B, 64)
    pre, cache = model.forward(params, tokens=tokens[:, :S], cache=cache,
                               cache_len=0, mode="prefill", logits_slice=1)
    check(pre[:, 0], full_logits[:, S - 1])
    cl = S
    for i in range(extra):
        step_logits, cache = model.forward(
            params, tokens=tokens[:, S + i:S + i + 1], cache=cache,
            cache_len=jnp.full((B,), cl, jnp.int32), mode="decode",
            logits_slice=1)
        check(step_logits[:, 0], full_logits[:, S + i])
        cl += 1


def test_extend_mode_chunked_prefill(rng):
    """Chunked prefill (engine path): two extends == one prefill."""
    cfg = get_config("glm4-9b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.fold_in(rng, 20))
    B, S = 2, 32
    tokens = jax.random.randint(jax.random.fold_in(rng, 21), (B, S), 0,
                                cfg.vocab_size)
    c1 = model.init_cache(B, 64)
    ref, c1 = model.forward(params, tokens=tokens, cache=c1, cache_len=0,
                            mode="prefill", logits_slice=1)
    c2 = model.init_cache(B, 64)
    _, c2 = model.forward(params, tokens=tokens[:, :16], cache=c2,
                          cache_len=jnp.zeros((B,), jnp.int32), mode="extend",
                          logits_slice=1)
    out, c2 = model.forward(params, tokens=tokens[:, 16:], cache=c2,
                            cache_len=jnp.full((B,), 16, jnp.int32),
                            mode="extend", logits_slice=1)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref[:, 0]),
                               rtol=1e-3, atol=1e-3)


def test_sliding_window_matches_ref(rng):
    """gemma2 local layers: windowed == dense-masked attention."""
    from repro.models.attention import attend_causal, attend_windowed
    B, S, H, D, W = 2, 64, 4, 16, 16
    ks = jax.random.split(jax.random.fold_in(rng, 30), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    win = attend_windowed(q, k, v, scale=0.25, window=W, q_chunk=16)
    # dense reference with the same mask
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.25
    pos = jnp.arange(S)
    mask = (pos[None] <= pos[:, None]) & (pos[None] > pos[:, None] - W)
    s = jnp.where(mask[None, None], s, -2e38)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(win), np.asarray(ref), atol=2e-5)


def test_mamba_chunked_matches_sequential(rng):
    """ssd_chunked == per-token recurrence."""
    from repro.models.mamba2 import ssd_chunked
    B, S, H, P, N = 2, 48, 4, 8, 8
    ks = jax.random.split(jax.random.fold_in(rng, 40), 4)
    xh = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, 1, N), jnp.float32)
    Cm = jax.random.normal(jax.random.fold_in(rng, 41), (B, S, 1, N))
    s0 = jnp.zeros((B, H, P, N))
    y, sf = ssd_chunked(xh, dt, A, Bm, Cm, s0, chunk=16)

    def seq_ref():
        S_ = np.zeros((B, H, P, N))
        ys = []
        for t in range(S):
            dA = np.exp(np.asarray(dt[:, t]) * np.asarray(A))      # (B,H)
            xb = np.asarray(xh[:, t]) * np.asarray(dt[:, t])[..., None]
            Bt = np.repeat(np.asarray(Bm[:, t]), H, axis=1)        # (B,H,N)
            Ct = np.repeat(np.asarray(Cm[:, t]), H, axis=1)
            S_ = dA[..., None, None] * S_ + np.einsum("bhp,bhn->bhpn", xb, Bt)
            ys.append(np.einsum("bhn,bhpn->bhp", Ct, S_))
        return np.stack(ys, axis=1), S_

    yref, sref = seq_ref()
    np.testing.assert_allclose(np.asarray(y), yref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(sf), sref, rtol=2e-4, atol=2e-4)


def test_rwkv_chunked_matches_sequential(rng):
    from repro.models.rwkv6 import _wkv_chunked
    from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref
    B, T, H, K = 2, 40, 2, 8
    ks = jax.random.split(jax.random.fold_in(rng, 50), 5)
    r = jax.random.normal(ks[0], (B, T, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, K)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) - 2.0)
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    s0 = jnp.zeros((B, H, K, K))
    o, sf = _wkv_chunked(r, k, v, logw, u, s0, chunk=16)
    oref, sref = rwkv6_scan_ref(r, k, v, jnp.exp(logw), u, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sref), rtol=1e-4,
                               atol=1e-4)


def test_fp8_kv_cache_decode_close(rng):
    """fp8 KV cache (§Perf cell C): decode stays close to bf16-cache path."""
    import dataclasses
    cfg = get_config("glm4-9b", smoke=True)
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="float8_e4m3fn")
    m16, m8 = Model(cfg), Model(cfg8)
    params = m16.init(jax.random.fold_in(rng, 60))
    B, S = 2, 24
    tokens = jax.random.randint(jax.random.fold_in(rng, 61), (B, S + 1), 0,
                                cfg.vocab_size)
    outs = []
    for model in (m16, m8):
        cache = model.init_cache(B, 64)
        _, cache = model.forward(params, tokens=tokens[:, :S], cache=cache,
                                 cache_len=0, mode="prefill", logits_slice=1)
        lg, _ = model.forward(params, tokens=tokens[:, S:], cache=cache,
                              cache_len=jnp.full((B,), S, jnp.int32),
                              mode="decode", logits_slice=1)
        outs.append(np.asarray(lg))
    # raw e4m3 (no per-block scales — the Pallas kernel adds those on TPU)
    # bounds logit error; greedy decisions must agree
    denom = np.maximum(np.abs(outs[0]).max(), 1e-6)
    assert np.abs(outs[0] - outs[1]).max() / denom < 0.35
    assert (outs[0].argmax(-1) == outs[1].argmax(-1)).all()


_MOE_A2A_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.dist.sharding import axis_rules, default_rules
from repro.launch.mesh import _make_mesh
from repro.models.common import init_params
from repro.models.moe import moe_apply, moe_specs

cfg0 = get_config("moonshot-v1-16b-a3b", smoke=True)       # E=8, top_k=2
mesh = _make_mesh((1, 8), ("data", "model"))
params = init_params(moe_specs(cfg0, "float32"), jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg0.d_model),
                      jnp.float32) * 0.5

outs = {}
for mode in ("ep", "ep_a2a"):
    cfg = dataclasses.replace(
        cfg0, moe=dataclasses.replace(cfg0.moe, sharding_mode=mode))
    rules = default_rules(cfg, mesh, step_kind="prefill")
    with mesh, axis_rules(rules):
        fn = jax.jit(lambda p, xx, c=cfg: moe_apply(p, xx, c))
        outs[mode] = np.asarray(fn(params, x), np.float32)

a, b = outs["ep"], outs["ep_a2a"]
# per-token comparison: capacity drops may differ between the global and
# per-shard-pair capacity plans, zeroing an occasional row in one path only
scale = np.maximum(np.linalg.norm(a, axis=-1), 1e-3)
rel = np.linalg.norm(a - b, axis=-1) / scale
frac_match = float(np.mean(rel < 0.1))
print("frac_match", frac_match, "median_rel", float(np.median(rel)))
assert frac_match >= 0.85, (frac_match, np.sort(rel.ravel())[-5:])
print("OK")
"""


def test_moe_a2a_matches_gspmd_path(rng):
    """Explicit shard_map all-to-all EP == grouped GSPMD dispatch (up to
    capacity-drop ordering and bf16 rounding). Needs 8 devices, so it runs
    in a subprocess with forced host-platform device count."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MOE_A2A_SCRIPT],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0 and "OK" in proc.stdout, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
