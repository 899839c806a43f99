"""The served path's kernels and steps compile for a TPU v5e at the full
width of qwen2-1.5b, with no chip attached: the TPU compiler runs for a
described device and refuses what the chip would (unaligned blocks, too
much fast memory, a program that does not fit). Every compile here keeps
the kernels as Mosaic custom calls, and the whole decode step and the
prefill extend fit the chip at the engine's default KV budget."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import paged_decode_attention_kernel
from repro.kernels.page_copy.kernel import (page_gather_kernel,
                                            page_scatter_kernel,
                                            token_append_kernel)
from repro.serving.backend import kv_pool_budget
from repro.serving.paged_runtime import PagedKVRuntime, _scatter_span
from repro.serving.profiler import DEVICE_PROFILES

CFG = get_config("qwen2-1.5b")
L, KV, DH, H = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim, CFG.num_heads
PAGE, POOL, B = 16, 2048, 8
MAX_LEN, CHUNK = 8192, 2048
HBM = DEVICE_PROFILES["TPU v5 lite"].hbm_bytes


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache without the chip: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def compile_for(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_paged_decode_attention_kernel(one_chip):
    pool = sds(one_chip, (L, POOL, PAGE, KV, DH), "bfloat16")
    c = compile_for(
        lambda q, k, v, t, n, layer: paged_decode_attention_kernel(
            q, k, v, t, n, layer=layer, interpret=False,
            return_residuals=True),
        sds(one_chip, (B, H, DH), "bfloat16"), pool, pool,
        sds(one_chip, (B, MAX_LEN // PAGE), "int32"),
        sds(one_chip, (B,), "int32"), sds(one_chip, (), "int32"))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("kernel", ["gather", "scatter", "append"])
def test_page_copy_kernels(one_chip, kernel):
    pool = sds(one_chip, (L, POOL, PAGE, KV, DH), "bfloat16")
    ids = sds(one_chip, (64,), "int32")
    if kernel == "gather":
        c = compile_for(lambda p, i: page_gather_kernel(p, i,
                                                        interpret=False),
                        pool, ids)
    elif kernel == "scatter":
        c = compile_for(lambda p, s, i: page_scatter_kernel(
            p, s, i, interpret=False),
            pool, sds(one_chip, (L, 64, PAGE, KV, DH), "bfloat16"), ids)
    else:
        tok = sds(one_chip, (L, B, KV, DH), "bfloat16")
        c = compile_for(lambda k, v, kt, vt, i, o: token_append_kernel(
            k, v, kt, vt, i, o, interpret=False),
            pool, pool, tok, tok, sds(one_chip, (B,), "int32"),
            sds(one_chip, (B,), "int32"))
    assert "tpu_custom_call" in c.as_text()


@pytest.fixture(scope="module")
def served(one_chip):
    """The runtime and abstract operands at the engine's default budget
    on a v5e (the published 16 GB: a described chip reports no limit)."""
    headroom = max(16, B)
    budget = kv_pool_budget(CFG, limit=HBM,
                            held_bytes=CFG.param_count() * 4,
                            max_len=MAX_LEN, chunk=CHUNK, max_batch=B,
                            extra_pages=headroom, page_size=PAGE)
    pages = int(budget / (PAGE * CFG.kv_bytes_per_token(2))) + headroom
    assert pages * PAGE >= MAX_LEN, "the default pool must hold a program"
    rt = PagedKVRuntime(CFG, n_pages=1, page_size=PAGE, interpret=False)
    params = jax.tree.map(lambda s: sds(one_chip, s.shape, s.dtype),
                          rt.model.abstract())
    pool = sds(one_chip, (L, pages, PAGE, KV, DH), "bfloat16")
    return rt, params, pool


def test_decode_step_compiles_kernels_and_fits(one_chip, served):
    rt, params, pool = served
    i32 = lambda *shape: sds(one_chip, shape, "int32")
    c = compile_for(rt._decode_step_impl, params, pool, pool, i32(B),
                    i32(B, MAX_LEN // PAGE), i32(B), i32(B), i32(B))
    text = c.as_text()
    # the attention kernel (scanned over layers) and the token append,
    # compiled: an interpreted kernel would leave no custom call
    assert text.count("tpu_custom_call") >= 2
    assert total_bytes(c) < HBM, total_bytes(c)


def test_prefill_extend_fits(one_chip, served):
    rt, params, pool = served
    cache = {k: sds(one_chip, (L, 1, MAX_LEN, KV, DH), "bfloat16")
             for k in ("k", "v")}
    fwd = jax.jit(rt.model.forward, static_argnames=("mode",)).lower(
        params, tokens=sds(one_chip, (1, CHUNK), "int32"), cache=cache,
        cache_len=sds(one_chip, (), "int32"), mode="extend",
        logits_at=sds(one_chip, (), "int32")).compile()
    pool_bytes = 2 * pool.size * pool.dtype.itemsize
    assert pool_bytes + total_bytes(fwd) < HBM
    n = CHUNK // PAGE + 1
    ids = sds(one_chip, (1 << (n - 1).bit_length(),), "int32")
    write = _scatter_span.lower(
        pool, pool, cache["k"], cache["v"], ids, ids,
        sds(one_chip, (), "int32"), sds(one_chip, (), "int32"),
        interpret=False).compile()
    assert "tpu_custom_call" in write.as_text()
    params_bytes = CFG.param_count() * 4
    assert params_bytes + total_bytes(write) < HBM
