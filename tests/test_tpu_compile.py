"""Compile rehearsal: the serving path's Pallas kernels compiled for a
described TPU v5e at TinyLlama-1.1B widths, and the paged decode kernel
at the benchmark cells' decode shapes too, with no chip attached.

Mosaic refuses things interpret mode accepts (tiles not aligned to the
layout, more VMEM than a kernel may use), so these compiles guard the
chip path from CPU.  ``interpret=False`` is passed explicitly because
the backend here is the CPU.  The topology is described inside a
fixture — never at import — so every pytest-xdist worker collects the
same tests and only the worker running this file loads the TPU library.
"""
import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kq_decode.kq_decode import kq_decode_attention
from repro.kernels.kq_decode.paged import (decode_heads_per_block,
                                           kq_decode_paged_attention,
                                           kq_prefill_paged_attention)

# TinyLlama-1.1B serving shapes: 8 slots, 32 query / 4 kv heads, KQ-SVD
# rank 64 (lane-padded to 128 by the kernels), 16-token pages over a
# 544-token context, 256-token prefill chunks
B, H, HKV, R, PS, T, S = 8, 32, 4, 64, 16, 544, 256
NPP = T // PS
N_PHYS = B * NPP + 1
SCALE = 1.0 / math.sqrt(64)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:      # no TPU compiler installed
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


_BF, _I32, _I8 = jnp.bfloat16, jnp.int32, jnp.int8
_DECODE = [((B, H, R), _BF), ((N_PHYS, HKV, PS, R), _BF),
           ((N_PHYS, HKV, PS, R), _BF), ((B,), _I32), ((B, NPP), _I32)]

# decode shapes (B, H, Hkv, R, page_size, T, pool pages): TinyLlama's
# above, and the benchmark cells' (bench/configs): phi-3's MHA at rank
# 48 (lane-padded to 128 by the kernel), 48 pages; deepseek-67b's GQA-8
# at rank 64, 384 pages
_SHAPES = {"tinyllama": (B, H, HKV, R, PS, T, B * NPP),
           "phi3v-azconv": (3, 32, 32, 48, 64, 3072, 48),
           "dsk67b-sharegpt": (48, 64, 8, 64, 64, 2048, 384)}
_DECODE_CASES = [pytest.param("tinyllama", 1, id="1"),
                 pytest.param("tinyllama", 4, id="4")] + [
    pytest.param(cell, n, id=f"{cell}-{n}")
    for cell in ("phi3v-azconv", "dsk67b-sharegpt") for n in (1, 8)]


def _decode_shapes(shape, dtype=_BF):
    b, h, hkv, r, ps, t, pages = _SHAPES[shape]
    pool = ((pages + 1, hkv, ps, r), dtype)
    return ([((b, h, r), _BF), pool, pool, ((b,), _I32),
             ((b, t // ps), _I32)], (pages + 1, hkv, ps, 1), t)


@pytest.mark.parametrize("shape, num_splits", _DECODE_CASES)
def test_paged_decode_compiles_for_v5e(one_chip, shape, num_splits):
    shapes, _, t = _decode_shapes(shape)
    fn = functools.partial(kq_decode_paged_attention, scale=SCALE,
                           interpret=False, max_len=t,
                           num_splits=num_splits)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("shape, num_splits", _DECODE_CASES)
def test_paged_decode_int8_compiles_for_v5e(one_chip, shape, num_splits):
    shapes, scale_pool, t = _decode_shapes(shape, _I8)

    def fn(q, kc, vc, lens, btab, ks, vs):
        return kq_decode_paged_attention(
            q, kc, vc, lens, btab, scale=SCALE, interpret=False,
            max_len=t, num_splits=num_splits, kscale=ks, vscale=vs)
    _compile(fn, one_chip, *shapes, *[(scale_pool, _BF)] * 2)


@pytest.mark.parametrize("shape", list(_SHAPES))
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_decode_folds_every_kv_head(shape, int8):
    """At TinyLlama's and both cells' shapes one decode program moves
    the page of every KV head: the VMEM budget admits them all."""
    _, _, hkv, r, ps, _, _ = _SHAPES[shape]
    r = -(-r // 128) * 128                      # as lane-padded
    assert decode_heads_per_block(hkv, ps, r, r, 1 if int8 else 2,
                                  2 if int8 else 0) == hkv


def test_paged_prefill_compiles_for_v5e(one_chip):
    fn = functools.partial(kq_prefill_paged_attention, scale=SCALE,
                           interpret=False, max_len=T)
    _compile(fn, one_chip, ((1, H, S, R), _BF), *_DECODE[1:3],
             ((1,), _I32), ((1,), _I32), ((1, NPP), _I32))


def test_dense_decode_compiles_for_v5e(one_chip):
    fn = functools.partial(kq_decode_attention, scale=SCALE,
                           interpret=False, max_len=T)
    _compile(fn, one_chip, ((B, H, R), _BF), ((B, HKV, T, R), _BF),
             ((B, HKV, T, R), _BF), ((B,), _I32))


def _named(name):
    """The compiled text's HLO instruction for the Pallas call ``name``
    (``pl.pallas_call(name=...)`` names the custom call after it)."""
    return f"%{name}."


def _flash(q, k, v):
    from repro.kernels.flash.flash import flash_attention
    return flash_attention(q, k, v, interpret=False)



_PREFILL = [((1, H, S, R), _BF), *_DECODE[1:3], ((1,), _I32),
            ((1,), _I32), ((1, NPP), _I32)]
_KERNELS = {
    "kq_decode_paged_attention": (functools.partial(
        kq_decode_paged_attention, scale=SCALE, interpret=False,
        max_len=T, num_splits=1), _DECODE),
    "kq_decode_paged_split": (functools.partial(
        kq_decode_paged_attention, scale=SCALE, interpret=False,
        max_len=T, num_splits=4), _DECODE),
    "kq_prefill_paged_attention": (functools.partial(
        kq_prefill_paged_attention, scale=SCALE, interpret=False,
        max_len=T), _PREFILL),
    "kq_decode_attention": (functools.partial(
        kq_decode_attention, scale=SCALE, interpret=False, max_len=T),
        [((B, H, R), _BF), ((B, HKV, T, R), _BF), ((B, HKV, T, R), _BF),
         ((B,), _I32)]),
    "flash_attention": (_flash, [((1, H, S, 64), _BF),
                                 ((1, HKV, S, 64), _BF),
                                 ((1, HKV, S, 64), _BF)]),
}


@pytest.mark.parametrize("name", list(_KERNELS))
def test_compiled_kernel_carries_its_name(one_chip, name):
    fn, shapes = _KERNELS[name]
    text = _compile(fn, one_chip, *shapes)
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(_named(name) in ln for ln in calls), calls


@pytest.fixture(scope="module")
def engine_programs(one_chip):
    """A started paged engine (reduced TinyLlama) with its Pallas
    kernels on the path as on a TPU, and its state as abstract values
    on the described chip."""
    import numpy as np
    import repro.kernels
    import repro.kernels.kq_decode.paged as paged
    from repro.config import ServeConfig
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ServingEngine
    cfg = get_config("tinyllama-1.1b").reduced()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, ServeConfig(
        max_seq_len=64, max_batch=4, paged=True, page_size=16,
        chunked_prefill=True, prefill_chunk=32))
    eng.start([])
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip),
        (eng.params, eng.proj, eng._cache, eng._logits, eng._pos,
         eng._emitted, eng._max_new, eng._done, eng._trunc, eng.rng,
         eng._btabs.device()))
    chunk = [jax.ShapeDtypeStruct(s, _I32, sharding=one_chip)
             for s in ((1, 32), (1,), (1,), eng._btabs.rows[:1].shape)]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(repro.kernels, "use_kernels", lambda: True)
        mp.setattr(paged, "default_interpret", lambda: False)
        yield eng, state, chunk


@pytest.mark.parametrize("program, kernels", [
    ("decode_chunk", ["kq_decode_paged_attention"]),
    ("prefill_chunk", ["kq_prefill_paged_attention"]),
    ("fused_step", ["kq_prefill_paged_attention",
                    "kq_decode_paged_attention"])])
def test_engine_programs_carry_kernel_names(engine_programs, program,
                                            kernels):
    eng, state, chunk = engine_programs
    if program == "decode_chunk":
        low = eng._decode_chunk.lower(*state, num_splits=1)
    elif program == "prefill_chunk":
        low = eng._prefill_chunk.lower(*state[:3], *chunk)
    else:
        low = eng._fused_step.lower(*state[:3], *chunk, *state[3:],
                                    num_splits=1)
    text = low.compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == len(kernels), calls
    for name in kernels:
        assert any(_named(name) in ln for ln in calls), (name, calls)
