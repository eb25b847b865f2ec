"""Compile rehearsal: the serving path's Pallas kernels compiled for a
described TPU v5e at TinyLlama-1.1B widths, with no chip attached.

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
from repro.kernels.kq_decode.paged import (kq_decode_paged_attention,
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


@pytest.mark.parametrize("num_splits", [1, 4])
def test_paged_decode_compiles_for_v5e(one_chip, num_splits):
    fn = functools.partial(kq_decode_paged_attention, scale=SCALE,
                           interpret=False, max_len=T,
                           num_splits=num_splits)
    _compile(fn, one_chip, *_DECODE)


@pytest.mark.parametrize("num_splits", [1, 4])
def test_paged_decode_int8_compiles_for_v5e(one_chip, num_splits):
    def fn(q, kc, vc, lens, btab, ks, vs):
        return kq_decode_paged_attention(
            q, kc, vc, lens, btab, scale=SCALE, interpret=False,
            max_len=T, num_splits=num_splits, kscale=ks, vscale=vs)
    pools = [((N_PHYS, HKV, PS, R), _I8)] * 2
    scales = [((N_PHYS, HKV, PS, 1), _BF)] * 2
    _compile(fn, one_chip, _DECODE[0], *pools, *_DECODE[3:], *scales)


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
