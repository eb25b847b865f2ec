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
import re

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


def _abstract_engine(eng, one_chip, bucket):
    """A started engine's program state and a prefill chunk's operands
    as abstract values on the described chip."""
    import numpy as np
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip),
        (eng.params, eng.proj, eng._cache, eng._logits, eng._pos,
         eng._emitted, eng._max_new, eng._done, eng._trunc, eng.rng,
         eng._btabs.device()))
    chunk = [jax.ShapeDtypeStruct(s, _I32, sharding=one_chip)
             for s in ((1, bucket), (1,), (1,), eng._btabs.rows[:1].shape)]
    return eng, state, chunk


@pytest.fixture(scope="module")
def kernels_on_path():
    """The Pallas kernels on the model's path, compiled (not
    interpreted), as on a TPU."""
    import repro.kernels
    import repro.kernels.kq_decode.paged as paged
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(repro.kernels, "use_kernels", lambda: True)
        mp.setattr(paged, "default_interpret", lambda: False)
        yield


@pytest.fixture(scope="module")
def engine_programs(one_chip, kernels_on_path):
    """A started paged engine (reduced TinyLlama) with its Pallas
    kernels on the path as on a TPU, and its state as abstract values
    on the described chip."""
    from repro.config import ServeConfig
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ServingEngine
    cfg = get_config("tinyllama-1.1b").reduced()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, ServeConfig(
        max_seq_len=64, max_batch=4, page_size=16, prefill_chunk=32))
    eng.start([])
    return _abstract_engine(eng, one_chip, 32)


@pytest.fixture(scope="module")
def phi3_programs(one_chip, kernels_on_path):
    """A started paged engine at ``phi3v-azconv``'s pool widths (32 KV
    heads of 96, KQ-SVD rank 48, 64-token pages, 48 pages at batch 3
    over 3072 tokens) with a narrow residual stream, its kernels on the
    path; state abstract on the described chip.  16 layers make each
    stacked pool (154 MB) larger than the chip's vector memory, as the
    cell's are, so the compiler cannot stage a whole stack there."""
    import numpy as np
    from repro.config import ModelConfig, ServeConfig
    from repro.core.calibration import ModelProjections
    from repro.models import build_model
    from repro.serving import ServingEngine
    L, hkv, dh, r, d = 16, 32, 96, 48, 256
    cfg = ModelConfig(name="phi3-pools", family="dense", n_layers=L,
                      d_model=d, n_heads=hkv, n_kv_heads=hkv, d_head=dh,
                      d_ff=512, vocab_size=512)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    qk = np.zeros((L, hkv, dh, r), np.float32)
    proj = ModelProjections(a_k=qk, b_q=qk, a_v=qk,
                            c_v=np.zeros((L, hkv, r, d), np.float32),
                            ranks_k=[r] * L, ranks_v=[r] * L)
    eng = ServingEngine(cfg, params, ServeConfig(
        max_seq_len=3072, max_batch=3, n_pages=48), projections=proj)
    eng.start([])
    return _abstract_engine(eng, one_chip, 512)


def _lower(programs, program):
    eng, state, chunk = programs
    if program == "decode_chunk":
        return eng._decode_chunk.lower(*state, num_splits=1)
    if program == "prefill_chunk":
        return eng._prefill_chunk.lower(*state[:3], *chunk)
    return eng._fused_step.lower(*state[:3], *chunk, *state[3:],
                                 num_splits=1)


@pytest.mark.parametrize("program, kernels", [
    ("decode_chunk", ["kq_decode_paged_attention"]),
    ("prefill_chunk", ["kq_prefill_paged_attention"]),
    ("fused_step", ["kq_prefill_paged_attention",
                    "kq_decode_paged_attention"])])
def test_engine_programs_carry_kernel_names(engine_programs, program,
                                            kernels):
    text = _lower(engine_programs, program).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == len(kernels), calls
    for name in kernels:
        assert any(_named(name) in ln for ln in calls), (name, calls)


class _Hlo:
    """The instructions of a compiled HLO text: opcode, result shape,
    operands and computation by name, and each computation's root."""

    _LINE = re.compile(r"\s*(ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)")

    def __init__(self, text: str):
        self.header = text.splitlines()[0]
        self.ops, self.root, self.params = {}, {}, {}
        comp = self.entry = None
        for ln in text.splitlines():
            head = re.match(r"(ENTRY )?%([\w.\-]+) .*\{$", ln)
            if head:
                comp = head.group(2)
                if head.group(1):
                    self.entry = comp
                continue
            m = self._LINE.match(ln)
            if not m:
                continue
            root, name, shape, op, rest = m.groups()
            depth, args = 1, ""
            for ch in rest:
                depth += (ch == "(") - (ch == ")")
                if not depth:
                    break
                args += ch
            dims = re.match(r"\w+\[([\d,]*)\]", shape)
            self.ops[name] = dict(
                op=op, comp=comp, line=ln,
                dims=tuple(int(x) for x in dims.group(1).split(",") if x)
                if dims else None,
                args=re.findall(r"%([\w.\-]+)", args))
            if root:
                self.root[comp] = op
            if op == "parameter" and ln.startswith("  %cache"):
                self.params[name] = int(re.search(r"parameter\((\d+)\)",
                                                  ln).group(1))
        self.users = {}
        for name, o in self.ops.items():
            for a in o["args"]:
                self.users.setdefault(a, []).append(name)

    def called_root(self, name: str) -> str:
        """The root opcode of the computation a fusion calls."""
        called = re.search(r"calls=%([\w.\-]+)", self.ops[name]["line"])
        return self.root.get(called.group(1)) if called else None


def _pool_dims(eng):
    """Stacked pool shapes (L, P, Hkv, ps, R), and a layer's: (P, Hkv,
    ps, R), (1, P, Hkv, ps, R) and lane-padded (P, Hkv, ps, R128)."""
    stacks = {tuple(x.shape) for x in jax.tree.leaves(eng._cache["steps"])}
    layers = set()
    for st in stacks:
        lane = -(-st[-1] // 128) * 128
        layers |= {st[1:], (1,) + st[1:], st[1:-1] + (lane,)}
    return stacks, layers


_IN_PLACE = [pytest.param(f, p, id=f"{f}-{p}")
             for f in ("engine_programs", "phi3_programs")
             for p in ("decode_chunk", "prefill_chunk", "fused_step")]


@pytest.fixture(scope="module")
def compiled_texts():
    """Compiled program texts, one compile per (engine, program)."""
    return {}


def _compiled(request, compiled_texts, fixture, program):
    key = (fixture, program)
    if key not in compiled_texts:
        programs = request.getfixturevalue(fixture)
        compiled_texts[key] = (programs[0], _Hlo(
            _lower(programs, program).compile().as_text()))
    return compiled_texts[key]


@pytest.mark.parametrize("fixture, program", _IN_PLACE)
def test_engine_programs_alias_every_pool(request, compiled_texts,
                                          fixture, program):
    """The cache is donated: every pool leaf enters and leaves the
    program as one buffer (``input_output_alias``)."""
    eng, hlo = _compiled(request, compiled_texts, fixture, program)
    aliased = {int(p) for p in re.findall(r"\(\s*(\d+),\s*\{[^}]*\},\s*"
                                          r"(?:may|must)-alias", hlo.header)}
    assert len(hlo.params) == len(jax.tree.leaves(eng._cache))
    assert set(hlo.params.values()) <= aliased, (hlo.params, aliased)


@pytest.mark.parametrize("fixture, program", _IN_PLACE)
def test_engine_programs_never_copy_a_whole_pool(request, compiled_texts,
                                                 fixture, program):
    """No op rebuilds or writes back a stacked pool, and none copies one
    inside the layer or token loops: the only ops there with a stacked
    pool's shape are the in-place writes of whole pages (scatters, fused
    or not).  At phi-3's widths the pools keep the layout they have at
    rest, with the page offset minor, and nothing copies them at all.
    The reduced TinyLlama's pools (17 pages of 16 x 16, f32) rest with
    the page axis minor, as deepseek-4l's 385 pages do, and no write of
    a page keeps that: they are relaid out once as the program starts
    and once as it ends, in the entry computation.  Being small, they
    may also be staged whole into the chip's vector memory (async
    copies and slices the compiler schedules)."""
    eng, hlo = _compiled(request, compiled_texts, fixture, program)
    stacks, _ = _pool_dims(eng)
    tiny = fixture == "engine_programs"
    staged = ("copy-start", "copy-done", "slice-start", "slice-done")

    def allowed(n, o):
        if o["op"] in ("parameter", "get-tuple-element", "bitcast", "tuple",
                       "while", "conditional", "scatter"):
            return True
        if o["op"] == "fusion":
            return hlo.called_root(n) == "scatter"
        if not tiny:
            return False
        return (o["op"] in staged or "ConcatBitcast" in o["line"]
                or (o["op"] == "copy" and o["comp"] == hlo.entry))

    bad = [(n, o["op"]) for n, o in hlo.ops.items()
           if o["dims"] in stacks and not allowed(n, o)]
    assert not bad, bad


@pytest.mark.parametrize("fixture, program", _IN_PLACE)
def test_engine_programs_read_a_layer_only_for_the_kernel(
        request, compiled_texts, fixture, program):
    """A layer's pool is read only to build the kernel's lane-padded
    operand: every op that outputs a layer's pool (or its padded copy)
    feeds nothing but the next op of that chain or the kernel, so no
    layer's pool is copied to be written back."""
    eng, hlo = _compiled(request, compiled_texts, fixture, program)
    _, layers = _pool_dims(eng)
    skip = ("parameter", "get-tuple-element", "tuple", "while",
            "conditional")
    fused = {c for n, o in hlo.ops.items() if o["op"] == "fusion"
             for c in re.findall(r"calls=%([\w.\-]+)", o["line"])}

    def feeds_kernel(name):
        users = hlo.users.get(name, [])
        return bool(users) and all(
            'custom_call_target="tpu_custom_call"' in hlo.ops[u]["line"]
            or (hlo.ops[u]["dims"] in layers and feeds_kernel(u))
            for u in users)

    chains = [n for n, o in hlo.ops.items()
              if o["dims"] in layers and o["op"] not in skip
              and o["comp"] not in fused]
    bad = [(n, hlo.ops[n]["op"]) for n in chains if not feeds_kernel(n)]
    assert chains and not bad, bad


# mimov2f-mooncake (MiMo-V2-Flash, bench/configs/mimo-v2-flash-12l.json):
# 24 slots over 3072 pages, 64 query heads over 8 KV heads (window
# layers, group 8) or 4 (full layers, group 16), KQ-SVD ranks 96/64
# lane-padded to 128 on every call as the engine serves them, 64-token
# pages, a 3-page ring per slot for the 128-token window, 512-token
# prefill chunks over a 16384-token context, 8 of 256 experts held
# (width 2048, d_model 4096)
_MB, _MH, _RK, _RV, _MPS, _MT = 24, 64, 96, 64, 64, 16384


@pytest.mark.parametrize("hkv", [8, 4], ids=["group8", "group16"])
def test_mimo_window_kernel_compiles_for_v5e(one_chip, hkv):
    from repro.kernels.kq_decode.paged import kq_window_paged_attention
    pool = 1 + _MB * 3

    def fn(q, kc, vc, lens, ring, sink):
        return kq_window_paged_attention(q, kc, vc, lens, ring, window=128,
                                         scale=192 ** -0.5, sink=sink,
                                         interpret=False)
    text = _compile(fn, one_chip, ((_MB, _MH, _RK), _BF),
                    ((pool, hkv, _MPS, _RK), _BF),
                    ((pool, hkv, _MPS, _RV), _BF), ((_MB,), _I32),
                    ((_MB, 3), _I32), ((_MH,), jnp.float32))
    assert _named("kq_window_paged_attention") in text


@pytest.mark.parametrize("hkv", [8, 4], ids=["group8", "group16"])
def test_mimo_full_decode_kernel_compiles_for_v5e(one_chip, hkv):
    npp, pool = _MT // _MPS, 3073
    _compile(functools.partial(kq_decode_paged_attention, scale=SCALE,
                               interpret=False, max_len=_MT), one_chip,
             ((_MB, _MH, _RK), _BF), ((pool, hkv, _MPS, _RK), _BF),
             ((pool, hkv, _MPS, _RV), _BF), ((_MB,), _I32),
             ((_MB, npp), _I32))


def test_mimo_full_prefill_kernel_compiles_for_v5e(one_chip):
    """Group 16: 16 x 512 query rows run as two 4096-row calls."""
    npp, pool = _MT // _MPS, 3073
    text = _compile(functools.partial(kq_prefill_paged_attention,
                                      scale=SCALE, interpret=False,
                                      max_len=_MT),
                    one_chip, ((1, _MH, 512, _RK), _BF),
                    ((pool, 4, _MPS, _RK), _BF), ((pool, 4, _MPS, _RV), _BF),
                    ((1,), _I32), ((1,), _I32), ((1, npp), _I32))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 2
    assert all(_named("kq_prefill_paged_attention") in ln for ln in calls)


@pytest.mark.parametrize("rows", [_MB * 8, 512 * 8], ids=["decode",
                                                          "prefill"])
def test_mimo_expert_grouped_matmul_compiles_for_v5e(one_chip, rows):
    def fn(x, w, gs):
        return jax.lax.ragged_dot(x, w, gs,
                                  preferred_element_type=jnp.float32)
    text = _compile(fn, one_chip, ((rows, 4096), _BF),
                    ((8, 4096, 2048), _BF), ((8,), _I32))
    assert "ragged-dot" in text
