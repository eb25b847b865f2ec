"""Multi-device coverage via subprocess (the main test process must keep
the single real CPU device — assignment requirement).

The subprocess fakes 8 devices, builds a (2, 4) data x model mesh, and
exercises: parameter sharding rules, sharded train-step lower+compile+run,
compressed decode lower+compile, and elastic checkpoint restore onto a
different mesh shape.
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.config import TrainConfig
from repro.models import build_model
from repro.sharding.partition import params_shardings, use_mesh
from repro.train.steps import make_train_step, make_decode_step
from repro import optim
from repro.launch import specs as S
from repro.checkpoint.manager import CheckpointManager

cfg = get_config("tinyllama-1.1b").reduced()
cfg = dataclasses.replace(cfg, n_layers=4)
model = build_model(cfg)
AUTO = (AxisType.Auto,) * 2
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO)

with use_mesh(mesh):
    params = model.init(jax.random.PRNGKey(0))
    ps = params_shardings(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0))), mesh, fsdp=True)
    params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, ps)
    tc = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=5,
                     checkpoint_every=0)
    opt = optim.init_state(params, tc)
    os_ = params_shardings(jax.eval_shape(
        lambda p: optim.init_state(p, tc), params), mesh, fsdp=True)
    opt = jax.tree.map(lambda x, s: jax.device_put(x, s), opt, os_)
    batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
             "labels": jnp.zeros((8, 32), jnp.int32)}
    bs = S.batch_shardings(batch, mesh)
    batch = jax.tree.map(lambda x, s: jax.device_put(x, s), batch, bs)
    step = jax.jit(make_train_step(model, tc),
                   in_shardings=(ps, os_, bs))
    p2, o2, m = step(params, opt, batch)
    loss = float(m["loss"])
    assert np.isfinite(loss), loss
    print("TRAIN_OK", loss)

    # sharded decode lower+compile (compressed variant)
    ranks = S.default_ranks(cfg)
    cache_abs = S.abstract_cache(model, 8, 64, ranks)
    cs = S.cache_shardings(cache_abs, mesh, seq_sharded=False)
    proj_abs = S.abstract_projections(model, ranks)
    pj = S.projection_shardings(proj_abs, mesh)
    dstep = make_decode_step(model, compressed=True)
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    ts = S.batch_shardings({"t": tok}, mesh)["t"]
    lowered = jax.jit(dstep, in_shardings=(ps, pj, cs, ts,
                                           S.replicated(mesh))).lower(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))),
        proj_abs, cache_abs, tok, jax.ShapeDtypeStruct((), jnp.int32))
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
    print("DECODE_COMPILE_OK")

    # elastic: save on (2,4), restore onto (4,2)
    ck = CheckpointManager(tempfile.mkdtemp(), keep=1, async_save=False)
    ck.save(1, {"params": p2})

mesh2 = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO)
with use_mesh(mesh2):
    template = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    ps2 = params_shardings(template, mesh2, fsdp=True)
    tree, meta = ck.restore({"params": template},
                            shardings={"params": ps2})
    ok = jax.tree.all(jax.tree.map(
        lambda a, b: jnp.allclose(a.astype(jnp.float32),
                                  b.astype(jnp.float32)),
        tree["params"], p2))
    assert bool(ok)
    print("ELASTIC_OK")
"""


@pytest.mark.slow
def test_multidevice_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    env["JAX_PLATFORMS"] = "cpu"       # forced host devices, never a chip
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "TRAIN_OK" in r.stdout
    assert "DECODE_COMPILE_OK" in r.stdout
    assert "ELASTIC_OK" in r.stdout
