"""Mini dry-run: the full launch machinery on an 8-device host mesh.

Runs in a subprocess so the forced device count doesn't leak into the
other tests (jax locks device topology at first init).
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.configs.base import ShapeConfig
from repro.core.qat import QATConfig
from repro.models import registry
from repro.models.common import sharding_rules
from repro.sharding.policy import ShardingPolicy
from repro.launch.steps import make_train_step, make_decode_step, make_optimizer
from repro.launch import hlo_cost

results = {}
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
for arch in ["tinyllama_1_1b", "mamba2_1_3b", "mixtral_8x7b"]:
    cfg = configs.reduced(configs.get(arch))
    policy = ShardingPolicy(mesh)
    model = registry.get_model(cfg)
    qcfg = QATConfig()
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspec = policy.params(params_shape)
    shape = ShapeConfig("mini", 64, 8, "train")
    in_specs = registry.input_specs(cfg, shape)
    bspec = policy.batch(in_specs)
    opt = make_optimizer(params_shape)
    ospec = policy.params(jax.eval_shape(opt.init, params_shape))
    fn = make_train_step(model, opt, qcfg, accum=2, opt_level=1,
                         grad_shardings=pspec)
    with mesh, sharding_rules(policy.activation_rules()):
        compiled = jax.jit(
            fn, in_shardings=(pspec, ospec, bspec, NamedSharding(mesh, P())),
            out_shardings=(pspec, ospec, None), donate_argnums=(0, 1),
        ).lower(params_shape, jax.eval_shape(opt.init, params_shape),
                in_specs, jax.ShapeDtypeStruct((), jnp.int32)).compile()
    an = hlo_cost.analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    results[arch + "/train"] = {
        "flops": an["flops"], "bytes": an["bytes"],
        "collective_total": an["collective_bytes"]["total"],
        "temp": mem.temp_size_in_bytes,
    }
    # decode path
    cache_shape = jax.eval_shape(lambda: model.init_cache(8, 64))
    cspec = policy.cache(cache_shape, 8)
    dfn = make_decode_step(model, qcfg)
    tok = jax.ShapeDtypeStruct((8,), jnp.int32)
    with mesh, sharding_rules(policy.activation_rules(seq_sharded=False)):
        dcompiled = jax.jit(
            dfn, in_shardings=(pspec, cspec, policy.batch({"t": tok})["t"],
                               NamedSharding(mesh, P())),
            out_shardings=(None, cspec), donate_argnums=(1,),
        ).lower(params_shape, cache_shape, tok,
                jax.ShapeDtypeStruct((), jnp.int32)).compile()
    results[arch + "/decode"] = {"ok": True}
print(json.dumps(results))
"""


@pytest.mark.slow
def test_mini_dryrun_compiles():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=520,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    for arch in ["tinyllama_1_1b", "mamba2_1_3b", "mixtral_8x7b"]:
        tr = results[arch + "/train"]
        assert tr["flops"] > 0 and tr["bytes"] > 0
        assert tr["collective_total"] > 0, "sharded step must communicate"
        assert results[arch + "/decode"]["ok"]
