"""Distributed integration: sharded train step on a multi-device host mesh.

Runs in a subprocess so the 8-device XLA flag never leaks into this test
process (smoke tests must see 1 device, per the assignment)."""
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
import numpy as np
from repro.configs import TrainConfig, get_smoke_config
from repro.distributed import sharding as shd
from repro.models import get_model
from repro.train import step as step_lib
from repro.data import TokenStream

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_smoke_config("llama3-8b")
model = get_model(cfg)
tc = TrainConfig(learning_rate=1e-3, microbatches=1)
pshard = shd.param_shardings(model, mesh)
state_sh = {"params": pshard, "opt": shd.opt_state_shardings(pshard, mesh)}
stream = TokenStream(cfg.vocab_size, 8, 32, seed=0)

with shd.activation_mesh(mesh):
    step = jax.jit(
        step_lib.make_train_step(model, tc),
        in_shardings=(state_sh, None), out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )
    state = step_lib.init_state(model, jax.random.PRNGKey(0))
    state = jax.device_put(state, state_sh)
    losses = []
    for i in range(8):
        batch = {k: jnp.asarray(v) for k, v in stream.batch_at(i).items()}
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))

# also check a sharded decode path
cache = model.init_cache(8, 64)
cache_sh = shd.cache_shardings(cfg, jax.eval_shape(lambda: model.init_cache(8, 64)), mesh)
params_b16 = jax.tree.map(
    lambda x: x.astype(jnp.bfloat16) if jnp.issubdtype(x.dtype, jnp.floating) else x,
    state["params"])
with shd.activation_mesh(mesh):
    pre = jax.jit(lambda p, b, c: model.prefill(p, b, c))
    logits, cache = pre(params_b16, {"tokens": jnp.ones((8, 16), jnp.int32)}, cache)
print(json.dumps({
    "losses": losses,
    "finite": bool(np.isfinite(losses).all()),
    "decreased": losses[-1] < losses[0],
    "prefill_ok": bool(jnp.all(jnp.isfinite(logits))),
}))
"""


@pytest.mark.slow
def test_sharded_train_step_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["finite"]
    assert res["decreased"], res["losses"]
    assert res["prefill_ok"]
