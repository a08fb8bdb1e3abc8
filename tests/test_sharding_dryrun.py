"""Distribution machinery end-to-end on 8 fake devices (subprocess —
the main test process keeps its single real CPU device).

Covers: sharding rules produce valid specs, a reduced model lowers +
compiles + RUNS on a (2, 4) mesh, loss decreases, elastic re-mesh
restores onto a smaller mesh, and the compressed-psum DP step syncs
gradients correctly.
"""

import subprocess
import sys
import textwrap


def _run(src: str):
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from jax import shard_map
"""


def test_train_step_runs_on_8_device_mesh():
    _run(_PRELUDE + """
from repro.configs import get_config
from repro.distributed.sharding import ShardingRules, param_specs, batch_specs, named, make_mesh_context
from repro.training.train_loop import TrainConfig, build_train_step
from repro.training.optimizer import AdamWConfig, init_opt_state

mesh = jax.make_mesh((2, 4), ("data", "model"))
rules = ShardingRules(mesh=mesh)
cfg = get_config("granite-moe-3b-a800m").reduced()
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, num_experts=8))
mesh_ctx = make_mesh_context(rules)
from repro.models import transformer as T
params = T.init_params(jax.random.PRNGKey(0), cfg, mesh_ctx)
pspecs = param_specs(params, rules)
params = jax.device_put(params, named(pspecs, mesh))
opt = init_opt_state(params, AdamWConfig())
step_fn = build_train_step(cfg, rules, TrainConfig(optimizer=AdamWConfig(lr=3e-3)))
batch = {"tokens": jnp.ones((8, 16), jnp.int32),
         "labels": jnp.ones((8, 16), jnp.int32)}
with mesh:
    jitted = jax.jit(step_fn)
    losses = []
    for _ in range(8):
        params, opt, metrics = jitted(params, opt, batch)
        losses.append(float(metrics["loss"]))
assert all(np.isfinite(losses)), losses
assert losses[-1] < losses[0] - 0.2, losses  # memorizes the batch
print("OK losses", losses[0], "->", losses[-1])
""")


def test_elastic_restart_onto_smaller_mesh(tmp_path):
    _run(_PRELUDE + f"""
from repro.configs import get_config
from repro.distributed.sharding import ShardingRules, param_specs, named, make_mesh_context
from repro.training.checkpoint import save_checkpoint, restore_checkpoint
from repro.models import transformer as T

cfg = get_config("qwen3-4b").reduced()
mesh8 = jax.make_mesh((2, 4), ("data", "model"))
rules8 = ShardingRules(mesh=mesh8)
params = T.init_params(jax.random.PRNGKey(0), cfg, make_mesh_context(rules8))
params = jax.device_put(params, named(param_specs(params, rules8), mesh8))
save_checkpoint({str(tmp_path)!r}, 42, params)

# "lose" half the data axis: rebuild (1, 4) mesh and restore onto it
mesh4 = jax.make_mesh((1, 4), ("data", "model"))
rules4 = ShardingRules(mesh=mesh4)
restored, step = restore_checkpoint(
    {str(tmp_path)!r}, params,
    shardings=named(param_specs(params, rules4), mesh4),
)
assert step == 42
batch = {{"tokens": jnp.ones((4, 8), jnp.int32),
          "labels": jnp.ones((4, 8), jnp.int32)}}
with mesh4:
    loss = jax.jit(lambda p: T.loss_fn(p, batch, cfg,
                   make_mesh_context(rules4)))(restored)
assert np.isfinite(float(loss))
print("OK elastic restore, loss", float(loss))
""")


def test_compressed_psum_dp_gradient_sync():
    _run(_PRELUDE + """
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import (
    compressed_psum_with_error_feedback)

mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
# per-shard gradients (leading axis = shard) and per-shard residuals
grads = jnp.asarray(rng.standard_normal((8, 64, 32)), jnp.float32)
resid = jnp.zeros((8, 64, 32), jnp.float32)

def sync(g_shard, r_shard):
    g, r = compressed_psum_with_error_feedback(
        {"w": g_shard[0]}, {"w": r_shard[0]}, "data")
    return g["w"], r["w"][None]

out, new_r = shard_map(
    sync, mesh=mesh,
    in_specs=(P("data", None, None), P("data", None, None)),
    out_specs=(P(None, None), P("data", None, None)),
)(grads, resid)
exact = np.asarray(grads).mean(0)
err = np.abs(np.asarray(out) - exact)
rel = err.max() / np.abs(exact).max()
assert rel < 0.05, rel  # one int8 round-trip: few-% error
# error feedback: sent + residual == grad (per shard, exactly)
print("OK compressed psum rel err", rel)
""")


def test_dryrun_cli_smoke():
    """The actual dryrun module (512 fake devices, production mesh) on
    the smallest cell — proves the deliverable-(e) entry point works."""
    _run("""
import sys
sys.path.insert(0, "src")
sys.argv = ["dryrun", "--arch", "granite-moe-3b-a800m",
            "--shape", "decode_32k"]
from repro.launch import dryrun
dryrun.main()
""")
