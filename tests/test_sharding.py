"""Sharding rules + launch-layer behaviour (host-scale mesh + spec validation).

The full 512-device validation is the dry-run (repro.launch.dryrun, separate
process because it forces the device count); here we verify the SPEC TREES are
structurally valid for the production mesh shape and that the sharded train
step runs on a 1×1 host mesh.
"""
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ARCH_IDS, get_config, get_smoke_config
from repro.launch import sharding as SH
from repro.models import transformer as T
from repro.optim.adamw import init_opt_state


class FakeMesh:
    """Axis-name/size stand-in so spec construction can target 16×16 without
    actually building 256 devices inside the test process."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


PROD = FakeMesh({"data": 16, "model": 16})
PROD2 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def _spec_valid(spec, shape, mesh) -> bool:
    if spec is None:
        return True
    dims = list(spec)
    assert len(dims) <= len(shape), (spec, shape)
    used = []
    for d, n in zip(dims, shape):
        if d is None:
            continue
        names = d if isinstance(d, tuple) else (d,)
        size = 1
        for nm in names:
            assert nm in mesh.shape, f"unknown axis {nm}"
            assert nm not in used, f"axis {nm} used twice in {spec}"
            used.append(nm)
            size *= mesh.shape[nm]
        assert n % size == 0, f"dim {n} not divisible by {size} in {spec} {shape}"
    return True


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh", [PROD, PROD2], ids=["1pod", "2pod"])
@pytest.mark.parametrize("fsdp", [False, True], ids=["tp", "fsdp"])
def test_param_specs_divisible(arch, mesh, fsdp):
    cfg = get_config(arch)
    p_struct = jax.eval_shape(
        lambda k: T.init_params(cfg, k, jnp.bfloat16),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = SH.param_pspecs(cfg, p_struct, mesh, fsdp=fsdp)
    jax.tree.map(
        lambda s, l: _spec_valid(s, l.shape, mesh), specs, p_struct,
        is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", ["qwen2_5_32b", "granite_20b", "mamba2_130m",
                                  "recurrentgemma_9b"])
def test_cache_specs_divisible(arch):
    from repro.models.cache import KVCache
    cfg = get_config(arch)
    cache = jax.eval_shape(
        lambda: KVCache.init(cfg, 128, 32_768, jnp.bfloat16))
    specs = SH.cache_pspecs(cfg, cache, PROD, 128)
    jax.tree.map(
        lambda s, l: _spec_valid(s, l.shape, PROD), specs, cache,
        is_leaf=lambda x: isinstance(x, P))


def test_opt_specs_add_zero1_data_axis():
    cfg = get_config("qwen2_5_32b")
    p_struct = jax.eval_shape(
        lambda k: T.init_params(cfg, k, jnp.bfloat16),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_specs = SH.param_pspecs(cfg, p_struct, PROD)
    opt_struct = jax.eval_shape(init_opt_state, p_struct)
    opt_specs = SH.opt_pspecs(p_specs, opt_struct, PROD)
    flat = [s for s in jax.tree.leaves(
        opt_specs["master"], is_leaf=lambda x: isinstance(x, P))
        if isinstance(s, P)]
    n_data = sum(1 for s in flat
                 if any("data" in (d if isinstance(d, tuple) else (d,))
                        for d in s if d))
    assert n_data / len(flat) > 0.9  # nearly every master leaf is ZeRO-sharded


@pytest.mark.slow
def test_train_step_runs_under_host_mesh(key):
    """The exact sharded train path executes on a 1×1 mesh (CPU)."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import make_train_step
    from repro.optim.adamw import AdamWConfig
    mesh = make_host_mesh()
    cfg = get_smoke_config("qwen3-1.7b")
    params = T.init_params(cfg, key, jnp.float32)
    opt_state = init_opt_state(params)
    batch = {
        "tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (2, 16), 0, cfg.vocab_size),
    }
    p_specs = SH.param_pspecs(cfg, params, mesh)
    step = jax.jit(
        make_train_step(cfg, AdamWConfig(lr=1e-3), remat=True),
        in_shardings=(SH.to_sharding(mesh, p_specs), None, None))
    with mesh:
        _, _, loss = step(params, opt_state, batch)
    assert bool(jnp.isfinite(loss))


@pytest.mark.slow
def test_dryrun_cli_one_pair(tmp_path):
    """The dry-run CLI end-to-end on the cheapest pair (subprocess because it
    forces 512 host devices)."""
    # a virtual-CPU-mesh compile: the child must never reach for libtpu
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "mamba2-130m", "--shape", "long_500k"],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "OK" in out.stdout, out.stdout + out.stderr[-2000:]
