"""Sharding-rule machinery + a miniature dry-run (8 fake devices) so the
AOT path is covered by pytest without the full 512-device sweep."""
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding import (FSDP_RULES, RULE_SETS, TP_RULES, logical_to_pspec)


class _FakeMesh:
    def __init__(self, shape_map):
        self._m = shape_map

    @property
    def axis_names(self):
        return tuple(self._m)

    @property
    def shape(self):
        return self._m


MESH = _FakeMesh({"data": 4, "model": 2})


def test_pspec_basic_mapping():
    spec = logical_to_pspec(("batch", "seq", "embed"), TP_RULES, MESH,
                            (8, 16, 32))
    assert spec == P("data")          # pod missing -> dropped; seq/embed None


def test_pspec_drops_nondividing():
    spec = logical_to_pspec(("vocab", "embed"), TP_RULES, MESH, (3, 32))
    assert spec == P()                # 3 % 2 != 0 -> unsharded


def test_pspec_no_axis_reuse():
    # both vocab and mlp map to "model": second use must drop
    spec = logical_to_pspec(("vocab", "mlp"), TP_RULES, MESH, (4, 4))
    assert spec == P("model")


def test_fsdp_shards_weights_two_ways():
    spec = logical_to_pspec(("embed", "mlp"), FSDP_RULES, MESH, (8, 8))
    assert spec == P("data", "model")


def test_all_rule_sets_resolve_every_axis():
    axes = ["batch", "seq", "embed", "vocab", "heads", "kv_heads", "mlp",
            "experts", "expert_mlp", "cache_seq", "cache_batch", "layers",
            "embed_table"]
    for name, rules in RULE_SETS.items():
        for ax in axes:
            assert ax in rules, (name, ax)


MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import base as cb
from repro.launch import specs as sp
from repro.launch.steps import make_train_step, make_serve_step
from repro.optim import adamw, constant
from repro.optim.optimizers import state_specs
from repro.sharding import RULE_SETS, use_rules, logical_to_pspec, spec_map
from repro.models import model as mdl
from jax.sharding import NamedSharding

mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = cb.smoke("tinyllama-1.1b")
rules = RULE_SETS["tp"]
params = sp.param_structs(cfg, mesh, rules)
opt = adamw(constant(1e-3))
ost = spec_map(lambda s: jax.ShapeDtypeStruct(
    s.shape, s.dtype or jnp.float32,
    sharding=NamedSharding(mesh, logical_to_pspec(s.axes, rules, mesh, s.shape))),
    state_specs(opt, mdl.param_specs(cfg)))
batch = sp.batch_specs(cfg, 64, 8, with_labels=True, mesh=mesh, rules=rules)
with use_rules(rules, mesh):
    c = jax.jit(make_train_step(cfg, opt, n_micro=2),
                donate_argnums=(0, 1)).lower(
        params, ost, batch, jax.ShapeDtypeStruct((), jnp.int32)).compile()
ca = c.cost_analysis()
assert ca.get("flops", 0) > 0
dec = sp.input_specs(cfg, cb.ShapeSpec("d", 128, 8, "decode"), mesh, rules)
with use_rules(rules, mesh):
    c2 = jax.jit(make_serve_step(cfg), donate_argnums=(3,)).lower(
        params, dec["token"], dec["pos"], dec["cache"]).compile()
print("MINI_DRYRUN_OK")
"""


def test_mini_dryrun_8_devices():
    """Full AOT path (train + decode) on an 8-device fake mesh."""
    r = subprocess.run([sys.executable, "-c", MINI_DRYRUN],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert "MINI_DRYRUN_OK" in r.stdout, r.stderr[-3000:]
