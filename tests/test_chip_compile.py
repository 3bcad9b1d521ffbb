"""The kernel families and the pallas fleet epoch compile for a TPU v5e.

Each test lowers at the widths `chip_smoke.py` runs on the chip (the
paper cluster: N = 87, L = 4,096, apply window 8; key space 100,000;
B = 32 members vmapped; digest tiers of 550 and 3,584 slots; 8 Multi-Raft
groups) and compiles for one chip of a described `v5e:2x2` topology.
The interpreter cannot see what this catches: tiling, scoped VMEM, and
programs that do not fit the device.  Nothing runs, so this says
nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.bwraft_kv import CONFIG
from repro.core import fleet as fleet_mod
from repro.core.fleet import FleetSim
from repro.kernels.ae_sync import ops as ae_ops
from repro.kernels.group_digest import ops as gd_ops
from repro.kernels.leader_fanout import ops as lf_ops
from repro.kernels.raft_tick import ops as rt_ops

B, N, L, K, A, S = 32, 87, 4096, 100_000, 8, 4
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """Compiled kernels, no persistent cache, and a maker of shapes that
    live on one described chip."""
    mp = pytest.MonkeyPatch()
    for mod in (rt_ops, lf_ops, ae_ops, gd_ops):
        mp.setattr(mod, "use_interpret", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    mp.undo()
    jax.config.update("jax_enable_compilation_cache", cache_on)
    jax.clear_caches()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
    return compiled


def test_raft_tick_kernels_compile(spec):
    bool_ = jnp.bool_
    _compile(jax.vmap(lambda *a: rt_ops.log_match_append(*a, w=256)),
             *[spec((B, N, L))] * 3, *[spec((B, L))] * 3,
             *[spec((B, N))] * 3, spec((B, N), bool_))
    _compile(jax.vmap(rt_ops.commit_majority), spec((B, N)),
             spec((B, N), bool_), spec((B, L)), spec((B,)), spec((B,)))
    _compile(jax.vmap(rt_ops.apply_last_wins), spec((B, N, K)),
             spec((B, N, A)), spec((B, N, A)), spec((B, N, A), bool_))


def test_leader_fanout_compiles(spec):
    fn = lambda *a: lf_ops.leader_fanout(*a, msg_budget=16, max_ship=256,
                                         entries_per_msg=32)
    _compile(jax.vmap(fn), *[spec((B, N))] * 10, spec((B, N, N)),
             *[spec((B,))] * 6)


@pytest.mark.parametrize("O", [550, 3584])
def test_ae_sync_compiles(spec, O):
    u32, bool_ = jnp.uint32, jnp.bool_
    _compile(jax.vmap(ae_ops.ae_sync),
             spec((B, O), bool_), *[spec((B, O))] * 3, spec((B, O), u32),
             *[spec((B, O))] * 3, spec((B, N), bool_), spec((B, N), bool_),
             spec((B, N)), spec((B, N)), spec((B, N), u32), spec((B, N)),
             spec((B, S, S)), spec((B,)), spec((B,)))


@pytest.fixture(scope="module")
def fleet_args(spec):
    """A one-member pallas fleet at the smoke's widths, and its epoch
    arguments widened to B = 32 members as shapes."""
    cfg = dataclasses.replace(CONFIG, key_space=K)
    fleet = FleetSim.from_sweep(cfg, {"seed": [0]}, backend="pallas")
    widen = lambda x: spec((B,) + x.shape[1:], x.dtype)
    args = jax.tree.map(widen, (fleet.state, fleet._split_epoch_rngs(),
                                fleet._bstatic, fleet._cfg_c))
    return fleet, args


def test_group_digest_compiles(spec, fleet_args):
    fleet, args = fleet_args
    _, digest = jax.eval_shape(fleet._epoch_fn.fn, *args)
    digest = jax.tree.map(lambda x: spec(x.shape, x.dtype), digest)
    _compile(lambda d, g: fleet_mod._group_digest(d, g, 8,
                                                  backend="pallas"),
             digest, spec((B,)))


def test_pallas_fleet_epoch_compiles(fleet_args):
    fleet, args = fleet_args
    compiled = fleet._epoch_fn.fn.lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 4
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert need < HBM_BYTES, need
