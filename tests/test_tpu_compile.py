"""The serving path's Pallas kernels and fused phase compile for a TPU v5e.

No chip is attached: the TPU compiler compiles for a described ``v5e:2x2``
topology, which refuses what interpret mode accepts — block shapes that
break the (8, 128) tiling rule, more VMEM than a kernel may use, a program
larger than the chip's HBM. Sizes are the paper's student at width 10
(2,014,885 parameters per session) in a fused stack of four sessions.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import stacking
from repro.kernels.masked_adam.masked_adam import masked_adam_stacked_3d
from repro.kernels.topk_mask.topk_mask import (PALLAS_TOPK_MAX_PER_SESSION,
                                               topk_threshold_bits_3d)
from repro.launch.mesh import chip_peaks

N_PARAMS = 2_014_885  # SegConfig(width=10.0, n_classes=5)
B = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


def test_masked_adam_stacked_compiles_for_v5e(one_chip):
    rows = stacking.padded_rows(N_PARAMS)
    assert rows % stacking.SUBLANES == 0
    tile = _spec((B, rows, stacking.LANES), jnp.float32, one_chip)
    fn = jax.jit(lambda p, g, m, v, b, bc: masked_adam_stacked_3d(
        p, g, m, v, b, bc, b1=0.9, b2=0.999, eps=1e-8, interpret=False))
    compiled = fn.lower(tile, tile, tile, tile, tile,
                        _spec((B,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("per_session", [N_PARAMS, PALLAS_TOPK_MAX_PER_SESSION])
def test_topk_threshold_compiles_for_v5e(one_chip, per_session):
    """At the student's size and at the dispatch ceiling: the selection
    layer never offers the kernel a size the compiler refuses."""
    rows = stacking.padded_rows(per_session)
    fn = jax.jit(lambda bits: topk_threshold_bits_3d(
        bits, max(int(0.05 * per_session), 1), interpret=False))
    compiled = fn.lower(_spec((B, rows, stacking.LANES), jnp.uint32,
                              one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_phase_pallas_compiles_for_v5e(one_chip, monkeypatch):
    """The fused-phase executable with the Pallas masked-Adam in it, at
    the paper's operating point (K=20, batch 8, 256x512 frames, B=4),
    fits the chip's HBM."""
    import repro.kernels
    from repro.core import batched
    from repro.core.masked_adam import init_state
    from repro.models.seg.student import SegConfig, make_student
    from repro.sim.seg_world import _compiled_fns

    # this process's backend is the CPU; compile the kernels for Mosaic
    monkeypatch.setattr(repro.kernels, "interpret_default", lambda: False)
    cfg = SegConfig(width=10.0, n_classes=5)
    loss_and_grad = _compiled_fns(cfg)[0]
    one = jax.eval_shape(lambda: make_student(cfg, jax.random.PRNGKey(0)))
    assert sum(leaf.size for leaf in jax.tree.leaves(one)) == N_PARAMS

    def stacked(tree, dtype=None):
        return jax.tree.map(lambda leaf: _spec(
            (B,) + leaf.shape, dtype or leaf.dtype, one_chip), tree)

    k_iters, batch, h, w = 20, 8, 256, 512
    phase = batched._build_phase_fn(loss_and_grad, "adam", 1e-3, 0.9, 0.999,
                                    1e-8, 0.9, "scan", "pallas")
    compiled = phase.lower(
        stacked(one), stacked(jax.eval_shape(init_state, one)),
        stacked(one, jnp.bool_),
        _spec((k_iters, B, batch, h, w, 3), jnp.float32, one_chip),
        _spec((k_iters, B, batch, h, w), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    hbm = chip_peaks(next(iter(one_chip.device_set)).device_kind).hbm_bytes
    assert total < hbm, f"fused phase needs {total / 1e9:.2f} GB of {hbm}"
