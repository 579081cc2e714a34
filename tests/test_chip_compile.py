"""Compile the served path's programs at published width for a described,
unattached TPU v5e: the qwen3-4b embed, layer and head segments, the
stablelm-1.6b layer segment, and the three Pallas kernels. Nothing runs;
the TPU compiler refuses here what it would refuse on the chip (layouts,
VMEM limits, programs that do not fit the device's memory).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.kernels import decode_attention, flash_attention, rglru_scan
from repro.models import api
from repro.models.segmentation import SegmentedService

pytestmark = pytest.mark.slow

V5E_HBM_BYTES = 16 * 2 ** 30
SEQ = 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return compiled, mem


def _service_shapes(sharding, arch, batch):
    """A published-width service over shape-only params on ``sharding``,
    with its token and activation shapes."""
    cfg = get_config(arch)
    params = jax.tree.map(lambda s: _on(sharding, s.shape, s.dtype),
                          api.build_params(cfg, key=None))
    svc = SegmentedService(cfg, params, batch, SEQ)
    tokens = _on(sharding, (batch, SEQ), jnp.int32)
    x = _on(sharding, (batch, SEQ, cfg.d_model), jnp.dtype(cfg.dtype))
    index = _on(sharding, (), jnp.int32)
    return cfg, params, svc, tokens, x, index


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


@pytest.mark.parametrize("segment", ["embed", "layer", "head"])
def test_qwen3_segment_compiles_at_published_width(one_chip, segment):
    cfg, params, svc, tokens, x, index = _service_shapes(
        one_chip, "qwen3-4b", batch=1)
    weights, inputs = {
        "embed": (params["embed"], tokens),
        "layer": (params["layers"], (index, x)),
        "head": ((params["final_norm"], params["lm_head"]), x)}[segment]
    args = {"embed": (params, tokens),
            "layer": (params["layers"], index, x),
            "head": (params, x)}[segment]
    _, mem = _compile(svc.programs[segment], *args)
    # the compiled program takes only the weights it reads (unused
    # params are pruned) and its inputs
    want = _nbytes(weights) + _nbytes(inputs)
    assert want <= mem.argument_size_in_bytes < want + 2 ** 16


def test_stablelm_layer_compiles_at_published_width(one_chip):
    cfg, params, svc, _, x, index = _service_shapes(
        one_chip, "stablelm-1.6b", batch=2)
    _compile(svc.programs["layer"], params["layers"], index, x)
    assert cfg.num_layers == 24 and cfg.d_model == 2048


def test_flash_attention_compiles_for_v5e(one_chip):
    q = _on(one_chip, (1, 32, 1024, 128), jnp.bfloat16)
    kv = _on(one_chip, (1, 8, 1024, 128), jnp.bfloat16)
    compiled, _ = _compile(jax.jit(flash_attention), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles_for_v5e(one_chip):
    q = _on(one_chip, (2, 32, 128), jnp.bfloat16)
    kv = _on(one_chip, (2, 8, 4096, 128), jnp.bfloat16)
    kpos = _on(one_chip, (4096,), jnp.int32)
    pos = _on(one_chip, (), jnp.int32)
    compiled, _ = _compile(jax.jit(decode_attention), q, kv, kv, kpos, pos)
    assert "tpu_custom_call" in compiled.as_text()


def test_rglru_scan_compiles_for_v5e(one_chip):
    ab = _on(one_chip, (2, 1024, 2560), jnp.float32)
    h0 = _on(one_chip, (2, 2560), jnp.float32)
    compiled, _ = _compile(jax.jit(rglru_scan), ab, ab, h0)
    assert "tpu_custom_call" in compiled.as_text()
