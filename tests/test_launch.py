"""Launch-layer tests: step builders (reduced scale), sharding specs,
HLO cost extraction with trip-count correction."""
import jax
import jax.numpy as jnp
import pytest

from repro.config import InputShape, get_config
from repro.launch.hlo_cost import (bytes_accessed_corrected,
                                   collective_bytes_corrected,
                                   cost_analysis_dict,
                                   dot_flops_corrected)
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import default_grad_accum, make_step
from repro.sharding import specs as sh

pytestmark = pytest.mark.slow


SMALL = {
    "train": InputShape("t", 32, 4, "train"),
    "prefill": InputShape("p", 64, 2, "prefill"),
    "decode": InputShape("d", 64, 2, "decode"),
}


@pytest.mark.parametrize("arch", ["qwen3-4b", "llama4-scout-17b-a16e",
                                  "mamba2-2.7b"])
@pytest.mark.parametrize("kind", list(SMALL))
def test_make_step_compiles_reduced(arch, kind):
    cfg = get_config(arch).reduced()
    mesh = make_host_mesh()
    with mesh:
        jitted, args = make_step(cfg, mesh, SMALL[kind])
        compiled = jitted.lower(*args).compile()
    assert cost_analysis_dict(compiled).get("flops", 0) > 0


def test_train_step_executes_and_updates():
    cfg = get_config("stablelm-1.6b").reduced()
    mesh = make_host_mesh()
    shape = SMALL["train"]
    from repro.models import api
    from repro.optim.adamw import adamw_init
    with mesh:
        jitted, _ = make_step(cfg, mesh, shape)
        params = api.build_params(cfg, jax.random.key(0))
        opt = adamw_init(params)
        tokens = jnp.zeros((shape.global_batch, shape.seq_len), jnp.int32)
        labels = jnp.ones_like(tokens)
        p0 = jax.tree.leaves(params)[0].copy()
        new_params, new_opt, metrics = jitted(params, opt, tokens, labels)
    assert jnp.isfinite(metrics["loss"])
    assert int(new_opt.step) == 1
    assert not jnp.array_equal(p0, jax.tree.leaves(new_params)[0])


def test_param_specs_divisibility_guard():
    cfg = get_config("qwen3-4b")      # kv_heads=8, not divisible by 16
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    from repro.models import api
    params = api.build_params(cfg, key=None)
    specs = sh.param_specs(params, mesh)
    # structure matches exactly
    jax.tree.map(lambda a, b: None, params, specs)
    wk = specs["layers"]["attn"]["wk"]
    # on a 1-sized axis everything divides; the guard is exercised via the
    # 16x16 production mesh in the dry-run (kv=8 -> replicated there)
    assert len(wk) == 4


def test_grad_accum_heuristic_monotone():
    mesh = make_host_mesh()
    big = get_config("deepseek-v2-236b")
    small = get_config("stablelm-1.6b")
    t = InputShape("t", 4096, 256, "train")
    assert default_grad_accum(big, mesh, t) >= \
        default_grad_accum(small, mesh, t)


def test_hlo_cost_trip_count_correction():
    """A scanned matmul must be counted trip-count times."""
    n, m, k, trips = 64, 64, 64, 10
    w = jnp.ones((m, k), jnp.float32)

    @jax.jit
    def f(x):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=trips)
        return out

    compiled = f.lower(jnp.ones((n, m), jnp.float32)).compile()
    hlo = compiled.as_text()
    flops = dot_flops_corrected(hlo)
    expect = 2 * n * m * k * trips
    assert flops == pytest.approx(expect, rel=0.01), (flops, expect)
    # cost_analysis undercounts by the trip count (the bug we correct)
    raw = cost_analysis_dict(compiled).get("flops", 0)
    assert raw <= expect / 2
    assert bytes_accessed_corrected(hlo) > 0


def test_collective_bytes_corrected_counts_psum():
    # single-device: no collectives expected -> empty dict, no crash
    @jax.jit
    def f(a):
        return a * 2
    hlo = f.lower(jnp.ones((4,))).compile().as_text()
    assert collective_bytes_corrected(hlo) == {}
