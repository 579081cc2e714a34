"""Segmented services (reduced, float32): the segments compose to
``api.forward``'s logits, each segment program compiles once per kind, no
program closes over a parameter array, and a dropped service frees its
params at once."""
import gc
import weakref
from functools import partial

import jax
import numpy as np
import pytest

from repro.config import get_config
from repro.models import api
from repro.models.segmentation import SegmentedService

pytestmark = pytest.mark.slow

# family -> (arch, compiled entries expected per program after warmup)
FAMILIES = {
    "dense": ("qwen3-4b", {"embed": 1, "layer": 1, "head": 1}),
    "ssm": ("mamba2-2.7b", {"embed": 1, "layer": 1, "head": 1}),
    "hybrid": ("recurrentgemma-9b",
               {"embed": 1, "rec": 1, "attn": 1, "head": 1}),
    "encdec": ("seamless-m4t-medium",
               {"encode": 1, "dec_layer": 1, "head": 1}),
    # reduced llama4: one chunked-attention and one full-attention layer,
    # two layer kinds keyed by the static ``is_full``
    "moe": ("llama4-scout-17b-a16e", {"embed": 1, "layer": 2, "head": 1}),
    "vlm": ("llava-next-mistral-7b", {"embed": 1, "layer": 1, "head": 1}),
}
BATCH, SEQ = 2, 64


def _service(arch):
    cfg = get_config(arch).reduced()
    params = api.build_params(cfg, jax.random.key(0))
    return cfg, params, SegmentedService(cfg, params, BATCH, SEQ)


def _run(svc, state):
    for seg in svc.segments:
        state = seg.fn(state)
    return state


@pytest.mark.parametrize("family", list(FAMILIES))
def test_segments_compose_to_forward_and_compile_once(family):
    arch, compiles = FAMILIES[family]
    cfg, params, svc = _service(arch)
    svc.warmup()
    batch = svc.make_input(jax.random.key(1))
    got = _run(svc, batch)
    want, _ = jax.jit(partial(api.forward, cfg=cfg))(params, batch)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # one program per segment kind, however many layers share it
    assert {name: prog._cache_size()
            for name, prog in svc.programs.items()} == compiles


@pytest.mark.parametrize("family", list(FAMILIES))
def test_segment_programs_close_over_no_params(family):
    _, params, svc = _service(FAMILIES[family][0])
    state = svc.make_input()
    for seg in svc.segments:
        outer = jax.make_jaxpr(seg.fn)(state)
        inner = [e.params["jaxpr"] for e in outer.jaxpr.eqns
                 if "jaxpr" in e.params]
        assert inner, seg.name
        const_bytes = sum(getattr(c, "nbytes", 0)
                          for j in inner for c in j.consts)
        assert const_bytes < 64 * 1024, (seg.name, const_bytes)
        state = seg.fn(state)


def test_dropped_service_frees_params_without_gc():
    """The refcount alone frees the params: no reference cycle holds them
    on the device until the next collection."""
    gc.disable()
    try:
        _, params, svc = _service(FAMILIES["dense"][0])
        svc.warmup()
        refs = [weakref.ref(a) for a in jax.tree.leaves(params)]
        del params, svc
        assert all(r() is None for r in refs)
    finally:
        gc.enable()
