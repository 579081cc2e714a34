"""The program family as a lookup by name: the dense cell reads through
its family module what the module's functions give, a family with no
module is refused, and a family of two layer programs is counted and
read over both."""
import json
import sys
import types

import jax
import numpy as np
import pytest

from benchlib import flops, traffic, trace, weights
from benchlib import spec as specs
from benchlib.cell import Cell
from benchlib.families import dense
from benchlib.runview import RunView
from tiny import BENCH, FAKE_PEAK, TINY_TRAFFIC, tiny_config, tiny_model

SEED = 2 ** 31 + 29
EMPTY_WINDOW = {"seconds": 1.0, "t0": 0.0, "hi": [], "segments": [],
                "fills": 0, "lo_done": []}


def test_dense_cell_reads_through_its_family_what_the_functions_give():
    cell = Cell(tiny_config(), TINY_TRAFFIC, SEED)
    for name, role in cell.roles.items():
        assert role.family is dense
        m, b, s = role.m, role.batch, role.seq
        role.build(SEED)
        w = weights.make_weights(dense, m, SEED)
        got = jax.tree.map(np.asarray, role.service.svc.params)
        want = jax.tree.map(np.asarray, dense.program_tree(m, w))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, x in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.dtype == x.dtype and np.array_equal(g, x)
        role.service = None
        toks = traffic.tokens(SEED, name, 1, 0, m["vocab_size"], b, s)
        for precision in ("float32", "fp8"):
            assert np.array_equal(
                role.family.served_logits(m, w, toks, precision),
                dense.served_logits(m, w, toks, precision))
        assert role.family.layer_programs(m, b, s) == {
            "layer": (2, dense.layer_flops(m, b, s),
                      dense.layer_bytes(m, b, s))}
    view = RunView(cell, EMPTY_WINDOW, FAKE_PEAK)
    for name, role in cell.roles.items():
        m, b, s = role.m, role.batch, role.seq
        assert view.request_flops(name) == (
            2 * dense.layer_flops(m, b, s) + flops.head_flops(m, b, s))
        assert view.least_time(name) == {"layer": flops.least_time(
            dense.layer_flops(m, b, s), dense.layer_bytes(m, b, s),
            FAKE_PEAK)}


def test_a_family_with_no_module_is_refused_naming_the_file_to_add():
    config = tiny_config()
    config["roles"]["lo"]["model"]["family"] = "no_such_family"
    with pytest.raises(ValueError,
                       match="bench/benchlib/families/no_such_family.py"):
        Cell(config, TINY_TRAFFIC, SEED)


TOY = {"rec": (3, 600, 50), "attn": (1, 100, 400)}   # calls, FLOPs, bytes


@pytest.fixture
def toy_cell(monkeypatch):
    """A tiny cell whose hi role is of a family ``toy`` with two layer
    programs, its module put in place for this test only."""
    toy = types.ModuleType("benchlib.families.toy")
    toy.layer_programs = lambda m, batch, seq: dict(TOY)
    toy.head_flops = lambda m, batch, seq: 7
    monkeypatch.setitem(sys.modules, "benchlib.families.toy", toy)
    config = tiny_config()
    config["roles"]["hi"]["model"] = dict(tiny_model(), family="toy")
    cell = Cell(config, TINY_TRAFFIC, SEED)
    assert cell.roles["hi"].family is toy
    return cell


def test_a_family_of_two_layer_programs_is_counted_over_both(toy_cell):
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    view = RunView(toy_cell, EMPTY_WINDOW, peak)
    assert view.request_flops("hi") == 3 * 600 + 1 * 100 + 7
    assert view.least_time("hi") == {"rec": (6.0, "compute"),
                                     "attn": (40.0, "memory")}


@pytest.mark.parametrize("spans,want", [
    # 4 rec calls of least 6 s and 2 attn calls of least 40 s in 200 s
    ({"hi/rec": (4, 80.0), "hi/attn": (2, 120.0)}, 100 * (24 + 80) / 200),
    ({"hi/rec": (4, 80.0)}, 100 * 24 / 80),       # attn not in the trace
    ({"lo/layer": (4, 80.0)}, None),              # neither
])
def test_layer_roofline_sums_over_the_programs_in_the_trace(toy_cell, spans,
                                                            want):
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    reduced = {"spans": {k: {"calls": c, "device_s": d}
                         for k, (c, d) in spans.items()}}
    got = specs.reader(BENCH.parent, "layer_roofline.hi")(
        RunView(toy_cell, EMPTY_WINDOW, peak, reduced))
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))


# what the readers read on the recorded chip trace before the family
# lookup, for qwen-stablelm.steady's sizes at the v5e's peaks: the sample
# holds lo layer calls only
BEFORE = {"layer_roofline.hi": None, "layer_roofline.lo": 59.98883488781184}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_rooflines_read_as_before_on_the_recorded_trace(name):
    config = json.loads((BENCH / "configs"
                         / "hi-qwen3-4b.lo-stablelm-1.6b.json").read_text())
    peak = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    reduced = trace.reduce(json.loads(
        (BENCH / "tests" / "data" / "trace_sample.json").read_text()))
    view = RunView(Cell(config, TINY_TRAFFIC, SEED), EMPTY_WINDOW, peak,
                   reduced)
    got = specs.reader(BENCH.parent, name)(view)
    want = BEFORE[name]
    assert got == (None if want is None else pytest.approx(want, rel=1e-9))
