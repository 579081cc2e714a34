"""A run whose timed path is broken underneath reads ``correct`` false,
once for each fault a served cell can have."""
import jax.numpy as jnp
import numpy as np
import pytest

from tiny import load_run, make_root, result_line


def layer_returns_its_state(monkeypatch):
    from repro.models.segmentation import SegmentedService
    orig = SegmentedService._run_layer

    def skip_first(prog, args, x):
        if int(args[1]) == 0:
            return x
        return orig(prog, args, x)
    monkeypatch.setattr(SegmentedService, "_run_layer",
                        staticmethod(skip_first))


def half_the_batch_left_out(monkeypatch):
    from repro.models import transformer
    orig = transformer.unembed

    def first_half(params, x, cfg):
        h = max(x.shape[0] // 2, 1)
        y = orig(params, x[:h], cfg)
        return jnp.concatenate([y] * -(-x.shape[0] // h), axis=0)[
            :x.shape[0]]
    monkeypatch.setattr(transformer, "unembed", first_half)


def token_altered_where_produced(monkeypatch):
    from repro.models.segmentation import SegmentedService
    orig = SegmentedService._sample_work

    def altered(self):
        work = orig(self)

        def w(logits):
            toks = np.array(work(logits))
            toks[0, 0] = (toks[0, 0] + 32) % 64
            return toks
        return w
    monkeypatch.setattr(SegmentedService, "_sample_work", altered)


@pytest.mark.parametrize("fault", [layer_returns_its_state,
                                   half_the_batch_left_out,
                                   token_altered_where_produced])
def test_broken_timed_path_is_not_correct(fault, tmp_path, capsys,
                                          monkeypatch):
    run = load_run(monkeypatch)
    root = make_root(tmp_path)
    fault(monkeypatch)
    rc = run.main(["--workload", "tiny.steady", "--seed", str(2 ** 31 + 7),
                   "--seconds", "2", "--trace", "0"], root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err
    res = result_line(out)
    assert res["correct"] is False
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in res["checks"].values())
