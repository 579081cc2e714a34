"""Whole runs of the harness on the CPU, on a tiny cell: the refusal off
a TPU, a cell added as files plus an entry, and the traced run."""
import json

from tiny import TINY_TRAFFIC, load_run, make_root, result_line

SEED = str(2 ** 31 + 101)


def test_refuses_to_run_off_a_tpu_and_prints_no_result(tmp_path, capsys):
    run = load_run()
    root = make_root(tmp_path)
    rc = run.main(["--workload", "tiny.steady", "--seed", SEED,
                   "--seconds", "1", "--trace", "0"], root=root)
    out, err = capsys.readouterr()
    assert rc != 0
    assert out.strip() == ""
    assert "needs a TPU" in err


def test_a_new_cell_is_files_plus_an_entry(tmp_path, capsys, monkeypatch):
    run = load_run(monkeypatch)
    slow = dict(TINY_TRAFFIC, hi={"rate_per_s": 8.0})
    root = make_root(tmp_path, cells=(
        ("tiny.steady", "tiny-pair", "tiny"),
        ("tiny.slow", "tiny-pair", "tiny-slow")),
        traffics={"tiny-slow": slow})
    rc = run.main(["--workload", "tiny.slow", "--seed", SEED,
                   "--seconds", "2", "--trace", "0"], root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err
    res = result_line(out)
    assert res["correct"] is True
    assert res["attempted"] >= 16 and res["failed"] == 0
    bench = json.loads((root / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert "hi rate 8.0 /s" in out
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check gap.lo:")


def test_traced_run_reports_per_layer_metrics(tmp_path, capsys, monkeypatch):
    run = load_run(monkeypatch)
    root = make_root(tmp_path)
    rc = run.main(["--workload", "tiny.steady", "--seed", SEED,
                   "--seconds", "2", "--trace", "1"], root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err
    res = result_line(out)
    assert res["correct"] is True
    bench = json.loads((root / "BENCHMARK.json").read_text())
    per_layer = {m["name"] for m in bench["per_layer"]}
    # the CPU has no device plane: the device readers find nothing
    assert set(res["metrics"]) == per_layer - {
        "device_idle_share", "layer_roofline.hi", "layer_roofline.lo"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_close_frees_what_set_up_put_on_the_device():
    import jax

    from benchlib.cell import Cell
    from tiny import tiny_config

    before = {id(a) for a in jax.live_arrays()}
    cell = Cell(tiny_config(), TINY_TRAFFIC, 2 ** 31 + 3)
    cell.setup()
    cell.window(1.0)
    cell.close()
    left = [a for a in jax.live_arrays() if id(a) not in before]
    assert left == [], f"{len(left)} arrays outlive close()"
