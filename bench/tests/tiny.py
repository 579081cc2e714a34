"""A tiny cell for CPU tests: both roles two layers deep at d_model 64,
in bfloat16 as served, on 16-token prompts."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def tiny_model(d_model=64, heads=4, kv=2, layers=2, qk_norm=False,
               rotary_pct=1.0, vocab=128, d_ff=128, tie=False):
    return {"num_layers": layers, "d_model": d_model, "num_heads": heads,
            "num_kv_heads": kv, "head_dim": 0, "d_ff": d_ff,
            "vocab_size": vocab, "qk_norm": qk_norm, "rope_theta": 10000.0,
            "rotary_pct": rotary_pct, "norm_eps": 1e-05,
            "sliding_window": None, "tie_embeddings": tie,
            "dtype": "bfloat16"}


def tiny_config(name="tiny-pair"):
    return {
        "name": name,
        "roles": {
            "hi": {"arch": "qwen3-4b", "batch": 1, "seq": 16,
                   "model": tiny_model(qk_norm=True, tie=True)},
            "lo": {"arch": "stablelm-1.6b", "batch": 2, "seq": 16,
                   "model": tiny_model(rotary_pct=0.25, kv=4)},
        },
        "serving": {
            "mode": "fikit", "measure_runs": 2, "max_inflight": 16,
            "classes": {
                "hi": {"name": "gold", "priority": 0, "queue_limit": 1024,
                       "max_batch": 1},
                "lo": {"name": "bronze", "priority": 5,
                       "queue_limit": 1024, "max_batch": 1}}},
        "check": {"requests": {"hi": 3, "lo": 2},
                  "limits": {"gap.hi": 0.05, "gap.lo": 0.05}},
    }


TINY_TRAFFIC = {"hi": {"rate_per_s": 20.0}, "lo": {"backlog": 2}}


def make_root(tmp: Path, cells=(("tiny.steady", "tiny-pair", "tiny"),),
              config=None, traffics=None) -> Path:
    """A checkout holding BENCHMARK.json with ``cells`` (workload,
    configuration, traffic), the traffic files ``traffics`` (name ->
    parameters, TINY_TRAFFIC by default) and the real per-layer readers,
    for CPU runs of the harness."""
    traffics = traffics or {}
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", tmp / "bench" / "metrics")
    configs = []
    for _, cname, tname in cells:
        if not (tmp / "bench" / "configs" / f"{cname}.json").exists():
            (tmp / "bench" / "configs" / f"{cname}.json").write_text(
                json.dumps(config or tiny_config(cname)))
            configs.append({"name": cname, "source": "test",
                            "file": f"bench/configs/{cname}.json",
                            "reduced": [], "why": "test"})
        (tmp / "bench" / "traffic" / f"{tname}.json").write_text(
            json.dumps(traffics.get(tname, TINY_TRAFFIC)))
    bench = dict(real, configs=configs, workloads=[
        {"name": w, "config": c, "traffic": t, "chips": 1, "why": "test"}
        for w, c, t in cells])
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


FAKE_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def load_run(monkeypatch=None):
    """bench/run.py as a module; with ``monkeypatch``, its look for a chip
    is skipped and the CPU is given made-up peaks."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    if monkeypatch is not None:
        import jax
        monkeypatch.setattr(run, "require_accelerator",
                            lambda chips: jax.devices()[:chips])
        monkeypatch.setattr(run, "load_peak", lambda root, kind: FAKE_PEAK)
    return run


def result_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])
