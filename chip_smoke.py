"""Smoke run of the served FIKIT path on one TPU chip, at published width.

    python chip_smoke.py

Hosts qwen3-4b (high priority) and stablelm-1.6b (low priority), the
registered configs unreduced (36 and 24 layers, d_model 2560 and 2048,
bfloat16, random weights from seed 0), through ``serve_pair`` in FIKIT
mode: onboarding (measurement phase), then both services sharing the chip.

Before serving, each model's segments (embed, one program per layer, head)
are run on one request and compared with one jitted ``api.forward`` on the
same params and tokens. Both logits must be finite and agree to a relative
L2 error of at most ``REL_L2_TOL`` (8 bfloat16 ulps at 1.0).

It is a smoke run, not a benchmark: one pass, few requests, JCTs from the
host clock. It fails (non-zero exit, no result line) when JAX finds no TPU
and when any phase fails. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import math
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

HIGH, LOW = "qwen3-4b", "stablelm-1.6b"
BATCH = 1                 # the low service runs at 2 * BATCH
SEQ = 256
MEASURE_RUNS = 3
REQUESTS = 4
REL_L2_TOL = 2.0 ** -5


def check_forward(cfg, batch: int, seq: int) -> dict:
    """Run one request through the segments and through ``api.forward``;
    return set-up seconds and the agreement between the two logits."""
    import jax
    import numpy as np

    from repro.models import api
    from repro.models.segmentation import SegmentedService

    t0 = time.perf_counter()
    params = jax.block_until_ready(api.build_params(cfg, jax.random.key(0)))
    t_init = time.perf_counter() - t0
    svc = SegmentedService(cfg, params, batch, seq)
    t0 = time.perf_counter()
    svc.warmup()
    t_compile = time.perf_counter() - t0

    tokens = svc.make_input(jax.random.key(1))
    state = tokens
    for seg in svc.segments:
        state = seg.fn(state)
    ref, _ = jax.jit(partial(api.forward, cfg=cfg))(params, tokens)
    got = np.asarray(state, np.float32)
    want = np.asarray(ref, np.float32)
    leaves = jax.tree.leaves(params)
    return {
        "params": sum(a.size for a in leaves),
        "param_dtypes": sorted({str(a.dtype) for a in leaves}),
        "logits_shape": got.shape,
        "init_s": t_init,
        "segments_compile_s": t_compile,
        "finite": bool(np.isfinite(got).all() and np.isfinite(want).all()),
        "rel_l2": float(np.linalg.norm(got - want) / np.linalg.norm(want)),
        "max_abs_diff": float(np.abs(got - want).max()),
        "max_abs_ref": float(np.abs(want).max()),
        "argmax_agree": float((got.argmax(-1) == want.argmax(-1)).mean()),
    }


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1

    from repro.config import get_config
    from repro.launch.serve import serve_pair, use_compile_cache
    use_compile_cache()

    print("SMOKE RUN (not a benchmark)")
    print(f"device: {dev.device_kind}  count: {len(devices)}  "
          f"platform: {dev.platform}")
    high, low = get_config(HIGH), get_config(LOW)
    ok = True

    print(f"-- forward agreement (segments vs api.forward, "
          f"rel_l2 <= {REL_L2_TOL})")
    for cfg, batch in ((high, BATCH), (low, 2 * BATCH)):
        r = check_forward(cfg, batch, SEQ)
        agree = r["finite"] and r["rel_l2"] <= REL_L2_TOL
        ok &= agree
        print(f"{cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
              f"dtype={cfg.dtype} params={r['params']} "
              f"param_dtypes={r['param_dtypes']} batch={batch} seq={SEQ}")
        print(f"  init_s={r['init_s']:.3f} "
              f"segments_compile_s={r['segments_compile_s']:.3f}")
        print(f"  logits{r['logits_shape']} finite={r['finite']} "
              f"rel_l2={r['rel_l2']:.3e} max_abs_diff={r['max_abs_diff']:.3e} "
              f"max_abs_ref={r['max_abs_ref']:.3e} "
              f"argmax_agree={r['argmax_agree']:.4f} "
              f"{'PASS' if agree else 'FAIL'}")

    print(f"-- serve_pair mode=fikit high={HIGH} low={LOW} batch={BATCH}/"
          f"{2 * BATCH} seq={SEQ} measure_runs={MEASURE_RUNS} "
          f"requests={REQUESTS}")
    out = serve_pair(high, low, mode="fikit", requests=REQUESTS,
                     measure_runs=MEASURE_RUNS, batch=BATCH, seq=SEQ,
                     verbose=False)
    for k, v in out.items():
        print(f"  {k}: {v}")
    gib = 2 ** 30
    print(f"  HBM after onboarding both: in use "
          f"{out['device_bytes_in_use'] / gib:.3f} GiB, peak "
          f"{out['device_peak_bytes_in_use'] / gib:.3f} GiB")
    served = (out["high_completed"] == REQUESTS
              and out["low_completed"] == REQUESTS
              and all(math.isfinite(out[k]) and out[k] > 0
                      for k in ("measure_high_ms", "measure_low_ms",
                                "high_jct_ms", "low_jct_ms")))
    print(f"  served: {'PASS' if served else 'FAIL'}")
    ok &= served

    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
