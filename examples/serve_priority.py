"""End-to-end serving driver: two REAL reduced-scale models (qwen3 + mamba2)
share the device through the wall-clock FIKIT engine — real jitted JAX
segments, real threads, real measured JCTs.

Lifecycle per the paper: onboard (measurement phase, exclusive, per-kernel
timing) -> concurrent sharing phase under FIKIT vs default sharing. The
final run spreads the same workload over TWO device executors through the
placement layer (device election + idle-device work stealing). On a
single-accelerator host the two executor threads share the hardware, so
this demonstrates the scheduling path; see SimScheduler(devices=K) /
benchmarks/bench_placement.py for scaling measurements.

    PYTHONPATH=src python examples/serve_priority.py

Both models are the reduced (2-layer, float32) variants, sized for a CPU.
"""
from repro.config import get_config
from repro.launch.serve import serve_pair

HIGH = get_config("qwen3-4b").reduced()
LOW = get_config("mamba2-2.7b").reduced()
SEQ = 64    # a multiple of the reduced mamba2's ssm_chunk (32)

for mode in ("sharing", "fikit"):
    print(f"--- mode={mode} ---")
    out = serve_pair(HIGH, LOW, seq=SEQ, mode=mode, requests=6,
                     measure_runs=4)
    print()

print("--- mode=fikit devices=2 (placement layer) ---")
out = serve_pair(HIGH, LOW, seq=SEQ, mode="fikit", requests=6,
                 measure_runs=4, devices=2)
print()

# Intra-device queue disciplines (repro.core.queues.QUEUE_DISCIPLINES):
# "sjf" orders each priority level shortest-predicted-first; "edf" orders
# by the per-request deadline tag — here every low-priority invocation
# carries a 250 ms budget, and deadline_misses counts blown budgets.
print("--- mode=fikit discipline=sjf ---")
out = serve_pair(HIGH, LOW, seq=SEQ, mode="fikit", requests=6,
                 measure_runs=4, discipline="sjf")
print()

print("--- mode=fikit discipline=edf deadline=0.25 ---")
out = serve_pair(HIGH, LOW, seq=SEQ, mode="fikit", requests=6,
                 measure_runs=4, discipline="edf", deadline=0.25)
print()
