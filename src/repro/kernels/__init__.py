# Pallas TPU kernels for the perf-critical compute layers:
#   microbench       the paper's artificial iterative workload (per-core FMA
#                    chain) — the measurement instrument itself
#   flash_attention  blockwise causal attention (train/prefill hot spot)
#   ssd              mamba2 intra-chunk SSD kernel
# Each has kernel.py (pallas_call + BlockSpec), ops.py (jit wrapper) and
# ref.py (pure-jnp oracle).  platform.pallas_call compiles a kernel for a
# TPU and interprets it elsewhere, so the CPU tests sweep shapes/dtypes in
# interpret mode; tests/test_tpu_compile.py compiles them for a v5e.
