# Pallas kernel layer. Two kinds of kernels live here:
#   * model-substrate kernels (flash_attention / ssd / rwkv6 via ops.py +
#     ref.py oracles) used by the ML workloads the scheduler places;
#   * scheduler-core kernels: jrba_congestion fuses the sparse JRBA
#     relaxation's per-step pipeline (link load, smoothed congestion,
#     gradient, Adam) for the hot solver loop in core/jrba.py, which
#     lazy-imports it so minimal environments never pay the import unless
#     the pallas solver mode is selected.
# All kernels are validated on CPU CI in interpret mode; compiled paths
# target TPU (jrba_congestion's compile for a TPU v5e is tested in
# tests/test_tpu_compile.py and it runs on the chip in chip_smoke.py).
