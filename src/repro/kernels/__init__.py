"""Pallas TPU kernels for the framework's compute hot-spots.

  dp_clip          — per-example gradient clip-scale-accumulate, the DP-SGD
                     throughput bottleneck (paper Phase 2 inner loop)
  l1_distance      — pairwise ℓ1 over flattened client weights (Phase 1)
  flash_attention  — causal (and sliding-window) GQA flash attention, forward
                     and backward: the language model's attention

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle). Backend selection and tile autotuning
live in ``dispatch.py`` (``RunConfig.kernels``): compiled Pallas on TPU, the
jnp reference on CPU, and the interpreter only when explicitly requested for
debugging.
"""
