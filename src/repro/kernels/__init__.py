"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel ships <name>.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd wrapper with impl switch: the pallas kernel, run in the
Pallas interpreter only when the caller passes ``interpret=True``, or jnp
fallbacks), and ref.py (pure-jnp oracle used by the allclose sweeps in
tests/test_kernels.py).

  zns_alloc        wear-min per-LUN top-G selection (paper Table 4 hotspot)
  flash_attention  blocked causal GQA attention (train/prefill)
  decode_attention streaming GQA decode over long KV caches
  ssm_scan         chunked selective-state-space scan (Mamba/Jamba)
"""
